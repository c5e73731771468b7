"""The part of the collective ops' intervals in which no other op runs on
that device, over the traced window, averaged over devices."""
from bench import trace


def read(r):
    if not r.trace.ops:
        return None
    total, exposed = trace.collective_shares(r.trace, r.window)
    return 100.0 * exposed if total > 0 else None

"""Union of the collective ops' intervals (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all: the ATP boundaries of
``core/atp.py`` and ``core/overlap.py``) over the traced window, averaged
over devices."""
from bench import trace


def read(r):
    if not r.trace.ops:
        return None
    total, _ = trace.collective_shares(r.trace, r.window)
    return 100.0 * total if total > 0 else None

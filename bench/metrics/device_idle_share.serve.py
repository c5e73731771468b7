"""Share of the traced serve window in which no op ran on the device,
averaged over the cell's devices (the scheduler's host work,
``runtime/server.py``)."""
from bench import trace


def read(r):
    if not r.trace.ops:
        return None
    return 100.0 * trace.idle_share(r.trace, r.window)

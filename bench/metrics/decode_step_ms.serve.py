"""Device time inside the host spans of the paged decode calls
(``serve.decode``: ``launch/steps.py`` paged step at the decode shape,
``models/paging.py``'s gather and attention), per decode call."""
from bench import trace


def read(r):
    t, n = trace.device_time_in(r.trace, "serve.decode", r.window)
    return 1e3 * t / n if n and t > 0 else None

"""Device time of the ops under the optimizer's ``opt:adamw`` name scope
(``optim/adamw.py``: the update and its collectives) over device busy
time in the traced window."""
from bench import trace


def read(r):
    share = trace.scope_share(r.trace, r.window, ("opt:adamw",))
    return None if not share else 100.0 * share

"""Device time of the ops under the attention core's ``attn:core`` name
scope (``models/layers.py``: scores, mask, softmax, ``p @ v``; forward,
backward and recomputed) over device busy time in the traced window."""
from bench import trace


def read(r):
    share = trace.scope_share(r.trace, r.window, ("attn:core",))
    return None if not share else 100.0 * share

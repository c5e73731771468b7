"""Device time of the ops under the paged decode attention kernel's
``attn:paged`` name scope (``models/transformer.py``, the Pallas kernel of
``kernels/paged_attention.py`` and its block-diagonal query) over device
busy time in the traced window; silent where no op carries the scope."""
from bench import trace


def read(r):
    share = trace.scope_share(r.trace, r.window, ("attn:paged",))
    return None if not share else 100.0 * share

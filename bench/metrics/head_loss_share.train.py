"""Device time of the ops under the model shell's ``shell:head`` and
``shell:loss`` name scopes (``models/lm.py``), forward and backward, over
device busy time in the traced window."""
from bench import trace


def read(r):
    share = trace.scope_share(r.trace, r.window, ("shell:head", "shell:loss"))
    return None if share is None else 100.0 * share

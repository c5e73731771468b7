"""Compiles in the traced window of the server: ``serve.call.recompile``
markers (a paged-step call that compiled, ``launch/serve.py``).  Nothing
when the server has no ``serve.call.dispatch`` span there."""


def read(r):
    inside = [s.name for s in r.trace.spans
              if r.window[0] <= s.start and s.end <= r.window[1]]
    if "serve.call.dispatch" not in inside:
        return None
    return float(inside.count("serve.call.recompile"))

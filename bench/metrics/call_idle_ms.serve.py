"""Device idle time inside the paged-step call's host spans
(``serve.call.upload`` / ``dispatch`` / ``read``: ``launch/serve.py``'s
step wrapper), averaged over devices, per call (``serve.call.dispatch``
spans in the traced window)."""
from bench import trace


def read(r):
    calls = [(s.start, s.end) for s in r.trace.spans
             if s.name.startswith("serve.call.")]
    n = sum(s.name == "serve.call.dispatch" and r.window[0] <= s.start
            and s.end <= r.window[1] for s in r.trace.spans)
    if not n or not r.trace.ops:
        return None
    idle = {d: trace.subtract([r.window], b)
            for d, b in trace.busy(r.trace, r.window).items()}
    t = sum(trace.length(trace.intersect(v, calls))
            for v in idle.values()) / len(idle)
    return 1e-3 * t / n

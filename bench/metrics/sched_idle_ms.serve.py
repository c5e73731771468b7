"""Device idle time inside the scheduler's host spans (``serve.sched.*``:
``runtime/server.py``'s admission, prefill feed and decode bookkeeping)
and outside the paged-step calls (``serve.call.*``), averaged over
devices, per tick (``serve.sched.admit`` spans in the traced window)."""
from bench import trace


def read(r):
    sched = [(s.start, s.end) for s in r.trace.spans
             if s.name.startswith("serve.sched.")]
    calls = [(s.start, s.end) for s in r.trace.spans
             if s.name.startswith("serve.call.")]
    n = sum(s.name == "serve.sched.admit" and r.window[0] <= s.start
            and s.end <= r.window[1] for s in r.trace.spans)
    if not n or not r.trace.ops:
        return None
    outside = trace.subtract(trace.clip(sched, r.window), calls)
    idle = {d: trace.subtract([r.window], b)
            for d, b in trace.busy(r.trace, r.window).items()}
    t = sum(trace.length(trace.intersect(v, outside))
            for v in idle.values()) / len(idle)
    return 1e-3 * t / n

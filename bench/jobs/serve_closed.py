"""Job ``serve_closed``: the program's paged server under a closed loop.

``launch.serve.make_paged_server`` builds the server on weights the
benchmark draws from the seed.  A fixed number of clients each keep one
request in flight: a client sends its next request at the end of the
scheduler tick in which its last one completed.  Before the window the
loop runs until every client has completed one request, so both compiled
shapes (prefill chunk and decode tick) are warm and every slot has turned
over; then the window runs ticks until ``--seconds`` have passed.

The benchmark's clock stamps each output token at the end of the tick
that produced it.  ``serve_tokens_per_s`` counts the tokens stamped in
the window over the window, and ``itl_p95_ms`` is taken over every gap
between consecutive tokens of a request whose later token is stamped in
the window.  The time to first token (submit to first stamp) of the
requests whose first token falls in the window is logged as a reading.

Correctness: after the window, a sample drawn from the seed of the
requests completed in it, the longest always among them, runs through the
reference as prompt plus served tokens; the number compared is the widest
gap by which a served token's reference logit lies below the reference's
best at that position.  Every request must also have produced exactly
its requested number of tokens.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench.model import Sizes, make_weights, program_config, seed_key
from bench.traffic import Requests, _rng


@dataclasses.dataclass
class Track:
    req: object
    client: int
    submit: float
    times: list = dataclasses.field(default_factory=list)


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def sample(done: list[Track], seed: int, tokens: int) -> list[Track]:
    """The longest completed request, then others in the seed's order,
    until ``tokens`` served tokens are covered."""
    by_len = sorted(done, key=lambda t: -(len(t.req.prompt) + len(t.req.out)))
    rest = [by_len[i] for i in _rng(seed, 3).permutation(len(by_len) - 1) + 1]
    out, n = [by_len[0]], len(by_len[0].req.out)
    for t in rest:
        if n >= tokens:
            break
        out.append(t)
        n += len(t.req.out)
    return out


def run(cell, profile=None):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import flops
    from bench.reference import serve_gaps
    from bench.run import JobResult, log, memory_peak
    from repro.core.mesh import atp_topo
    from repro.launch.serve import make_paged_server
    from repro.launch.steps import resolve_ctx
    from repro.models import lm
    from repro.models.paging import GARBAGE_PAGE, PagedConfig
    from repro.runtime.server import Request, ServerConfig

    w = cell.workload
    sizes = Sizes.from_file(cell.config)
    cfg = program_config(cell.config)
    log(flops.describe(sizes, w["pages_per_slot"] * w["page_size"]))
    scfg = ServerConfig(
        batch_slots=w["slots"], prefill_chunk=w["prefill_chunk"],
        prefill_chunks_per_tick=w["prefill_chunks_per_tick"],
        paged=PagedConfig(page_size=w["page_size"], num_pages=w["num_pages"],
                          pages_per_slot=w["pages_per_slot"]))
    topo = atp_topo(1, w["plan"]["d1"], w["plan"]["d2"])
    ctx = resolve_ctx(topo, None, decode=True)
    mesh = topo.build(cell.devices)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                         lm.param_specs(cfg, ctx),
                         is_leaf=lambda x: isinstance(x, P))
    key = seed_key(cell.seed)
    server, _ = make_paged_server(cfg, scfg,
                                  make_weights(sizes, key, out_shardings=shard),
                                  topo=topo)

    inner = server.step_fn
    costs: list | None = None      # per call, while the trace runs

    def step_fn(tokens, start, table, caches):
        prefill = tokens.shape[1] > 1
        if costs is not None:
            if prefill:
                q, logits = [(int(np.count_nonzero(tokens[0])), int(start[0]))], 1
            else:
                live = np.nonzero(table[:, 0] != GARBAGE_PAGE)[0]
                q, logits = [(1, int(start[i])) for i in live], len(live)
            costs.append(flops.serve_step_cost(sizes, q, logits))
        with jax.profiler.TraceAnnotation(
                "serve.prefill" if prefill else "serve.decode"):
            return inner(tokens, start, table, caches)

    server.step_fn = step_fn

    requests = Requests(cell.traffic, cell.seed, sizes.vocab)
    tracks: dict[int, Track] = {}
    inflight: list[int] = []
    finished_once: set[int] = set()

    def submit(client: int, now: float):
        prompt, olen = requests.next()
        rid = len(tracks)
        req = Request(rid=rid, prompt=prompt, max_new=olen)
        server.submit(req)
        tracks[rid] = Track(req, client, now)
        inflight.append(rid)

    def tick() -> float:
        with jax.profiler.TraceAnnotation("serve.tick"):
            server.step()
        now = time.perf_counter()
        for rid in list(inflight):
            t = tracks[rid]
            n = len(t.req.out)
            if n > len(t.times):
                t.times.extend([now] * (n - len(t.times)))
            if t.req.done:
                inflight.remove(rid)
                finished_once.add(t.client)
                submit(t.client, now)
        return now

    clients = cell.traffic["clients"]
    now = time.perf_counter()
    for c in range(clients):
        submit(c, now)
    while len(finished_once) < clients:
        now = tick()
    t_open = now
    ticks = 0
    while now < t_open + cell.seconds:
        if profile is not None and ticks == w["trace_skip_ticks"]:
            profile.start()
            costs = []
        now = tick()
        ticks += 1
        if profile is not None and ticks == w["trace_skip_ticks"] + w["trace_ticks"]:
            profile.stop()
            counters = {"serve_costs": costs}
            costs = None
    t_close = now
    if profile is not None and costs is not None:
        raise RuntimeError("the window ended inside the traced sub-window: "
                           "give --seconds more room")
    peak = memory_peak(cell.devices)
    expired = len(server.expired)
    del server, inner, step_fn
    gc.collect()

    def inside(t):
        return t_open < t <= t_close

    window = t_close - t_open
    out_tokens = sum(sum(1 for x in t.times if inside(x))
                     for t in tracks.values())
    ttft = [(t.times[0] - t.submit) * 1e3 for t in tracks.values()
            if t.times and inside(t.times[0])]
    itl = [(b - a) * 1e3 for t in tracks.values()
           for a, b in zip(t.times, t.times[1:]) if inside(b)]
    done = [t for t in tracks.values() if t.req.done and inside(t.times[-1])]
    wrong = sum(len(t.req.out) != t.req.max_new for t in done)
    e2e = {"serve_tokens_per_s": out_tokens / window,
           "itl_p95_ms": percentile(itl, 95),
           "setup_s": t_open - cell.t_start}
    log(f"window {window:.3f} s, {ticks} ticks, {out_tokens} tokens, "
        f"{len(done)} requests completed, {len(ttft)} first tokens, "
        f"{len(itl)} gaps; ttft p50 {percentile(ttft, 50):.1f} ms (a reading: "
        f"too few samples for a tail), itl p50 "
        f"{percentile(itl, 50):.1f} ms; set-up {e2e['setup_s']:.3f} s")

    t0 = time.perf_counter()
    picked = sample(done, cell.seed, w["sample_tokens"])
    seqs = [(np.concatenate([t.req.prompt, np.asarray(t.req.out[:-1], np.int32)]),
             len(t.req.prompt) - 1, np.asarray(t.req.out, np.int32))
            for t in picked]
    gaps = serve_gaps(sizes, make_weights(sizes, key), seqs,
                      width=scfg.paged.max_seq,
                      slots=cell.traffic["output"]["max"])
    worst = max(float(np.max(g)) for g in gaps)
    log(f"reference {time.perf_counter() - t0:.1f} s over {len(picked)} "
        f"requests, {sum(len(g) for g in gaps)} served tokens; widest gap "
        f"{worst!r}")
    return JobResult(
        attempted=len(done), failed=expired + wrong, e2e=e2e,
        checks=[("served_gap", worst, w["limits"]["served_gap"])],
        memory_peak_bytes=peak,
        counters=counters if profile is not None else {},
        trace_dir=str(profile.logdir) if profile is not None else None)

"""Serving fast-path smoke gate (``make serve-smoke``).

Exercises the paged continuous-batching pipeline end to end on the
simulated 8-device host mesh and exits non-zero on any mismatch:

    decode-objective plan search (decode sub-plan attached, save/load
    round-trip) -> serving stack built on the decode view -> mixed-length
    requests through chunked prefill + continuous decode with slot
    recycling -> greedy tokens IDENTICAL to the wave loop baseline ->
    page accounting returns to empty.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve_smoke
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
import tempfile

import jax
import numpy as np


#: a bf16 paged token may differ from the wave reference only where the
#: reference's top-2 logit margin is under this many bf16 ulps of its top
#: logit (a near-tie).  The chunked paged prefill and the one-shot wave
#: prefill add the same bf16 terms in a different order, so the two paths
#: can differ by a rounding or two; measured on the reduced zamba: the one
#: flip sits at margin 0, an exact bf16 tie of the two logits.
BF16_TIE_ULPS = 2


def bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 values (8 significant bits) at ``x``."""
    return 2.0 ** (np.floor(np.log2(abs(x))) - 7)


def check(ok: bool, what: str):
    if not ok:
        print(f"[serve-smoke] FAIL: {what}")
        sys.exit(1)
    print(f"[serve-smoke] ok: {what}")


def top2(cfg, params, seq) -> tuple[float, float, int]:
    """(top logit, top-2 margin, runner-up token) of the greedy pick
    after ``seq``, by a one-shot forward on one device."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.atp import make_context
    from repro.core.mesh import MeshTopo
    from repro.models import lm

    topo = MeshTopo((("data", 1),))
    ctx = make_context(topo)
    fn = shard_map(lambda p, t: lm.prefill_logits(ctx, cfg, p, {"tokens": t}),
                   mesh=topo.build(jax.devices()[:1]), in_specs=(P(), P()),
                   out_specs=P())
    logits = np.asarray(jax.jit(fn)(params, np.asarray(seq, np.int32)[None]),
                        np.float32)[0]
    top, second = np.argsort(logits)[::-1][:2]
    return (float(logits[top]), float(logits[top] - logits[second]),
            int(second))


def main():
    from repro.configs.registry import get_config
    from repro.core.plan import ParallelPlan, plan_search
    from repro.launch.serve import make_paged_server, serve
    from repro.models import lm
    from repro.models.paging import PagedConfig
    from repro.runtime.server import Request, ServerConfig

    ndev = len(jax.devices())
    check(ndev >= 8, f"8 simulated devices attached (have {ndev})")

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    MAX_NEW = 6

    # 1. decode-objective search: the serve plan carries a decode sub-plan
    #    and (on the IB preset at tp=8) its factorization differs from
    #    train's — the bandwidth objective balances payload across both
    #    dims, the latency objective folds everything into one boundary
    res = plan_search("ic4", 8, model=cfg, batch=4, seq=16,
                      decode_batch=4)
    plan = res.best
    check(plan.decode is not None, f"decode sub-plan attached: {plan.describe()}")
    check((plan.decode.d1, plan.decode.d2) != (plan.d1, plan.d2),
          "decode objective picks a different factorization than train "
          f"on ic4: train ({plan.d1},{plan.d2}) vs decode "
          f"({plan.decode.d1},{plan.decode.d2})")
    with tempfile.TemporaryDirectory() as td:
        path = plan.save(os.path.join(td, "plan.json"))
        loaded = ParallelPlan.load(path)
    check(loaded == plan, "v3 plan JSON round-trip is exact")

    # 1b. static conformance: the plain prefill build (train-view) and the
    #     decode-view decode build — exactly what the wave-loop reference
    #     runs — must emit the collectives the serve plan priced
    from repro.analysis import assert_step_conforms
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import (batch_struct, build_decode_step,
                                    build_prefill)

    dview = loaded.decode_view()
    ap = lm.abstract_params(cfg)
    pfn, _ = build_prefill(cfg, plan=loaded)
    ab = batch_struct(cfg, ShapeConfig("x", 16, 4, "prefill"), "prefill")
    assert_step_conforms(pfn, cfg, loaded, "prefill", 4, 16, ap, ab)
    dfn, dinfo = build_decode_step(cfg, B=4, s_max=32, plan=dview)
    acaches, _ = lm.init_decode_caches(cfg, dinfo.ctx, 4, 32, abstract=True)
    assert_step_conforms(dfn, cfg, dview, "decode", 4, 1, ap,
                         jax.ShapeDtypeStruct((4, 1), np.int32),
                         jax.ShapeDtypeStruct((), np.int32), acaches)
    check(True, "prefill + decode-view builds conform to the serve plan "
                "(static lint)")

    # 2. mixed-length workload through the paged continuous server built
    #    on the decode view
    rng = np.random.default_rng(0)
    lens = [10, 7, 3, 12, 5, 9]
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in lens]
    pool = 1 + sum(-(-(n + MAX_NEW) // 4) for n in lens)
    scfg = ServerConfig(
        batch_slots=3, prefill_chunk=4,
        paged=PagedConfig(page_size=4, num_pages=pool, pages_per_slot=8))
    server, info = make_paged_server(cfg, scfg, params, plan=loaded)
    check((info.ctx.d1, info.ctx.d2) == (loaded.decode.d1, loaded.decode.d2),
          "serving mesh is the decode sub-plan's factorization")
    for rid, p in enumerate(prompts):
        server.submit(Request(rid=rid, prompt=p, max_new=MAX_NEW))
    ticks = server.run_until_drained()
    check(len(server.completed) == len(prompts),
          f"all {len(prompts)} requests drained in {ticks} ticks")
    check(server.alloc.free_pages == pool - 1,
          "every page returned to the pool after drain")
    got = [r.out for r in sorted(server.completed, key=lambda r: r.rid)]

    # 3. wave-loop baseline (equal-length waves padded to the longest
    #    prompt) must emit the SAME greedy tokens per request
    view = loaded.decode_view()
    pad_to = max(lens)
    padded = []
    for p in prompts:
        buf = np.zeros((pad_to,), np.int32)
        buf[: len(p)] = p
        padded.append(buf)
    ref = []
    for i in range(0, len(prompts), 3):
        batch = padded[i: i + 3]
        while len(batch) < 3:
            batch.append(np.zeros(pad_to, np.int32))
        outs = serve(cfg, None, params, batch, MAX_NEW, 32, plan=view)
        ref.extend(o.tolist() for o in outs[: len(padded[i: i + 3])])
    ref = ref[: len(prompts)]
    # the wave loop left-pads with token 0 *inside* the sequence when a
    # prompt is shorter than the wave — compare only requests whose
    # natural length equals the wave pad (exact semantics); for the rest
    # compare against the per-request B=1 wave run
    exact = [i for i, n in enumerate(lens) if n == pad_to]
    check(all(got[i] == ref[i] for i in exact),
          f"wave-loop parity on full-length prompts {exact}")
    solo = []
    for p in prompts:
        outs = serve(cfg, None, params, [p], MAX_NEW, 32, plan=view)
        solo.append(outs[0].tolist())
    check(got == solo,
          "paged continuous greedy tokens == per-request wave reference "
          "for every mixed-length prompt")

    # 4. recurrent-kind paged serving: zamba's mixed attention+mamba
    #    super-blocks run the same continuous scheduler (slot-addressed
    #    state pools + paged KV for the shared attention block) at
    #    wave-loop token parity: exact, except that a bf16 near-tie of the
    #    wave reference (top-2 margin under BF16_TIE_ULPS) may pick its
    #    runner-up, after which the request is compared no further
    import dataclasses as _dc

    zcfg = get_config("zamba2-7b").reduced()
    zparams = lm.init_params(zcfg, jax.random.PRNGKey(1))
    zlens = [9, 4, 11, 6]
    zprompts = [rng.integers(0, zcfg.vocab_size, size=n, dtype=np.int32)
                for n in zlens]
    zpool = 1 + sum(-(-(n + MAX_NEW) // 4) for n in zlens)
    zscfg = ServerConfig(
        batch_slots=2, prefill_chunk=4,
        paged=PagedConfig(page_size=4, num_pages=zpool, pages_per_slot=8))
    zserver, zinfo = make_paged_server(zcfg, zscfg, zparams, plan=loaded)
    check(zserver.cfg.recurrent, "zamba server runs the slot-addressed step")
    for rid, p in enumerate(zprompts):
        zserver.submit(Request(rid=rid, prompt=p, max_new=MAX_NEW))
    zticks = zserver.run_until_drained()
    check(len(zserver.completed) == len(zprompts)
          and zserver.alloc.free_pages == zpool - 1,
          f"zamba paged serve drained in {zticks} ticks, pages returned")
    zgot = [r.out for r in sorted(zserver.completed, key=lambda r: r.rid)]
    zsolo = []
    for p in zprompts:
        outs = serve(zcfg, None, zparams, [p], MAX_NEW, 32, plan=view)
        zsolo.append(outs[0].tolist())
    ties = 0
    for rid, (g, r) in enumerate(zip(zgot, zsolo)):
        k = next((i for i, (a, b) in enumerate(zip(g, r)) if a != b), None)
        if k is None:
            continue
        top, margin, runner_up = top2(
            zcfg, zparams, list(zprompts[rid]) + r[:k])
        bound = BF16_TIE_ULPS * bf16_ulp(top)
        print(f"[serve-smoke] zamba request {rid}: token {k} paged {g[k]} "
              f"vs wave {r[k]}; wave top logit {top:.6f}, top-2 margin "
              f"{margin:.6f}, runner-up {runner_up}")
        check(margin < bound and g[k] == runner_up,
              f"zamba request {rid} token {k}: a near-tie, the paged pick "
              f"is the runner-up (margin {margin:.6f} < {bound:.6f})")
        ties += 1
    check(len(zgot) == len(zsolo) and ties <= 1,
          f"zamba paged continuous greedy tokens == wave reference up to "
          f"{ties} near-tie(s) in {len(zsolo)} requests")

    # 5. MTP self-speculative decode: same arch with the MTP head, served
    #    with --speculate, must emit EXACTLY the plain paged greedy tokens
    #    (speculation changes latency, never the argmax sequence)
    mcfg = _dc.replace(cfg, mtp=True)
    mparams = lm.init_params(mcfg, jax.random.PRNGKey(0))
    plain_server, _ = make_paged_server(mcfg, scfg, mparams, plan=loaded)
    for rid, p in enumerate(prompts):
        plain_server.submit(Request(rid=rid, prompt=p, max_new=MAX_NEW))
    plain_server.run_until_drained()
    plain = [r.out for r in sorted(plain_server.completed,
                                   key=lambda r: r.rid)]
    sscfg = _dc.replace(scfg, speculate=True)
    spec_server, _ = make_paged_server(mcfg, sscfg, mparams, plan=loaded)
    check(spec_server.cfg.speculate, "speculative server enabled")
    for rid, p in enumerate(prompts):
        spec_server.submit(Request(rid=rid, prompt=p, max_new=MAX_NEW))
    sticks = spec_server.run_until_drained()
    check(len(spec_server.completed) == len(prompts)
          and spec_server.alloc.free_pages == pool - 1,
          f"speculative serve drained in {sticks} ticks, pages returned")
    spec = [r.out for r in sorted(spec_server.completed,
                                  key=lambda r: r.rid)]
    st = spec_server.stats()
    check(spec == plain,
          f"speculative greedy tokens EXACTLY match plain paged decode "
          f"(accept_rate={st['spec_accept_rate']:.3f})")

    # 6. copy-on-write prefix cache: a shared system prompt admits with
    #    page-aligned reuse and still produces identical greedy tokens
    pscfg = _dc.replace(scfg, prefix_cache=True)
    sys_prefix = rng.integers(0, cfg.vocab_size, size=8, dtype=np.int32)
    pprompts = [np.concatenate([sys_prefix, p]) for p in prompts[:4]]
    ppool = 1 + sum(-(-(len(p) + MAX_NEW) // 4) for p in pprompts)
    pscfg = _dc.replace(
        pscfg, paged=_dc.replace(scfg.paged, num_pages=ppool))
    pref_server, _ = make_paged_server(cfg, pscfg, params, plan=loaded)
    for rid, p in enumerate(pprompts):
        pref_server.submit(Request(rid=rid, prompt=p, max_new=MAX_NEW))
    pref_server.run_until_drained()
    pst = pref_server.stats()
    check(pst["prefix_hit_rate"] > 0.0,
          f"prefix cache hit on the shared system prompt "
          f"(hit_rate={pst['prefix_hit_rate']:.3f})")
    pgot = [r.out for r in sorted(pref_server.completed,
                                  key=lambda r: r.rid)]
    psolo = []
    for p in pprompts:
        outs = serve(cfg, None, params, [p], MAX_NEW, 32, plan=view)
        psolo.append(outs[0].tolist())
    check(pgot == psolo,
          "prefix-cached greedy tokens == wave reference (COW is exact)")
    print("[serve-smoke] PASS")


if __name__ == "__main__":
    main()

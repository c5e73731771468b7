"""Serving launcher: paged continuous batching (fast path) or the wave loop.

Two modes:

  - ``--mode paged`` (default): chunked prefill + continuous batching
    over block-paged KV caches (``runtime.server.Server``).  Mixed-length
    requests share one compiled paged step (prefill chunks at b=1, decode
    ticks at b=slots) — no per-length recompiles, no wave barriers.
  - ``--mode wave``: the seed-era wave loop (kept as a baseline).

The distribution strategy comes from a ParallelPlan — searched in-process
(``--auto-atp``, which also runs the latency-aware DECODE objective and
attaches its sub-plan) or loaded from a saved artifact (``--plan``).
Serving is decode-dominated, so when the plan carries a decode sub-plan
whose factorization differs from the train mesh, the whole serving stack
is built on ``plan.decode_view()`` — the ATP thesis applied to inference:
the objective (here: per-token latency, not per-step bandwidth) picks the
mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --reduced --requests 6 --max-new 8 [--plan plan.json | --auto-atp]
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.mesh import atp_topo
from repro.core.plan import ParallelPlan
from repro.launch.compile_cache import dispatch_span, enable_compile_cache
from repro.launch.steps import (build_decode_step, build_paged_step,
                                init_params_sharded, resolve_ctx)
from repro.models import lm
from repro.models.paging import PagedConfig
from repro.runtime.server import CALL_ARGS, Request, Server, ServerConfig

log = logging.getLogger("repro.serve")
span = jax.profiler.TraceAnnotation


def serve(cfg, topo, params, prompts, max_new: int, max_seq: int,
          plan: ParallelPlan | None = None):
    """Wave baseline.  prompts: list of equal-length int arrays (one wave)."""
    topo = topo if topo is not None else plan.topo()
    mesh = topo.build()
    ctx = resolve_ctx(topo, plan, decode=True)
    B = len(prompts)
    plen = len(prompts[0])
    prefill_fn, info = build_decode_step(cfg, topo, B, max_seq, mesh=mesh,
                                         seq_in=plen, plan=plan)
    decode_fn, _ = build_decode_step(cfg, topo, B, max_seq, mesh=mesh,
                                     plan=plan)
    params = jax.device_put(params, info.sharding(info.pspecs))
    caches, cache_specs = lm.init_decode_caches(cfg, ctx, B, max_seq)
    caches = jax.device_put(caches, info.sharding(cache_specs))

    toks = jnp.asarray(np.stack(prompts))
    nxt, caches = prefill_fn(params, toks, jnp.int32(0), caches)
    outs = [np.asarray(nxt)]
    pos = plen
    for _ in range(max_new - 1):
        nxt, caches = decode_fn(params, jnp.asarray(outs[-1])[:, None],
                                jnp.int32(pos), caches)
        outs.append(np.asarray(nxt))
        pos += 1
    return np.stack(outs, axis=1)  # [B, max_new]


def serving_view(plan: ParallelPlan | None, topo=None):
    """``(plan, topo)`` the serving stack is built on.

    With a plan, its ``decode_view()`` wins: serving is decode-dominated,
    and prefill/decode share one set of sharded params and caches, so the
    decode sub-plan's (d1, d2) is the serving mesh."""
    if plan is None:
        if topo is None:
            raise TypeError("serving needs a plan or a topo")
        return None, topo
    view = plan.decode_view()
    if (view.d1, view.d2) != (plan.d1, plan.d2):
        log.info("decode sub-plan re-meshes serving: %s -> "
                 "DeviceMesh(%d,%d)", plan.describe(), view.d1, view.d2)
    return view, view.topo()


def init_serving_params(cfg, key, plan: ParallelPlan | None = None,
                        topo=None):
    """Params created in place on the mesh ``make_paged_server`` builds
    for the same ``plan``/``topo``, so handing them over moves nothing."""
    plan, topo = serving_view(plan, topo)
    ctx = resolve_ctx(topo, plan, decode=True)
    return init_params_sharded(cfg, key, topo.build(),
                               lm.param_specs(cfg, ctx))


def make_paged_server(cfg, scfg: ServerConfig, params,
                      plan: ParallelPlan | None = None, topo=None):
    """Build the paged continuous-batching server on the serving mesh.

    With a plan whose decode sub-plan prescribes a different (d1, d2)
    than the train mesh, the stack is built from ``plan.decode_view()``
    — serving is decode-dominated, and prefill/decode share one set of
    sharded params and caches, so the decode mesh wins.

    Mode resolution: recurrent archs (mamba/zamba/xlstm segments) get
    the slot-addressed step automatically; ``speculate``/``prefix_cache``
    come from the ServerConfig OR the plan's decode sub-plan (the search
    records when they pay), and are downgraded with a log line when the
    arch cannot support them (no MTP head, recurrent state).
    """
    from repro.configs.base import segments

    plan, topo = serving_view(plan, topo)
    if plan is not None and plan.decode is not None:
        dec = plan.decode
        scfg = dataclasses.replace(
            scfg, speculate=scfg.speculate or dec.speculate,
            prefix_cache=scfg.prefix_cache or dec.prefix_cache)
    recurrent = any(s.kind in lm.RECURRENT_STATE_KINDS
                    for s in segments(cfg))
    if scfg.speculate and (not cfg.mtp or recurrent):
        log.info("speculative decode off: %s",
                 "no MTP head" if not cfg.mtp else "recurrent state")
        scfg = dataclasses.replace(scfg, speculate=False)
    if scfg.prefix_cache and recurrent:
        log.info("prefix cache off: recurrent state is not page-addressable")
        scfg = dataclasses.replace(scfg, prefix_cache=False)
    scfg = dataclasses.replace(scfg, recurrent=recurrent)
    step_fn, init_caches, info = _build_paged_step_fn(cfg, scfg, params,
                                                      topo, plan)
    return Server(scfg, step_fn, init_caches), info


def _build_paged_step_fn(cfg, scfg: ServerConfig, params, topo,
                         plan: ParallelPlan | None, devices=None):
    """Compile the paged step for one mesh and wrap it in the Server's
    host-side calling convention.

    The mode flags in ``scfg`` must already be resolved (see
    ``make_paged_server``).  ``devices`` restricts the mesh to a device
    subset — the elastic remesh path passes the survivors.  Returns
    ``(step_fn, init_caches, info)``: everything ``Server(...)`` or
    ``Server.reshape(...)`` needs.
    """
    recurrent = scfg.recurrent
    mesh = topo.build(devices) if devices is not None else topo.build()
    step, info = build_paged_step(
        cfg, topo, paged_cfg=scfg.paged, mesh=mesh, plan=plan,
        slots=scfg.batch_slots if recurrent else None,
        speculate=scfg.speculate)
    params = jax.device_put(params, info.sharding(info.pspecs))

    def init_caches():
        caches, cache_specs = lm.init_paged_caches(
            cfg, info.ctx, scfg.paged,
            slots=scfg.batch_slots if recurrent else None)
        return jax.device_put(caches, info.sharding(cache_specs))

    def step_fn(*args):
        """(tokens, start, table[, slot], caches) -> (tokens[, drafts],
        caches): host arrays in, host tokens out, one profiler span per
        part of the call."""
        *host, caches = args
        tags = CALL_ARGS.get()
        with span("serve.call.upload", **tags):
            dev = [jnp.asarray(a) for a in host]
        with dispatch_span("serve.call.dispatch", "serve.call.recompile",
                           **tags):
            *toks, caches = step(params, *dev, caches)
        with span("serve.call.read", **tags):
            return (*(np.asarray(t) for t in toks), caches)

    step_fn.decode_attention = info.decode_attention
    return step_fn, init_caches, info


def remesh_paged_server(server: Server, cfg, params,
                        plan: ParallelPlan | None = None, topo=None,
                        devices=None):
    """Shrink (or re-mesh) a live paged server onto surviving devices.

    Recompiles the paged step on the new mesh — ``plan`` should be the
    re-searched survivors' plan (its ``decode_view`` wins, exactly as at
    construction) or ``topo`` an explicit topology; ``devices`` the
    surviving pool — and hands it to ``Server.reshape``, which replays
    every in-flight request's progress as prompt continuation on the new
    mesh (greedy-token parity; see its docstring).  The server keeps its
    queue, completed/expired lists, deadlines and counters: from the
    client's side a remesh is just a burst of re-prefill latency.
    Returns the new step ``info``.
    """
    plan, topo = serving_view(plan, topo)
    step_fn, init_caches, info = _build_paged_step_fn(
        cfg, server.cfg, params, topo, plan, devices=devices)
    server.reshape(step_fn, init_caches)
    log.info("server remeshed onto %s: %d in-flight requests replaying",
             topo, len(server.queue))
    return info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", choices=("paged", "wave"), default="paged")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--d1", type=int, default=1)
    ap.add_argument("--d2", type=int, default=1)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size (0 = sized to the workload)")
    ap.add_argument("--page-dtype", choices=("bf16", "int8", "fp8"),
                    default="bf16",
                    help="KV page-pool storage dtype (int8/fp8 store 1 "
                         "byte/elem + fp16 per-position scales)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="copy-on-write prefix sharing across requests "
                         "(radix index over page contents)")
    ap.add_argument("--speculate", action="store_true",
                    help="MTP self-speculative decode (needs cfg.mtp; "
                         "exact greedy parity)")
    ap.add_argument("--plan", default=None,
                    help="load a saved ParallelPlan JSON (train --save-plan)")
    ap.add_argument("--auto-atp", action="store_true",
                    help="search a plan for this arch/shape (paper §3.5), "
                         "including the latency-aware decode objective")
    ap.add_argument("--topology", default="v5e",
                    help="comm-matrix preset for --auto-atp")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    plan = None
    if args.plan:
        plan = ParallelPlan.load(args.plan)
        log.info("loaded plan %s: %s", args.plan, plan.describe())
    elif args.auto_atp:
        from repro.core.plan import plan_search

        plan = plan_search(
            args.topology, args.d1 * args.d2, model=cfg,
            batch=args.slots, seq=args.prompt_len + args.max_new,
            dp=args.dp, decode_batch=args.slots).best
        log.info("ATP plan search picked %s", plan.describe())
    topo = plan.topo() if plan is not None else atp_topo(args.dp, args.d1,
                                                         args.d2)
    params = init_serving_params(cfg, jax.random.PRNGKey(0), plan=plan,
                                 topo=topo)

    rng = np.random.default_rng(0)
    if args.mode == "paged":
        # mixed prompt lengths: the workload the paged path is built for
        lens = [max(1, int(rng.integers(args.prompt_len // 2,
                                        args.prompt_len + 1)))
                for _ in range(args.requests)]
    else:
        # the wave loop decodes in lockstep from one shared position and
        # would condition shorter prompts on their padding — keep its
        # workload equal-length (mixed lengths are the paged mode's job)
        lens = [args.prompt_len] * args.requests
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in lens]

    if args.mode == "paged":
        mp = -(-(args.max_seq) // args.page_size)
        num_pages = args.num_pages or (
            1 + sum(-(-(n + args.max_new) // args.page_size)
                    for n in lens))
        scfg = ServerConfig(
            batch_slots=args.slots, prefill_chunk=args.prefill_chunk,
            paged=PagedConfig(page_size=args.page_size,
                              num_pages=num_pages, pages_per_slot=mp,
                              page_dtype=args.page_dtype),
            prefix_cache=args.prefix_cache, speculate=args.speculate)
        server, _ = make_paged_server(cfg, scfg, params, plan=plan,
                                      topo=topo)
        for rid, p in enumerate(prompts):
            server.submit(Request(rid=rid, prompt=p, max_new=args.max_new))
        ticks = server.run_until_drained()
        for req in sorted(server.completed, key=lambda r: r.rid):
            log.info("request %d (%d prompt tokens) -> %s",
                     req.rid, len(req.prompt), req.out)
        st = server.stats()
        log.info("served %d requests in %d ticks (continuous); "
                 "pages_shared=%d prefix_hit_rate=%.3f "
                 "spec_accept_rate=%.3f used_cache_bytes=%d",
                 len(server.completed), ticks, st["pages_shared"],
                 st["prefix_hit_rate"], st["spec_accept_rate"],
                 st["used_cache_bytes"])
        return

    # wave baseline: equal-length waves
    done = 0
    wave = 0
    pending = list(prompts)
    while pending:
        batch = pending[: args.slots]
        pending = pending[args.slots:]
        while len(batch) < args.slots:   # pad the last wave with dummies
            batch.append(np.zeros(args.prompt_len, np.int32))
        outs = serve(cfg, topo, params, batch, args.max_new, args.max_seq,
                     plan=plan)
        for i, o in enumerate(outs[: min(args.slots, done + args.requests - done)]):
            log.info("wave %d slot %d -> %s", wave, i, o.tolist())
        done += len(batch)
        wave += 1
    log.info("served %d requests in %d waves", args.requests, wave)


if __name__ == "__main__":
    main()

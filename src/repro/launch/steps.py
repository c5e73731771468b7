"""Step builders: train_step / prefill / decode, shard_map'd + jitted.

Shared by the dry-run, the trainer, and the server.  Every builder returns
(jitted_fn, StepInfo) where StepInfo carries the specs needed to construct
ShapeDtypeStruct inputs (dry-run) or to device_put host data (real run).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.configs.base import ModelConfig, ShapeConfig, segments
from repro.core.atp import ATPContext, make_context
from repro.core.mesh import MeshTopo
from repro.models import lm
from repro.optim import adamw


@dataclasses.dataclass
class StepInfo:
    mesh: jax.sharding.Mesh
    ctx: ATPContext
    pspecs: Any
    bspecs: Any
    ospecs: Any = None
    cache_specs: Any = None

    def sharding(self, spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))


def init_params_sharded(cfg: ModelConfig, key, mesh: jax.sharding.Mesh,
                        pspecs):
    """``lm.init_params`` compiled with the param shardings as outputs, so
    each device only ever holds its own shards (an unsharded init puts the
    whole model on the first device before any ``device_put``).  Same
    values as the unsharded init for the same key."""
    out = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    return jax.jit(lambda k: lm.init_params(cfg, k), out_shardings=out)(key)


def _dp_axes_spec(topo: MeshTopo):
    names = [a for a in ("pod", "data")
             if topo.has_axis(a) and topo.axis_size(a) > 1]
    return tuple(names) if len(names) > 1 else (names[0] if names else None)


def batch_pspecs(cfg: ModelConfig, topo: MeshTopo, kind: str):
    dp = _dp_axes_spec(topo)
    if cfg.frontend == "vision_patches":
        # a size-1 tp2 is no model axis (the context drops it): sharding
        # the embeds over it would type them varying where nothing
        # reduces them
        ax2 = ("tp2" if topo.has_axis("tp2") and topo.axis_size("tp2") > 1
               else None)
        sp = {"embeds": P(dp, None, ax2), "positions3": P(None, dp, None)}
    else:
        sp = {"tokens": P(dp, None)}
    if kind == "train":
        sp["labels"] = P(dp, None)
    return sp


def batch_struct(cfg: ModelConfig, shape: ShapeConfig, kind: str):
    """ShapeDtypeStruct stand-ins for every model input (dry-run §e.2)."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.frontend == "vision_patches":
        b = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16),
             "positions3": jax.ShapeDtypeStruct((3, B, S), jnp.int32)}
    else:
        b = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if kind == "train":
        b["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return b


# ---------------------------------------------------------------------------


def resolve_ctx(topo: MeshTopo | None, plan, chunks: int = 1,
                 decode: bool = False) -> ATPContext:
    """One context path for every builder: the plan wins when given.

    Keeping this single funnel is what guarantees a searched/saved plan
    reaches train, prefill AND decode identically (no builder hand-rolls
    its own defaults and silently drops knobs).  ``decode`` does two
    things:

      - masks seq_parallel — globally AND in every per-segment entry: the
        sequence-parallel block I/O spec is defined over a full sequence
        and does not apply to cached decode (the model raises if asked);
      - applies the plan's :class:`~repro.core.atp.DecodePlan` sub-plan
        (format_version 3) for the mesh-layout-NEUTRAL knobs: decode
        boundary_mode and chunks=1 replace the train knobs in every
        segment view.  The decode factorization (d1, d2) is NOT applied
        here — a builder cannot re-mesh mid-serving under shared params;
        a deployment that wants the decode mesh builds everything from
        ``plan.decode_view()`` up front (``launch/serve.py`` does).
    """
    if plan is not None:
        ctx = make_context(topo, plan=plan)
    elif topo is None:
        raise TypeError("builder needs a MeshTopo or a ParallelPlan")
    else:
        ctx = make_context(topo, chunks=chunks)
    if decode and plan is not None \
            and getattr(plan, "decode", None) is not None:
        dec = plan.decode
        wd = getattr(dec, "wire_dtype", "bf16")
        ctx = dataclasses.replace(
            ctx, chunks=dec.chunks, boundary_mode=dec.boundary_mode,
            wire_dtype=wd,
            segment_plans=tuple(
                dataclasses.replace(s, chunks=dec.chunks,
                                    boundary_mode=dec.boundary_mode,
                                    wire_dtype=wd)
                for s in ctx.segment_plans))
    if decode and ctx.any_seq_parallel:
        ctx = dataclasses.replace(
            ctx, seq_parallel=False,
            segment_plans=tuple(dataclasses.replace(s, seq_parallel=False)
                                for s in ctx.segment_plans))
    return ctx


def _check_vma(ctx: ATPContext) -> bool:
    """Ring boundaries decompose psums into ppermute rings whose outputs
    the vma type system labels *varying* (unlike lax.psum's invariant
    output), so the replication checker cannot certify them — numerical
    equivalence is pinned by the bitwise-parity tests instead.  Ring in ANY
    segment's plan disqualifies the whole step.  Delegates to
    :func:`repro.core.atp.vma_rewrite_active` — the same predicate gates
    the manual ``grad_sync`` barriers (no rewrite => manual psums), so
    every build path has exactly one gradient reduction."""
    from repro.core.atp import vma_rewrite_active

    return vma_rewrite_active(ctx)


def build_train_step(cfg: ModelConfig, topo: MeshTopo | None = None,
                     opt_cfg: adamw.AdamWConfig | None = None,
                     chunks: int = 1, remat: bool = True,
                     mesh: jax.sharding.Mesh | None = None,
                     plan=None):
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    ctx = resolve_ctx(topo, plan, chunks)
    topo = ctx.topo
    mesh = mesh if mesh is not None else topo.build()
    pspecs = lm.param_specs(cfg, ctx)
    ospecs = adamw.opt_state_specs(pspecs, ctx, opt_cfg.mode)
    rep = adamw.replication_factors(pspecs, ctx)
    bspecs = batch_pspecs(cfg, topo, "train")
    mspecs = {"loss": P(), "lr": P(), "grad_norm": P()}

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: lm.train_loss(ctx, cfg, p, batch, remat=remat))(params)
        new_p, new_o, metrics = adamw.apply_adamw(
            opt_cfg, ctx, params, grads, opt_state, rep)
        metrics["loss"] = loss
        return new_p, new_o, metrics

    fn = shard_map(local_step, mesh=mesh,
                   in_specs=(pspecs, ospecs, bspecs),
                   out_specs=(pspecs, ospecs, mspecs), check_vma=_check_vma(ctx))
    info = StepInfo(mesh, ctx, pspecs, bspecs, ospecs)
    jit_fn = jax.jit(
        fn,
        in_shardings=(info.sharding(pspecs), info.sharding(ospecs),
                      info.sharding(bspecs)),
        out_shardings=(info.sharding(pspecs), info.sharding(ospecs),
                       info.sharding(mspecs)),
        donate_argnums=(0, 1))
    return jit_fn, info


def build_prefill(cfg: ModelConfig, topo: MeshTopo | None = None,
                  chunks: int = 1,
                  mesh: jax.sharding.Mesh | None = None,
                  plan=None):
    """Forward-only serve step: batch -> greedy next token [B]."""
    ctx = resolve_ctx(topo, plan, chunks)
    topo = ctx.topo
    mesh = mesh if mesh is not None else topo.build()
    pspecs = lm.param_specs(cfg, ctx)
    bspecs = batch_pspecs(cfg, topo, "prefill")
    dp = _dp_axes_spec(topo)

    def local(params, batch):
        logits = lm.prefill_logits(ctx, cfg, params, batch)
        return _greedy_pick(ctx, cfg, logits)

    fn = shard_map(local, mesh=mesh, in_specs=(pspecs, bspecs),
                   out_specs=P(dp), check_vma=_check_vma(ctx))
    info = StepInfo(mesh, ctx, pspecs, bspecs)
    jit_fn = jax.jit(fn,
                     in_shardings=(info.sharding(pspecs), info.sharding(bspecs)),
                     out_shardings=NamedSharding(mesh, P(dp)))
    return jit_fn, info


def _greedy_pick(ctx: ATPContext, cfg: ModelConfig, logits):
    """Vocab-parallel greedy argmax.  logits [b, V/d1] -> token ids [b]."""
    with jax.named_scope("shell:pick"):
        v_loc = logits.shape[-1]
        lf = logits.astype(jnp.float32)
        local_max = jnp.max(lf, axis=-1)
        local_arg = (jnp.argmax(lf, axis=-1).astype(jnp.int32)
                     + ctx.index1() * v_loc)
        if ctx.ax1 is None:
            return local_arg
        gmax = lax.pmax(local_max, ctx.ax1)
        cand = jnp.where(local_max >= gmax, local_arg, jnp.int32(2**30))
        return lax.pmin(cand, ctx.ax1)


def build_paged_step(cfg: ModelConfig, topo: MeshTopo | None = None,
                     paged_cfg=None,
                     mesh: jax.sharding.Mesh | None = None,
                     plan=None, slots: int | None = None,
                     speculate: bool = False):
    """The compiled paged cache-write step (decode tick AND prefill chunk).

    Signature: (params, tokens [b, s], start [b], table [b, mp],
    caches) -> (greedy tokens [b, s], new caches).

    The serving fast path runs this one jitted function at exactly two
    shapes — prefill chunk (b=1, s=chunk) and decode tick (b=slots, s=1)
    — and reuses them across every request length: lengths/positions are
    runtime data (per-slot ``start`` + page-table rows), not shapes, so
    mixed-length continuous batching never recompiles.  Greedy picks for
    every input position come back so the scheduler can read the last
    *valid* position of a padded final chunk on the host.

    Two opt-in variants (the default signature is untouched):

      - recurrent archs (mamba/zamba/xlstm segments) need ``slots`` (the
        scheduler's ``batch_slots``, sizing the per-slot state pools) and
        the step gains a 4th positional input ``slot [b]`` — per-row slot
        ids, sentinel = ``slots`` for masked rows (state writes drop);
      - ``speculate=True`` (requires ``cfg.mtp``) returns
        (tokens, drafts, caches): ``drafts[b, s]`` is the MTP head's
        greedy pick for the position AFTER each trunk pick — the free
        draft token self-speculative decode verifies next tick.

    ``decode=True`` context resolution applies the plan's decode
    sub-plan knobs (boundary_mode, chunks=1) and masks seq_parallel.
    """
    from repro.models.paging import PagedConfig

    pcfg = paged_cfg if paged_cfg is not None else PagedConfig()
    needs_slot = any(s.kind in lm.RECURRENT_STATE_KINDS
                     for s in segments(cfg))
    if needs_slot and slots is None:
        raise ValueError(
            "recurrent kinds (mamba/zamba/xlstm) need "
            "build_paged_step(..., slots=<scheduler batch_slots>)")
    if speculate and not cfg.mtp:
        raise ValueError("speculate=True needs an MTP head (cfg.mtp)")
    if speculate and needs_slot:
        raise NotImplementedError(
            "self-speculative decode rolls rejected drafts back by KV "
            "length; recurrent state has no position axis to roll back")
    ctx = resolve_ctx(topo, plan, decode=True)
    topo = ctx.topo
    mesh = mesh if mesh is not None else topo.build()
    pspecs = lm.param_specs(cfg, ctx)
    _, cache_specs = lm.init_paged_caches(cfg, ctx, pcfg, abstract=True,
                                          slots=slots)
    tspec = P(None, None)
    info = StepInfo(mesh, ctx, pspecs, tspec, cache_specs=cache_specs)

    if needs_slot:
        def local(params, tokens, start, table, slot, caches):
            logits, new_caches = lm.paged_step(ctx, cfg, params, tokens,
                                               start, table, caches,
                                               slot=slot)
            return _greedy_pick(ctx, cfg, logits), new_caches

        fn = shard_map(local, mesh=mesh,
                       in_specs=(pspecs, tspec, P(None), tspec, P(None),
                                 cache_specs),
                       out_specs=(tspec, cache_specs),
                       check_vma=_check_vma(ctx))
        jit_fn = jax.jit(
            fn,
            in_shardings=(info.sharding(pspecs), NamedSharding(mesh, tspec),
                          NamedSharding(mesh, P(None)),
                          NamedSharding(mesh, tspec),
                          NamedSharding(mesh, P(None)),
                          info.sharding(cache_specs)),
            out_shardings=(NamedSharding(mesh, tspec),
                           info.sharding(cache_specs)),
            donate_argnums=(5,))
        return jit_fn, info

    if speculate:
        def local(params, tokens, start, table, caches):
            logits, h, new_caches = lm.paged_step(ctx, cfg, params, tokens,
                                                  start, table, caches,
                                                  with_hidden=True)
            toks = _greedy_pick(ctx, cfg, logits)
            b, s = tokens.shape
            prange = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            if cfg.mrope_sections:
                positions = jnp.broadcast_to(prange[None], (3, b, s))
            else:
                positions = prange
            dl = lm.mtp_draft_logits(ctx, cfg, params, h, positions, toks)
            return toks, _greedy_pick(ctx, cfg, dl), new_caches

        fn = shard_map(local, mesh=mesh,
                       in_specs=(pspecs, tspec, P(None), tspec, cache_specs),
                       out_specs=(tspec, tspec, cache_specs),
                       check_vma=_check_vma(ctx))
        jit_fn = jax.jit(
            fn,
            in_shardings=(info.sharding(pspecs), NamedSharding(mesh, tspec),
                          NamedSharding(mesh, P(None)),
                          NamedSharding(mesh, tspec),
                          info.sharding(cache_specs)),
            out_shardings=(NamedSharding(mesh, tspec),
                           NamedSharding(mesh, tspec),
                           info.sharding(cache_specs)),
            donate_argnums=(4,))
        return jit_fn, info

    def local(params, tokens, start, table, caches):
        logits, new_caches = lm.paged_step(ctx, cfg, params, tokens, start,
                                           table, caches)
        return _greedy_pick(ctx, cfg, logits), new_caches

    fn = shard_map(local, mesh=mesh,
                   in_specs=(pspecs, tspec, P(None), tspec, cache_specs),
                   out_specs=(tspec, cache_specs), check_vma=_check_vma(ctx))
    jit_fn = jax.jit(
        fn,
        in_shardings=(info.sharding(pspecs), NamedSharding(mesh, tspec),
                      NamedSharding(mesh, P(None)),
                      NamedSharding(mesh, tspec),
                      info.sharding(cache_specs)),
        out_shardings=(NamedSharding(mesh, tspec),
                       info.sharding(cache_specs)),
        donate_argnums=(4,))
    return jit_fn, info


def build_decode_step(cfg: ModelConfig, topo: MeshTopo | None = None,
                      B: int = 1, s_max: int = 64,
                      mesh: jax.sharding.Mesh | None = None,
                      seq_in: int = 1, plan=None):
    """One decode step (seq_in>1 = prefill-into-cache for serving).

    Signature: (params, tokens [B, seq_in], pos scalar, caches) ->
    (next tokens [B], new caches)."""
    ctx = resolve_ctx(topo, plan, decode=True)
    topo = ctx.topo
    mesh = mesh if mesh is not None else topo.build()
    pspecs = lm.param_specs(cfg, ctx)
    _, cache_specs = lm.init_decode_caches(cfg, ctx, B, s_max, abstract=True)
    dp = _dp_axes_spec(topo) if (ctx.dp and B % ctx.dp == 0) else None
    tspec = P(dp, None)

    def local(params, tokens, pos, caches):
        logits, new_caches = lm.decode_step(ctx, cfg, params, tokens, pos, caches)
        return _greedy_pick(ctx, cfg, logits), new_caches

    fn = shard_map(local, mesh=mesh,
                   in_specs=(pspecs, tspec, P(), cache_specs),
                   out_specs=(P(dp), cache_specs), check_vma=_check_vma(ctx))
    info = StepInfo(mesh, ctx, pspecs, tspec, cache_specs=cache_specs)
    jit_fn = jax.jit(
        fn,
        in_shardings=(info.sharding(pspecs), NamedSharding(mesh, tspec),
                      NamedSharding(mesh, P()), info.sharding(cache_specs)),
        out_shardings=(NamedSharding(mesh, P(dp)), info.sharding(cache_specs)),
        donate_argnums=(3,))
    return jit_fn, info

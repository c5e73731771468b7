"""Unified language model: embedding -> block stacks -> head, ATP-sharded.

Every architecture is expressed as a list of *segments*; each segment is a
scan over `count` identical blocks with stacked params (compile-time
compact HLO).  Segment kinds:

  dense       GQA attention + MLP (all dense archs; gemma2 via window array)
  moe         GQA attention + MoE FFN (dbrx)
  mla_dense   MLA attention + dense MLP (deepseek first 3 layers)
  mla_moe     MLA attention + MoE (deepseek)
  zamba       super-block: shared attention block + 5 mamba2 blocks
  mamba       plain mamba2 blocks (zamba tail)
  xlstm       super-block: 7 mLSTM + 1 sLSTM

All functions here run INSIDE shard_map (local shards + explicit
collectives) except the init/spec helpers.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ModelConfig, Segment,  # noqa: F401 (re-export)
                                ShapeConfig, segments)
from repro.core.atp import (ATPContext, atp_boundary,
                            atp_reduce_scatter, seq_gather, seq_scatter,
                            shard_slice)
from repro.models import layers as L
from repro.models import mamba2, mla, moe, paging, transformer, xlstm

# The segment plan (Segment / segments) lives in repro.configs.base so the
# strategy stack can derive per-segment workloads without importing model
# code; re-exported here because this module is its execution consumer.


# ---------------------------------------------------------------------------
# Per-kind params / specs / apply.
# ---------------------------------------------------------------------------


def _block_params(kind: str, key, cfg: ModelConfig, dtype):
    if kind == "dense":
        return transformer.dense_block_params(key, cfg, dtype)
    if kind == "moe":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln_attn": L.norm_params(cfg, cfg.d_model),
            "attn": transformer.attn_params(k1, cfg, dtype),
            "ln_mlp": L.norm_params(cfg, cfg.d_model),
            "moe": moe.moe_params(k2, cfg, dtype),
        }
    if kind == "mla_dense":
        k1, k2 = jax.random.split(key)
        return {
            "ln_attn": L.norm_params(cfg, cfg.d_model),
            "mla": mla.mla_params(k1, cfg, dtype),
            "ln_mlp": L.norm_params(cfg, cfg.d_model),
            "mlp": transformer.mlp_params(k2, cfg, dtype),
        }
    if kind == "mla_moe":
        k1, k2 = jax.random.split(key)
        return {
            "ln_attn": L.norm_params(cfg, cfg.d_model),
            "mla": mla.mla_params(k1, cfg, dtype),
            "ln_mlp": L.norm_params(cfg, cfg.d_model),
            "moe": moe.moe_params(k2, cfg, dtype),
        }
    if kind == "mamba":
        return mamba2.mamba_params(key, cfg, dtype)
    if kind == "zamba":
        # stacked part: (per-1) mamba blocks per super-block
        per = cfg.ssm.shared_attn_every
        ks = jax.random.split(key, per - 1)
        return {"mamba": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[mamba2.mamba_params(k, cfg, dtype) for k in ks])}
    if kind == "xlstm":
        per = cfg.ssm.slstm_every
        ks = jax.random.split(key, per)
        ml = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[xlstm.mlstm_params(k, cfg, dtype) for k in ks[:-1]])
        sl = xlstm.slstm_params(ks[-1], cfg, dtype)
        return {"mlstm": ml, "slstm": sl}
    raise ValueError(kind)


def _block_specs(kind: str, ctx: ATPContext, cfg: ModelConfig):
    nspec = {"scale": L.feat_spec(ctx)}
    if cfg.norm_kind == "layernorm":
        nspec["bias"] = L.feat_spec(ctx)
    if kind == "dense":
        return transformer.dense_block_specs(ctx, cfg)
    if kind == "moe":
        return {
            "ln_attn": dict(nspec),
            "attn": transformer.attn_param_specs(ctx, cfg),
            "ln_mlp": dict(nspec),
            "moe": moe.moe_param_specs(ctx, cfg),
        }
    if kind == "mla_dense":
        return {
            "ln_attn": dict(nspec),
            "mla": mla.mla_param_specs(ctx, cfg),
            "ln_mlp": dict(nspec),
            "mlp": transformer.mlp_param_specs(ctx, cfg),
        }
    if kind == "mla_moe":
        return {
            "ln_attn": dict(nspec),
            "mla": mla.mla_param_specs(ctx, cfg),
            "ln_mlp": dict(nspec),
            "moe": moe.moe_param_specs(ctx, cfg),
        }
    if kind == "mamba":
        return mamba2.mamba_param_specs(ctx, cfg)
    if kind == "zamba":
        return {"mamba": _stack_specs(mamba2.mamba_param_specs(ctx, cfg))}
    if kind == "xlstm":
        return {"mlstm": _stack_specs(xlstm.mlstm_param_specs(ctx, cfg)),
                "slstm": xlstm.slstm_param_specs(ctx, cfg)}
    raise ValueError(kind)


def _stack_specs(specs):
    return jax.tree.map(lambda s: P(None, *s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _apply_block(kind: str, ctx, cfg, p, x, positions, plan, window, cache,
                 emb0=None, shared=None, paged=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "dense":
        x, nc = transformer.dense_block(ctx, cfg, p, x, positions, plan,
                                        layer_window=window, cache=cache,
                                        paged=paged)
        return x, nc, aux
    if kind == "moe":
        h = L.norm(ctx, cfg, x, p["ln_attn"])
        a, nc = transformer.attn_block(ctx, cfg, p["attn"], h, positions, plan,
                                       layer_window=window, cache=cache,
                                       paged=paged)
        x = x + a
        h = L.norm(ctx, cfg, x, p["ln_mlp"])
        m, aux = moe.moe_block(ctx, cfg, p["moe"], h)
        return x + m, nc, aux
    if kind in ("mla_dense", "mla_moe"):
        # mla_dense supports the sequence-parallel spec: entry norms fold
        # the seq all-gather, and the wo / mlp-down row boundaries
        # psum_scatter back (mla_moe's ctx arrives with seq_parallel
        # masked — MoE dispatch needs ax1-replicated full-sequence I/O)
        sp = ctx.seq_parallel and cache is None
        h = L.norm(ctx, cfg, x, p["ln_attn"], gather_seq=sp)
        a, nc = mla.mla_block(ctx, cfg, p["mla"], h, positions, cache=cache,
                              paged=paged)
        x = x + a
        h = L.norm(ctx, cfg, x, p["ln_mlp"], gather_seq=sp)
        if kind == "mla_dense":
            m = transformer.mlp_block(ctx, cfg, p["mlp"], h)
        else:
            m, aux = moe.moe_block(ctx, cfg, p["moe"], h)
        return x + m, nc, aux
    if kind == "mamba":
        x, ns = mamba2.mamba_block(ctx, cfg, p, x, state=cache)
        return x, ns, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Full model params/specs.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0, 2))
def init_params(cfg: ModelConfig, key, dtype=None) -> dict[str, Any]:
    """Random params for ``cfg`` from ``key``.

    Always one compiled program: op-by-op execution rounds a few scaled
    draws differently, and the sharded init (``launch.steps.
    init_params_sharded``) must give the same bits as this call."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 16)
    h = cfg.d_model
    p: dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, h), jnp.float32)
                  * 0.02).astype(dtype),
        "final_norm": L.norm_params(cfg, h),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(keys[1], (h, cfg.vocab_size), jnp.float32)
                        / math.sqrt(h)).astype(dtype)
    for i, seg in enumerate(segments(cfg)):
        ks = jax.random.split(keys[2 + i], seg.count)
        # one traced block for the whole stack (the same values as one
        # call per key): compile time does not grow with depth
        p[f"seg{i}"] = jax.vmap(
            lambda k, kind=seg.kind: _block_params(kind, k, cfg, dtype))(ks)
    if any(s.kind == "zamba" for s in segments(cfg)):
        k1, k2, k3 = jax.random.split(keys[14], 3)
        # two separate [h, h] projections (a single [2h, h] would break the
        # ax2 row sharding of the concatenated input)
        p["shared_attn"] = {
            "w_in_h": (jax.random.normal(k1, (h, h), jnp.float32)
                       / math.sqrt(2 * h)).astype(dtype),
            "w_in_e": (jax.random.normal(k3, (h, h), jnp.float32)
                       / math.sqrt(2 * h)).astype(dtype),
            "block": transformer.dense_block_params(k2, cfg, dtype),
        }
    if cfg.mtp:
        k1, k2 = jax.random.split(keys[15])
        p["mtp"] = {
            "proj_h": (jax.random.normal(k1, (h, h), jnp.float32)
                       / math.sqrt(2 * h)).astype(dtype),
            "proj_e": (jax.random.normal(k2, (h, h), jnp.float32)
                       / math.sqrt(2 * h)).astype(dtype),
            "block": _block_params("mla_dense" if cfg.mla else "dense",
                                   keys[13], cfg, dtype),
            "norm": L.norm_params(cfg, h),
        }
    return p


def param_specs(cfg: ModelConfig, ctx: ATPContext) -> dict[str, Any]:
    sp: dict[str, Any] = {
        "embed": L.embed_spec(ctx),
        "final_norm": {"scale": L.feat_spec(ctx)},
    }
    if cfg.norm_kind == "layernorm":
        sp["final_norm"]["bias"] = L.feat_spec(ctx)
    if not cfg.tie_embeddings:
        sp["lm_head"] = P(ctx.ax2, ctx.ax1)  # rows over ax2, vocab over ax1
    for i, seg in enumerate(segments(cfg)):
        sp[f"seg{i}"] = _stack_specs(_block_specs(seg.kind, ctx, cfg))
    if any(s.kind == "zamba" for s in segments(cfg)):
        sp["shared_attn"] = {
            "w_in_h": L.col_w_spec(ctx),
            "w_in_e": L.col_w_spec(ctx),
            "block": transformer.dense_block_specs(ctx, cfg),
        }
    if cfg.mtp:
        sp["mtp"] = {
            "proj_h": L.col_w_spec(ctx),
            "proj_e": L.col_w_spec(ctx),
            "block": _block_specs("mla_dense" if cfg.mla else "dense", ctx, cfg),
            "norm": {"scale": L.feat_spec(ctx)},
        }
    return sp


def abstract_params(cfg: ModelConfig, dtype=None):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), dtype))


def count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Decode caches: global shapes + PartitionSpecs per segment kind.
# Replication (kv heads shared across r-group ranks, mLSTM conv) is stored
# explicitly in the global array — memory honesty for the dry-run.
# ---------------------------------------------------------------------------


def _flat_axes(ctx: ATPContext):
    return ctx.tp_axes if ctx.tp_axes else None


def _attn_cache_shape(cfg: ModelConfig, ctx: ATPContext, B: int, s_max: int):
    plan = L.make_attn_plan(ctx, cfg.num_heads, cfg.num_kv_heads)
    banks = ctx.tp * plan.kv_count
    return (B, s_max, banks, cfg.hd)


def init_decode_caches(cfg: ModelConfig, ctx: ATPContext, B: int, s_max: int,
                       dtype=jnp.bfloat16, abstract: bool = False):
    """Returns (caches, specs): per-segment stacked cache trees (GLOBAL
    shapes) and matching PartitionSpecs for shard_map."""
    n = ctx.tp
    # batch < DP degree (long_500k: B=1): replicate over the data axes —
    # DP ranks are idle for single-stream long-context decode
    dp_ok = ctx.dp_axes and B % ctx.dp == 0
    data_ax = ctx.dp_axes if dp_ok else None
    flat = _flat_axes(ctx)

    def arr(shape, dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    def attn_cache(count):
        shape = (count,) + _attn_cache_shape(cfg, ctx, B, s_max)
        c = {"k": arr(shape, dtype), "v": arr(shape, dtype),
             "len": arr((count,), jnp.int32)}
        sp = {"k": P(None, data_ax, None, flat, None),
              "v": P(None, data_ax, None, flat, None),
              "len": P(None)}
        return c, sp

    def mla_cache(count):
        m = cfg.mla
        c = {"ckv": arr((count, B, s_max, m.kv_lora_rank), dtype),
             "krope": arr((count, B, s_max, m.qk_rope_head_dim), dtype),
             "len": arr((count,), jnp.int32)}
        sp = {"ckv": P(None, data_ax, None, None),
              "krope": P(None, data_ax, None, None),
              "len": P(None)}
        return c, sp

    def mamba_cache(count):
        d_inner, nheads = mamba2.mamba_dims(cfg)
        k = cfg.ssm.conv_kernel
        c = {"conv_x": arr((count, B, k - 1, d_inner), dtype),
             "conv_bc": arr((count, B, k - 1, 2 * cfg.ssm.d_state), dtype),
             "ssd": arr((count, B, nheads, cfg.ssm.head_dim, cfg.ssm.d_state),
                        jnp.float32)}
        sp = {"conv_x": P(None, data_ax, None, flat),
              "conv_bc": P(None, data_ax, None, None),
              "ssd": P(None, data_ax, flat, None, None)}
        return c, sp

    def mlstm_cache(count):
        d_inner, nh, dk, dv = xlstm.mlstm_dims(cfg)
        g, r = xlstm.mlstm_plan(ctx, cfg)
        k = cfg.ssm.conv_kernel
        # conv state channels are flat-sharded (v2 head-major layout)
        c = {"conv": arr((count, B, k - 1, d_inner), dtype),
             "C": arr((count, B, n, nh // g, dk, dv // r + 1), jnp.float32)}
        sp = {"conv": P(None, data_ax, None, flat),
              "C": P(None, data_ax, flat, None, None, None)}
        return c, sp

    def slstm_cache(count):
        nh, dh = cfg.num_heads, cfg.d_model // cfg.num_heads
        c = {k2: arr((count, B, nh, dh), jnp.float32) for k2 in ("c", "n", "h")}
        sp = {k2: P(None, data_ax, None, None) for k2 in ("c", "n", "h")}
        return c, sp

    caches, specs = {}, {}
    for i, seg in enumerate(segments(cfg)):
        if seg.kind in ("dense", "moe"):
            caches[f"seg{i}"], specs[f"seg{i}"] = attn_cache(seg.count)
        elif seg.kind in ("mla_dense", "mla_moe"):
            caches[f"seg{i}"], specs[f"seg{i}"] = mla_cache(seg.count)
        elif seg.kind == "mamba":
            caches[f"seg{i}"], specs[f"seg{i}"] = mamba_cache(seg.count)
        elif seg.kind == "zamba":
            ac, asp = attn_cache(seg.count)
            mc, msp = mamba_cache(seg.count)
            mc = jax.tree.map(
                lambda x: (jax.ShapeDtypeStruct(
                    (x.shape[0], seg.inner - 1) + x.shape[1:], x.dtype)
                    if abstract else
                    jnp.zeros((x.shape[0], seg.inner - 1) + x.shape[1:], x.dtype)),
                mc)
            msp = jax.tree.map(lambda s: P(None, *s), msp,
                               is_leaf=lambda x: isinstance(x, P))
            caches[f"seg{i}"] = {"attn": ac, "mamba": mc}
            specs[f"seg{i}"] = {"attn": asp, "mamba": msp}
        elif seg.kind == "xlstm":
            mc, msp = mlstm_cache(seg.count)
            mc = jax.tree.map(
                lambda x: (jax.ShapeDtypeStruct(
                    (x.shape[0], seg.inner - 1) + x.shape[1:], x.dtype)
                    if abstract else
                    jnp.zeros((x.shape[0], seg.inner - 1) + x.shape[1:], x.dtype)),
                mc)
            msp = jax.tree.map(lambda s: P(None, *s), msp,
                               is_leaf=lambda x: isinstance(x, P))
            sc, ssp = slstm_cache(seg.count)
            caches[f"seg{i}"] = {"mlstm": mc, "slstm": sc}
            specs[f"seg{i}"] = {"mlstm": msp, "slstm": ssp}
    return caches, specs


#: segment kinds whose O(s) caches live in a block-paged pool.
PAGED_CACHE_KINDS = frozenset({"dense", "moe", "mla_dense", "mla_moe"})

#: segment kinds holding O(1)-per-slot recurrent state.  They have no
#: token axis to page; instead ``init_paged_caches`` gives them per-slot
#: STATE POOLS (a ``slots`` axis where the dense cache has batch) and the
#: forward pass gathers/scatters each batch row's state by its slot id —
#: masked rows carry the sentinel id ``slots`` and their scatter drops,
#: which is what lets a b=1 prefill chunk or a partially-live decode tick
#: touch only its own slot's state.
RECURRENT_STATE_KINDS = frozenset({"mamba", "zamba", "xlstm"})


def init_paged_caches(cfg: ModelConfig, ctx: ATPContext,
                      pcfg: "paging.PagedConfig",
                      dtype=jnp.bfloat16, abstract: bool = False,
                      slots: int | None = None):
    """Block-paged decode caches: (caches, specs) page pools per segment.

    Unlike :func:`init_decode_caches` there is no per-slot ``s_max`` axis
    and no ``len`` leaf: every O(s) cache tensor stores
    ``num_pages x page_size`` token positions shared by all serving
    slots, and per-slot position state (page table rows + lengths) is
    passed into each step by the scheduler (``runtime.server``).  Memory
    scales with *live tokens*, not ``slots x s_max``.

    Per segment kind:
      attn (dense/moe)   k/v pools ``[count, np, pg, banks * hd]``,
                         lane-dense (a last dim of 64 would be padded to
                         the TPU's 128 lanes), banks the outer factor and
                         sharded over the flat TP axes, so each device
                         holds the same banks as in the dense cache;
                         quantized pools keep one scale per bank,
                         ``[count, np, pg, banks]``;
      mla (mla_dense/moe) latent pools ``[count, np, pg, rank]`` +
                         ``[count, np, pg, rope_dim]``, TP-replicated
                         (caching the latent is MLA's whole point);
      mamba/zamba/xlstm  O(1)-per-slot recurrent state — not paged but
                         *pooled*: dense-cache shapes with the batch axis
                         replaced by a ``slots`` axis (slot-replicated,
                         so any batch row can address any slot).  These
                         kinds require ``slots`` (the scheduler's
                         ``batch_slots``) and a per-row ``slot`` id map
                         fed to each step.

    :func:`forward` carries the stacked pools of a paged segment through
    its layer scan and writes each layer's rows in place.
    """
    n = ctx.tp
    flat = _flat_axes(ctx)
    np_, pg = pcfg.num_pages, pcfg.page_size
    store = paging.page_store_dtype(pcfg.page_dtype)
    pool_dtype = dtype if store is None else store

    def arr(shape, dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    def attn_pool(count):
        banks = _attn_cache_shape(cfg, ctx, 1, 1)[2]
        shape = (count, np_, pg, banks * cfg.hd)
        c = {"k": arr(shape, pool_dtype), "v": arr(shape, pool_dtype)}
        sp = {"k": P(None, None, None, flat),
              "v": P(None, None, None, flat)}
        if pcfg.quantized:
            # parallel scale pools: same paging, feature dim dropped
            c["k_scale"] = arr((count, np_, pg, banks), jnp.float16)
            c["v_scale"] = arr((count, np_, pg, banks), jnp.float16)
            sp["k_scale"] = P(None, None, None, flat)
            sp["v_scale"] = P(None, None, None, flat)
        return c, sp

    def mla_pool(count):
        m = cfg.mla
        c = {"ckv": arr((count, np_, pg, m.kv_lora_rank), pool_dtype),
             "krope": arr((count, np_, pg, m.qk_rope_head_dim), pool_dtype)}
        sp = {"ckv": P(None, None, None, None),
              "krope": P(None, None, None, None)}
        if pcfg.quantized:
            c["ckv_scale"] = arr((count, np_, pg), jnp.float16)
            c["krope_scale"] = arr((count, np_, pg), jnp.float16)
            sp["ckv_scale"] = P(None, None, None)
            sp["krope_scale"] = P(None, None, None)
        return c, sp

    # recurrent state pools: the dense-cache builders with B -> slots and
    # the slot axis replicated (a b=1 prefill row must reach ANY slot)
    def mamba_state(count):
        d_inner, nheads = mamba2.mamba_dims(cfg)
        k = cfg.ssm.conv_kernel
        c = {"conv_x": arr((count, slots, k - 1, d_inner), dtype),
             "conv_bc": arr((count, slots, k - 1, 2 * cfg.ssm.d_state), dtype),
             "ssd": arr((count, slots, nheads, cfg.ssm.head_dim,
                         cfg.ssm.d_state), jnp.float32)}
        sp = {"conv_x": P(None, None, None, flat),
              "conv_bc": P(None, None, None, None),
              "ssd": P(None, None, flat, None, None)}
        return c, sp

    def mlstm_state(count):
        d_inner, nh, dk, dv = xlstm.mlstm_dims(cfg)
        g, r = xlstm.mlstm_plan(ctx, cfg)
        k = cfg.ssm.conv_kernel
        c = {"conv": arr((count, slots, k - 1, d_inner), dtype),
             "C": arr((count, slots, n, nh // g, dk, dv // r + 1),
                      jnp.float32)}
        sp = {"conv": P(None, None, None, flat),
              "C": P(None, None, flat, None, None, None)}
        return c, sp

    def slstm_state(count):
        nh, dh = cfg.num_heads, cfg.d_model // cfg.num_heads
        c = {k2: arr((count, slots, nh, dh), jnp.float32)
             for k2 in ("c", "n", "h")}
        sp = {k2: P(None, None, None, None) for k2 in ("c", "n", "h")}
        return c, sp

    def stack_inner(tree, sp_tree, inner):
        tree = jax.tree.map(
            lambda x: (jax.ShapeDtypeStruct(
                (x.shape[0], inner) + x.shape[1:], x.dtype)
                if abstract else
                jnp.zeros((x.shape[0], inner) + x.shape[1:], x.dtype)),
            tree)
        sp_tree = jax.tree.map(lambda s: P(None, *s), sp_tree,
                               is_leaf=lambda x: isinstance(x, P))
        return tree, sp_tree

    if slots is None and any(s.kind in RECURRENT_STATE_KINDS
                             for s in segments(cfg)):
        raise ValueError(
            "paged serving of recurrent kinds (mamba/zamba/xlstm) needs "
            "slots=<scheduler batch_slots> to size the per-slot state "
            "pools (init_paged_caches(..., slots=...))")

    caches, specs = {}, {}
    for i, seg in enumerate(segments(cfg)):
        if seg.kind in ("dense", "moe"):
            caches[f"seg{i}"], specs[f"seg{i}"] = attn_pool(seg.count)
        elif seg.kind in ("mla_dense", "mla_moe"):
            caches[f"seg{i}"], specs[f"seg{i}"] = mla_pool(seg.count)
        elif seg.kind == "mamba":
            caches[f"seg{i}"], specs[f"seg{i}"] = mamba_state(seg.count)
        elif seg.kind == "zamba":
            ac, asp = attn_pool(seg.count)
            mc, msp = stack_inner(*mamba_state(seg.count), seg.inner - 1)
            caches[f"seg{i}"] = {"attn": ac, "mamba": mc}
            specs[f"seg{i}"] = {"attn": asp, "mamba": msp}
        elif seg.kind == "xlstm":
            mc, msp = stack_inner(*mlstm_state(seg.count), seg.inner - 1)
            sc, ssp = slstm_state(seg.count)
            caches[f"seg{i}"] = {"mlstm": mc, "slstm": sc}
            specs[f"seg{i}"] = {"mlstm": msp, "slstm": ssp}
        else:
            raise ValueError(seg.kind)
    return caches, specs


def _state_take(pool, slot, fresh=None):
    """Gather per-slot recurrent state rows ``[b, ...]`` from a per-layer
    state pool ``[slots, ...]``.  Out-of-range ids (the masked-row
    sentinel ``slots``) read row 0 — harmless, because the conjugate
    :func:`_state_put` drops their writes.

    ``fresh`` ([b] bool) zeroes the gathered rows for requests whose fed
    window starts at position 0: a recycled slot's pool row still holds
    the previous occupant's state, and unlike the page table (which is
    remapped at admission) recurrent state has no per-token addressing to
    hide behind — it must be reset exactly when a new prompt begins."""
    def take(a):
        r = jnp.take(a, jnp.clip(slot, 0, a.shape[0] - 1), axis=0)
        if fresh is not None:
            keep = jnp.reshape(~fresh, (-1,) + (1,) * (r.ndim - 1))
            r = r * keep.astype(r.dtype)
        return r

    return jax.tree.map(take, pool)


def _state_put(pool, rows, slot):
    """Scatter updated state rows back into the pool.  Ids past the pool
    (sentinel = ``slots``; never negative — JAX wraps those) are dropped,
    so masked batch rows leave every slot's state untouched."""
    return jax.tree.map(
        lambda a, r: a.at[slot].set(r.astype(a.dtype), mode="drop"),
        pool, rows)


# ---------------------------------------------------------------------------
# Embedding / head / loss (vocab-parallel over ax1, feature over ax2).
# ---------------------------------------------------------------------------


def _gather_ax1_invariant(ctx: ATPContext, u):
    """Gather an ax1-sharded feature dim to full width with a provably
    ax1-invariant result (place + psum; all_gather output cannot be typed
    invariant under vma — see DESIGN.md)."""
    if ctx.ax1 is None:
        return u
    full = u.shape[-1] * ctx.d1
    placed = jnp.zeros(u.shape[:-1] + (full,), u.dtype)
    placed = lax.dynamic_update_slice_in_dim(
        placed, u, ctx.index1() * u.shape[-1], axis=u.ndim - 1)
    return lax.psum(placed, ctx.ax1)


def embed_tokens(ctx: ATPContext, cfg: ModelConfig, emb, tokens,
                 scatter_seq: bool = False):
    """emb local [V/d1, h/d2]; tokens [b, s] -> x [b, s, h/d2].

    With ``scatter_seq`` (sequence-parallel entry) the vocab-parallel
    all-reduce over ax1 is fused with the seq slice into one psum_scatter
    — half the ax1 wire bytes of psum-then-slice."""
    v_loc = emb.shape[0]
    rel = tokens - ctx.index1() * v_loc
    ok = (rel >= 0) & (rel < v_loc)
    safe = jnp.clip(rel, 0, v_loc - 1)
    x = jnp.take(emb, safe, axis=0) * ok[..., None].astype(emb.dtype)
    if scatter_seq and ctx.seq_parallel and ctx.ax1 is not None:
        if x.shape[1] % ctx.d1:
            raise ValueError(
                f"seq_parallel requires seq ({x.shape[1]}) divisible by "
                f"d1={ctx.d1}")
        x = atp_reduce_scatter(x, ctx.ax1, dim=1)
    else:
        x = atp_boundary(x, ctx.ax1)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    return x


def lm_logits(ctx: ATPContext, cfg: ModelConfig, params, x):
    """x [b, s, h/d2] -> logits [b, s, V/d1] (ax2-replicated)."""
    if cfg.tie_embeddings:
        w = params["embed"].T  # [h/d2, V/d1] local (embed is [V/d1, h/d2])
    else:
        w = params["lm_head"]
    logits = atp_boundary(jnp.einsum("...k,kn->...n", x, w), ctx.ax2)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def vocab_parallel_ce(ctx: ATPContext, logits, labels, ignore: int = -1):
    """logits [b, s, V/d1] local; labels [b, s] global ids.

    Returns per-token loss [b, s] (invariant over TP axes)."""
    lf = logits.astype(jnp.float32)
    v_loc = lf.shape[-1]
    zmax = jnp.max(lax.stop_gradient(lf), axis=-1)
    if ctx.ax1 is not None:
        zmax = lax.pmax(zmax, ctx.ax1)
    sumexp = jnp.sum(jnp.exp(lf - zmax[..., None]), axis=-1)
    sumexp = atp_boundary(sumexp, ctx.ax1)
    lse = jnp.log(sumexp) + zmax
    rel = labels - ctx.index1() * v_loc
    ok = (rel >= 0) & (rel < v_loc)
    safe = jnp.clip(rel, 0, v_loc - 1)
    picked = jnp.take_along_axis(lf, safe[..., None], axis=-1)[..., 0]
    picked = atp_boundary(picked * ok.astype(jnp.float32), ctx.ax1)
    loss = lse - picked
    return jnp.where(labels == ignore, 0.0, loss)


# ---------------------------------------------------------------------------
# Forward (inside shard_map).
# ---------------------------------------------------------------------------


def _gemma_window_array(cfg: ModelConfig, count: int):
    """Per-layer sliding window sizes (0 = global) for alternating archs."""
    if not cfg.local_global_period:
        return jnp.zeros((count,), jnp.int32)
    pat = [cfg.local_window if i % cfg.local_global_period == 0 else 0
           for i in range(count)]
    return jnp.asarray(pat, jnp.int32)


def forward(
    ctx: ATPContext,
    cfg: ModelConfig,
    params,
    tokens,                 # [b, s] int32, or None when embeds given
    positions,              # [b, s] ([3, b, s] for M-RoPE)
    embeds=None,            # [b, s, h/d2] (vision frontend stub)
    caches=None,            # decode: per-segment stacked cache trees
    remat: bool = False,
    paged=None,             # paged serving: dict(table=[b,mp], start=[b])
):
    """Returns (hidden [b, s, h/d2], new_caches, aux_sum, x_emb0).

    Per-segment execution (plan format_version 2): each segment runs under
    ``ctx.for_segment(kind)`` — its own (chunks, boundary_mode,
    seq_parallel) view of the shared mesh.  Kinds outside
    ``SEQ_PARALLEL_KINDS`` have seq_parallel masked by the view, so a
    dense-prefix + MoE stack runs its dense segments sequence-parallel
    while the MoE segment stays on replicated full-sequence block I/O;
    the loop inserts the conjugate seq scatter/gather at every domain
    transition.
    """
    segs = segments(cfg)
    slot = paged.get("slot") if paged is not None else None
    if paged is not None and slot is None and any(
            s.kind in RECURRENT_STATE_KINDS for s in segs):
        raise ValueError(
            "paged serving of recurrent kinds needs paged['slot'] — the "
            "per-row slot ids addressing the state pools (see "
            "launch.steps.build_paged_step)")
    # a row whose fed window starts at 0 is a NEW request in a possibly
    # recycled slot: its gathered state must read as zeros
    fresh = (paged["start"] == 0) if slot is not None else None
    seg_ctxs = tuple(ctx.for_segment(s.kind) for s in segs)
    entry_sp = bool(seg_ctxs) and seg_ctxs[0].seq_parallel
    if caches is not None and any(c.seq_parallel for c in seg_ctxs):
        raise NotImplementedError("seq_parallel does not apply to decode")
    # entry always uses the FIRST segment's (masked) view — the global
    # knobs may request seq_parallel that the first segment's kind masks,
    # and the scatter must follow the masked decision
    entry_ctx = seg_ctxs[0] if seg_ctxs else ctx
    # `shell:*` / `seg{i}:{kind}` scope names are load-bearing: the
    # repro.analysis conformance linter attributes collectives to plan
    # segments by reading them out of the jaxpr name stacks
    with jax.named_scope("shell:embed"):
        if embeds is not None:
            x = embeds
            x_emb0 = x
            # externally-supplied embeds are ax1-replicated: free local slice
            x = seq_scatter(entry_ctx, x, dim=1)
        else:
            # seq-parallel entry fuses the vocab-parallel psum(ax1) with the
            # seq slice into one psum_scatter (x_emb0 is then seq-sharded,
            # fine: its consumers — zamba/MTP — never run seq-parallel)
            x = embed_tokens(entry_ctx, cfg, params["embed"], tokens,
                             scatter_seq=entry_sp)
            x_emb0 = x
    aux_total = jnp.zeros((), jnp.float32)
    if cfg.moe is not None and ctx.dp_axes:
        # MoE aux loss varies with this rank's tokens -> type it varying
        # over the data axes so the scan carry is consistent
        aux_total = lax.pcast(aux_total, ctx.dp_axes, to="varying")
    new_caches = {} if caches is not None else None

    plan = (L.make_attn_plan(ctx, cfg.num_heads, cfg.num_kv_heads)
            if cfg.family != "ssm" else None)

    cur_sp = entry_sp
    last_sp_ctx = seg_ctxs[0] if entry_sp else None
    for i, seg in enumerate(segs):
        sctx = seg_ctxs[i]
        # domain transition: the residual stream must enter each segment in
        # that segment's block I/O spec
        with jax.named_scope(f"shell:trans{i}"):
            if sctx.seq_parallel and not cur_sp:
                x = seq_scatter(sctx, x, dim=1)    # free slice (replicated in)
            elif cur_sp and not sctx.seq_parallel:
                x = seq_gather(last_sp_ctx, x, dim=1)  # conjugate all-gather
        cur_sp = sctx.seq_parallel
        if cur_sp:
            last_sp_ctx = sctx
        sp = params[f"seg{i}"]
        seg_cache = caches.get(f"seg{i}") if caches is not None else None

        if paged is not None and seg.kind in PAGED_CACHE_KINDS:
            # the stacked page pools ride the scan as carry, each layer
            # scattering into and gathering from its own rows (``layer``):
            # the donated pool is written in place, where as xs/ys every
            # call would slice each layer's pool out and restack it
            def pbody(carry, xs, _kind=seg.kind, _ctx=sctx):
                h, aux, pools = carry
                bp, win, layer = xs
                h, pools, a = _apply_block(_kind, _ctx, cfg, bp, h,
                                           positions, plan, win, pools,
                                           paged=dict(paged, layer=layer))
                return (h, aux + a, pools), None

            with jax.named_scope(f"seg{i}:{seg.kind}"):
                (x, aux_total, new_caches[f"seg{i}"]), _ = lax.scan(
                    pbody, (x, aux_total, seg_cache),
                    (sp, _gemma_window_array(cfg, seg.count),
                     jnp.arange(seg.count, dtype=jnp.int32)))

        elif seg.kind in ("dense", "moe", "mla_dense", "mla_moe", "mamba"):
            windows = _gemma_window_array(cfg, seg.count)

            def body(carry, xs, _kind=seg.kind, _ctx=sctx):
                h, aux = carry
                bp, win, c = xs
                if _kind == "mamba" and paged is not None:
                    # paged recurrent: this batch row's state lives at its
                    # slot's pool row; gather, step, drop-mode scatter back
                    rows = _state_take(c, slot, fresh)
                    h, nr, a = _apply_block(_kind, _ctx, cfg, bp, h,
                                            positions, plan, win, rows,
                                            paged=paged)
                    return (h, aux + a), _state_put(c, nr, slot)
                h, nc, a = _apply_block(_kind, _ctx, cfg, bp, h, positions,
                                        plan, win, c, paged=paged)
                return (h, aux + a), nc

            fn = jax.checkpoint(body) if remat else body
            with jax.named_scope(f"seg{i}:{seg.kind}"):
                (x, aux_total), ncs = lax.scan(
                    fn, (x, aux_total), (sp, windows, seg_cache))
            if new_caches is not None:
                new_caches[f"seg{i}"] = ncs

        elif seg.kind == "zamba":
            shared = params["shared_attn"]

            def zbody(carry, xs, _ctx=sctx):
                h, aux = carry
                bp, c = xs
                # shared attention block on (h, emb0): two column-first
                # projections sharing one f-boundary psum(ax2)
                u = atp_boundary(
                    jnp.einsum("...k,kn->...n", h, shared["w_in_h"])
                    + jnp.einsum("...k,kn->...n", x_emb0, shared["w_in_e"]),
                    _ctx.ax2)                      # [.., h/d1] ax1-sharded
                u = _gather_ax1_invariant(_ctx, u)  # back to block I/O spec
                if _ctx.ax2 is not None:
                    u = shard_slice(u, _ctx.index2(), _ctx.d2, dim=-1)
                ac = c["attn"] if c is not None else None
                h2, nac = transformer.dense_block(_ctx, cfg, shared["block"], h + u,
                                                  positions, plan, cache=ac,
                                                  paged=paged)
                h = h2

                def mbody(hc, xs2):
                    hh = hc
                    mp, mc = xs2
                    if paged is not None:
                        rows = _state_take(mc, slot, fresh)
                        hh, nr = mamba2.mamba_block(_ctx, cfg, mp, hh,
                                                    state=rows)
                        return hh, _state_put(mc, nr, slot)
                    hh, nmc = mamba2.mamba_block(_ctx, cfg, mp, hh, state=mc)
                    return hh, nmc

                mc = c["mamba"] if c is not None else None
                h, nmc = lax.scan(mbody, h, (bp["mamba"], mc))
                ncs = {"attn": nac, "mamba": nmc} if c is not None else 0.0
                return (h, aux), ncs

            fn = jax.checkpoint(zbody) if remat else zbody
            with jax.named_scope(f"seg{i}:{seg.kind}"):
                (x, aux_total), ncs = lax.scan(fn, (x, aux_total),
                                               (sp, seg_cache))
            if new_caches is not None:
                new_caches[f"seg{i}"] = ncs

        elif seg.kind == "xlstm":
            def xbody(carry, xs, _ctx=sctx):
                h, aux = carry
                bp, c = xs

                def mb(hc, xs2):
                    mp, mc = xs2
                    if paged is not None:
                        rows = _state_take(mc, slot, fresh)
                        hh, ns = xlstm.mlstm_block(_ctx, cfg, mp, hc,
                                                   state=rows)
                        return hh, _state_put(mc, ns, slot)
                    hh, ns = xlstm.mlstm_block(_ctx, cfg, mp, hc, state=mc)
                    return hh, ns

                mc = c["mlstm"] if c is not None else None
                h, nms = lax.scan(mb, h, (bp["mlstm"], mc))
                sc = c["slstm"] if c is not None else None
                if paged is not None:
                    rows = _state_take(sc, slot, fresh)
                    h, nr = xlstm.slstm_block(_ctx, cfg, bp["slstm"], h,
                                              state=rows)
                    nss = _state_put(sc, nr, slot)
                else:
                    h, nss = xlstm.slstm_block(_ctx, cfg, bp["slstm"], h,
                                               state=sc)
                ncs = {"mlstm": nms, "slstm": nss} if c is not None else 0.0
                return (h, aux), ncs

            fn = jax.checkpoint(xbody) if remat else xbody
            with jax.named_scope(f"seg{i}:{seg.kind}"):
                (x, aux_total), ncs = lax.scan(fn, (x, aux_total),
                                               (sp, seg_cache))
            if new_caches is not None:
                new_caches[f"seg{i}"] = ncs
        else:
            raise ValueError(seg.kind)

    with jax.named_scope("shell:exit"):
        x = L.norm(ctx, cfg, x, params["final_norm"])
        # leave the sequence-parallel domain: heads/loss see the full sequence
        if cur_sp:
            x = seq_gather(last_sp_ctx, x, dim=1)
    return x, new_caches, aux_total, x_emb0


def train_loss(ctx: ATPContext, cfg: ModelConfig, params, batch, remat=True):
    """batch: tokens [b,s], labels [b,s] (+ embeds/positions3).  Scalar loss."""
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    if cfg.mrope_sections:
        positions = batch["positions3"]
        b, s = positions.shape[1], positions.shape[2]
    else:
        ref = tokens if tokens is not None else embeds
        b, s = ref.shape[0], ref.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h, _, aux, x_emb0 = forward(ctx, cfg, params, tokens, positions,
                                embeds=embeds, remat=remat)
    with jax.named_scope("shell:head"):
        logits = lm_logits(ctx, cfg, params, h)
        per_tok = vocab_parallel_ce(ctx, logits, batch["labels"])
    with jax.named_scope("shell:loss"):
        total = jnp.sum(per_tok)
        count = jnp.asarray(per_tok.size, jnp.float32)
        if ctx.dp_axes:
            total = lax.psum(total, ctx.dp_axes)
            count = lax.psum(count, ctx.dp_axes)
        loss = total / count

    if cfg.mtp and tokens is not None:
        # multi-token prediction: predict t+2 from (h_t, emb(t+1)).  h left
        # the sequence-parallel domain at forward()'s exit gather, so the
        # MTP head always runs on replicated full-sequence block I/O — use
        # an sp-free context view regardless of the plan's segment knobs.
        with jax.named_scope("shell:mtp"):
            mctx = dataclasses.replace(ctx, seq_parallel=False,
                                       segment_plans=())
            mp = params["mtp"]
            emb_next = embed_tokens(mctx, cfg, params["embed"],
                                    jnp.roll(tokens, -1, axis=1))
            u = atp_boundary(
                jnp.einsum("...k,kn->...n", h, mp["proj_h"])
                + jnp.einsum("...k,kn->...n", emb_next, mp["proj_e"]),
                mctx.ax2)
            if mctx.ax1 is not None:  # back to [.., h/d2] block I/O spec
                u = lax.all_gather(u, mctx.ax1, axis=-1, tiled=True)
            u = (shard_slice(u, mctx.index2(), mctx.d2, dim=-1)
                 if mctx.ax2 is not None else u)
            plan = L.make_attn_plan(mctx, cfg.num_heads, cfg.num_kv_heads)
            u, _, _ = _apply_block("mla_dense" if cfg.mla else "dense",
                                   mctx, cfg, mp["block"], u, positions,
                                   plan, 0, None)
            u = L.norm(mctx, cfg, u, mp["norm"])
            logits2 = lm_logits(mctx, cfg, params, u)
            mtp_labels = jnp.roll(batch["labels"], -1, axis=1)
            l2 = jnp.sum(vocab_parallel_ce(ctx, logits2, mtp_labels))
            if ctx.dp_axes:
                l2 = lax.psum(l2, ctx.dp_axes)
            loss = loss + cfg.mtp_loss_weight * l2 / count

    if cfg.moe is not None:
        with jax.named_scope("shell:loss"):
            if ctx.dp_axes:
                aux = lax.pmean(aux, ctx.dp_axes)
            loss = loss + cfg.moe.aux_loss_weight * aux / max(1, cfg.num_layers)
    return loss


def prefill_logits(ctx: ATPContext, cfg: ModelConfig, params, batch):
    """Forward only; returns last-position logits [b, V/d1]."""
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    if cfg.mrope_sections:
        positions = batch["positions3"]
        b, s = positions.shape[1], positions.shape[2]
    else:
        ref = tokens if tokens is not None else embeds
        b, s = ref.shape[0], ref.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h, _, _, _ = forward(ctx, cfg, params, tokens, positions, embeds=embeds)
    with jax.named_scope("shell:head"):
        logits = lm_logits(ctx, cfg, params, h[:, -1:])
    return logits[:, 0]


def decode_step(ctx: ATPContext, cfg: ModelConfig, params, tokens, pos, caches):
    """One token step.  tokens [b,1]; pos scalar; caches per-segment trees.

    Returns (next-token logits [b, V/d1], new caches)."""
    b, s = tokens.shape
    prange = (pos + jnp.arange(s)).astype(jnp.int32)
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(prange[None, None, :], (3, b, s))
    else:
        positions = jnp.broadcast_to(prange[None, :], (b, s))
    h, new_caches, _, _ = forward(ctx, cfg, params, tokens, positions,
                                  caches=caches)
    with jax.named_scope("shell:head"):
        logits = lm_logits(ctx, cfg, params, h[:, -1:])
    return logits[:, 0], new_caches


def paged_step(ctx: ATPContext, cfg: ModelConfig, params, tokens, start,
               table, caches, slot=None, with_hidden: bool = False):
    """One paged cache-write step — decode tick AND prefill chunk.

    tokens [b, s] (decode: b=slots, s=1; prefill chunk: b=1, s=chunk);
    start [b] per-slot absolute position of tokens[:, 0]; table [b, mp]
    page-table rows; caches from :func:`init_paged_caches`; slot [b]
    per-row slot ids (required for recurrent kinds — masked rows carry
    the sentinel id = pool slot count, whose state writes drop).

    Returns (logits [b, s, V/d1] for EVERY input position, new caches);
    ``with_hidden`` adds the final-norm hidden [b, s, h/d2] in the middle
    (speculative decode feeds it to :func:`mtp_draft_logits`).  Returning
    all positions keeps one compiled step reusable across prompt lengths:
    the scheduler picks the logits of the last *valid* token of a padded
    final chunk on the host, instead of forcing a recompile per length.
    """
    b, s = tokens.shape
    prange = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(prange[None], (3, b, s))
    else:
        positions = prange
    paged = {"table": table, "start": start}
    if slot is not None:
        paged["slot"] = slot
    h, new_caches, _, _ = forward(ctx, cfg, params, tokens, positions,
                                  caches=caches, paged=paged)
    with jax.named_scope("shell:head"):
        logits = lm_logits(ctx, cfg, params, h)
    if with_hidden:
        return logits, h, new_caches
    return logits, new_caches


def mtp_draft_logits(ctx: ATPContext, cfg: ModelConfig, params, h, positions,
                     next_tokens):
    """MTP head as a decode-time draft proposer.

    Training teaches the head p(t+2 | h_t, emb(t+1)); at decode time we
    feed the trunk hidden ``h`` [b, s, h/d2] (paged_step's
    ``with_hidden`` output) and the greedy picks ``next_tokens`` [b, s]
    just made from it, giving draft logits for the position AFTER each
    pick — a free extra token per tick for self-speculative decode.
    Mirrors the train head exactly (sp-free context, same block), except
    the draft block attends only within the fed window (cache=None over
    ``s`` positions): a weaker proposer, never a correctness issue —
    the trunk verifies every draft before it is kept.
    """
    with jax.named_scope("shell:mtp"):
        mctx = dataclasses.replace(ctx, seq_parallel=False, segment_plans=())
        mp = params["mtp"]
        emb_next = embed_tokens(mctx, cfg, params["embed"], next_tokens)
        u = atp_boundary(
            jnp.einsum("...k,kn->...n", h, mp["proj_h"])
            + jnp.einsum("...k,kn->...n", emb_next, mp["proj_e"]), mctx.ax2)
        if mctx.ax1 is not None:  # back to [.., h/d2] block I/O spec
            u = lax.all_gather(u, mctx.ax1, axis=-1, tiled=True)
        u = (shard_slice(u, mctx.index2(), mctx.d2, dim=-1)
             if mctx.ax2 is not None else u)
        plan = L.make_attn_plan(mctx, cfg.num_heads, cfg.num_kv_heads)
        u, _, _ = _apply_block("mla_dense" if cfg.mla else "dense",
                               mctx, cfg, mp["block"], u, positions, plan,
                               0, None)
        u = L.norm(mctx, cfg, u, mp["norm"])
        return lm_logits(mctx, cfg, params, u)

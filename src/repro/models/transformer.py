"""Dense transformer block with ATP row/column-first tensor parallelism.

Per-block communication schedule (paper Fig. 6):
  f1: psum(ax2) after the column-first q/k/v projections
  f2: psum(ax1) after the row-first output projection
  f3: psum(ax2) after the column-first MLP up(+gate) projection
  f4: psum(ax1) after the row-first MLP down projection
plus the core scatter (free slice) / gather (all-gather over ax2).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.core.atp import (ATPContext, atp_boundary, atp_linear, atp_scope,
                            grad_sync, shard_slice)
from repro.kernels import ops
from repro.models import layers as L
from repro.models import paging


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def attn_params(key, cfg: ModelConfig, dtype) -> dict[str, Any]:
    h, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 8)
    s = 1.0 / math.sqrt(h)
    p = {
        "wq": _init(ks[0], (h, qd), s, dtype),
        "wk": _init(ks[1], (h, kvd), s, dtype),
        "wv": _init(ks[2], (h, kvd), s, dtype),
        "wo": _init(ks[3], (qd, h), 1.0 / math.sqrt(qd), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.hd,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.hd,), jnp.float32)
    return p


def attn_param_specs(ctx: ATPContext, cfg: ModelConfig) -> dict[str, Any]:
    sp = {
        "wq": L.col_w_spec(ctx), "wk": L.col_w_spec(ctx), "wv": L.col_w_spec(ctx),
        "wo": L.row_w_spec(ctx),
    }
    if cfg.qkv_bias:
        sp["bq"] = L.col_b_spec(ctx)
        sp["bk"] = L.col_b_spec(ctx)
        sp["bv"] = L.col_b_spec(ctx)
    if cfg.qk_norm:
        sp["q_norm"] = L.replicated_spec()
        sp["k_norm"] = L.replicated_spec()
    return sp


def mlp_params(key, cfg: ModelConfig, dtype, d_ff: int | None = None) -> dict[str, Any]:
    h = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(h)
    p = {"w_up": _init(ks[0], (h, ff), s, dtype),
         "w_down": _init(ks[1], (ff, h), 1.0 / math.sqrt(ff), dtype)}
    if cfg.mlp_kind in ("swiglu", "geglu"):
        p["w_gate"] = _init(ks[2], (h, ff), s, dtype)
    return p


def mlp_param_specs(ctx: ATPContext, cfg: ModelConfig) -> dict[str, Any]:
    sp = {"w_up": L.col_w_spec(ctx), "w_down": L.row_w_spec(ctx)}
    if cfg.mlp_kind in ("swiglu", "geglu"):
        sp["w_gate"] = L.col_w_spec(ctx)
    return sp


def mlp_block(ctx: ATPContext, cfg: ModelConfig, p, x):
    """Feed-forward with column-first up(+gate), row-first down (f3/f4)."""
    if cfg.mlp_kind in ("swiglu", "geglu"):
        # fuse up+gate into one column-first GEMM + single f3 boundary
        w_cat = jnp.concatenate([p["w_up"], p["w_gate"]], axis=1)
        ug = atp_linear(ctx, x, w_cat, kind="col")
        u, g = jnp.split(ug, 2, axis=-1)
        act = jax.nn.silu(g) if cfg.mlp_kind == "swiglu" else jax.nn.gelu(g, approximate=True)
        y = u * act
    else:
        y = jax.nn.gelu(atp_linear(ctx, x, p["w_up"], kind="col"), approximate=True)
    return atp_linear(ctx, y, p["w_down"], kind="row")


def _qk_norm(q, gamma, eps):
    qf = q.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(qf * qf, axis=-1, keepdims=True) + eps)
    return (qf * inv * gamma).astype(q.dtype)


def attn_block(
    ctx: ATPContext,
    cfg: ModelConfig,
    p,
    x,                      # [b, s, h/d2]
    positions,              # [b, s] (or [3, b, s] for M-RoPE)
    plan: L.AttnPlan,
    layer_window: int = 0,  # sliding window for this layer (0 = global)
    cache=None,             # decode: dict(k=[b,S,kvb,hd], v=..., len=scalar)
                            # or paged pools dict(k=[np,pg,kvb*hd], v=...),
                            # stacked [count, np, ...] when paged["layer"]
    paged=None,             # paged serving: dict(table=[b,mp], start=[b],
                            # optional layer=scalar into stacked pools,
                            # kernel=True: one-row calls read full-width
                            # pools through the paged attention kernel)
):
    """Returns (attn output [b, s, h/d2], new_cache)."""
    # f1: column-first q/k/v projections, one fused boundary psum(ax2)
    parts = jnp.concatenate([p["wq"], p["wk"], p["wv"]], axis=1)
    with atp_scope(ctx, "col", ctx.ax2):
        qkv = atp_boundary(jnp.einsum("...k,kn->...n", x, parts), ctx.ax2)
    d1 = ctx.d1
    qd, kvd = cfg.q_dim // d1, cfg.kv_dim // d1
    qp, kp, vp = (qkv[..., :qd], qkv[..., qd:qd + kvd], qkv[..., qd + kvd:])
    # the head split, and the gradient reductions of the replicated bias
    # and qk-norm gains it makes rank-partial
    with atp_scope(ctx, "heads", ctx.tp_axes):
        if cfg.qkv_bias:
            # bias shards are ax2-replicated (P(ax1)) but consumed by the
            # rank-local head/seq split below, so their cotangent is
            # ax2-partial
            qp = qp + grad_sync(ctx, p["bq"], ctx.ax2)
            kp = kp + grad_sync(ctx, p["bk"], ctx.ax2)
            vp = vp + grad_sync(ctx, p["bv"], ctx.ax2)

        q, k, v, bid, rid = L.split_qkv_heads(ctx, cfg, qp, kp, vp, plan)

        if cfg.qk_norm:
            # per-head norm gains see only the rank-local heads' cotangent
            q = _qk_norm(q, grad_sync(ctx, p["q_norm"], ctx.tp_axes),
                         cfg.norm_eps)
            k = _qk_norm(k, grad_sync(ctx, p["k_norm"], ctx.tp_axes),
                         cfg.norm_eps)

    decode = cache is not None
    sq_offset = 0
    if not decode and plan.r > 1:
        # seq-split the q rows over the r leftover ranks (k/v keep full seq)
        s_r = q.shape[1] // plan.r
        q = lax.dynamic_slice_in_dim(q, rid * s_r, s_r, axis=1)
        sq_offset = rid * s_r

    if cfg.use_rope or cfg.mrope_sections:
        if cfg.mrope_sections:
            qpos = (lax.dynamic_slice_in_dim(positions, sq_offset, q.shape[1], axis=2)
                    if not decode else positions)
            q = L.apply_mrope(q, qpos, cfg.rope_theta, cfg.mrope_sections)
            k = L.apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            qpos = (lax.dynamic_slice_in_dim(positions, sq_offset, q.shape[1], axis=1)
                    if not decode else positions)
            q = L.apply_rope(q, qpos, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if decode and paged is not None:
        # paged serving: scatter this run's k/v through the slot page
        # tables, then attend over each slot's MAPPED pages only (per-slot
        # positions; garbage-page reads are masked by start + s).  The
        # pools are lane-dense, [.., banks * hd]: k/v go in flattened and
        # come back out as [b, S, banks, hd]
        table, start = paged["table"], paged["start"]
        layer = paged.get("layer")
        pd = paging.pool_page_dtype(cache["k"])
        flat = k.shape[:2] + (-1,)
        ck, cks = paging.append_tokens_q(cache["k"], cache.get("k_scale"),
                                         table, start, k.reshape(flat), pd,
                                         layer)
        cv, cvs = paging.append_tokens_q(cache["v"], cache.get("v_scale"),
                                         table, start, v.reshape(flat), pd,
                                         layer)
        new_cache = {"k": ck, "v": cv}
        if cks is not None:
            new_cache["k_scale"], new_cache["v_scale"] = cks, cvs
        if paged.get("kernel") and q.shape[1] == 1:
            # a decode tick: the kernel reads each slot's live pages in
            # place, no gather of the whole table width
            with jax.named_scope("attn:paged"):
                o = ops.paged_attention(
                    q[:, 0], ck, cv, table, start + 1,
                    0 if layer is None else layer, layer_window,
                    softcap=cfg.attn_softcap)[:, None]
        else:
            kk = paging.gather_pages_q(ck, cks, table, out_dtype=k.dtype,
                                       layer=layer)
            vv = paging.gather_pages_q(cv, cvs, table, out_dtype=v.dtype,
                                       layer=layer)
            kk = kk.reshape(kk.shape[:2] + k.shape[2:])
            vv = vv.reshape(vv.shape[:2] + v.shape[2:])
            o = L.attention_core(cfg, q, kk, vv, q_offset=start,
                                 kv_len=start + q.shape[1],
                                 window=layer_window)
    elif decode:
        # append this step's k/v at cache['len'] (s >= 1: also serves as
        # prefill-into-cache for the serving loop)
        klen = cache["len"]
        ck = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), klen, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), klen, axis=1)
        new_cache = {"k": ck, "v": cv, "len": klen + q.shape[1]}
        o = L.attention_core(cfg, q, ck, cv, q_offset=klen,
                             kv_len=klen + q.shape[1], window=layer_window)
    else:
        o = L.attention_core(cfg, q, k, v, q_offset=sq_offset, window=layer_window)

    with atp_scope(ctx, "heads", ctx.tp_axes):
        o = L.core_output_gather(ctx, cfg, o, plan, seq_split=not decode)
    # f2: row-first output projection, boundary psum(ax1)
    out = atp_linear(ctx, o, p["wo"], kind="row")
    return out, new_cache


def dense_block_params(key, cfg: ModelConfig, dtype, d_ff: int | None = None):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    # norm params are created at GLOBAL size; sharded by spec
    p = {
        "ln_attn": L.norm_params(cfg, cfg.d_model),
        "attn": attn_params(k1, cfg, dtype),
        "ln_mlp": L.norm_params(cfg, cfg.d_model),
        "mlp": mlp_params(k2, cfg, dtype, d_ff),
    }
    if cfg.post_block_norms:
        p["ln_post_attn"] = L.norm_params(cfg, cfg.d_model)
        p["ln_post_mlp"] = L.norm_params(cfg, cfg.d_model)
    return p


def dense_block_specs(ctx: ATPContext, cfg: ModelConfig):
    nspec = {"scale": L.feat_spec(ctx)}
    if cfg.norm_kind == "layernorm":
        nspec = {"scale": L.feat_spec(ctx), "bias": L.feat_spec(ctx)}
    sp = {
        "ln_attn": dict(nspec),
        "attn": attn_param_specs(ctx, cfg),
        "ln_mlp": dict(nspec),
        "mlp": mlp_param_specs(ctx, cfg),
    }
    if cfg.post_block_norms:
        sp["ln_post_attn"] = dict(nspec)
        sp["ln_post_mlp"] = dict(nspec)
    return sp


def dense_block(
    ctx: ATPContext, cfg: ModelConfig, p, x, positions, plan,
    layer_window: int = 0, cache=None, paged=None,
):
    """With ``ctx.seq_parallel`` the residual stream x is seq-sharded over
    ax1: the entry norms fold the all-gather to full sequence, and the
    row-first output projections (f2/f4) psum_scatter back — post-block
    norms and residual adds stay in the seq-sharded domain.  The two
    sublayers run under the ``block:attn`` and ``block:mlp`` scopes."""
    sp = ctx.seq_parallel and cache is None
    with jax.named_scope("block:attn"):
        h = L.norm(ctx, cfg, x, p["ln_attn"], gather_seq=sp)
        a, new_cache = attn_block(ctx, cfg, p["attn"], h, positions, plan,
                                  layer_window=layer_window, cache=cache,
                                  paged=paged)
        if cfg.post_block_norms:
            a = L.norm(ctx, cfg, a, p["ln_post_attn"])
    x = x + a
    with jax.named_scope("block:mlp"):
        h = L.norm(ctx, cfg, x, p["ln_mlp"], gather_seq=sp)
        m = mlp_block(ctx, cfg, p["mlp"], h)
        if cfg.post_block_norms:
            m = L.norm(ctx, cfg, m, p["ln_post_mlp"])
    return x + m, new_cache

"""Mixture-of-Experts with expert parallelism over the flat TP axis.

Scheme (DESIGN.md §5): experts are sharded over the d1*d2 flat TP ranks
(EP); ATP's grouped all-reduce has no role inside a (small) expert, so the
paper's technique applies to the surrounding dense layers while the MoE
layer uses EP all-to-all dispatch:

  1. token-scatter: every TP rank takes a 1/n slice of the local tokens
     (free slice over ax1 + all-gather(ax2) of the feature shards)
  2. route + capacity-bounded dispatch to [n_dst, cap, h] send buffer
  3. all_to_all over the flat TP axes
  4. local grouped expert FFN [E_loc, cap*n, h]
  5. all_to_all back + weighted combine
  6. token-gather back to the block I/O spec [Replicate, Shard(feature)]
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.core.atp import (ATPContext, grad_sync, shard_slice,
                            vma_rewrite_active)
from repro.models import layers as L


def moe_params(key, cfg: ModelConfig, dtype) -> dict[str, Any]:
    mc = cfg.moe
    h, ff, e = cfg.d_model, mc.d_ff_expert, mc.num_experts
    ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(h)
    p = {
        "router": (jax.random.normal(ks[0], (h, e), jnp.float32) * 0.02),
        "w_up": (jax.random.normal(ks[1], (e, h, ff), jnp.float32) * s).astype(dtype),
        "w_gate": (jax.random.normal(ks[2], (e, h, ff), jnp.float32) * s).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, ff, h), jnp.float32) / math.sqrt(ff)).astype(dtype),
    }
    if mc.num_shared:
        from repro.models.transformer import mlp_params
        p["shared"] = mlp_params(ks[4], cfg, dtype, d_ff=mc.d_ff_expert * mc.num_shared)
    return p


def moe_param_specs(ctx: ATPContext, cfg: ModelConfig) -> dict[str, Any]:
    ep = ctx.tp_axes or None  # experts sharded over flat TP
    sp = {
        "router": L.replicated_spec(),
        "w_up": jax.sharding.PartitionSpec(ep),
        "w_gate": jax.sharding.PartitionSpec(ep),
        "w_down": jax.sharding.PartitionSpec(ep),
    }
    if cfg.moe.num_shared:
        from repro.models.transformer import mlp_param_specs
        sp["shared"] = mlp_param_specs(ctx, cfg)
    return sp


def _all_to_all(x, axes: tuple[str, ...], split_axis: int, concat_axis: int):
    if not axes:
        return x
    return lax.all_to_all(x, axes, split_axis=split_axis, concat_axis=concat_axis,
                          tiled=True)


def moe_block(ctx: ATPContext, cfg: ModelConfig, p, x):
    """x: [b, s, h/d2] -> same spec.  Capacity-dropped top-k routing."""
    mc = cfg.moe
    n = ctx.tp
    b, s, hl = x.shape
    h = cfg.d_model
    e = mc.num_experts
    e_loc = max(1, e // n)

    t = x.reshape(b * s, hl)
    replicated_dispatch = (b * s) % n != 0 or (b * s) // n == 0
    if replicated_dispatch:
        # decode-sized token counts (T < n): keep ALL tokens on every rank
        # (full-h via all_gather(ax2): safe here — no token slicing, so no
        # interleave hazard); each rank runs only its local experts and the
        # combine below assembles with a psum over the flat TP group.
        if ctx.ax2 is not None:
            t = lax.all_gather(t, ctx.ax2, axis=-1, tiled=True)
        tokens = t                                                   # [T, h]
    else:
        # ---- 1. token scatter: [b*s, h/d2] -> this rank's 1/n token slice,
        # full h.  all_to_all(ax2) swaps token-sharding for feature-gathering
        # *within the same ax2 ring* (a plain all_gather(ax2) would mix
        # feature shards of different token blocks); the ax1 slice is then
        # free (replicated).
        if ctx.ax2 is not None:
            t = _all_to_all(t, (ctx.ax2,), split_axis=0, concat_axis=1)
        tokens = shard_slice(t, ctx.index1(), ctx.d1, dim=0)         # [T/n, h]

    # ---- 2. route (router weight replicated; logits from full-h tokens)
    # each rank routes its own token shard (or combines only its local
    # experts), so the router's cotangent is TP-partial: sync its grad
    router = grad_sync(ctx, p["router"], ctx.tp_axes)
    logits = (tokens.astype(jnp.float32) @ router)            # [T/n, E]
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(gates, mc.top_k)                   # [T/n, k]
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)

    # aux load-balance loss (Switch-style); tokens differ per TP rank here,
    # so average the per-rank partials over the flat TP group.  Under
    # replicated dispatch every rank holds the same aux, typed invariant
    # over the axes its tokens were replicated on: cast it varying there
    # first (the mean of identical copies is the value itself).  Builds
    # without vma typing (ring boundary, quantized wire) have nothing to
    # cast, and the cast's transpose would be a typed psum there
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * e
    if ctx.tp_axes:
        invariant = tuple(a for a in ctx.tp_axes
                          if a not in jax.typeof(aux).vma)
        if invariant and vma_rewrite_active(ctx):
            aux = lax.pcast(aux, invariant, to="varying")
        aux = lax.psum(aux, ctx.tp_axes) / n

    # ---- capacity-bounded slot assignment
    tn = tokens.shape[0]
    cap = max(1, int(mc.capacity_factor * tn * mc.top_k / e))
    flat_e = topi.reshape(-1)                                 # [tn*k]
    flat_w = topv.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot - 1        # slot within expert
    slot = jnp.sum(pos_in_e * onehot, axis=-1)
    keep = slot < cap
    dst = flat_e // e_loc                                     # owning rank
    tok_rep = jnp.repeat(tokens, mc.top_k, axis=0)
    w_up, w_gate, w_down = p["w_up"], p["w_gate"], p["w_down"]

    if replicated_dispatch:
        # every rank holds all tokens; keep only slots owned by my experts
        mine = keep & (dst == ctx.tp_index())
        buf = jnp.zeros((e_loc, cap, h), tokens.dtype)
        buf = buf.at[jnp.where(mine, flat_e % e_loc, e_loc),
                     jnp.where(mine, slot, 0)].add(tok_rep, mode="drop")
        up = jnp.einsum("ech,ehf->ecf", buf, w_up)
        gate = jnp.einsum("ech,ehf->ecf", buf, w_gate)
        yb = jnp.einsum("ecf,efh->ech", up * jax.nn.silu(gate), w_down)
        gathered = yb[jnp.where(mine, flat_e % e_loc, 0),
                      jnp.where(mine, slot, 0)]
        gathered = jnp.where(mine[:, None], gathered, 0.0)
        combined = (gathered * flat_w[:, None].astype(gathered.dtype)).reshape(
            tn, mc.top_k, h).sum(axis=1)                      # partial over TP
        if ctx.tp_axes:
            combined = lax.psum(combined, ctx.tp_axes)        # [T, h] invariant
        if ctx.ax2 is not None:
            combined = shard_slice(combined, ctx.index2(), ctx.d2, dim=-1)
        out = combined.reshape(b, s, hl)
    else:
        # send buffer [n, e_loc * cap, h]
        send = jnp.zeros((n, e_loc * cap, h), tokens.dtype)
        buf_idx = (flat_e % e_loc) * cap + slot
        send = send.at[jnp.where(keep, dst, n),
                       jnp.where(keep, buf_idx, 0)].add(tok_rep, mode="drop")

        # ---- 3. all-to-all over flat TP
        recv = _all_to_all(send, ctx.tp_axes, split_axis=0, concat_axis=0)

        # ---- 4. local grouped expert FFN over [e_loc, n*cap, h]
        xin = recv.reshape(n, e_loc, cap, h).transpose(1, 0, 2, 3) \
            .reshape(e_loc, n * cap, h)
        up = jnp.einsum("ech,ehf->ecf", xin, w_up)
        gate = jnp.einsum("ech,ehf->ecf", xin, w_gate)
        y = jnp.einsum("ecf,efh->ech", up * jax.nn.silu(gate), w_down)
        y = y.reshape(e_loc, n, cap, h).transpose(1, 0, 2, 3) \
            .reshape(n, e_loc * cap, h)

        # ---- 5. return path + weighted combine
        back = _all_to_all(y, ctx.tp_axes, split_axis=0, concat_axis=0)
        gathered = back[jnp.where(keep, dst, 0), jnp.where(keep, buf_idx, 0)]
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        combined = (gathered * flat_w[:, None].astype(gathered.dtype)).reshape(
            tn, mc.top_k, h).sum(axis=1)                      # [T/n, h]

        # ---- 6. token gather back to [b*s, h/d2]: exact inverse of step 1.
        # The ax1 gather uses place+psum (not all_gather) so the result is
        # provably ax1-invariant under vma typing — matching the block I/O
        # spec [Replicate@ax1, Shard@ax2] (all_gather output cannot be typed
        # invariant; costs 2x gather bytes, noted in DESIGN.md).
        if ctx.ax1 is not None:
            t_d2 = combined.shape[0] * ctx.d1
            placed = jnp.zeros((t_d2,) + combined.shape[1:], combined.dtype)
            placed = lax.dynamic_update_slice_in_dim(
                placed, combined, ctx.index1() * combined.shape[0], axis=0)
            combined = lax.psum(placed, ctx.ax1)                  # [T/d2, h]
        if ctx.ax2 is not None:
            combined = _all_to_all(combined, (ctx.ax2,),
                                   split_axis=1, concat_axis=0)
        out = combined.reshape(b, s, hl)

    # ---- shared experts (deepseek): plain ATP dense MLP path
    if mc.num_shared:
        from repro.models.transformer import mlp_block
        out = out + mlp_block(ctx, cfg, p["shared"], x)
    return out, aux

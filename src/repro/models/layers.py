"""Shared model layers, ATP-sharded.  All code runs inside shard_map.

Activation convention between blocks (paper Fig. 6): spec
[Replicate@ax1, Shard(feature)@ax2] — local shape [..., d_model/d2].
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.atp import (ATPContext, atp_boundary, atp_scope, grad_sync,
                            seq_gather, shard_slice)

# ---------------------------------------------------------------------------
# Param spec helpers (global tensor -> PartitionSpec over ATP axes).
# ---------------------------------------------------------------------------


def col_w_spec(ctx: ATPContext) -> P:
    """Column-first weight [K, N]: [Shard(1)@ax1, Shard(0)@ax2]."""
    return P(ctx.ax2, ctx.ax1)


def row_w_spec(ctx: ATPContext) -> P:
    """Row-first weight [K, N]: [Shard(0)@ax1, Shard(1)@ax2]."""
    return P(ctx.ax1, ctx.ax2)


def col_b_spec(ctx: ATPContext) -> P:
    return P(ctx.ax1)


def row_b_spec(ctx: ATPContext) -> P:
    return P(ctx.ax2)


def feat_spec(ctx: ATPContext) -> P:
    """1D feature param (norm scale): sharded like activations (ax2)."""
    return P(ctx.ax2)


def embed_spec(ctx: ATPContext) -> P:
    """Embedding [V, h]: vocab over ax1, features over ax2."""
    return P(ctx.ax1, ctx.ax2)


def replicated_spec() -> P:
    return P()


# ---------------------------------------------------------------------------
# Norms (duplicated per TP worker per the paper; feature dim is ax2-sharded
# so the variance reduction needs one tiny psum over ax2).
#
# Under the sequence-parallel block I/O spec the norm input is additionally
# seq-sharded over ax1; normalisation is per-row, so the math is unchanged
# and runs on 1/d1 of the rows.  ``gather_seq=True`` folds the conjugate
# all-gather back to full sequence into the norm epilogue (block-entry
# norms gather; post-block norms stay in the seq-sharded domain).
# ---------------------------------------------------------------------------


def rms_norm(ctx: ATPContext, x, gamma, eps: float = 1e-6,
             plus_one: bool = False, gather_seq: bool = False):
    # ax2-sharded scale, but its cotangent is ax1-PARTIAL: the norm output
    # feeds a column boundary whose out dim is ax1-sharded, so each rank's
    # scale grad sums only its columns (and, under sp, its tokens).
    with atp_scope(ctx, "norm", ctx.ax2):
        gamma = grad_sync(ctx, gamma, ctx.ax1)
        xf = x.astype(jnp.float32)
        ss = jnp.sum(xf * xf, axis=-1, keepdims=True)
        ss = atp_boundary(ss, ctx.ax2)  # full-feature sum of squares
        d = x.shape[-1] * ctx.d2
        inv = lax.rsqrt(ss / d + eps)
        g = (1.0 + gamma) if plus_one else gamma
        out = (xf * inv * g).astype(x.dtype)
        return seq_gather(ctx, out, dim=out.ndim - 2) if gather_seq else out


def layer_norm(ctx: ATPContext, x, gamma, beta, eps: float = 1e-5,
               gather_seq: bool = False):
    with atp_scope(ctx, "norm", ctx.ax2):
        gamma = grad_sync(ctx, gamma, ctx.ax1)
        beta = grad_sync(ctx, beta, ctx.ax1)
        xf = x.astype(jnp.float32)
        d = x.shape[-1] * ctx.d2
        s = atp_boundary(jnp.sum(xf, axis=-1, keepdims=True), ctx.ax2)
        mu = s / d
        ss = atp_boundary(jnp.sum((xf - mu) ** 2, axis=-1, keepdims=True),
                          ctx.ax2)
        inv = lax.rsqrt(ss / d + eps)
        out = ((xf - mu) * inv * gamma + beta).astype(x.dtype)
        return seq_gather(ctx, out, dim=out.ndim - 2) if gather_seq else out


def norm(ctx: ATPContext, cfg: ModelConfig, x, p, gather_seq: bool = False):
    if cfg.norm_kind == "layernorm":
        return layer_norm(ctx, x, p["scale"], p["bias"], cfg.norm_eps,
                          gather_seq=gather_seq)
    plus_one = cfg.name.startswith("gemma2")
    return rms_norm(ctx, x, p["scale"], cfg.norm_eps, plus_one=plus_one,
                    gather_seq=gather_seq)


def norm_params(cfg: ModelConfig, d_local: int):
    if cfg.norm_kind == "layernorm":
        return {"scale": jnp.ones((d_local,), jnp.float32),
                "bias": jnp.zeros((d_local,), jnp.float32)}
    init = jnp.zeros if cfg.name.startswith("gemma2") else jnp.ones
    return {"scale": init((d_local,), jnp.float32)}


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE for qwen2-vl).
# ---------------------------------------------------------------------------


def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float):
    """x: [b, s, heads, hd]; positions: [b, s] int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [b, s, hd/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: tuple[int, ...]):
    """qwen2-vl M-RoPE: positions3 [3, b, s] (t/h/w ids), per-section bands."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # [hd/2]
    sec = jnp.asarray(
        sum(([i] * s for i, s in enumerate(sections)), []), dtype=jnp.int32
    )  # [hd/2] -> which of t/h/w drives this band
    pos = jnp.take(positions3, sec, axis=0)  # [hd/2, b, s]
    ang = jnp.moveaxis(pos, 0, -1).astype(jnp.float32) * freqs  # [b, s, hd/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention sharding plan (DESIGN.md §6).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnPlan:
    """Static plan for sharding the attention core over d1*d2 flat ranks.

    g          : number of head blocks (ranks holding distinct q heads)
    q_loc      : q heads per block
    r          : leftover rank factor (seq-split in train/prefill,
                 redundant-compute in decode)
    q_regroup  : q must be all-gathered over ax1 (Hq % d1 != 0)
    kv_regroup : k/v must be all-gathered over ax1 (KV % d1 != 0)
    kv_start_of/kv_count: per-block kv head selection (GQA replication)
    """

    g: int
    q_loc: int
    r: int
    h2: int
    q_regroup: bool
    kv_regroup: bool
    kv_count: int
    ratio: int  # q heads per kv head


def make_attn_plan(ctx: ATPContext, num_heads: int, num_kv: int) -> AttnPlan:
    n, d1, d2 = ctx.tp, ctx.d1, ctx.d2
    q_regroup = num_heads % d1 != 0
    if q_regroup:
        g = math.gcd(num_heads, n)
        h2 = 1
    else:
        h2 = math.gcd(num_heads // d1, d2)
        g = d1 * h2
    q_loc = num_heads // g
    r = n // g
    ratio = max(1, num_heads // num_kv)
    kv_count = max(1, q_loc // ratio)
    kv_regroup = num_kv % d1 != 0
    return AttnPlan(g=g, q_loc=q_loc, r=r, h2=h2, q_regroup=q_regroup,
                    kv_regroup=kv_regroup, kv_count=kv_count, ratio=ratio)


def _block_and_r_index(ctx: ATPContext, plan: AttnPlan):
    """(head-block id, r-index) for this rank."""
    if plan.q_regroup:
        i = ctx.tp_index()
        return i // plan.r, i % plan.r
    i2 = ctx.index2()
    r2 = plan.r  # r divides d2 in the aligned case
    return ctx.index1() * plan.h2 + i2 // r2, i2 % r2


def split_qkv_heads(ctx: ATPContext, cfg: ModelConfig, qp, kp, vp, plan: AttnPlan):
    """qp/kp/vp: per-part GEMM outputs, each [..., part_dim/d1] ax1-sharded
    and ax2-replicated (q/k/v use separate weights so each part shards over
    d1 independently even when head counts don't divide d1).

    Returns this core rank's (q [b,s,q_loc,hd], k/v [b,s,kv_count,hd],
    block id, r index).
    """
    hd = cfg.hd
    d1 = ctx.d1
    bid, rid = _block_and_r_index(ctx, plan)

    if plan.q_regroup:
        q = lax.all_gather(qp, ctx.ax1, axis=-1, tiled=True) if ctx.ax1 else qp
        q = q.reshape(q.shape[:-1] + (cfg.num_heads, hd))
        q = lax.dynamic_slice_in_dim(q, bid * plan.q_loc, plan.q_loc, axis=-2)
    else:
        q = qp.reshape(qp.shape[:-1] + (cfg.num_heads // d1, hd))
        sub = (bid % plan.h2) if plan.h2 > 1 else 0
        q = lax.dynamic_slice_in_dim(q, sub * plan.q_loc, plan.q_loc, axis=-2)

    if plan.kv_regroup:
        k = lax.all_gather(kp, ctx.ax1, axis=-1, tiled=True) if ctx.ax1 else kp
        v = lax.all_gather(vp, ctx.ax1, axis=-1, tiled=True) if ctx.ax1 else vp
        k = k.reshape(k.shape[:-1] + (cfg.num_kv_heads, hd))
        v = v.reshape(v.shape[:-1] + (cfg.num_kv_heads, hd))
        kv_start = (bid * plan.q_loc) // plan.ratio
        k = lax.dynamic_slice_in_dim(k, kv_start, plan.kv_count, axis=-2)
        v = lax.dynamic_slice_in_dim(v, kv_start, plan.kv_count, axis=-2)
    else:
        k = kp.reshape(kp.shape[:-1] + (cfg.num_kv_heads // d1, hd))
        v = vp.reshape(vp.shape[:-1] + (cfg.num_kv_heads // d1, hd))
        local_q_start = (bid % plan.h2) * plan.q_loc if plan.h2 > 1 else 0
        kv_start = local_q_start // plan.ratio
        k = lax.dynamic_slice_in_dim(k, kv_start, plan.kv_count, axis=-2)
        v = lax.dynamic_slice_in_dim(v, kv_start, plan.kv_count, axis=-2)
    return q, k, v, bid, rid


def core_output_gather(ctx: ATPContext, cfg: ModelConfig, o, plan: AttnPlan, seq_split: bool):
    """o: [b, s_r, q_loc, hd] core output -> [b, s, q_dim/d1] ax2-replicated.

    seq_split: whether the r factor sliced seq (train/prefill) or produced
    redundant copies (decode).
    """
    b = o.shape[0]
    o = o.reshape(b, o.shape[1], plan.q_loc * cfg.hd)
    if ctx.tp == 1:
        return o
    if plan.q_regroup:
        gathered = lax.all_gather(o, ctx.tp_axes, axis=0, tiled=False)
        # entries ordered by flat index = bid * r + rid
        gathered = gathered.reshape((plan.g, plan.r) + o.shape)
        if seq_split and plan.r > 1:
            # [g, r, b, s_r, F] -> [g, b, r*s_r, F]
            gathered = jnp.moveaxis(gathered, 1, 3).reshape(
                plan.g, b, plan.r * o.shape[1], o.shape[2])
        else:
            gathered = gathered[:, 0]
        # heads: [g, b, s, F] -> [b, s, g*F], then slice this rank's ax1 part
        full = jnp.moveaxis(gathered, 0, 2).reshape(b, gathered.shape[2], plan.g * o.shape[2])
        return shard_slice(full, ctx.index1(), ctx.d1, dim=2)
    if ctx.ax2 is None:
        return o
    gathered = lax.all_gather(o, ctx.ax2, axis=0, tiled=False)  # [d2, b, s_r, F]
    gathered = gathered.reshape((plan.h2, plan.r) + o.shape)
    if seq_split and plan.r > 1:
        gathered = jnp.moveaxis(gathered, 1, 3).reshape(
            plan.h2, b, plan.r * o.shape[1], o.shape[2])
    else:
        gathered = gathered[:, 0]
    return jnp.moveaxis(gathered, 0, 2).reshape(b, gathered.shape[2], plan.h2 * o.shape[2])


# ---------------------------------------------------------------------------
# Attention core math (GQA + causal/local masks + softcap).
# ---------------------------------------------------------------------------


def attention_core(
    cfg: ModelConfig,
    q, k, v,                      # q: [b, sq, hq, hd]; k/v: [b, skv, hkv, hd]
    q_offset,                     # absolute position of q[0]: scalar, or
                                  # [b] per-slot (paged continuous batching)
    kv_len=None,                  # decode: valid cache length (scalar or [b])
    window: int = 0,              # sliding window (0 = global)
):
    """The scores, mask, softmax and ``p @ v`` of attention, under the
    ``attn:core`` name scope (what a fused attention kernel replaces)."""
    with jax.named_scope("attn:core"):
        b, sq, hq, hd = q.shape
        skv, hkv = k.shape[1], k.shape[2]
        rep = hq // hkv
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        scale = 1.0 / math.sqrt(cfg.hd if cfg.mla is None else q.shape[-1])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        if cfg.attn_softcap:
            scores = cfg.attn_softcap * jnp.tanh(scores / cfg.attn_softcap)
        # per-slot offsets/lengths (paged serving) build a [b, 1, sq, skv]
        # mask; the scalar path keeps its original [1, 1, sq, skv] shape
        q_off = jnp.asarray(q_offset)
        per_slot = q_off.ndim > 0
        if per_slot:
            qpos = q_off[:, None, None] + jnp.arange(sq)[None, :, None]
            kpos = jnp.arange(skv)[None, None, :]
        else:
            qpos = q_off + jnp.arange(sq)[:, None]
            kpos = jnp.arange(skv)[None, :]
        mask = kpos <= qpos
        # window may be a traced per-layer scalar (scanned); 0 means global
        win = jnp.asarray(window, jnp.int32)
        win_eff = jnp.where(win > 0, win, jnp.int32(2**30))
        mask &= kpos > qpos - win_eff
        if kv_len is not None:
            kl = jnp.asarray(kv_len)
            mask = mask & (kpos < (kl[:, None, None] if kl.ndim else kl))
        # [b, sq, skv] -> [b, 1, sq, skv]; scalar path [sq, skv] -> [1, 1, ...]
        mask = mask[:, None] if mask.ndim == 3 else mask[None, None]
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

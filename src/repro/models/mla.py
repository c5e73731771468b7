"""DeepSeek-V3 Multi-head Latent Attention, ATP-sharded.

Sharding decisions (DESIGN.md §5):
  - down-projections to the tiny latents (q: 1536, kv: 512+64) produce
    *replicated* latents: rows sharded over ax2, psum(ax2) -> replicated.
  - up-projections shard their per-head outputs over ax1 (column-first with
    no row sharding: input is replicated, so no boundary psum is needed).
  - attention core: heads over the flat d1*d2 ranks (128 % 256-rank meshes
    always divide for the assigned meshes: 128/16 = 8).
  - decode caches the *latent* (c_kv + k_rope), replicated over TP —
    that is MLA's entire point; the absorbed form computes scores directly
    against the latent.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.atp import (ATPContext, atp_boundary, atp_linear, grad_sync,
                            shard_slice)
from repro.models import layers as L
from repro.models import paging


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def mla_params(key, cfg: ModelConfig, dtype) -> dict[str, Any]:
    m = cfg.mla
    h, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    s = 1.0 / math.sqrt(h)
    return {
        "w_dq": _init(ks[0], (h, m.q_lora_rank), s, dtype),
        "w_uq": _init(ks[1], (m.q_lora_rank, H * qk), 1 / math.sqrt(m.q_lora_rank), dtype),
        "w_dkv": _init(ks[2], (h, m.kv_lora_rank + m.qk_rope_head_dim), s, dtype),
        "w_ukv": _init(ks[3], (m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)),
                       1 / math.sqrt(m.kv_lora_rank), dtype),
        "wo": _init(ks[4], (H * m.v_head_dim, h), 1 / math.sqrt(H * m.v_head_dim), dtype),
        "q_ln": jnp.ones((m.q_lora_rank,), jnp.float32),
        "kv_ln": jnp.ones((m.kv_lora_rank,), jnp.float32),
    }


def mla_param_specs(ctx: ATPContext, cfg: ModelConfig) -> dict[str, Any]:
    return {
        "w_dq": P(ctx.ax2, None),    # rows over ax2, replicated output
        "w_uq": P(None, ctx.ax1),    # latent replicated, heads over ax1
        "w_dkv": P(ctx.ax2, None),
        "w_ukv": P(None, ctx.ax1),
        "wo": L.row_w_spec(ctx),
        "q_ln": L.replicated_spec(),
        "kv_ln": L.replicated_spec(),
    }


def _latent_norm(x, gamma, eps):
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * gamma).astype(x.dtype)


def _heads_per_rank(ctx: ATPContext, cfg: ModelConfig) -> int:
    assert cfg.num_heads % ctx.tp == 0, "MLA heads must divide flat TP"
    return cfg.num_heads // ctx.tp


def mla_block(
    ctx: ATPContext,
    cfg: ModelConfig,
    p,
    x,                  # [b, s, h/d2]
    positions,          # [b, s]
    cache=None,         # decode: dict(ckv=[b,S,rank], krope=[b,S,rd], len=..)
                        # or paged pools dict(ckv=[np,pg,rank], krope=...),
                        # stacked [count, np, ...] when paged["layer"]
    paged=None,         # paged serving: dict(table=[b,mp], start=[b],
                        # optional layer=scalar into stacked pools)
):
    """Returns ([b, s, h/d2], new_cache)."""
    m = cfg.mla
    qk_nope, qk_rope, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    h_loc = _heads_per_rank(ctx, cfg)
    i2 = ctx.index2()

    # ---- latents (replicated): rows of w_d* are ax2-sharded -> psum(ax2).
    # Grad barriers: the latents' cotangent flows back from the rank-local
    # (ax1-col x ax2-subslice) head shard, so the replicated latent-norm
    # gains are tp-partial; the down-proj weights (ax1-replicated storage,
    # ax2-completed ct via the boundary transpose) are ax1-partial.
    cq = atp_boundary(jnp.einsum("...k,kn->...n", x,
                                 grad_sync(ctx, p["w_dq"], ctx.ax1)), ctx.ax2)
    cq = _latent_norm(cq, grad_sync(ctx, p["q_ln"], ctx.tp_axes), cfg.norm_eps)
    ckv_full = atp_boundary(jnp.einsum("...k,kn->...n", x,
                                       grad_sync(ctx, p["w_dkv"], ctx.ax1)),
                            ctx.ax2)
    ckv = _latent_norm(ckv_full[..., : m.kv_lora_rank],
                       grad_sync(ctx, p["kv_ln"], ctx.tp_axes), cfg.norm_eps)
    k_rope = ckv_full[..., m.kv_lora_rank:]             # [b, s, rope_dim]

    # ---- q up-projection: heads over ax1, extra d2 factor sliced from ax1's
    # block (w_uq columns are ax1-sharded; slice the ax2 sub-block locally —
    # the slice makes the ax2-replicated up-proj grads ax2-partial)
    uq = jnp.einsum("...k,kn->...n", cq, grad_sync(ctx, p["w_uq"], ctx.ax2))
    uq = shard_slice(uq, i2, ctx.d2, dim=-1)            # [b, s, H*(qk)/n]
    q = uq.reshape(uq.shape[:-1] + (h_loc, qk_nope + qk_rope))
    q_nope, q_pe = q[..., :qk_nope], q[..., qk_nope:]
    q_pe = L.apply_rope(q_pe, positions if cache is None else positions, cfg.rope_theta)

    new_cache = None
    if cache is None:
        # ---- train/prefill: expand latent to per-head k/v
        ukv = jnp.einsum("...k,kn->...n", ckv, grad_sync(ctx, p["w_ukv"], ctx.ax2))
        ukv = shard_slice(ukv, i2, ctx.d2, dim=-1)
        kv = ukv.reshape(ukv.shape[:-1] + (h_loc, qk_nope + dv))
        k_nope, v = kv[..., :qk_nope], kv[..., qk_nope:]
        k_pe = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
        k_pe = jnp.broadcast_to(k_pe, k_nope.shape[:-1] + (qk_rope,))
        k = jnp.concatenate([k_nope, k_pe], axis=-1)
        qq = jnp.concatenate([q_nope, q_pe], axis=-1)
        o = L.attention_core(cfg, qq, k, v, q_offset=0)           # [b,s,h_loc,dv]
    else:
        # ---- decode (absorbed): score against the latent directly
        sq = x.shape[1]
        k_pe_new = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
        if paged is not None:
            # paged serving: the latent pools are position-paged exactly
            # like K/V; scatter this run, gather the slot's mapped pages
            table, start = paged["table"], paged["start"]
            layer = paged.get("layer")
            pd = paging.pool_page_dtype(cache["ckv"])
            pckv, pckv_s = paging.append_tokens_q(
                cache["ckv"], cache.get("ckv_scale"), table, start, ckv, pd,
                layer)
            pkr, pkr_s = paging.append_tokens_q(
                cache["krope"], cache.get("krope_scale"), table, start,
                k_pe_new, pd, layer)
            new_cache = {"ckv": pckv, "krope": pkr}
            if pckv_s is not None:
                new_cache["ckv_scale"], new_cache["krope_scale"] = pckv_s, pkr_s
            cckv = paging.gather_pages_q(pckv, pckv_s, table,
                                         out_dtype=ckv.dtype,
                                         layer=layer)  # [b,S_alloc,rank]
            ckr = paging.gather_pages_q(pkr, pkr_s, table,
                                        out_dtype=k_pe_new.dtype, layer=layer)
            klen = start                                 # [b] per-slot
        else:
            klen = cache["len"]
            cckv = lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), klen, axis=1)
            ckr = lax.dynamic_update_slice_in_dim(
                cache["krope"], k_pe_new.astype(cache["krope"].dtype), klen, axis=1)
            new_cache = {"ckv": cckv, "krope": ckr, "len": klen + sq}
        # absorb W_ukv(k-part) into q:  q_abs = q_nope @ W_uk^T  [b,1,hl,rank]
        w_ukv = p["w_ukv"].reshape(m.kv_lora_rank, cfg.num_heads // ctx.d1, qk_nope + dv)
        w_ukv = shard_slice(w_ukv, i2, ctx.d2, dim=1)   # [rank, h_loc, qk+dv]
        w_uk, w_uv = w_ukv[..., :qk_nope], w_ukv[..., qk_nope:]
        q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)
        scores = (
            jnp.einsum("bqhr,bkr->bhqk", q_abs.astype(jnp.float32),
                       cckv.astype(jnp.float32))
            + jnp.einsum("bqhd,bkd->bhqk", q_pe.astype(jnp.float32),
                         ckr.astype(jnp.float32))
        ) / math.sqrt(qk_nope + qk_rope)
        kpos = jnp.arange(cckv.shape[1])[None, None, None, :]
        if paged is not None:
            qpos = klen[:, None, None, None] + jnp.arange(sq)[None, None, :, None]
        else:
            qpos = klen + jnp.arange(sq)[None, None, :, None]
        scores = jnp.where(kpos <= qpos, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhqk,bkr->bqhr", probs, cckv.astype(jnp.float32))
        o = jnp.einsum("bqhr,rhd->bqhd", o_lat, w_uv.astype(jnp.float32)).astype(x.dtype)

    o = o.reshape(o.shape[0], o.shape[1], h_loc * dv)
    # gather core output over ax2 back to ax1-sharded layout for row-first wo
    if ctx.ax2 is not None:
        o = lax.all_gather(o, ctx.ax2, axis=-1, tiled=True)
    return atp_linear(ctx, o, p["wo"], kind="row"), new_cache

"""xLSTM blocks (mLSTM + sLSTM), ATP-sharded.

mLSTM: matrix-memory recurrence C_t = f_t C_{t-1} + i_t v_t k_t^T with
readout h_t = (C_t q_t) / max(|n_t . q_t|, 1), implemented chunkwise (same
structure as the SSD scan: per-head scalar decay).

Sharding (v2 layout — the §Perf hillclimb result; v1 all-gathered the full
up-projection and re-gathered the output, making xlstm the most
collective-bound arch in the baseline table):
  - up/z projections: column-first with a (head-major, value-dim) column
    order, so each flat TP rank's natural column slice IS its
    (head-block, dv-slice) shard — no gather.
  - q/k (+ i/f gates): computed from the block input with a
    replicated-output projection (rows over ax2, psum(ax2)); every rank
    holds full per-head q/k (tiny: 2*nh*dk) and slices its head.
    v is the conv'd up-projection slice directly (as in official mLSTM).
  - down projection: rows are flat-sharded, so the boundary all-reduces
    over BOTH mesh dims at once ([b,s,h/d2] — same volume as f4).
  - conv is depthwise -> sharding-transparent on the local channel slice.

sLSTM: inherently sequential, small -> replicated across TP (documented
applicability boundary of the paper's technique), 1 block in 8.

Deviations from official xLSTM (documented in DESIGN.md): sigmoid input
gate (bounded; removes the max-stabilizer state); q/k projected from the
block input rather than the conv'd up-projection.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.atp import (ATPContext, atp_boundary, gather_features,
                            grad_sync, shard_slice, vma_rewrite_active)
from repro.models import layers as L


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def mlstm_dims(cfg: ModelConfig):
    d_inner = int(cfg.ssm.proj_factor * cfg.d_model)
    nh = cfg.num_heads
    dv = d_inner // nh          # value/head dim
    dk = dv // 2                # query/key dim (official mLSTM uses dv/2)
    return d_inner, nh, dk, dv


def mlstm_plan(ctx: ATPContext, cfg: ModelConfig):
    """(head shard g, value-dim shard r): g*r == flat tp."""
    _, nh, _, dv = mlstm_dims(cfg)
    g = math.gcd(nh, ctx.tp)
    r = ctx.tp // g
    assert dv % r == 0, "mLSTM value dim must divide leftover TP factor"
    assert (nh // g) == 1 or r == 1, \
        "flat column slicing needs one head per block (or r == 1)"
    return g, r


def mlstm_params(key, cfg: ModelConfig, dtype) -> dict[str, Any]:
    h = cfg.d_model
    d_inner, nh, dk, dv = mlstm_dims(cfg)
    ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(h)
    return {
        "ln": jnp.ones((h,), jnp.float32),
        # columns ordered (head-major, dv): rank slice == (head, dv) shard
        "w_up": _init(ks[0], (h, d_inner), s, dtype),     # v path
        "w_z": _init(ks[1], (h, d_inner), s, dtype),      # output gate path
        "conv": _init(ks[2], (cfg.ssm.conv_kernel, d_inner), 0.5, jnp.float32),
        # q/k from the block input: column-first sharded over ax1, gathered
        # (small: 2*nh*dk == d_inner/1); i/f gates replicated-out (tiny)
        "w_qk": _init(ks[3], (h, 2 * nh * dk), s, dtype),
        "w_if": _init(jax.random.fold_in(ks[3], 1), (h, 2 * nh), s, dtype),
        "b_if": jnp.zeros((2 * nh,), jnp.float32),
        "w_down": _init(ks[4], (d_inner, h), 1.0 / math.sqrt(d_inner), dtype),
        "gn": jnp.ones((d_inner,), jnp.float32),
    }


def mlstm_param_specs(ctx: ATPContext, cfg: ModelConfig) -> dict[str, Any]:
    flat = ctx.tp_axes or None
    return {
        "ln": L.feat_spec(ctx),
        # columns over ax1; the ax2 sub-slice happens in-code (a spec may
        # not name tp2 on two dims), yielding the flat (head, dv) shard
        "w_up": L.col_w_spec(ctx),
        "w_z": L.col_w_spec(ctx),
        "conv": P(None, flat),
        "w_qk": L.col_w_spec(ctx),
        "w_if": P(ctx.ax2, None),     # replicated output (tiny)
        "b_if": L.replicated_spec(),
        "w_down": P(flat, None),      # rows flat-sharded, cols whole
        "gn": P(flat),
    }


def _mlstm_chunked(q, k, v, li, lf, chunk: int, state=None):
    """Chunkwise mLSTM.  q,k: [b,s,nh,dk]; v: [b,s,nh,dv];
    li/lf: [b,s,nh] log input/forget gates.  state: [b,nh,dk,dv+1].

    The normalizer n is folded in as an extra value channel of ones.
    Returns (h [b,s,nh,dv], state_out)."""
    b, s, nh, dk = q.shape
    dv = v.shape[-1]
    nc = max(1, s // chunk)
    cl = s // nc
    ones = jnp.ones(v.shape[:-1] + (1,), v.dtype)
    va = jnp.concatenate([v, ones], axis=-1)                     # [b,s,nh,dv+1]

    qr = q.reshape(b, nc, cl, nh, dk).astype(jnp.float32)
    kr = k.reshape(b, nc, cl, nh, dk).astype(jnp.float32)
    vr = va.reshape(b, nc, cl, nh, dv + 1).astype(jnp.float32)
    lir = li.reshape(b, nc, cl, nh)
    lfr = lf.reshape(b, nc, cl, nh)

    lc = jnp.cumsum(lfr, axis=2)                                 # cumulative log f
    seg = lc[:, :, :, None, :] - lc[:, :, None, :, :]            # [b,nc,t,u,nh]
    causal = jnp.tril(jnp.ones((cl, cl), bool))
    decay = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    qk = jnp.einsum("bnthd,bnuhd->bntuh", qr, kr) / math.sqrt(dk)
    w = qk * decay * jnp.exp(lir)[:, :, None, :, :]
    h_intra = jnp.einsum("bntuh,bnuhe->bnthe", w, vr)

    dec_end = jnp.exp(lc[:, :, -1:, :] - lc + lir)               # [b,nc,cl,nh]
    S = jnp.einsum("bnuhd,bnuhe->bnhde", kr * dec_end[..., None], vr)
    gain = jnp.exp(lc[:, :, -1, :])

    def step(carry, inp):
        S_n, g_n = inp
        return carry * g_n[:, :, None, None] + S_n, carry

    Sm = jnp.moveaxis(S, 1, 0)
    # zeros_like keeps the vma type of S (varying over the right mesh axes)
    init = (jnp.zeros_like(Sm[0]) if state is None
            else state.astype(jnp.float32))
    state_out, entering = lax.scan(step, init, (Sm, jnp.moveaxis(gain, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)
    h_cross = jnp.einsum("bnthd,bnhde->bnthe", qr, entering) * \
        jnp.exp(lc)[..., None] / math.sqrt(dk)

    ha = (h_intra + h_cross).reshape(b, s, nh, dv + 1)
    num, den = ha[..., :dv], ha[..., dv:]
    out = num / jnp.maximum(jnp.abs(den), 1.0)
    return out.astype(q.dtype), state_out


def _mlstm_step(q, k, v, li, lf, state):
    """Decode step.  q,k: [b,1,nh,dk]; state [b,nh,dk,dv+1]."""
    b, _, nh, dk = q.shape
    dv = v.shape[-1]
    ones = jnp.ones(v.shape[:-1] + (1,), v.dtype)
    va = jnp.concatenate([v, ones], -1)[:, 0].astype(jnp.float32)
    f = jnp.exp(lf[:, 0])[:, :, None, None]
    i = jnp.exp(li[:, 0])[:, :, None, None]
    new = state.astype(jnp.float32) * f + i * jnp.einsum(
        "bhd,bhe->bhde", k[:, 0].astype(jnp.float32), va)
    ha = jnp.einsum("bhd,bhde->bhe", q[:, 0].astype(jnp.float32), new) / math.sqrt(dk)
    num, den = ha[..., :dv], ha[..., dv:]
    out = (num / jnp.maximum(jnp.abs(den), 1.0))[:, None]
    return out.astype(q.dtype), new


def mlstm_block(ctx: ATPContext, cfg: ModelConfig, p, x, state=None):
    """x: [b, s, h/d2] -> (same spec, new_state).

    state (decode): dict(conv=[b,k-1,d_inner/n], C=[b,1,nh_loc,dk,dv_loc+1])."""
    d_inner, nh, dk, dv = mlstm_dims(cfg)
    g, r = mlstm_plan(ctx, cfg)
    flat = ctx.tp_index()
    hb = flat // r       # head block (nh_loc == 1 when r > 1)
    nh_loc = nh // g
    dv_loc = dv // r

    h_in = L.rms_norm(ctx, x, p["ln"], cfg.norm_eps)

    # up/z: column-first (ax1) + in-code ax2 sub-slice: with the head-major
    # (head, dv) column order, the flat slice i1*d2+i2 IS this rank's
    # (head-block, dv-slice) shard — no gather
    w_cat = jnp.concatenate([p["w_up"], p["w_z"]], axis=1)
    ug = atp_boundary(jnp.einsum("...k,kn->...n", h_in, w_cat), ctx.ax2)
    u_loc, z_loc = jnp.split(ug, 2, axis=-1)          # [b, s, d_inner/d1]
    if ctx.ax2 is not None:
        u_loc = shard_slice(u_loc, ctx.index2(), ctx.d2, dim=-1)
        z_loc = shard_slice(z_loc, ctx.index2(), ctx.d2, dim=-1)
    # u_loc/z_loc: [b, s, d_inner/n]

    # depthwise conv on the local channel slice (spec-sliced weights)
    cstate = state["conv"] if state is not None else None
    u_c, conv_ns = _conv_local(u_loc, p["conv"], cstate)
    v = jax.nn.silu(u_c).reshape(u_c.shape[0], u_c.shape[1], nh_loc, dv_loc)

    # q/k: column-first sharded over ax1, then a small all-gather (the qk
    # tensor is 2*nh*dk ~= d_model wide — ~8x less than the v1 full-u gather)
    qk = atp_boundary(jnp.einsum("...k,kn->...n", h_in, p["w_qk"]), ctx.ax2)
    if ctx.ax1 is not None:
        qk = lax.all_gather(qk, ctx.ax1, axis=-1, tiled=True)
    qf = qk[..., : nh * dk].reshape(*qk.shape[:2], nh, dk)
    kf = qk[..., nh * dk:].reshape(*qk.shape[:2], nh, dk)
    # i/f gates: replicated-output projection (tiny).  The gate cotangent
    # is rank-head-partial: w_if (ax1-replicated storage) needs the ax1
    # barrier after the boundary transpose's psum(ax2); b_if (fully
    # replicated, added past the boundary) needs the whole flat group.
    if_pre = atp_boundary(jnp.einsum("...k,kn->...n", h_in,
                                     grad_sync(ctx, p["w_if"], ctx.ax1)),
                          ctx.ax2).astype(jnp.float32) \
        + grad_sync(ctx, p["b_if"], ctx.tp_axes)
    li_all = jax.nn.log_sigmoid(if_pre[..., :nh])
    lf_all = jax.nn.log_sigmoid(if_pre[..., nh:])
    q = lax.dynamic_slice_in_dim(qf, hb * nh_loc, nh_loc, axis=2)
    k = lax.dynamic_slice_in_dim(kf, hb * nh_loc, nh_loc, axis=2)
    li = lax.dynamic_slice_in_dim(li_all, hb * nh_loc, nh_loc, axis=-1)
    lf = lax.dynamic_slice_in_dim(lf_all, hb * nh_loc, nh_loc, axis=-1)

    if state is None:
        y, _ = _mlstm_chunked(q, k, v, li, lf, cfg.ssm.chunk)
        new_state = None
    else:
        if q.shape[1] == 1:
            y, C_new = _mlstm_step(q, k, v, li, lf, state["C"][:, 0])
        else:  # prefill-into-state
            y, C_new = _mlstm_chunked(q, k, v, li, lf, cfg.ssm.chunk,
                                      state=state["C"][:, 0])
        new_state = {"conv": conv_ns,
                     "C": C_new[:, None].astype(state["C"].dtype)}

    gn = p["gn"].reshape(nh_loc, dv_loc)              # spec-sliced
    yf = y.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(yf * yf, axis=-1, keepdims=True) + 1e-6)
    y = (yf * inv * gn).astype(y.dtype)

    # output gate from the local z slice
    zh = z_loc.reshape(z_loc.shape[0], z_loc.shape[1], nh_loc, dv_loc)
    y = (y * jax.nn.silu(zh)).reshape(y.shape[0], y.shape[1], nh_loc * dv_loc)

    # down projection: rows flat-sharded (spec-sliced) -> one all-reduce
    # over both mesh dims, then the free ax2 feature slice.  (At d2>1 a
    # reduce-scatter(ax2)+psum(ax1) pair would halve the bytes — noted in
    # EXPERIMENTS §Perf; the production (16,1) baseline is already optimal.)
    out = atp_boundary(jnp.einsum("...k,kn->...n", y, p["w_down"]),
                       ctx.tp_axes if ctx.tp_axes else None)
    if ctx.ax2 is not None:
        out = shard_slice(out, ctx.index2(), ctx.d2, dim=-1)
    return x + out, new_state


def _conv_local(x, w, state=None):
    from repro.models.mamba2 import _causal_conv
    return _causal_conv(x, w, state)


# ---------------------------------------------------------------------------
# sLSTM (replicated across TP; sequential lax.scan over time).
# ---------------------------------------------------------------------------


def slstm_params(key, cfg: ModelConfig, dtype) -> dict[str, Any]:
    h = cfg.d_model
    nh = cfg.num_heads
    dh = h // nh
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(h)
    d_ff = int(1.3 * h)
    return {
        "ln": jnp.ones((h,), jnp.float32),
        "w_gates": _init(ks[0], (h, 4 * h), s, jnp.float32),      # z i f o
        "r_gates": _init(ks[1], (nh, dh, 4 * dh), 1 / math.sqrt(dh), jnp.float32),
        "b_gates": jnp.zeros((4 * h,), jnp.float32),
        "gn": jnp.ones((h,), jnp.float32),
        "w_ff1": _init(ks[2], (h, d_ff), s, dtype),
        "w_ff2": _init(ks[3], (d_ff, h), 1 / math.sqrt(d_ff), dtype),
    }


def slstm_param_specs(ctx: ATPContext, cfg: ModelConfig) -> dict[str, Any]:
    # replicated: inherently sequential recurrence, small block
    return {k: P() for k in
            ("ln", "w_gates", "r_gates", "b_gates", "gn", "w_ff1", "w_ff2")}


def slstm_block(ctx: ATPContext, cfg: ModelConfig, p, x, state=None):
    """x: [b, s, h/d2]; recurrence runs on full-h replicated activations."""
    nh = cfg.num_heads
    h = cfg.d_model
    dh = h // nh
    xg = x
    if ctx.ax2 is not None:  # full h for the recurrent mixing; a cached
        # state is typed replicated over ax2, so with one the input must be
        xg = gather_features(ctx, x, invariant=state is not None)
    # All sLSTM params are fully replicated (P()) while the block's
    # cotangent is rank-partial over the whole flat group (residual ct is
    # ax1-partial by the row-boundary convention and ax2-chunked by the
    # exit shard_slice), so every param grad needs the full-group barrier.
    h_in = _rms_full(xg, grad_sync(ctx, p["ln"], ctx.tp_axes), cfg.norm_eps)
    r_gates = grad_sync(ctx, p["r_gates"], ctx.tp_axes)
    pre = h_in.astype(jnp.float32) @ grad_sync(ctx, p["w_gates"], ctx.tp_axes) \
        + grad_sync(ctx, p["b_gates"], ctx.tp_axes)                # [b,s,4h]

    def step(carry, u):
        c, n, hs = carry                                # [b, nh, dh] each
        rec = jnp.einsum("bhd,hde->bhe", hs, r_gates)   # [b, nh, 4dh]
        gts = u.reshape(u.shape[0], nh, 4 * dh) + rec
        z, i, f, o = jnp.split(gts, 4, axis=-1)
        z, i = jnp.tanh(z), jax.nn.sigmoid(i)
        f, o = jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        c_new = f * c + i * z
        n_new = f * n + i
        hs_new = o * (c_new / jnp.maximum(n_new, 1.0))
        return (c_new, n_new, hs_new), hs_new

    b = x.shape[0]
    if state is None:
        # zeros_like(slice of pre) keeps the vma type (varying over data/ax2)
        z0 = jnp.zeros_like(pre[:, 0, : nh * dh]).reshape(b, nh, dh)
        init = (z0, z0, z0)
    else:
        init = (state["c"], state["n"], state["h"])
    if vma_rewrite_active(ctx):
        # cast r_gates varying, once, over every axis the recurrence varies
        # on: left to the rewrite, the cast (and its transpose, the psum of
        # d(r_gates)) lands inside the scan body, one all-reduce per time
        # step.  Builds without the rewrite reduce it once in grad_sync.
        vary = tuple(a for a in jax.typeof(pre).vma
                     if a not in jax.typeof(r_gates).vma)
        if vary:
            r_gates = lax.pcast(r_gates, vary, to="varying")
    (c, n, hs), ys = lax.scan(step, init, jnp.moveaxis(pre, 1, 0))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, x.shape[1], h)
    new_state = {"c": c, "n": n, "h": hs} if state is not None else None

    y = _rms_full(y, grad_sync(ctx, p["gn"], ctx.tp_axes),
                  cfg.norm_eps).astype(x.dtype)
    y = jax.nn.gelu(y @ grad_sync(ctx, p["w_ff1"], ctx.tp_axes),
                    approximate=True) @ grad_sync(ctx, p["w_ff2"], ctx.tp_axes)
    if ctx.ax2 is not None:  # back to the block I/O feature shard
        y = shard_slice(y, ctx.index2(), ctx.d2, dim=-1)
    return x + y, new_state


def _rms_full(x, gamma, eps):
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf * inv * gamma


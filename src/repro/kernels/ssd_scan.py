"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid: (batch, heads, num_chunks) — chunk axis fastest; the running state
[hd, ds] persists in VMEM scratch across chunks of one (b, h) stream.
Per chunk: intra-chunk lower-triangular mix + cross-chunk read of the
carried state + state update — the [cl, cl] decay matrix and the state
never touch HBM (vs the jnp oracle, which materializes both per chunk).

Inputs are pre-arranged by ops.py into chunk-major layouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtr_ref, dtc_ref, a_ref, b_ref, c_ref, d_ref, o_ref,
                state, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, 0, 0].astype(jnp.float32)         # [cl, hd]
    dt_r = dtr_ref[0, 0, 0].astype(jnp.float32)    # [1, cl]  (dt along lanes)
    dt_c = dtc_ref[0, 0, 0].astype(jnp.float32)    # [cl, 1]  (dt along rows)
    A = -jnp.exp(a_ref[0].astype(jnp.float32))     # [1, 1]   (this head)
    B = b_ref[0, 0].astype(jnp.float32)            # [cl, ds]
    C = c_ref[0, 0].astype(jnp.float32)            # [cl, ds]
    D = d_ref[0].astype(jnp.float32)               # [1, 1]

    # inclusive prefix sums of dA in both orientations, as masked
    # reductions (no cumsum or transpose inside the kernel)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    u_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = u_idx <= t_idx
    la_c = jnp.sum(jnp.where(causal, dt_r * A, 0.0), axis=1,
                   keepdims=True)                  # [cl, 1]: la[t]
    la_r = jnp.sum(jnp.where(t_idx <= u_idx, dt_c * A, 0.0), axis=0,
                   keepdims=True)                  # [1, cl]: la[u]
    la_end = jnp.sum(dt_r * A, axis=1, keepdims=True)   # [1, 1]

    decay = jnp.where(causal, jnp.exp(la_c - la_r), 0.0)          # [cl, cl]
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))   # [cl(t), cl(u)]
    w = cb * decay * dt_r
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())))    # [cl, hd]

    # cross-chunk from carried state: y += exp(la)[:,None] * (C @ state^T)
    cross = jax.lax.dot_general(C, state[...], (((1,), (1,)), ((), ())))
    y += jnp.exp(la_c) * cross

    o_ref[0, 0, 0] = (y + D * x).astype(o_ref.dtype)

    # state' = state * exp(la[-1]) + sum_u exp(la[-1]-la[u]) dt_u x_u B_u^T
    dec_end = jnp.exp(la_end - la_c) * dt_c        # [cl, 1]
    upd = jax.lax.dot_general(x * dec_end, B,
                              (((0,), (0,)), ((), ())))        # [hd, ds]
    state[...] = state[...] * jnp.exp(la_end) + upd


def ssd_scan(x, dt, A_log, B, C, D, *, chunk: int = 64, interpret: bool = False):
    """x: [b, s, nh, hd]; dt: [b, s, nh]; B/C: [b, s, ds]; A_log/D: [nh].

    Returns y: [b, s, nh, hd] (state output handled by the jnp oracle in
    training; the kernel targets the long-sequence prefill hot spot).

    Every block's last two dims equal the array's (TPU tiling rule): dt
    rides in as a [1, cl] row and a [cl, 1] column, and the per-head
    scalars as [nh, 1, 1]."""
    b, s, nh, hd = x.shape
    ds = B.shape[-1]
    assert s % chunk == 0, "seq must divide the chunk size"
    nc = s // chunk
    # chunk-major layouts
    xc = jnp.moveaxis(x.reshape(b, nc, chunk, nh, hd), 3, 1)     # [b,nh,nc,cl,hd]
    dtc = jnp.moveaxis(dt.reshape(b, nc, chunk, nh), 3, 1)       # [b,nh,nc,cl]
    dt_row = dtc[:, :, :, None, :]                               # [b,nh,nc,1,cl]
    dt_col = dtc[..., None]                                      # [b,nh,nc,cl,1]
    Bc = B.reshape(b, nc, chunk, ds)
    Cc = C.reshape(b, nc, chunk, ds)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, hd), lambda i, h, c: (i, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda i, h, c: (i, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda i, h, c: (i, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, chunk, ds), lambda i, h, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, ds), lambda i, h, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, h, c: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, hd), lambda i, h, c: (i, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(xc.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        interpret=interpret,
    )(xc, dt_row, dt_col, A_log.reshape(nh, 1, 1), Bc, Cc, D.reshape(nh, 1, 1))
    return jnp.moveaxis(out, 1, 3).reshape(b, s, nh, hd)

"""jit'd public wrappers around the Pallas kernels.

The kernels compile for the TPU.  ``interpret=True`` runs them through the
Pallas interpreter instead, which is how the CPU correctness tests call
them; nothing switches to it implicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret=False):
    """q: [b, sq, hq, d]; k/v: [b, sk, hkv, d] (GQA-repeated here)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hq, -1, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hq, -1, d)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                            softcap=softcap, block_q=block_q, block_k=block_k,
                            interpret=interpret)
    return o.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, gamma, *, eps=1e-6, block_rows=128, interpret=False):
    shape = x.shape
    out = _rn.rmsnorm(x.reshape(-1, shape[-1]), gamma, eps=eps,
                      block_rows=block_rows, interpret=interpret)
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("activation", "block_m",
                                             "block_n", "block_k", "interpret"))
def matmul(a, b, bias=None, *, activation=None, block_m=128, block_n=128,
           block_k=128, interpret=False):
    """a @ b with optional fused bias [N] + activation epilogue."""
    return _mm.matmul(a, b, bias, activation=activation, block_m=block_m,
                      block_n=block_n, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A_log, B, C, D, *, chunk=64, interpret=False):
    return _ssd.ssd_scan(x, dt, A_log, B, C, D, chunk=chunk,
                         interpret=interpret)

"""Paged decode attention Pallas TPU kernel: one query row per slot, K/V
read in place from the lane-dense stacked page pool.

The pools are ``[layers, num_pages, page, banks * hd]`` (``lm.init_paged_caches``)
and stay in HBM (``memory_space=pl.ANY``).  Grid: one step per slot.  Each
step walks the slot's page blocks (``pages_per_block`` pages each) from the
first block its window reaches to the block holding its last position,
DMAing a block's pages from ``pool[layer, table[b, j]]`` into a VMEM
double buffer while the previous block computes; the last block of a slot
starts the next slot's first one.  Blocks past a slot's length are never
read, so a call costs the live pages, not the page-table width.

The math stays on the lane-dense rows (no relayout to ``[.., banks, hd]``):
the query enters as a block-diagonal ``[hq, banks * hd]`` matrix (head
``h`` in the columns of its KV bank ``h // rep``, which covers GQA), so one
MXU matmul against a K block gives every head's scores; ``p @ V`` lands in
``[hq, banks * hd]`` and each head keeps its own bank's columns at the end.
f32 online softmax, masks exactly as ``models.layers.attention_core`` for
one query at position ``length - 1``: positions below the length, inside
the window when it is positive, softcap before the mask.  Correctness is
checked in interpret mode on CPU against the gather + ``attention_core``
path (tests/test_paged_attention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: pages a block DMAs and computes at once (16 positions a page)
PAGES_PER_BLOCK = 16


def _kernel(layer_ref, table_ref, len_ref, win_ref,      # scalar prefetch
            q_ref, sel_ref, fold_ref, k_hbm, v_hbm,      # inputs
            o_ref,                                       # output
            kbuf, vbuf, sems, nextbuf,                   # scratch
            *, scale: float, softcap: float, ppb: int, mp: int):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    page = kbuf.shape[2]
    blk = ppb * page
    layer = layer_ref[0]
    win = win_ref[0]

    def span(s):
        """(first block, end block, lowest valid position) of slot s.  At
        least one block and none past the table: a slot whose loop ran no
        block would leave the next slot's first block unfetched."""
        n = len_ref[s]
        lo = jnp.where(win > 0, jnp.maximum(n - win, 0), 0)
        end = jnp.clip(lax.div(n + blk - 1, blk), 1, mp // ppb)
        return jnp.minimum(lax.div(lo, blk), end - 1), end, lo

    def copies(s, j, buf):
        out = []
        for p in range(ppb):
            pid = table_ref[s * mp + j * ppb + p]
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, pid], kbuf.at[buf, p], sems.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, pid], vbuf.at[buf, p], sems.at[1, buf]))
        return out

    first, end, lo = span(b)
    n = len_ref[b]

    @pl.when(b == 0)
    def _prime():
        nextbuf[0] = 0
        for c in copies(0, first, 0):
            c.start()

    buf0 = nextbuf[0]
    q = q_ref[...]                                   # [hq, F]
    hq, feat = q.shape

    def body(j, carry):
        m, l, acc = carry
        cur = lax.rem(buf0 + j - first, 2)
        nxt = 1 - cur

        @pl.when(j + 1 < end)
        def _same_slot():
            for c in copies(b, j + 1, nxt):
                c.start()

        @pl.when(jnp.logical_and(j + 1 == end, b + 1 < nb))
        def _next_slot():
            f2, _, _ = span(b + 1)
            for c in copies(b + 1, f2, nxt):
                c.start()
            nextbuf[0] = nxt

        for c in copies(b, j, cur):
            c.wait()
        k = kbuf[cur].reshape(blk, feat)
        v = vbuf[cur].reshape(blk, feat)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pos = j * blk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((pos < n) & (pos >= lo), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((hq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((hq, 1), jnp.float32)
    acc0 = jnp.zeros((hq, feat), jnp.float32)
    _, l, acc = lax.fori_loop(first, end, body, (m0, l0, acc0))
    # each head keeps its own bank's hd columns: mask to the block
    # diagonal, then fold the banks onto hd
    o = jnp.dot(acc / l * sel_ref[...], fold_ref[...],
                preferred_element_type=jnp.float32,
                precision=lax.Precision.HIGHEST)
    o_ref[...] = o.astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, table, lengths, layer, window,
                           *, softcap=0.0, interpret=False):
    """q [b, hq, hd]; k/v pools [layers, np, page, banks * hd] (or
    [np, page, banks * hd]); table [b, mp] page ids (unmapped entries at
    the garbage page); lengths [b] (the query sits at ``lengths - 1``);
    ``layer`` and ``window`` int scalars, traced or not (window 0 =
    global) -> [b, hq, hd] in q's dtype."""
    if k_pages.ndim == 3:
        k_pages, v_pages = k_pages[None], v_pages[None]
    b, hq, hd = q.shape
    _, _, page, feat = k_pages.shape
    banks = feat // hd
    rep = hq // banks
    mp = table.shape[1]
    ppb = min(PAGES_PER_BLOCK, mp)
    mp_pad = -(-mp // ppb) * ppb
    table = jnp.pad(table.astype(jnp.int32), ((0, 0), (0, mp_pad - mp)))
    # block-diagonal query: head h's hd values in the columns of bank h//rep
    diag = np.arange(banks)[None, :] == (np.arange(hq) // rep)[:, None]
    qbd = (q[:, :, None, :] * jnp.asarray(diag, q.dtype)[None, :, :, None])
    qbd = qbd.reshape(b, hq, feat).astype(k_pages.dtype)
    sel = np.repeat(diag, hd, axis=1).astype(np.float32)            # [hq, F]
    fold = np.tile(np.eye(hd, dtype=np.float32), (banks, 1))        # [F, hd]

    kern = functools.partial(_kernel, scale=1.0 / math.sqrt(hd),
                             softcap=softcap, ppb=ppb, mp=mp_pad)
    const = lambda i, *_: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, hq, feat), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((hq, feat), const),
            pl.BlockSpec((feat, hd), const),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, hq, hd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, feat), k_pages.dtype),
            pltpu.VMEM((2, ppb, page, feat), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        # inside shard_map the output varies over the axes its inputs do
        out_shape=jax.ShapeDtypeStruct(
            (b, hq, hd), q.dtype,
            vma=jax.typeof(qbd).vma | jax.typeof(k_pages).vma),
        # the next slot's first block is fetched by this one: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      table.reshape(-1), lengths.astype(jnp.int32),
      jnp.reshape(jnp.asarray(window, jnp.int32), (1,)),
      qbd, jnp.asarray(sel), jnp.asarray(fold), k_pages, v_pages)

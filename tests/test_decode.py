"""Decode path: step-by-step decode with caches must reproduce the full
forward logits (per family: KV-cache, MLA latent cache, SSD/mLSTM state)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs.registry import get_config
from repro.core.atp import make_context
from repro.core.mesh import MeshTopo
from repro.models import lm

TOPO1 = MeshTopo((("data", 1),))

DECODE_ARCHS = ["llama3-8b", "gemma2-2b", "deepseek-v3-671b",
                "zamba2-7b", "xlstm-1.3b"]


def _forward_logits_all(cfg, params, tokens):
    mesh = TOPO1.build(jax.devices()[:1])
    ctx = make_context(TOPO1)

    def f(p, b):
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
        h, _, _, _ = lm.forward(ctx, cfg, p, b["tokens"], pos)
        return lm.lm_logits(ctx, cfg, p, h)

    g = shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                  check_vma=True)
    return jax.jit(g)(params, {"tokens": tokens})


def _decode_logits_seq(cfg, params, tokens, s_max):
    mesh = TOPO1.build(jax.devices()[:1])
    ctx = make_context(TOPO1)
    B, S = tokens.shape
    caches, _ = lm.init_decode_caches(cfg, ctx, B, s_max, dtype=jnp.float32)

    def step(p, tok, pos, caches):
        return lm.decode_step(ctx, cfg, p, tok, pos, caches)

    g = jax.jit(shard_map(step, mesh=mesh, in_specs=(P(), P(), P(), P()),
                          out_specs=(P(), P()), check_vma=True))
    outs = []
    for t in range(S):
        logits, caches = g(params, tokens[:, t: t + 1], jnp.int32(t), caches)
        outs.append(logits)
    return jnp.stack(outs, axis=1)  # [B, S, V]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    full = _forward_logits_all(cfg, params, tokens)
    dec = _decode_logits_seq(cfg, params, tokens, s_max=S + 4)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# Paged KV caches: block-paged decode must reproduce the dense path.
# ---------------------------------------------------------------------------

PAGED_ARCHS = ["llama3-8b", "deepseek-v3-671b"]  # attn-cache + MLA-latent


def _paged_step_fn(cfg, ctx, mesh):
    def step(p, tok, start, table, caches):
        return lm.paged_step(ctx, cfg, p, tok, start, table, caches)

    return jax.jit(shard_map(step, mesh=mesh,
                             in_specs=(P(), P(), P(), P(), P()),
                             out_specs=(P(), P()), check_vma=True))


def _paged_cfg():
    from repro.models.paging import PagedConfig

    return PagedConfig(page_size=4, num_pages=16, pages_per_slot=4)


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_paged_decode_matches_dense_mixed_lengths(arch):
    """Per-slot lengths differ; every valid position's logits must match
    the dense token-by-token decode."""
    from repro.models.paging import PageAllocator

    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh = TOPO1.build(jax.devices()[:1])
    ctx = make_context(TOPO1)
    B, S = 2, 12
    S1 = S - 5  # slot 1 stops early: independent lengths
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    ref = _decode_logits_seq(cfg, params, tokens, s_max=S + 4)

    pcfg = _paged_cfg()
    alloc = PageAllocator(pcfg, slots=B)
    caches, _ = lm.init_paged_caches(cfg, ctx, pcfg, dtype=jnp.float32)
    g = _paged_step_fn(cfg, ctx, mesh)
    outs = []
    for t in range(S):
        live1 = t < S1
        alloc.ensure(0, t + 1)
        if live1:
            alloc.ensure(1, t + 1)
        tok = np.zeros((B, 1), np.int32)
        tok[0, 0] = int(tokens[0, t])
        tok[1, 0] = int(tokens[1, t]) if live1 else 0
        start = np.array([t, t if live1 else 0], np.int32)
        table = alloc.table()
        if not live1:   # inactive slot writes route to the garbage page
            table[1, :] = 0
        logits, caches = g(params, jnp.asarray(tok), jnp.asarray(start),
                           jnp.asarray(table), caches)
        outs.append(logits[:, 0])
    outs = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(ref[0]),
                               rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(outs[1, :S1]),
                               np.asarray(ref[1, :S1]),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_paged_prefill_chunks_match_full_forward(arch):
    """b=1 chunked prefill through the page pool == full-sequence logits
    (one compiled step reused across chunk starts)."""
    from repro.models.paging import PageAllocator

    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh = TOPO1.build(jax.devices()[:1])
    ctx = make_context(TOPO1)
    S, C = 12, 4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0,
                                cfg.vocab_size)
    full = _forward_logits_all(cfg, params, tokens)

    pcfg = _paged_cfg()
    alloc = PageAllocator(pcfg, slots=1)
    alloc.ensure(0, S)
    caches, _ = lm.init_paged_caches(cfg, ctx, pcfg, dtype=jnp.float32)
    g = _paged_step_fn(cfg, ctx, mesh)
    got = []
    for c0 in range(0, S, C):
        logits, caches = g(params, tokens[:, c0: c0 + C],
                           jnp.asarray(np.array([c0], np.int32)),
                           jnp.asarray(alloc.table()), caches)
        got.append(logits)
    got = jnp.concatenate(got, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=5e-3, atol=5e-3)


def test_page_table_reuse_after_slot_recycle():
    """Pages released by a finished request and re-mapped to a new one
    must serve the new sequence exactly (stale contents fully masked)."""
    from repro.models.paging import PageAllocator

    cfg = get_config("llama3-8b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh = TOPO1.build(jax.devices()[:1])
    ctx = make_context(TOPO1)
    S = 8
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, cfg.vocab_size)
    t2 = jax.random.randint(jax.random.PRNGKey(2), (1, S), 0, cfg.vocab_size)
    ref2 = _forward_logits_all(cfg, params, t2)

    pcfg = _paged_cfg()
    alloc = PageAllocator(pcfg, slots=1)
    caches, _ = lm.init_paged_caches(cfg, ctx, pcfg, dtype=jnp.float32)
    g = _paged_step_fn(cfg, ctx, mesh)
    # request 1 occupies pages, then recycles
    alloc.ensure(0, S)
    pages_first = alloc.slot_pages(0)
    _, caches = g(params, t1, jnp.asarray(np.zeros(1, np.int32)),
                  jnp.asarray(alloc.table()), caches)
    alloc.release(0)
    # request 2 receives the SAME physical pages (LIFO free list)
    alloc.ensure(0, S)
    assert set(alloc.slot_pages(0)) == set(pages_first)
    logits, caches = g(params, t2, jnp.asarray(np.zeros(1, np.int32)),
                       jnp.asarray(alloc.table()), caches)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref2),
                               rtol=5e-3, atol=5e-3)


def test_decode_plan_knobs_thread_resolve_ctx():
    """The decode sub-plan's mesh-neutral knobs reach the decode context
    (and ONLY the decode context) through the resolve_ctx funnel."""
    from repro.core.atp import DecodePlan, SegmentPlan
    from repro.core.plan import ParallelPlan
    from repro.launch.steps import resolve_ctx

    plan = ParallelPlan(
        d1=2, d2=2, chunks=4, boundary_mode="ring", seq_parallel=True,
        segments=(SegmentPlan("dense", chunks=4, boundary_mode="ring",
                              seq_parallel=True),),
        decode=DecodePlan(d1=4, d2=1, boundary_mode="psum"))
    train_ctx = resolve_ctx(None, plan)
    assert (train_ctx.chunks, train_ctx.boundary_mode) == (4, "ring")
    assert train_ctx.for_segment("dense").seq_parallel is True
    dec_ctx = resolve_ctx(None, plan, decode=True)
    # decode sub-plan knobs replace the train knobs in every view...
    assert (dec_ctx.chunks, dec_ctx.boundary_mode) == (1, "psum")
    seg = dec_ctx.for_segment("dense")
    assert (seg.chunks, seg.boundary_mode, seg.seq_parallel) == \
        (1, "psum", False)
    # ...but the mesh stays the plan's: re-meshing is decode_view's job
    assert (dec_ctx.d1, dec_ctx.d2) == (2, 2)
    view = plan.decode_view()
    assert (view.d1, view.d2) == (4, 1)
    vctx = resolve_ctx(None, view, decode=True)
    assert (vctx.d1, vctx.d2) == (4, 1)
    assert vctx.boundary_mode == "psum" and vctx.chunks == 1


def test_prefill_into_cache_matches_stepwise():
    """Multi-token decode_step (serving prefill) == token-by-token."""
    cfg = get_config("llama3-8b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh = TOPO1.build(jax.devices()[:1])
    ctx = make_context(TOPO1)
    B, S = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    def step(p, tok, pos, caches):
        return lm.decode_step(ctx, cfg, p, tok, pos, caches)

    g = jax.jit(shard_map(step, mesh=mesh, in_specs=(P(), P(), P(), P()),
                          out_specs=(P(), P()), check_vma=True),
                static_argnames=())
    caches, _ = lm.init_decode_caches(cfg, ctx, B, S + 4, dtype=jnp.float32)
    logits_bulk, caches_bulk = g(params, tokens, jnp.int32(0), caches)

    caches2, _ = lm.init_decode_caches(cfg, ctx, B, S + 4, dtype=jnp.float32)
    for t in range(S):
        logits_step, caches2 = g(params, tokens[:, t: t + 1], jnp.int32(t), caches2)
    np.testing.assert_allclose(np.asarray(logits_bulk), np.asarray(logits_step),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("page_dtype", ["bf16", "int8"])
def test_layer_indexed_pool_access_matches_per_layer_slice(page_dtype):
    """append/gather at ``layer`` on a stacked pool == slicing that layer
    out, writing it and restacking it.  Positions past the table and
    unmapped entries go to the layer's own garbage page; quantized pools
    keep one scale per KV bank of the lane-dense [.., banks * hd] row."""
    from repro.models import paging

    count, np_, pg, banks, hd = 3, 10, 4, 2, 8
    rng = np.random.default_rng(0)
    store = paging.page_store_dtype(page_dtype) or jnp.float32
    pool = jnp.asarray(rng.normal(size=(count, np_, pg, banks * hd)) * 4,
                       store)
    scales = (jnp.asarray(rng.uniform(size=(count, np_, pg, banks)),
                          jnp.float16) if store != jnp.float32 else None)
    # slot 0 runs past its 3-page table; slot 1 maps one page of three
    table = jnp.asarray([[3, 5, 8], [7, 0, 0]], jnp.int32)
    start = jnp.asarray([6, 2], jnp.int32)
    values = rng.normal(size=(2, 7, banks, hd))
    values[..., 1, :] *= 100.0      # banks of very different scale
    values = jnp.asarray(values.reshape(2, 7, banks * hd), jnp.float32)
    g = paging.GARBAGE_PAGE

    for layer in range(count):
        got, got_s = paging.append_tokens_q(pool, scales, table, start,
                                            values, page_dtype,
                                            layer=jnp.int32(layer))
        one, one_s = paging.append_tokens_q(
            pool[layer], None if scales is None else scales[layer], table,
            start, values, page_dtype)
        ref = pool.at[layer].set(one)
        ref_s = None if scales is None else scales.at[layer].set(one_s)
        # the garbage page's offset 0 takes three writes, in any order
        keep = np.ones(pool.shape[:3], bool)
        keep[layer, g, 0] = False
        np.testing.assert_array_equal(np.asarray(got)[keep],
                                      np.asarray(ref)[keep])
        if scales is not None:
            np.testing.assert_array_equal(np.asarray(got_s)[keep],
                                          np.asarray(ref_s)[keep])
        read = paging.gather_pages_q(got, got_s, table, jnp.float32,
                                     layer=jnp.int32(layer))
        want = paging.gather_pages_q(ref[layer], None if scales is None
                                     else ref_s[layer], table, jnp.float32)
        assert read.shape == (2, 3 * pg, banks * hd)
        pos = np.arange(3 * pg)
        same = ~((np.asarray(table)[:, pos // pg] == g) & (pos % pg == 0))
        np.testing.assert_array_equal(np.asarray(read)[same],
                                      np.asarray(want)[same])
        # what was written reads back; int8 within half a step of its
        # bank's own scale (one scale per row would lose the small bank)
        tol = 0.0 if scales is None else 0.5 / 127 * 1.01
        for b, lo, n in ((0, 6, 6), (1, 2, 2)):
            v = np.asarray(values[b, :n]).reshape(n, banks, hd)
            r = np.asarray(read[b, lo:lo + n]).reshape(n, banks, hd)
            amax = np.abs(v).max(-1, keepdims=True)
            assert np.all(np.abs(r - v) <= tol * amax + 1e-6)

"""End-to-end quantization: int8/fp8 wire collectives (parity + STE
grads), quantized page pools (margin-filtered greedy parity), the
quantized matmul epilogue, error-feedback DP gradient state, and the
planner pricing the quantized wire (format_version 4, search flips)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs.registry import get_config
from repro.core import comm_matrix, overlap
from repro.core.atp import make_context
from repro.core.calibrate import CalibEntry, CalibrationTable, calibrate_mesh
from repro.core.cost_model import LayerCommProfile, wire_bytes_per_elem
from repro.core.mesh import MeshTopo, atp_topo
from repro.core.plan import PLAN_FORMAT_VERSION, ParallelPlan, plan_search
from repro.core.search import search_strategy_overlap
from repro.models import lm
from repro.models.paging import PageAllocator, PagedConfig
from repro.optim import adamw
from repro.optim.grad_compress import compressed_psum_mean_ef

D = 8
GPT = LayerCommProfile.gpt(4096)


def _mesh8():
    return MeshTopo((("i", D),)).build()


def _run(f, in_specs, out_specs, *args):
    g = shard_map(f, mesh=_mesh8(), in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    return jax.jit(g)(*args)


def _x(seed=0, shape=(D, 16, 32)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _wire_bound(x, wire_dtype):
    """Worst-case absolute error of a quantized all-reduce of ``x``.

    Shared scale = global amax / qmax; each rank contributes at most half
    a grid step (int8) / half the top-of-range ulp (e4m3: 32 at 448)."""
    amax = float(jnp.max(jnp.abs(x)))
    per_rank = (amax / 448.0) * 16.0 if (
        wire_dtype == "fp8" and overlap._FP8_DTYPE is not None
    ) else (amax / 127.0) * 0.5
    return D * per_rank * 1.01


# ---------------------------------------------------------------------------
# Wire collectives: quantized ~= full-width within the grid-error bound.
# ---------------------------------------------------------------------------


QUANT_CASES = {
    "psum": (
        lambda v, wd: overlap.quant_psum(v, "i", wd),
        lambda v: lax.psum(v, "i")),
    "ring_ar": (
        lambda v, wd: overlap.quant_ring_all_reduce(v, "i", D, wd),
        lambda v: lax.psum(v, "i")),
    "reduce_scatter": (
        lambda v, wd: overlap.quant_reduce_scatter(v, "i", D, 1, wd),
        lambda v: lax.psum_scatter(v, "i", scatter_dimension=1, tiled=True)),
    "ring_rs": (
        lambda v, wd: overlap.quant_reduce_scatter(v, "i", D, 1, wd,
                                                   ring=True),
        lambda v: lax.psum_scatter(v, "i", scatter_dimension=1, tiled=True)),
}


@pytest.mark.parametrize("wd", ["int8", "fp8"])
@pytest.mark.parametrize("name", sorted(QUANT_CASES))
def test_quant_collective_within_grid_bound(devices8, name, wd):
    quant, ref = QUANT_CASES[name]
    x = _x()
    a = np.asarray(_run(lambda v: quant(v, wd), P("i"), P("i"), x))
    b = np.asarray(_run(ref, P("i"), P("i"), x))
    err = np.max(np.abs(a - b))
    assert err <= _wire_bound(x, wd), (name, wd, err)
    # and the wire really was quantized (not a full-width fallback)
    assert err > 0.0


@pytest.mark.parametrize("wd", ["int8", "fp8"])
def test_quant_collective_ste_grads(devices8, wd):
    """Backward is the mirrored quantized collective on the cotangent —
    a straight-through estimator.  A linear loss makes the cotangent
    exactly the weight tensor, so the grad difference IS one quantized
    all-reduce's grid error (nonlinear losses would additionally amplify
    the forward error, which is not what this pins)."""
    x, w = _x(), _x(seed=7)

    def loss(f):
        return lambda v, wt: jnp.sum(f(v) * wt)

    a = _run(jax.grad(loss(lambda v: overlap.quant_psum(v, "i", wd))),
             (P("i"), P("i")), P("i"), x, w)
    b = _run(jax.grad(loss(lambda v: lax.psum(v, "i"))),
             (P("i"), P("i")), P("i"), x, w)
    # grad = (quant_)psum(w): bounded by w's wire grid
    assert float(jnp.max(jnp.abs(a - b))) <= _wire_bound(w, wd)


@pytest.mark.parametrize("wd", ["int8", "fp8"])
def test_quant_overlap_matmul_ar_parity(devices8, wd):
    """Chunked collective matmul on the quantized wire: dequant rides the
    chunk epilogue, result stays within a few percent of full width."""
    x, w = _x(), jax.random.normal(jax.random.PRNGKey(1), (32, 24)) * 0.1
    b = jnp.ones((24,)) * 0.5

    def quant(v, wt):
        return overlap.overlap_matmul_ar(v, wt, "i", D, 4, b=b,
                                         wire_dtype=wd)

    def full(v, wt):
        return overlap.overlap_matmul_ar(v, wt, "i", D, 4, b=b)

    a = np.asarray(_run(quant, (P("i"), P()), P("i"), x, w))
    r = np.asarray(_run(full, (P("i"), P()), P("i"), x, w))
    rel = np.max(np.abs(a - r)) / np.max(np.abs(r))
    assert 0.0 < rel < 0.05, rel

    # grads flow through the quantized ring (STE), close to full width
    def lossq(v):
        return jnp.sum(jnp.sin(quant(v, w)))

    def lossf(v):
        return jnp.sum(jnp.sin(full(v, w)))

    ga = np.asarray(_run(jax.grad(lossq), P("i"), P("i"), x))
    gr = np.asarray(_run(jax.grad(lossf), P("i"), P("i"), x))
    assert np.all(np.isfinite(ga))
    grel = np.max(np.abs(ga - gr)) / (np.max(np.abs(gr)) + 1e-12)
    assert grel < 0.1, grel


# A quantized-wire train build runs check_vma=False, as a ring build does:
# the backward of its custom-VJP collectives reduces a rank-partial
# cotangent on the same quantized wire, and vma typing makes that
# cotangent invariant, leaving the wire nothing to reduce.  Without the
# rewrite each of the n = d1*d2 ranks holds the full gradient of the
# replicated loss, so the grads are n x the vma build's (the "x n
# convention", docs/analysis.md).  Per-leaf relative error of the scaled
# grads vs the bf16 wire, measured on reduced llama3 at (2,2): 0.068
# (int8), 0.095 (fp8); the bounds leave ~2x headroom and stay far under
# the 1.0 a double-counted reduction gives.
_GRAD_REL = {"int8": 0.15, "fp8": 0.2}


@pytest.mark.parametrize("wd", ["int8", "fp8"])
def test_quant_wire_train_step_grads_and_replicas(devices8, wd):
    """A whole quantized-wire train step on a 2x2 mesh: its gradients are
    n x the bf16-wire build's within the wire grid's error, and one AdamW
    update through ``build_train_step`` leaves every replica of every
    leaf bitwise identical (zero drift)."""
    from repro.launch.steps import _check_vma, batch_pspecs, \
        build_train_step, resolve_ctx

    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                             cfg.vocab_size)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}

    def grads(plan):
        ctx = resolve_ctx(None, plan)
        ps = lm.param_specs(cfg, ctx)
        fn = shard_map(
            jax.grad(lambda p, b: lm.train_loss(ctx, cfg, p, b,
                                                remat=False)),
            mesh=ctx.topo.build(jax.devices()[:4]),
            in_specs=(ps, batch_pspecs(cfg, ctx.topo, "train")),
            out_specs=ps, check_vma=_check_vma(ctx))
        return jax.tree.map(np.asarray, jax.jit(fn)(params, batch)), ctx

    quant = ParallelPlan(d1=2, d2=2, dp=1, wire_dtype=wd)
    ref, rctx = grads(ParallelPlan(d1=2, d2=2, dp=1))
    got, qctx = grads(quant)
    assert _check_vma(rctx) and not _check_vma(qctx)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(ref)):
        rel = np.linalg.norm(g / qctx.tp - r) / np.linalg.norm(r)
        assert rel <= _GRAD_REL[wd], (jax.tree_util.keystr(path), rel)

    step, info = build_train_step(cfg, plan=quant)
    opt = adamw.init_opt_state(params, info.pspecs, info.ctx, "zero1")
    new, _, metrics = step(
        jax.device_put(params, info.sharding(info.pspecs)),
        jax.device_put(opt, info.sharding(info.ospecs)),
        jax.device_put(batch, info.sharding(info.bspecs)))
    assert np.isfinite(float(metrics["loss"]))
    for path, leaf in jax.tree_util.tree_leaves_with_path(new):
        by_index = {}
        for shard in leaf.addressable_shards:
            by_index.setdefault(str(shard.index), []).append(
                np.asarray(shard.data))
        for copies in by_index.values():
            for c in copies[1:]:
                np.testing.assert_array_equal(
                    c, copies[0], err_msg=jax.tree_util.keystr(path))
        assert len(leaf.addressable_shards) == 4


# ---------------------------------------------------------------------------
# Quantized page pools: margin-filtered teacher-forced greedy parity.
# ---------------------------------------------------------------------------

TOPO1 = MeshTopo((("data", 1),))


def _teacher_forced_paged_logits(cfg, params, tokens, pcfg):
    """Feed the true token at every step through the paged cache; return
    [B, S, V] last-position logits."""
    B, S = tokens.shape
    mesh = TOPO1.build(jax.devices()[:1])
    ctx = make_context(TOPO1)
    alloc = PageAllocator(pcfg, slots=B)
    caches, _ = lm.init_paged_caches(cfg, ctx, pcfg, dtype=jnp.float32)

    def step(p, tok, start, table, caches):
        return lm.paged_step(ctx, cfg, p, tok, start, table, caches)

    g = jax.jit(shard_map(step, mesh=mesh,
                          in_specs=(P(), P(), P(), P(), P()),
                          out_specs=(P(), P()), check_vma=True))
    outs = []
    for t in range(S):
        for s in range(B):
            alloc.ensure(s, t + 1)
        start = jnp.full((B,), t, jnp.int32)
        logits, caches = g(params, tokens[:, t: t + 1], start,
                           jnp.asarray(alloc.table()), caches)
        outs.append(logits[:, 0])
    return jnp.stack(outs, axis=1)


# measured on this model/trace: worst argmax flip sits at margin 0.018
# (int8) / 0.149 (fp8, coarser e4m3 grid); thresholds leave ~3x headroom
_PARITY_MARGIN = {"int8": 0.05, "fp8": 0.25}


@pytest.mark.parametrize("page_dtype", ["int8", "fp8"])
def test_paged_decode_quant_greedy_parity(page_dtype):
    """Greedy argmax through int8/fp8 page pools matches the full-width
    pool wherever the full-width decision margin exceeds the quantization
    perturbation.  Near-ties below the threshold are the ONLY places
    quantization may flip the pick."""
    cfg = get_config("llama3-8b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S = 2, 112
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    geom = dict(page_size=8, num_pages=2 * (S // 8 + 1) + 2,
                pages_per_slot=S // 8 + 1)
    ref = _teacher_forced_paged_logits(cfg, params, tokens,
                                       PagedConfig(**geom))
    got = _teacher_forced_paged_logits(
        cfg, params, tokens, PagedConfig(page_dtype=page_dtype, **geom))

    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    top2 = np.sort(ref, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]          # [B, S]
    compared = margin > _PARITY_MARGIN[page_dtype]
    assert int(compared.sum()) >= 64, int(compared.sum())
    agree = ref.argmax(-1) == got.argmax(-1)
    assert bool(np.all(agree[compared])), (
        f"{int((~agree & compared).sum())} confident-argmax flips")
    # the pools really are narrow (+ fp16 scale tensors ride along)
    caches, _ = lm.init_paged_caches(
        cfg, make_context(TOPO1), PagedConfig(page_dtype=page_dtype, **geom),
        dtype=jnp.float32)
    assert any(x.dtype.itemsize == 1 for x in jax.tree.leaves(caches))


def test_quant_pool_bytes_ratio():
    """int8 pages + fp16 per-position scales cut pool bytes >= 1.8x vs a
    bf16 pool of the same geometry."""
    cfg = get_config("llama3-8b").reduced()
    ctx = make_context(TOPO1)
    geom = dict(page_size=8, num_pages=32, pages_per_slot=8)

    def nbytes(page_dtype, dtype):
        caches = jax.eval_shape(
            lambda: lm.init_paged_caches(
                cfg, ctx, PagedConfig(page_dtype=page_dtype, **geom),
                dtype=dtype)[0])
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(caches))

    ratio = nbytes("bf16", jnp.bfloat16) / nbytes("int8", jnp.bfloat16)
    assert ratio >= 1.8, ratio


# ---------------------------------------------------------------------------
# Quantized matmul kernel epilogue (interpret mode).
# ---------------------------------------------------------------------------


def test_quant_matmul_epilogue_interpret():
    from repro.kernels.matmul import matmul, quantize_for_matmul

    k = jax.random.PRNGKey(0)
    a = jax.random.normal(k, (64, 96), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (96, 48), jnp.float32) * 0.2
    bias = jnp.linspace(-1, 1, 48, dtype=jnp.float32)
    ref = jax.nn.gelu(a @ b + bias, approximate=True)

    qa, sa = quantize_for_matmul(a)
    qb, sb = quantize_for_matmul(b)
    assert qa.dtype == jnp.int8
    out = matmul(qa, qb, bias, scale=sa * sb, activation="gelu",
                 out_dtype=jnp.float32, block_m=32, block_n=32, block_k=32,
                 interpret=True)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.03, rel

    # full-width path is untouched by the new operand plumbing
    full = matmul(a, b, bias, activation="gelu", block_m=32, block_n=32,
                  block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Error-feedback DP gradient state.
# ---------------------------------------------------------------------------


def test_compressed_ef_residual_invariant(devices8):
    """The carried residual is REPLICATED over dp (it leaves a
    replication-checked shard_map with out_specs=P()) and approximates
    exactly what the quantized mean dropped:
    ``new_err ~= pmean(g) + err_in - mean_grad`` to one grid step."""
    topo = MeshTopo((("data", 8),))
    mesh = topo.build()
    g = jax.random.normal(jax.random.PRNGKey(3), (8, 64)) * 0.1
    err_in = (jax.random.normal(jax.random.PRNGKey(4), (8, 64)) * 0.01)[0]

    def f(g, err):
        mean, new_err = compressed_psum_mean_ef(g, err, ("data",))
        exact = lax.pmean(g.astype(jnp.float32) + err, "data")
        return mean, new_err, exact

    # out_specs=P() for new_err IS the replication assertion
    h = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("data"), P()),
                          out_specs=(P(), P(), P()), check_vma=True))
    mean, new_err, exact = h(g, err_in)
    grid = float(jnp.max(jnp.abs(g)) + jnp.max(jnp.abs(err_in))) / 127.0
    dropped = np.asarray(exact) - np.asarray(mean)
    np.testing.assert_allclose(np.asarray(new_err), dropped,
                               atol=1.01 * grid)
    assert float(jnp.max(jnp.abs(mean - exact))) < 0.02
    assert float(jnp.max(jnp.abs(new_err))) > 0.0


def _ef_toy():
    topo = MeshTopo((("data", 4), ("tp1", 2)))
    mesh = topo.build(jax.devices()[: topo.size])
    ctx = make_context(topo)
    W = jax.random.normal(jax.random.PRNGKey(0), (8, 16)) * 0.1
    pspecs = {"W": P(None, "tp1")}
    return mesh, ctx, {"W": W}, pspecs


def test_opt_state_compressed_carries_err(devices8):
    mesh, ctx, params, pspecs = _ef_toy()
    opt = adamw.init_opt_state(params, pspecs, ctx, "compressed")
    assert "err" in opt
    assert opt["err"]["W"].shape == params["W"].shape
    assert float(jnp.max(jnp.abs(opt["err"]["W"]))) == 0.0
    specs = adamw.opt_state_specs(pspecs, ctx, "compressed")
    assert specs["err"] == pspecs
    # plain/zero1 states stay err-free (checkpoint layout unchanged)
    assert "err" not in adamw.init_opt_state(params, pspecs, ctx, "plain")
    assert "err" not in adamw.opt_state_specs(pspecs, ctx, "zero1")


def test_apply_adamw_threads_error_feedback(devices8):
    """One compressed step leaves a nonzero residual in opt_state['err'];
    a legacy state without 'err' still applies (memoryless fallback)."""
    mesh, ctx, params, pspecs = _ef_toy()
    cfg = adamw.AdamWConfig(lr=1e-2, mode="compressed", grad_clip=0.0,
                            warmup_steps=1, total_steps=10)
    opt = adamw.init_opt_state(params, pspecs, ctx, "compressed")
    ospecs = adamw.opt_state_specs(pspecs, ctx, "compressed")
    rep = adamw.replication_factors(pspecs, ctx)
    X = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    Y = jax.random.normal(jax.random.PRNGKey(2), (16, 16))

    def step(params, opt, X, Y):
        def loss(p):
            l = jnp.sum((X @ p["W"] - Y) ** 2)
            return jax.lax.psum(l, ("data", "tp1"))

        grads = jax.grad(loss)(params)
        newp, newo, _ = adamw.apply_adamw(cfg, ctx, params, grads, opt, rep)
        return newp, newo

    f = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, ospecs, P("data", None), P("data", "tp1")),
        out_specs=(pspecs, ospecs), check_vma=True))
    newp, newo = f(params, opt, X, Y)
    assert "err" in newo
    assert float(jnp.max(jnp.abs(newo["err"]["W"]))) > 0.0
    assert not np.allclose(np.asarray(newp["W"]), np.asarray(params["W"]))

    # legacy checkpoint state: no 'err' key -> memoryless compression
    legacy = {k: v for k, v in opt.items() if k != "err"}
    lspecs = {k: v for k, v in ospecs.items() if k != "err"}
    g = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, lspecs, P("data", None), P("data", "tp1")),
        out_specs=(pspecs, lspecs), check_vma=True))
    lp, lo = g(params, legacy, X, Y)
    assert "err" not in lo
    assert not np.allclose(np.asarray(lp["W"]), np.asarray(params["W"]))


# ---------------------------------------------------------------------------
# Planner: the search prices the quantized wire (and can flip its pick).
# ---------------------------------------------------------------------------


def test_wire_bytes_per_elem():
    assert wire_bytes_per_elem("bf16", 2) == 2
    assert wire_bytes_per_elem("int8", 2) == 1
    assert wire_bytes_per_elem("fp8", 4) == 1
    with pytest.raises(ValueError, match="wire_dtype"):
        wire_bytes_per_elem("int4", 2)


def test_ic1_analytic_mesh_flip_under_int8():
    """The acceptance pin: PCIe 8-GPU box, llama3-8b dense profile.

    Full width the search folds all TP into the fast leaf axis (8, 1);
    halving the wire bytes shrinks that comm-volume advantage below the
    (4, 2) factorization's larger ring-overlap credit, flipping the
    winning mesh — quantization changes the PLAN, not just the bytes."""
    m = comm_matrix.ic1_pcie_8gpu()
    cfg = get_config("llama3-8b")
    kw = dict(layers=cfg.num_layers, batch=4, seq=2048,
              profile=LayerCommProfile.dense(cfg))
    full = search_strategy_overlap(m, 8, **kw)
    quant = search_strategy_overlap(m, 8, wire_dtype="int8", **kw)
    assert (full.best.d1, full.best.d2) == (8, 1)
    assert (quant.best.d1, quant.best.d2) == (4, 2)
    # quantized wire is strictly cheaper, and (8,1) is still ranked —
    # just beaten by the overlap credit at (4,2)
    assert quant.best.t_exposed < full.best.t_exposed
    q81 = next(c for c in quant.ranked if (c.d1, c.d2) == (8, 1))
    assert quant.best.t_exposed < q81.t_exposed


def test_calibrated_quant_bandwidths_steer_search(devices8):
    """Measured b1_q/b2_q override the full-width table for quantized
    plans: a fabric whose quantized path is slow on one factorization
    demotes it ONLY under wire_dtype=int8."""
    m = comm_matrix.ic1_pcie_8gpu()
    kw = dict(layers=8, batch=8, seq=1024, profile=GPT,
              chunks_options=(1,), seq_parallel_options=(False,))
    table = CalibrationTable(entries=(
        # (8,1): superb full-width axis, terrible quantized path
        ((8, 1), CalibEntry(b1=200.0, b2=float("inf"),
                            b1_q=1.0, b2_q=float("inf"))),
        # (4,2): mediocre full width, fast quantized collectives
        ((4, 2), CalibEntry(b1=20.0, b2=20.0, b1_q=60.0, b2_q=60.0)),
        ((2, 4), CalibEntry(b1=10.0, b2=10.0)),
        ((1, 8), CalibEntry(b1=float("inf"), b2=10.0)),
    ))
    full = search_strategy_overlap(m, 8, calibration=table, **kw)
    quant = search_strategy_overlap(m, 8, calibration=table,
                                    wire_dtype="int8", **kw)
    assert (full.best.d1, full.best.d2) == (8, 1)
    assert (quant.best.d1, quant.best.d2) == (4, 2)


def test_measured_launch_cost_steers_chunks_to_one(devices8):
    """Satellite pin (double-count fix): chunk_eff is pure bandwidth
    efficiency now, so a big measured per-chunk launch cost must come
    from launch_s — eff=1.0 plus large launch_s forces chunks=1."""
    m = comm_matrix.ic4_ib_cluster_16gpu()
    kw = dict(layers=24, batch=64, seq=2048, profile=GPT, peak_tflops=5.0,
              alpha_s=2e-6, chunks_options=(1, 2, 4),
              seq_parallel_options=(False,))
    base = search_strategy_overlap(m, 16, **kw)
    assert base.best.chunks > 1
    entry = CalibEntry(b1=25.0, b2=25.0, launch_s=0.05,
                       chunk_eff=((2, 1.0, 1.0), (4, 1.0, 1.0)))
    table = CalibrationTable(entries=tuple(
        ((d1, d2), entry) for d1, d2 in
        ((1, 16), (2, 8), (4, 4), (8, 2), (16, 1))))
    steered = search_strategy_overlap(m, 16, calibration=table, **kw)
    assert steered.best.chunks == 1
    # zero launch cost with perfect chunk efficiency leaves chunking on
    free = dataclasses.replace(entry, launch_s=0.0)
    table0 = CalibrationTable(entries=tuple(
        ((d1, d2), free) for d1, d2 in
        ((1, 16), (2, 8), (4, 4), (8, 2), (16, 1))))
    kept = search_strategy_overlap(m, 16, calibration=table0, **kw)
    assert kept.best.chunks == base.best.chunks


def test_calibrate_mesh_measures_quant_and_launch(devices8):
    """The on-device micro-benchmark fills launch_s and b1_q/b2_q and
    they survive the JSON round trip."""
    t = calibrate_mesh(4, payload_kb=8, repeats=1)
    for key in ((4, 1), (2, 2), (1, 4)):
        e = dict(t.entries)[key]
        assert e.launch_s is not None and e.launch_s >= 0.0
        q = t.quant_bandwidths(*key)
        assert q is not None
        assert all(b > 0 for b in q)
    back = CalibrationTable.from_dict(json.loads(json.dumps(t.to_dict())))
    assert back == t


# ---------------------------------------------------------------------------
# format_version 4 schema + migration discipline.
# ---------------------------------------------------------------------------


def test_v3_fixture_still_loads():
    """PR-5-era format_version 3 files load under the current version:
    decode sub-plan intact, wire_dtype defaulting to full width
    everywhere."""
    plan = ParallelPlan.load("tests/data/plan_v3_pr5.json")
    assert plan.wire_dtype == "bf16"
    assert plan.decode is not None and plan.decode.wire_dtype == "bf16"
    assert all(s.wire_dtype == "bf16" for s in plan.segments)
    e = dict(plan.calibration.entries)[(4, 2)]
    assert e.launch_s is None and e.b1_q is None  # pre-v4 table fields
    d = plan.to_dict()
    assert d["format_version"] == PLAN_FORMAT_VERSION == 5
    assert ParallelPlan.from_dict(d) == plan


def test_newer_format_version_rejected():
    plan = ParallelPlan.load("tests/data/plan_v3_pr5.json")
    d = plan.to_dict()
    d["format_version"] = PLAN_FORMAT_VERSION + 1
    with pytest.raises(ValueError, match="format_version"):
        ParallelPlan.from_dict(d)


def test_plan_search_emits_quantized_v4_plans():
    res = plan_search("ic1", 8, layers=16, batch=8, seq=2048, profile=GPT,
                      wire_dtype="int8", decode_batch=8)
    best = res.best
    assert best.wire_dtype == "int8"
    assert all(s.wire_dtype == "int8" for s in best.segments)
    assert best.decode is not None and best.decode.wire_dtype == "int8"
    q = ParallelPlan.from_json(best.to_json())
    assert q == best
    assert q.decode_view().wire_dtype == "int8"
    with pytest.raises(ValueError, match="wire_dtype"):
        dataclasses.replace(best, wire_dtype="int4")


def test_resolve_ctx_threads_wire_dtype():
    from repro.launch.steps import resolve_ctx

    plan = plan_search("ic1", 8, layers=16, batch=8, seq=2048, profile=GPT,
                       wire_dtype="int8", decode_batch=8).best
    ctx = resolve_ctx(atp_topo(1, plan.d1, plan.d2), plan)
    assert ctx.wire_dtype == "int8"
    assert all(s.wire_dtype == "int8" for s in ctx.segment_plans)
    # serving executes the decode mesh via decode_view (serve.py path)
    view = plan.decode_view()
    dctx = resolve_ctx(atp_topo(1, view.d1, view.d2), view, decode=True)
    assert dctx.wire_dtype == "int8"
    assert dctx.chunks == 1
    assert (dctx.d1, dctx.d2) == (plan.decode.d1, plan.decode.d2)

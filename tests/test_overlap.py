"""Ring-decomposed boundary collectives (repro.core.overlap) and the
sequence-parallel block I/O spec: numerical equivalence vs the monolithic
lax collectives (fwd + grads) on 8 simulated devices, bitwise logits
parity for a 2-layer model on a 2x2 mesh, and the overlap-aware cost
model/search properties."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import comm_matrix as cm
from repro.core import overlap
from repro.core.atp import atp_linear, make_context
from repro.core.cost_model import LayerCommProfile, t_comm_overlap
from repro.core.mesh import MeshTopo
from repro.core.search import search_strategy, search_strategy_overlap

D = 8


def _mesh8():
    return MeshTopo((("i", D),)).build()


def _x():
    return jax.random.normal(jax.random.PRNGKey(0), (D, 16, 32))


# ring collectives run with check_vma=False: their custom_vjp pins the
# transpose schedule explicitly, which the 0.4 replication checker cannot
# type (the lax reference ops get the same setting for a fair comparison).
def _run(f, in_specs, out_specs, *args):
    g = shard_map(f, mesh=_mesh8(), in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    return jax.jit(g)(*args)


RING_CASES = {
    "all_reduce": (
        lambda v: overlap.ring_all_reduce(v, "i", D),
        lambda v: lax.psum(v, "i")),
    "reduce_scatter": (
        lambda v: overlap.ring_reduce_scatter(v, "i", D, 1),
        lambda v: lax.psum_scatter(v, "i", scatter_dimension=1, tiled=True)),
    "all_gather": (
        lambda v: overlap.ring_all_gather(v, "i", D, 1),
        lambda v: lax.all_gather(v, "i", axis=1, tiled=True)),
}


@pytest.mark.parametrize("name", sorted(RING_CASES))
def test_ring_collective_matches_lax_forward(devices8, name):
    ring, ref = RING_CASES[name]
    a = _run(ring, P("i"), P("i"), _x())
    b = _run(ref, P("i"), P("i"), _x())
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("name", sorted(RING_CASES))
def test_ring_collective_matches_lax_grads(devices8, name):
    ring, ref = RING_CASES[name]

    def loss(f):
        return lambda v: jnp.sum(jnp.sin(f(v)))

    a = _run(jax.grad(loss(ring)), P("i"), P("i"), _x())
    b = _run(jax.grad(loss(ref)), P("i"), P("i"), _x())
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


MM_CASES = {
    "ar_chunked": (
        lambda v, w: overlap.overlap_matmul_ar(v, w, "i", D, 4),
        lambda v, w: lax.psum(jnp.einsum("...k,kn->...n", v, w), "i")),
    "ar_uneven": (
        lambda v, w: overlap.overlap_matmul_ar(v, w, "i", D, 3),
        lambda v, w: lax.psum(jnp.einsum("...k,kn->...n", v, w), "i")),
    "reduce_scatter": (
        lambda v, w: overlap.overlap_matmul_rs(v, w, "i", D, 1),
        lambda v, w: lax.psum_scatter(jnp.einsum("...k,kn->...n", v, w),
                                      "i", scatter_dimension=1, tiled=True)),
    "all_gather": (
        lambda v, w: overlap.overlap_matmul_ag(v, w, "i", D, 1),
        lambda v, w: jnp.einsum(
            "...k,kn->...n", lax.all_gather(v, "i", axis=1, tiled=True), w)),
}


@pytest.mark.parametrize("name", sorted(MM_CASES))
def test_collective_matmul_matches_monolithic(devices8, name):
    ring, ref = MM_CASES[name]
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 24)) * 0.1
    a = _run(ring, (P("i"), P()), P("i"), _x(), w)
    b = _run(ref, (P("i"), P()), P("i"), _x(), w)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)

    def loss(f):
        return lambda v, ww: jnp.sum(jnp.sin(f(v, ww)))

    ga = _run(jax.grad(loss(ring), argnums=(0, 1)), (P("i"), P()),
              (P("i"), P()), _x(), w)
    gb = _run(jax.grad(loss(ref), argnums=(0, 1)), (P("i"), P()),
              (P("i"), P()), _x(), w)
    for x1, x2 in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x2),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# atp_linear chunking satellites: uneven array_split + fused bias epilogue.
# ---------------------------------------------------------------------------


def test_atp_linear_uneven_chunks_and_fused_bias(devices8):
    topo = MeshTopo((("tp1", 2), ("tp2", 2)))
    mesh = topo.build(jax.devices()[:4])
    X = jax.random.normal(jax.random.PRNGKey(0), (7, 16))  # 7 % 3 != 0
    A = jax.random.normal(jax.random.PRNGKey(1), (16, 32)) * 0.1
    bA = jax.random.normal(jax.random.PRNGKey(2), (32,)) * 0.1

    def run(chunks):
        ctx = make_context(topo, chunks=chunks)

        def f(x, a, b):
            return atp_linear(ctx, x, a, b, kind="col")

        g = shard_map(f, mesh=mesh,
                      in_specs=(P(None, "tp2"), P("tp2", "tp1"), P("tp1")),
                      out_specs=P(None, "tp1"), check_vma=False)
        return jax.jit(g)(X, A, bA)

    base = run(1)
    for chunks in (2, 3, 5):  # none divide 7: jnp.array_split fallback
        np.testing.assert_allclose(np.asarray(run(chunks)), np.asarray(base),
                                   rtol=1e-5, atol=1e-6)


def test_pallas_matmul_fused_bias_epilogue():
    from repro.kernels.matmul import matmul

    a = jax.random.normal(jax.random.PRNGKey(0), (48, 96), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (96, 56), jnp.float32) * 0.1
    bias = jax.random.normal(jax.random.PRNGKey(2), (56,), jnp.float32)
    got = matmul(a, b, bias, activation="gelu", block_m=32, block_n=32,
                 block_k=32, interpret=True)
    want = jax.nn.gelu(a @ b + bias, approximate=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Sequence-parallel block I/O: bitwise logits parity on a 2x2 mesh.
# ---------------------------------------------------------------------------


def _logits(cfg, topo, mesh, params, batch, **ctx_kwargs):
    from repro.models import lm

    ctx = make_context(topo, **ctx_kwargs)
    specs = lm.param_specs(cfg, ctx)

    def f(p, b):
        logits = lm.prefill_logits(ctx, cfg, p, b)
        return lax.all_gather(logits, "tp1", axis=-1, tiled=True)

    g = shard_map(f, mesh=mesh, in_specs=(specs, P()), out_specs=P(),
                  check_vma=False)
    return jax.jit(g)(params, batch)


@pytest.mark.parametrize("mode_kwargs", [
    dict(seq_parallel=True),
    dict(seq_parallel=True, boundary_mode="ring"),
    dict(boundary_mode="ring"),
], ids=["seq-parallel", "seq-parallel-ring", "ring"])
def test_seq_parallel_logits_bitwise_match(devices8, mode_kwargs):
    from repro.configs.registry import get_config
    from repro.models import lm

    cfg = dataclasses.replace(get_config("llama3-8b").reduced(), num_layers=2)
    topo = MeshTopo((("tp1", 2), ("tp2", 2)))
    mesh = topo.build(jax.devices()[:4])
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)}
    base = _logits(cfg, topo, mesh, params, batch)
    got = _logits(cfg, topo, mesh, params, batch, **mode_kwargs)
    assert bool((np.asarray(base) == np.asarray(got)).all()), \
        f"{mode_kwargs}: logits differ (max |d| = " \
        f"{np.abs(np.asarray(base) - np.asarray(got)).max()})"


def test_seq_parallel_gated_per_segment(devices8):
    """The whole-network 'seq_parallel is dense-only' error became
    per-segment gating: unsupported kinds mask the knob in their
    ``for_segment`` view instead of failing the whole forward."""
    from repro.configs.registry import get_config
    from repro.models import lm

    cfg = get_config("dbrx-132b").reduced()  # moe segments: sp masked
    topo = MeshTopo((("tp1", 2), ("tp2", 2)))
    ctx = make_context(topo, seq_parallel=True)
    (seg,) = lm.segments(cfg)
    assert seg.kind == "moe"
    assert ctx.for_segment("moe").seq_parallel is False
    assert ctx.for_segment("dense").seq_parallel is True
    # and decode still refuses an (explicitly forced) seq-parallel segment
    import dataclasses as dc

    from repro.core.atp import SegmentPlan

    forced = dc.replace(ctx, segment_plans=(
        SegmentPlan("dense", seq_parallel=True),))
    with pytest.raises(NotImplementedError, match="decode"):
        lm.forward(forced, get_config("qwen1.5-0.5b").reduced(), {},
                   jnp.zeros((1, 8), jnp.int32),
                   jnp.zeros((1, 8), jnp.int32), caches={})


# ---------------------------------------------------------------------------
# Overlap-aware cost model + search.
# ---------------------------------------------------------------------------

PROF = LayerCommProfile.gpt(4096)


def test_seq_parallel_halves_modeled_ax1_boundary_bytes():
    rep = t_comm_overlap(cm.ic4_ib_cluster_16gpu(), 8, 2, layers=4, batch=4,
                         seq=2048, profile=PROF, seq_parallel=False)
    sp = t_comm_overlap(cm.ic4_ib_cluster_16gpu(), 8, 2, layers=4, batch=4,
                        seq=2048, profile=PROF, seq_parallel=True)
    assert rep.ax1_boundary_bytes / sp.ax1_boundary_bytes >= 1.9
    # total fwd+bwd ax1 volume (boundary + conjugate gathers) is conserved
    assert sp.ax1_total_bytes == pytest.approx(rep.ax1_boundary_bytes)


def test_chunking_strictly_cheaper_when_gemm_covers_ring():
    hits = 0
    # sweep compute speeds: slow devices (big GEMM time) must fully
    # overlap; latency-dominated fast ones must not claim the property
    for peak in (5.0, 50.0, 500.0):
        for chunks in (2, 4, 8):
            base = t_comm_overlap(cm.ic4_ib_cluster_16gpu(), 8, 2, layers=4,
                                  batch=4, seq=2048, profile=PROF, chunks=1,
                                  peak_tflops=peak, alpha_s=2e-6)
            c = t_comm_overlap(cm.ic4_ib_cluster_16gpu(), 8, 2, layers=4,
                               batch=4, seq=2048, profile=PROF, chunks=chunks,
                               peak_tflops=peak, alpha_s=2e-6)
            if c.fully_overlapped:
                hits += 1
                assert c.t_exposed < base.t_exposed
    assert hits > 0  # the property must actually be exercised


def test_overlap_search_parity_with_seed_when_disabled():
    """With chunking/seq-parallel off and Rabenseifner accounting, the
    (d1, d2) optimum matches the seed Eq. 2 search on every preset."""
    for matrix, n in ((cm.ic3_nvswitch_8gpu(), 8),
                      (cm.ic4_ib_cluster_16gpu(), 16),
                      (cm.tpu_v5e_pod(), 16)):
        seed = search_strategy(matrix, n, layers=4, batch=4, seq=2048,
                               profile=PROF)
        ov = search_strategy_overlap(
            matrix, n, layers=4, batch=4, seq=2048, profile=PROF,
            chunks_options=(1,), seq_parallel_options=(False,),
            algo="rabenseifner", alpha_s=0.0)
        assert ov.mesh() == seed.mesh(), matrix.name
        seed_rank = [(c.d1, c.d2) for c in seed.ranked]
        ov_rank = [(c.d1, c.d2) for c in ov.ranked]
        assert ov_rank == seed_rank, matrix.name


def test_overlap_search_explores_chunks_and_seq_parallel():
    r = search_strategy_overlap(cm.ic4_ib_cluster_16gpu(), 16, layers=4,
                                batch=4, seq=2048, profile=PROF,
                                peak_tflops=50.0, alpha_s=2e-6)
    explored = {(c.chunks, c.seq_parallel) for c in r.ranked}
    assert len(explored) > 1
    cfgs = r.config()
    assert set(cfgs) == {"d1", "d2", "chunks", "seq_parallel"}
    # exposed time never exceeds raw comm time anywhere in the ranking
    assert all(c.t_exposed <= c.t_comm + 1e-12 for c in r.ranked)

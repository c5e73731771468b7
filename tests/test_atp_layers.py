"""ATP row/column-first layers: numerical equivalence vs dense reference,
forward + grads, with and without chunk-based overlapping."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.atp import (atp_linear, core_gather, core_scatter,
                            make_context, plan_core_sharding)
from repro.core.mesh import MeshTopo

TOPO = MeshTopo((("data", 2), ("tp1", 2), ("tp2", 2)))


def _setup():
    mesh = TOPO.build()
    X = jax.random.normal(jax.random.PRNGKey(0), (8, 16), jnp.float32)
    A = jax.random.normal(jax.random.PRNGKey(1), (16, 32), jnp.float32) * 0.1
    bA = jax.random.normal(jax.random.PRNGKey(4), (32,), jnp.float32) * 0.1
    B = jax.random.normal(jax.random.PRNGKey(2), (32, 16), jnp.float32) * 0.1
    bB = jax.random.normal(jax.random.PRNGKey(5), (16,), jnp.float32) * 0.1
    return mesh, (A, bA, B, bB), X


def _ref_loss(params, x):
    A, bA, B, bB = params
    return jnp.sum((jax.nn.gelu(x @ A + bA) @ B + bB) ** 2)


def _global_loss(ctx, params, x):
    """The dense loss inside shard_map.  z is invariant over tp1 (post-f4
    psum) and varies over (data, tp2), so the psum runs over exactly the
    axes it varies on.  Under vma typing, jax.grad through that psum gives
    every param its full gradient: the transpose of each implicit pvary
    (a replicated param meeting varying data) is the gradient all-reduce
    over that param's replicated axes."""
    A, bA, B, bB = params
    y = jax.nn.gelu(atp_linear(ctx, x, A, bA, kind="col"))
    z = atp_linear(ctx, y, B, bB, kind="row")
    return jax.lax.psum(jnp.sum(z ** 2), ("data", "tp2"))


@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_mlp_forward_and_grads_match_dense(devices8, chunks):
    mesh, params, X = _setup()
    ctx = make_context(TOPO, chunks=chunks)

    def step(params, x):
        return jax.value_and_grad(lambda p: _global_loss(ctx, p, x))(params)

    in_specs = ((P("tp2", "tp1"), P("tp1"), P("tp1", "tp2"), P("tp2")),
                P("data", "tp2"))
    f = shard_map(step, mesh=mesh,
                  in_specs=in_specs, out_specs=(P(), in_specs[0]),
                  check_vma=True)
    loss, grads = jax.jit(f)(params, X)
    rloss, rgrads = jax.value_and_grad(_ref_loss)(params, X)
    np.testing.assert_allclose(loss, rloss, rtol=1e-5)
    for g, rg in zip(grads, rgrads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                                   rtol=1e-4, atol=1e-5)


def test_eq2_collective_count(devices8):
    """The lowered HLO of one MLP block contains exactly the paper's two
    forward boundaries (f3 psum(ax2), f4 psum(ax1)) + their two backward
    conjugates: 4 all-reduces of activation tensors, plus the
    DP/replication grad reductions (up to 4 more, partially fused by XLA)
    and the loss psum."""
    mesh, params, X = _setup()
    ctx = make_context(TOPO)

    def grads(params, x):
        return jax.grad(lambda p: _global_loss(ctx, p, x))(params)

    in_specs = ((P("tp2", "tp1"), P("tp1"), P("tp1", "tp2"), P("tp2")),
                P("data", "tp2"))
    f = jax.jit(shard_map(grads, mesh=mesh, in_specs=in_specs,
                          out_specs=in_specs[0], check_vma=True))
    hlo = f.lower(params, X).compile().as_text()
    n_ar = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
    # f3 fwd, f4 fwd, f4 bwd, f3 bwd + grad reductions
    assert 4 <= n_ar <= 9, f"expected the Eq.2 schedule, got {n_ar} all-reduces"


def test_core_scatter_gather_roundtrip(devices8):
    mesh = TOPO.build()
    ctx = make_context(TOPO)
    x = jax.random.normal(jax.random.PRNGKey(7), (8, 3, 4, 5))

    def f(x):
        cs = plan_core_sharding(ctx, heads_after_ax1=2, batch_local=4)
        y = core_scatter(ctx, x, cs, head_dim=2, batch_dim=0)
        rt = core_gather(ctx, y, cs, head_dim=2, batch_dim=0)
        err = jnp.max(jnp.abs(rt - x))
        return jax.lax.pmax(jax.lax.pmax(err, ("tp1", "tp2")), "data")

    g = shard_map(f, mesh=mesh, in_specs=P("data", None, "tp1"),
                  out_specs=P(), check_vma=False)
    assert float(jax.jit(g)(x)) < 1e-6


def test_batch_factor_roundtrip(devices8):
    mesh = TOPO.build()
    ctx = make_context(TOPO)
    x = jax.random.normal(jax.random.PRNGKey(8), (8, 3, 2, 5))

    def f(x):
        cs = plan_core_sharding(ctx, heads_after_ax1=1, batch_local=4)
        assert cs.b2 == 2 and cs.h2 == 1
        y = core_scatter(ctx, x, cs, head_dim=2, batch_dim=0)
        rt = core_gather(ctx, y, cs, head_dim=2, batch_dim=0)
        err = jnp.max(jnp.abs(rt - x))
        return jax.lax.pmax(jax.lax.pmax(err, ("tp1", "tp2")), "data")

    g = shard_map(f, mesh=mesh, in_specs=P("data", None, "tp1"),
                  out_specs=P(), check_vma=False)
    assert float(jax.jit(g)(x)) < 1e-6

"""GPipe pipeline over the pod axis: forward == dense, grads == dense."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.mesh import make_mesh
from repro.core.pipeline import gpipe_forward, gpipe_loss


def _setup(S=4, M=8):
    mesh = make_mesh((S,), ("pod",))
    # S stages, each one matmul + tanh; stacked stage params [S, d, d]
    d = 16
    Ws = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * (0.5 / d ** 0.5)
    X = jax.random.normal(jax.random.PRNGKey(1), (M, 4, d))  # M microbatches
    return mesh, Ws, X


def _stage(w, x):
    return jnp.tanh(x @ w)


def _dense(Ws, X):
    y = X
    for i in range(Ws.shape[0]):
        y = _stage(Ws[i], y)
    return y


def test_gpipe_forward_matches_dense(devices8):
    mesh, Ws, X = _setup()

    def f(w, x):
        return gpipe_forward(_stage, w[0], x, "pod")

    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("pod"), P()),
                          out_specs=P(), check_vma=True))
    # output valid on the last stage; with out_specs P() + check_vma=True
    # the last stage's copy must equal the dense result after psum-style
    # selection; select it explicitly instead:
    def f2(w, x):
        outs = gpipe_forward(_stage, w[0], x, "pod")
        # broadcast the last stage's result to everyone for checking
        ok = (jax.lax.axis_index("pod") == lax.axis_size("pod") - 1)
        return jax.lax.psum(jnp.where(ok, outs, 0.0), "pod")

    g2 = jax.jit(shard_map(f2, mesh=mesh, in_specs=(P("pod"), P()),
                           out_specs=P(), check_vma=True))
    out = g2(Ws, X)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_dense(Ws, X)),
                               rtol=1e-5, atol=1e-6)


def test_gpipe_grads_match_dense(devices8):
    mesh, Ws, X = _setup()

    def loss_pipe(w, x):
        return gpipe_loss(_stage, lambda y: jnp.sum(y ** 2), w[0], x, "pod")

    def loss_dense(w, x):
        return jnp.sum(_dense(w, x) ** 2)

    g = jax.jit(shard_map(jax.grad(loss_pipe), mesh=mesh,
                          in_specs=(P("pod"), P()), out_specs=P("pod"),
                          check_vma=True))
    grads = g(Ws, X)
    ref = jax.grad(loss_dense)(Ws, X)
    np.testing.assert_allclose(np.asarray(grads), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_gpipe_bubble_cost_is_s_minus_1(devices8):
    """The schedule runs M + S - 1 ticks (GPipe bubble)."""
    mesh, Ws, X = _setup(S=4, M=8)
    ticks = {"n": 0}

    def counting_stage(w, x):
        ticks["n"] += 1  # traced once per scan body: structural check only
        return _stage(w, x)

    def f(w, x):
        outs = gpipe_forward(counting_stage, w[0], x, "pod")
        ok = (jax.lax.axis_index("pod") == lax.axis_size("pod") - 1)
        return jax.lax.psum(jnp.where(ok, outs, 0.0), "pod")

    hlo = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("pod"), P()),
                            out_specs=P(), check_vma=True)) \
        .lower(Ws, X).compile().as_text()
    assert ticks["n"] == 1  # one traced body
    assert '"known_trip_count":{"n":"11"}' in hlo  # M + S - 1 = 8 + 3
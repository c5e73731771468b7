"""Quickstart: the paper's Figure-9 API in JAX.

The paper's snippet:
    mesh = init_mesh(ndevice=4, mesh_shape=(2, 2))
    fc1 = ATPLinear(in_dim, out_dim, mesh, strategy="col")

Here: build a DeviceMesh(2,2), shard a two-layer MLP with column- and
row-first tensor parallelism, and verify against the dense computation.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.atp import atp_linear, make_context
from repro.core.mesh import MeshTopo


def main():
    # DeviceMesh(2, 2): d1 = d2 = 2 (the paper's Figure 4/9 example)
    topo = MeshTopo((("tp1", 2), ("tp2", 2)))
    mesh = topo.build()
    ctx = make_context(topo)
    print(f"device mesh: {topo.shape} axes={topo.names} "
          f"(d1={ctx.d1}, d2={ctx.d2})")

    in_dim, hidden, out_dim, batch = 16, 32, 16, 8
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (batch, in_dim))
    w1 = jax.random.normal(jax.random.PRNGKey(1), (in_dim, hidden)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (hidden, out_dim)) * 0.1

    def mlp(x, w1, w2):
        # column-first ATPLinear -> GeLU -> row-first ATPLinear (Fig. 6)
        y = jax.nn.gelu(atp_linear(ctx, x, w1, kind="col"))
        return atp_linear(ctx, y, w2, kind="row")

    f = shard_map(
        mlp, mesh=mesh,
        in_specs=(P(None, "tp2"),      # activations: [Replicate, Shard(1)]
                  P("tp2", "tp1"),     # W1: [Shard(1), Shard(0)] col-first
                  P("tp1", "tp2")),    # W2: [Shard(0), Shard(1)] row-first
        out_specs=P(None, "tp2"),
        check_vma=True)
    out = jax.jit(f)(x, w1, w2)
    ref = jax.nn.gelu(x @ w1) @ w2
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"ATP(2,2) output matches dense reference: max|err| = {err:.2e}")
    assert err < 1e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    print("OK")


if __name__ == "__main__":
    main()

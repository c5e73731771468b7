"""HLO analysis: trip-aware collective/FLOP/traffic accounting."""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.mesh import make_mesh

from repro.launch.hlo_analysis import (collective_bytes, full_analysis,
                                       shape_bytes)


def test_shape_bytes():
    assert shape_bytes("f32[4,8]{1,0}") == 128
    assert shape_bytes("bf16[10]") == 20
    assert shape_bytes("(f32[2], s32[3])") == 8 + 12
    assert shape_bytes("pred[]") == 1


def test_shape_bytes_quantized_dtypes():
    assert shape_bytes("f8e5m2[16]") == 16
    assert shape_bytes("f8e4m3fn[16]") == 16
    assert shape_bytes("s4[16]") == 8     # two nibbles per byte
    assert shape_bytes("u4[7]") == 4      # packed: ceil(7/2)


_ASYNC_HLO = """\
HloModule async

ENTRY %main (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %ars = f32[8,16]{1,0} all-reduce-start(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %ard = f32[8,16]{1,0} all-reduce-done(%ars)
  %ags = (f32[8,16]{1,0}, f32[32,16]{1,0}) all-gather-start(%ard), replica_groups={{0,1,2,3}}, dimensions={0}
  %agd = f32[32,16]{1,0} all-gather-done(%ags)
  %rss = (f32[32,16]{1,0}, f32[8,16]{1,0}) reduce-scatter-start(%agd), replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
  %rsd = f32[8,16]{1,0} reduce-scatter-done(%rss)
  %a2a = f32[8,16]{1,0} all-to-all-start(%rsd), replica_groups={{0,1,2,3}}, dimensions={0}
  %a2d = f32[8,16]{1,0} all-to-all-done(%a2a)
  %cps = (f32[8,16]{1,0}, f32[8,16]{1,0}, u32[], u32[]) collective-permute-start(%a2d), source_target_pairs={{0,1},{1,2}}
  ROOT %cpd = f32[8,16]{1,0} collective-permute-done(%cps)
}
"""


def test_async_pairs_counted_once_uniformly():
    """*-start carries the payload; *-done contributes nothing; async
    tuple results (operand, dest, contexts) are not double-counted."""
    got = collective_bytes(_ASYNC_HLO)["per_op_bytes"]
    buf = 8 * 16 * 4
    assert got["all-reduce"] == buf
    assert got["all-gather"] == 4 * buf          # result on each device
    assert got["reduce-scatter"] == 4 * buf      # shard x group = operand
    assert got["all-to-all"] == buf
    assert got["collective-permute"] == buf


def _compile(f, in_specs, out_specs, *args, mesh=None):
    mesh = mesh or make_mesh((4,), ("m",))
    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs,
                             check_vma=False)).lower(*args).compile().as_text()


def test_collectives_counted_with_trip_multiplier(devices8):
    L = 7

    def f(x):
        def body(c, _):
            return lax.psum(c, "m"), None
        y, _ = lax.scan(body, x, None, length=L)
        return y

    hlo = _compile(f, P(), P(), jnp.ones((8, 16)))
    got = collective_bytes(hlo)
    # one 8x16 f32 psum per iteration
    assert got["per_op_bytes"]["all-reduce"] == 8 * 16 * 4 * L


def test_dot_flops_trip_aware(devices8):
    L, m, k, n = 5, 32, 64, 16

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = lax.scan(body, x, None, length=L)
        return y

    hlo = _compile(f, (P(), P()), P(), jnp.ones((m, k)),
                   jnp.ones((k, k)))
    got = full_analysis(hlo)
    assert got["dot_flops"] == 2 * m * k * k * L


def test_xla_cost_analysis_counts_loops_once():
    """The reason full_analysis exists: XLA's own flops ignore trip count."""
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = lax.scan(body, x, None, length=10)
        return y

    x, w = jnp.zeros((64, 64)), jnp.zeros((64, 64))
    c = jax.jit(f).lower(x, w).compile()
    one_iter = 2 * 64 * 64 * 64
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca  # list-of-dicts on 0.4
    got = ca.get("flops")
    assert one_iter <= got < 1.01 * one_iter, got  # ~1 iteration, NOT 10x


def test_paper_gpt_models_smoke():
    """The paper's M1..M4 eval configs instantiate and train-step (reduced)."""
    from repro.configs.registry import PAPER_MODELS
    from repro.core.atp import make_context
    from repro.core.mesh import MeshTopo
    from repro.models import lm

    cfg = PAPER_MODELS["gpt-m1"].reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    topo = MeshTopo((("data", 1),))
    mesh = topo.build(jax.devices()[:1])
    ctx = make_context(topo)
    batch = {
        "tokens": jnp.zeros((2, 16), jnp.int32),
        "labels": jnp.zeros((2, 16), jnp.int32),
    }
    f = shard_map(lambda p, b: lm.train_loss(ctx, cfg, p, b, remat=False),
                  mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                  check_vma=True)
    loss = jax.jit(f)(params, batch)
    assert np.isfinite(float(loss))
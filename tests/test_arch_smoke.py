"""Per-architecture smoke tests (deliverable f): REDUCED config of the same
family, one forward/train step on CPU, output shapes + no NaNs; key archs
also checked distributed-vs-single-device."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs.registry import ARCHS, get_config
from repro.core.atp import make_context
from repro.core.mesh import MeshTopo
from repro.models import lm

ALL_ARCHS = sorted(ARCHS)

TOPO1 = MeshTopo((("data", 1),))
TOPO8 = MeshTopo((("data", 2), ("tp1", 2), ("tp2", 2)))
TOPO_MEG = MeshTopo((("data", 2), ("model", 4)))  # ATP (4,1) baseline shape


def _batch(cfg, B=4, S=32, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    b = {}
    if cfg.frontend == "vision_patches":
        b["embeds"] = jax.random.normal(ks[0], (B, S, cfg.d_model),
                                        jnp.float32) * 0.02
        b["positions3"] = jnp.broadcast_to(jnp.arange(S)[None, None], (3, B, S))
    else:
        b["tokens"] = jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)
    b["labels"] = jax.random.randint(ks[2], (B, S), 0, cfg.vocab_size)
    return b


def _loss_on(topo, cfg, params, batch, remat=False, grad=False):
    """Train loss of ``batch`` on ``topo``'s mesh (vma-checked); with
    ``grad``, the gradients w.r.t. ``params`` instead, sharded like them."""
    mesh = topo.build(jax.devices()[: topo.size])
    ctx = make_context(topo)
    specs = lm.param_specs(cfg, ctx)
    bspec = {k: P("data") if topo.axis_size("data") > 1 else P()
             for k in batch}
    if "positions3" in batch:
        bspec["positions3"] = (P(None, "data") if topo.axis_size("data") > 1
                               else P())
    if "embeds" in batch:
        ax2 = "tp2" if topo.has_axis("tp2") else None
        bspec["embeds"] = (P("data", None, ax2)
                           if topo.axis_size("data") > 1 else P(None, None, ax2))

    def f(p, b):
        return lm.train_loss(ctx, cfg, p, b, remat=remat)

    g = shard_map(jax.grad(f) if grad else f, mesh=mesh,
                  in_specs=(specs, bspec),
                  out_specs=specs if grad else P(), check_vma=True)
    return jax.jit(g)(params, batch)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_train_step_no_nans(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = _batch(cfg)
    loss = _loss_on(TOPO1, cfg, params, batch)
    assert np.isfinite(float(loss)), f"{arch}: loss is not finite"
    assert 0.0 < float(loss) < 20.0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_prefill_shapes(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = {k: v for k, v in _batch(cfg).items() if k != "labels"}
    topo = TOPO1
    mesh = topo.build(jax.devices()[:1])
    ctx = make_context(topo)

    def f(p, b):
        return lm.prefill_logits(ctx, cfg, p, b)

    g = shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                  check_vma=True)
    logits = jax.jit(g)(params, batch)
    assert logits.shape == (4, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


# distributed == single device, per family representative
DIST_ARCHS = ["llama3-8b", "gemma2-2b", "dbrx-132b", "deepseek-v3-671b",
              "zamba2-7b", "xlstm-1.3b", "qwen2-vl-7b", "musicgen-medium"]


@pytest.mark.parametrize("arch", DIST_ARCHS)
def test_distributed_matches_reference(devices8, arch):
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        # avoid capacity-drop divergence between layouts
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = _batch(cfg)
    ref = _loss_on(TOPO1, cfg, params, batch)
    dist = _loss_on(TOPO8, cfg, params, batch, remat=True)
    np.testing.assert_allclose(float(dist), float(ref), rtol=5e-3)


@pytest.mark.parametrize("arch", DIST_ARCHS)
def test_distributed_grads_match_reference(devices8, arch):
    """Every leaf's gradient on the (dp, d1, d2) = (2, 2, 2) mesh equals
    the single-device gradient: the vma rewrite's reductions, grad_sync
    and the sLSTM's hoisted recurrent-weight cast reduce each exactly
    once (float32, so only the summation order differs)."""
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        # as above: no capacity drops in either layout.  The load-balance
        # loss is a mean of per-rank products (each rank routes its own
        # tokens), not the single device's product of means, so it is off
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0,
                                         aux_loss_weight=0.0))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = _batch(cfg)
    ref = _loss_on(TOPO1, cfg, params, batch, grad=True)
    dist = _loss_on(TOPO8, cfg, params, batch, grad=True)
    for (path, d), r in zip(jax.tree_util.tree_leaves_with_path(dist),
                            jax.tree.leaves(ref)):
        d, r = np.asarray(d), np.asarray(r)
        rel = np.linalg.norm(d - r) / (np.linalg.norm(r) + 1e-30)
        assert rel < 1e-3, (jax.tree_util.keystr(path), rel)


@pytest.mark.parametrize("arch", ["llama3-8b", "musicgen-medium"])
def test_megatron_mesh_matches_reference(devices8, arch):
    """ATP (N,1) degenerate point (single 'model' axis) == reference.
    musicgen exercises the q_regroup path (24 heads % 4 != 0)."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = _batch(cfg)
    ref = _loss_on(TOPO1, cfg, params, batch)
    meg = _loss_on(TOPO_MEG, cfg, params, batch)
    np.testing.assert_allclose(float(meg), float(ref), rtol=5e-3)


def test_param_counts_match_analytic():
    """init param count ~= ModelConfig.param_count (exact for dense)."""
    for arch in ("llama3-8b", "qwen3-8b", "gemma2-2b"):
        cfg = get_config(arch)
        abstract = lm.abstract_params(cfg)
        got = lm.count_params(abstract)
        expect = cfg.param_count()
        assert abs(got - expect) / expect < 0.02, (arch, got, expect)

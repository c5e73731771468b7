"""Compile the main path for a described TPU v5e, with no chip attached.

XLA's TPU compiler is installed with jaxlib, and it compiles for devices
that are described rather than attached: it refuses what the chip would
refuse (a Pallas block the tiling rejects, a program that does not fit
HBM) at no chip time.  Nothing here runs, so nothing here is a time or a
result.  The topology is described inside a fixture, never at import: only
the process that runs these tests loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig
from repro.configs.registry import get_config
from repro.core.plan import ParallelPlan
from repro.kernels import ops
from repro.launch.steps import batch_struct, build_paged_step, build_train_step
from repro.models import lm
from repro.models.paging import PagedConfig
from repro.optim import adamw

#: one v5e chip's HBM
HBM_BYTES = 16 * 10**9

#: the serving geometry chip_smoke.py runs: 8 slots x 64 pages of 16
PAGED = PagedConfig(page_size=16, num_pages=1 + 8 * 64, pages_per_slot=64)

#: the benchmark's serve cell: 32 slots x 160 pages of 16 in 4097 pages
SERVE_CELL = PagedConfig(page_size=16, num_pages=4097, pages_per_slot=160)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, s):
    """(jitted fn, abstract args) for one kernel at a real model width."""
    bf = jnp.bfloat16
    if name == "flash_attention_d64":     # qwen1.5-0.5b heads
        q = _sds((1, 2048, 16, 64), bf, s)
        return (lambda q, k, v: ops.flash_attention(q, k, v)), (q, q, q)
    if name == "flash_attention_d128":    # qwen3-8b heads (GQA 32:8)
        q = _sds((1, 2048, 32, 128), bf, s)
        kv = _sds((1, 2048, 8, 128), bf, s)
        return (lambda q, k, v: ops.flash_attention(q, k, v)), (q, kv, kv)
    if name == "rmsnorm":                 # qwen1.5-0.5b, 8 x 512 tokens
        return ops.rmsnorm, (_sds((8, 512, 1024), bf, s),
                             _sds((1024,), bf, s))
    if name == "matmul":                  # qwen1.5-0.5b mlp up-projection
        return ops.matmul, (_sds((4096, 1024), bf, s),
                            _sds((1024, 2816), bf, s))
    if name == "ssd_scan":                # zamba2-7b mamba2 heads
        b, sq, nh, hd, ds = 1, 2048, 112, 64, 64
        f32 = jnp.float32
        return ops.ssd_scan, (
            _sds((b, sq, nh, hd), bf, s), _sds((b, sq, nh), f32, s),
            _sds((nh,), f32, s), _sds((b, sq, ds), bf, s),
            _sds((b, sq, ds), bf, s), _sds((nh,), f32, s))
    raise ValueError(name)


@pytest.mark.parametrize("name", ["flash_attention_d64",
                                  "flash_attention_d128", "rmsnorm",
                                  "matmul", "ssd_scan"])
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "kernel not in program"


def _mesh(plan, topo):
    t = plan.topo()
    return t, t.build(topo.devices[: t.size])


def _paged_args(cfg, info, b, s, pcfg=PAGED):
    i32 = jnp.int32
    caches, _ = lm.init_paged_caches(cfg, info.ctx, pcfg, abstract=True)
    return (lm.abstract_params(cfg), jax.ShapeDtypeStruct((b, s), i32),
            jax.ShapeDtypeStruct((b,), i32),
            jax.ShapeDtypeStruct((b, pcfg.pages_per_slot), i32), caches)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return ma.argument_size_in_bytes + ma.temp_size_in_bytes


@pytest.mark.parametrize("b,s", [(1, 128), (8, 1)],
                         ids=["prefill_chunk", "decode_tick"])
def test_paged_step_qwen1_5_0_5b_compiles_on_one_chip(topo, b, s):
    cfg = get_config("qwen1.5-0.5b")
    plan = ParallelPlan(d1=1, d2=1, dp=1)
    t, mesh = _mesh(plan, topo)
    step, info = build_paged_step(cfg, t, paged_cfg=PAGED, mesh=mesh,
                                  plan=plan)
    compiled = step.lower(*_paged_args(cfg, info, b, s)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


#: HLO ops that move a whole buffer; over the page pool each one is a copy
#: of gigabytes on every call (the in-place ``scatter`` is not one of them)
_COPYING_OPS = ("copy", "dynamic-slice", "dynamic-update-slice",
                "custom-call")


@pytest.mark.parametrize("b,s", [(1, 512), (32, 1)],
                         ids=["prefill_chunk", "decode_tick"])
def test_paged_step_keeps_pool_in_place(topo, b, s):
    """At the serve cell's geometry the layer scan writes the donated page
    pool in place: no op copies the stacked pool or one layer of it, and
    the temp bytes stay below one stacked pool tensor."""
    cfg = get_config("qwen1.5-0.5b")
    plan = ParallelPlan(d1=1, d2=1, dp=1)
    t, mesh = _mesh(plan, topo)
    step, info = build_paged_step(cfg, t, paged_cfg=SERVE_CELL, mesh=mesh,
                                  plan=plan)
    args = _paged_args(cfg, info, b, s, SERVE_CELL)
    pool = args[-1]["seg0"]["k"]
    compiled = step.lower(*args).compile()
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
    shapes = {"[" + ",".join(map(str, shape)) + "]"
              for shape in (pool.shape, pool.shape[1:],
                            (pool.shape[0] * pool.shape[1],) + pool.shape[2:])}
    text = compiled.as_text()
    assert any(f"{sh}{{" in text for sh in shapes), "pool not in program"
    op = re.compile(r"=\s*(.*?)\s+(" + "|".join(_COPYING_OPS) + r")\(")
    copies = [line.strip()[:200] for line in text.splitlines()
              if (m := op.search(line))
              and any(sh in m.group(1) for sh in shapes)]
    assert not copies, copies[:4]


def test_train_step_qwen1_5_0_5b_fits_one_chip(topo):
    """The chip_smoke train step: full published width, batch 8 x 512."""
    cfg = get_config("qwen1.5-0.5b")
    plan = ParallelPlan(d1=1, d2=1, dp=1)
    t, mesh = _mesh(plan, topo)
    opt_cfg = adamw.AdamWConfig()
    step, info = build_train_step(cfg, t, opt_cfg, mesh=mesh, plan=plan)
    params = lm.abstract_params(cfg)
    opt = adamw.init_opt_state(params, info.pspecs, info.ctx, opt_cfg.mode,
                               abstract=True)
    batch = batch_struct(cfg, ShapeConfig("smoke", 512, 8, "train"), "train")
    compiled = step.lower(params, opt, batch).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_paged_step_qwen3_8b_compiles_on_2x2_mesh(topo):
    """Full depth (36 layers, 16.4 GB of bf16): only fits sharded."""
    cfg = get_config("qwen3-8b")
    plan = ParallelPlan(d1=2, d2=2, dp=1)
    t, mesh = _mesh(plan, topo)
    assert np.asarray(mesh.devices).size == 4
    step, info = build_paged_step(cfg, t, paged_cfg=PAGED, mesh=mesh,
                                  plan=plan)
    compiled = step.lower(*_paged_args(cfg, info, 8, 1)).compile()
    assert _device_bytes(compiled) < HBM_BYTES

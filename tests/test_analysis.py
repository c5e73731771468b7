"""Plan-conformance static analysis: signature vs expectation vs vma lint.

Covers every segment kind, the two pinned mesh flips (their winning
plans must lint clean), the corrupted-plan diagnostics, and the
replication lint's ability to catch a lying out_spec.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.analysis.expect import (PlanConformanceError, check_conformance,
                                   expected_signature, lint_conformance)
from repro.analysis.lint import lint_build
from repro.analysis.replication import verify_replication
from repro.analysis.signature import extract
from repro.configs.base import segments
from repro.configs.registry import get_config
from repro.core import comm_matrix
from repro.core.plan import ParallelPlan, plan_search
from repro.core import atp

B, S = 4, 32

PLAN_2x2 = ParallelPlan(d1=2, d2=2, dp=2, chunks=2, boundary_mode="psum",
                        seq_parallel=True)
PLAN_RING = ParallelPlan(d1=4, d2=1, dp=2, boundary_mode="ring",
                         seq_parallel=True)


def _zamba_with_tail():
    """zamba2 with a trailing pure-mamba segment (num_layers % super != 0)
    so the sweep covers the standalone 'mamba' kind too."""
    cfg = get_config("zamba2-7b").reduced()
    return dataclasses.replace(cfg, num_layers=5)


#: (config, expected segment kinds) — all seven kinds between them
KIND_CASES = [
    ("llama3-8b", {"dense"}),
    ("dbrx-132b", {"moe"}),
    ("deepseek-v3-671b", {"mla_dense", "mla_moe"}),
    ("xlstm-1.3b", {"xlstm"}),
]


@pytest.mark.parametrize("name,kinds", KIND_CASES,
                         ids=[c[0] for c in KIND_CASES])
def test_segment_kind_conformance(devices8, name, kinds):
    cfg = get_config(name).reduced()
    assert {s.kind for s in segments(cfg)} == kinds
    for phase in ("train", "prefill", "decode"):
        errors, op_bytes = lint_build(cfg, PLAN_2x2, phase)
        assert not errors, f"{name} {phase}: {errors[:4]}"
        assert sum(op_bytes.values()) > 0


def test_zamba_and_mamba_kinds_conform(devices8):
    cfg = _zamba_with_tail()
    assert [s.kind for s in segments(cfg)] == ["zamba", "mamba"]
    for phase in ("train", "prefill", "decode"):
        errors, _ = lint_build(cfg, PLAN_2x2, phase)
        assert not errors, f"{phase}: {errors[:4]}"


def test_ring_plan_conformance_and_replication(devices8):
    """Ring boundaries: ppermute schedules forward AND backward, with
    every shard_map out_spec claim proven by the jaxpr walk (upstream's
    check_vma cannot certify these builds at all)."""
    cfg = get_config("llama3-8b").reduced()
    for phase in ("train", "prefill", "decode"):
        errors, _ = lint_build(cfg, PLAN_RING, phase)
        assert not errors, f"{phase}: {errors[:4]}"


# ---------------------------------------------------------------------------
# Pinned mesh flips: the searched winners must lint clean.
# ---------------------------------------------------------------------------


def test_ic1_int8_flip_plans_lint_clean(devices8):
    """The quant acceptance pin: int8 wire flips ic1 train (8,1)->(4,2).
    BOTH winning plans must conform once built."""
    cfg = get_config("llama3-8b")
    kw = dict(layers=cfg.num_layers, batch=4, seq=2048,
              profile=__import__("repro.core.cost_model",
                                 fromlist=["LayerCommProfile"])
              .LayerCommProfile.dense(cfg))
    full = plan_search("ic1", 8, **kw).best
    quant = plan_search("ic1", 8, wire_dtype="int8", **kw).best
    assert (full.d1, full.d2) == (8, 1)
    assert (quant.d1, quant.d2) == (4, 2)
    red = get_config("llama3-8b").reduced()
    for plan in (full, quant):
        errors, _ = lint_build(red, plan, "train")
        assert not errors, errors[:4]


def test_ic1_dbrx_decode_read_flip_lints_clean(devices8):
    """The serving pin: pricing the paged KV gather flips the dbrx decode
    mesh to (4,2) ring — the re-meshed decode build must conform to the
    decode view, quantified collectives and all."""
    from repro.core.cost_model import paged_read_model

    cfg = get_config("dbrx-132b")
    pr = paged_read_model(cfg, avg_len=4096, tp=8)
    plan = plan_search("ic1", 8, model=cfg, batch=4, seq=2048,
                       decode_batch=64, decode_paged_read=pr).best
    assert (plan.decode.d1, plan.decode.d2) == (4, 2)
    assert plan.decode.boundary_mode == "ring"
    # the default reduction keeps 4 experts — too few to dispatch over
    # the flipped flat tp=8 decode mesh, so widen the expert pool only
    red = get_config("dbrx-132b").reduced()
    red = dataclasses.replace(
        red, moe=dataclasses.replace(red.moe, num_experts=8))
    errors, _ = lint_build(red, plan, "decode")
    assert not errors, errors[:4]


# ---------------------------------------------------------------------------
# Diagnostics: corrupted plans fail with segment-specific messages.
# ---------------------------------------------------------------------------


def _corrupt_boundary(plan: ParallelPlan, mode: str) -> ParallelPlan:
    return dataclasses.replace(
        plan, boundary_mode=mode,
        segments=tuple(dataclasses.replace(s, boundary_mode=mode)
                       for s in plan.segments))


def test_corrupted_boundary_mode_fails_with_diagnostic(devices8):
    """A plan claiming ring boundaries over a psum-built step must name
    the offending segment and the missing ppermute schedule."""
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import batch_struct, build_train_step
    from repro.models import lm
    from repro.optim import adamw

    cfg = get_config("llama3-8b").reduced()
    fn, info = build_train_step(cfg, plan=PLAN_2x2)
    params = lm.abstract_params(cfg)
    pspecs = lm.param_specs(cfg, info.ctx)
    opt = adamw.init_opt_state(params, pspecs, info.ctx, abstract=True)
    batch = batch_struct(cfg, ShapeConfig("x", S, B, "train"), "train")
    sig = extract(fn, params, opt, batch)

    lying = _corrupt_boundary(PLAN_2x2, "ring")
    errors = check_conformance(sig, expected_signature(cfg, lying, "train",
                                                       B, S))
    assert errors
    assert any(re.search(r"seg0:dense fwd: expected \d+x ppermute", e)
               for e in errors), errors[:6]
    with pytest.raises(PlanConformanceError, match="seg0:dense"):
        lint_conformance(sig, cfg, lying, "train", B, S)
    # and the true plan still passes on the same signature
    assert lint_conformance(sig, cfg, PLAN_2x2, "train", B, S) == []


def test_wire_dtype_mismatch_diagnostic(devices8):
    """An int8-planned boundary emitting full-width payloads is a lint
    error with the quantization called out explicitly."""
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import batch_struct, build_prefill
    from repro.models import lm

    cfg = get_config("llama3-8b").reduced()
    bf16 = ParallelPlan(d1=2, d2=2, dp=2)
    fn, _ = build_prefill(cfg, plan=bf16)
    params = lm.abstract_params(cfg)
    batch = batch_struct(cfg, ShapeConfig("x", S, B, "prefill"), "prefill")
    sig = extract(fn, params, batch)

    int8 = ParallelPlan(d1=2, d2=2, dp=2, wire_dtype="int8")
    errors = check_conformance(sig, expected_signature(cfg, int8, "prefill",
                                                       B, S))
    assert any("quantized" in e for e in errors), errors[:6]


# ---------------------------------------------------------------------------
# Expectation engine consistency + replication lint unit coverage.
# ---------------------------------------------------------------------------


def test_seq_parallel_kinds_match_execution():
    from repro.analysis import expect

    assert expect.SEQ_PARALLEL_KINDS == atp.SEQ_PARALLEL_KINDS


def test_replication_lint_proves_psum_and_catches_lies(devices8):
    mesh = jax.sharding.Mesh(jax.devices()[:4], ("m",))

    def honest(x):
        return lax.psum(x, "m")

    def lying(x):
        # varies over 'm' but the out_spec P() claims replication
        return x * (1.0 + lax.axis_index("m"))

    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    ok_fn = jax.jit(shard_map(honest, mesh=mesh, in_specs=P("m"),
                              out_specs=P(), check_vma=False))
    assert verify_replication(ok_fn, x) == []

    bad_fn = jax.jit(shard_map(lying, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False))
    errs = verify_replication(bad_fn, x, strict=False)
    assert errs and "claims replication over 'm'" in errs[0]
    with pytest.raises(AssertionError, match="replication lint failed"):
        verify_replication(bad_fn, x)


def test_replication_lint_understands_rings(devices8):
    """A completed ppermute ring IS an all-reduce: per-hop dataflow says
    'varying', the ring-scope algebra restores the axis."""
    from repro.core.overlap import ring_all_reduce

    mesh = jax.sharding.Mesh(jax.devices()[:4], ("m",))

    def ring(x):
        return ring_all_reduce(x, "m", 4)

    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    fn = jax.jit(shard_map(ring, mesh=mesh, in_specs=P("m"),
                           out_specs=P(), check_vma=False))
    assert verify_replication(fn, x) == []

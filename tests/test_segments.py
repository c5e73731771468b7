"""ParallelPlan v2: heterogeneous per-segment overlap strategies.

Pins this PR's acceptance criteria:
  - plan-format migration: a checked-in PR-2-era v1 plan JSON loads by
    broadcasting its global knobs to every segment, and newer-than-
    supported versions still fail loudly;
  - v1/v2 parity: for a homogeneous dense network the per-segment search
    selects the identical strategy (same d1/d2/chunks/boundary_mode/
    seq_parallel, same predicted cost) as the v1 profile-based search;
  - per-segment knob threading: on a mixed dense+MoE stack the dense
    segment honors seq_parallel while the MoE segment masks it, in both
    the train and decode step builders, and different per-segment knobs
    actually reach execution (logit parity between mixed and replicated
    plans through the real prefill builder);
  - per-kind comm profiles derive from ModelConfig (MoE dispatch bytes,
    MLA compressed-KV dims, mamba recurrent-state volume);
  - measured alpha_s reaches the chunk-count choice;
  - replan_elastic keeps the calibration table and tags it stale.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, MoEConfig, segments
from repro.configs.registry import get_config
from repro.core import comm_matrix as cm
from repro.core.atp import (SEQ_PARALLEL_KINDS, SegmentPlan, make_context)
from repro.core.calibrate import CalibEntry, CalibrationTable, calibrate_mesh
from repro.core.cost_model import (LayerCommProfile, segment_workloads,
                                   t_comm_overlap)
from repro.core.mesh import atp_topo
from repro.core.plan import (PLAN_FORMAT_VERSION, ParallelPlan, plan_search,
                             replan_elastic)
from repro.core.search import search_strategy_overlap, search_strategy_segments

V1_FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                          "plan_v1_pr2.json")


def mixed_cfg() -> ModelConfig:
    """DBRX-style MoE stack with a DeepSeek-style dense prefix."""
    return ModelConfig(
        name="t-mixed", family="moe", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256, head_dim=16,
        dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                      first_dense_layers=1))


def mixed_plan(**dense_kw) -> ParallelPlan:
    return ParallelPlan(
        d1=2, d2=2, dp=2,
        segments=(SegmentPlan("dense", **dense_kw), SegmentPlan("moe")))


# ---------------------------------------------------------------------------
# Plan-format migration (v1 -> v2).
# ---------------------------------------------------------------------------


def test_v1_fixture_loads_and_broadcasts_global_knobs():
    plan = ParallelPlan.load(V1_FIXTURE)
    assert (plan.d1, plan.d2, plan.dp, plan.pods) == (2, 4, 3, 2)
    assert plan.segments == ()          # v1 files carry no per-segment entries
    # broadcast rule: every kind sees the file's global knobs
    for kind in ("dense", "moe", "mla_moe", "mamba"):
        seg = plan.segment_plan(kind)
        assert (seg.chunks, seg.boundary_mode, seg.seq_parallel) == \
            (4, "ring", True)
    # the calibration table came through intact (alpha_s absent -> None)
    assert plan.calibration.get(8, 1).b2 == math.inf
    assert plan.calibration.alpha(2, 4) is None
    # and the execution view applies the per-kind seq_parallel gate
    ctx = plan.context()
    assert ctx.for_segment("dense").seq_parallel is True
    assert ctx.for_segment("moe").seq_parallel is False
    assert ctx.for_segment("moe").chunks == 4


def test_v1_fixture_roundtrips_as_current():
    plan = ParallelPlan.load(V1_FIXTURE)
    d = plan.to_dict()
    assert d["format_version"] == PLAN_FORMAT_VERSION == 5
    assert d["segments"] == []
    assert d["decode"] is None       # v1 files carry no decode sub-plan
    assert ParallelPlan.from_dict(d) == plan


def test_newer_than_supported_version_fails_loudly():
    d = ParallelPlan.load(V1_FIXTURE).to_dict()
    d["format_version"] = PLAN_FORMAT_VERSION + 1
    with pytest.raises(ValueError, match="format_version"):
        ParallelPlan.from_dict(d)


def test_v2_segments_roundtrip_exact():
    plan = ParallelPlan(
        d1=2, d2=2, chunks=2, topology="ic3",
        segments=(SegmentPlan("dense", chunks=4, boundary_mode="ring",
                              seq_parallel=True),
                  SegmentPlan("moe", chunks=1)))
    q = ParallelPlan.from_json(plan.to_json())
    assert q == plan
    assert q.segment_plan("dense").seq_parallel is True
    assert q.segment_plan("moe").chunks == 1
    # an unknown kind falls back to the plan's global knobs
    assert q.segment_plan("mamba").chunks == plan.chunks


def test_segment_plan_validation():
    with pytest.raises(ValueError, match="chunks"):
        SegmentPlan("dense", chunks=0)
    with pytest.raises(ValueError, match="boundary_mode"):
        SegmentPlan("dense", boundary_mode="laser")
    with pytest.raises(ValueError, match="duplicate"):
        ParallelPlan(d1=2, d2=2, segments=(SegmentPlan("dense"),
                                           SegmentPlan("dense", chunks=2)))


# ---------------------------------------------------------------------------
# v1/v2 search parity (the pin) + per-kind profiles.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("preset", ("ic1", "ic3", "ic4"))
def test_single_dense_segment_parity_with_v1_search(preset):
    cfg = get_config("llama3-8b")
    assert [s.kind for s in segments(cfg)] == ["dense"]
    v1 = plan_search(preset, cm.PRESETS[preset]().num_devices,
                     layers=cfg.num_layers, batch=4, seq=2048,
                     profile=LayerCommProfile.dense(cfg))
    v2 = plan_search(preset, cm.PRESETS[preset]().num_devices,
                     model=cfg, batch=4, seq=2048)
    a, b = v1.best, v2.best
    assert (a.d1, a.d2, a.chunks, a.boundary_mode, a.seq_parallel) == \
        (b.d1, b.d2, b.chunks, b.boundary_mode, b.seq_parallel)
    assert b.predicted.t_exposed == pytest.approx(a.predicted.t_exposed,
                                                  rel=1e-12)
    assert b.predicted.t_comm == pytest.approx(a.predicted.t_comm, rel=1e-12)
    # the v2 plan additionally names its one segment
    assert [s.kind for s in b.segments] == ["dense"]
    assert b.segments[0].chunks == a.chunks


def test_segmented_search_masks_seq_parallel_per_kind():
    cfg = mixed_cfg()
    res = search_strategy_segments(
        cm.PRESETS["ic3"](), 4, workloads=segment_workloads(cfg),
        batch=8, seq=256)
    for mesh in res.ranked:
        by_kind = {c.kind: c for c in mesh.segments}
        assert not by_kind["moe"].seq_parallel
    assert "moe" not in SEQ_PARALLEL_KINDS
    assert {"dense", "mla_dense"} <= SEQ_PARALLEL_KINDS


def test_per_kind_profiles_derive_from_config():
    cfg = mixed_cfg()
    moe_p = LayerCommProfile.for_segment("moe", cfg)
    assert moe_p.flat_dispatch_out == pytest.approx(
        2.0 * cfg.moe.top_k * cfg.moe.capacity_factor * cfg.d_model)
    assert moe_p.col_first_out == pytest.approx(cfg.q_dim + 2 * cfg.kv_dim)

    ds = get_config("deepseek-v3-671b")
    mla_p = LayerCommProfile.for_segment("mla_dense", ds)
    m = ds.mla
    assert mla_p.col_full_out == pytest.approx(
        m.q_lora_rank + m.kv_lora_rank + m.qk_rope_head_dim)
    assert LayerCommProfile.for_segment("mla_moe", ds).flat_dispatch_out > 0

    za = get_config("zamba2-7b")
    mam = LayerCommProfile.for_segment("mamba", za)
    d_inner = za.ssm.expand * za.d_model
    assert mam.col_full_out == pytest.approx(
        2 * d_inner + 2 * za.ssm.d_state + d_inner // za.ssm.head_dim)
    # full-width ax1 psums (zamba regather / xlstm recurrent h) are priced
    # on the ROW (ax1) pool, not lumped into the ax2 pool
    assert LayerCommProfile.for_segment("zamba", za).row_full_out == \
        pytest.approx(za.d_model)
    xl = get_config("xlstm-1.3b")
    assert LayerCommProfile.for_segment("xlstm", xl).row_full_out == \
        pytest.approx(xl.ssm.slstm_every * xl.d_model)
    # ...and a d2==1 mesh still pays for them (ax1 traffic exists there)
    zprof = LayerCommProfile.for_segment("zamba", za)
    c = t_comm_overlap(cm.PRESETS["ic3"](), 4, 1, layers=2, batch=4,
                       seq=256, profile=zprof)
    assert c.ax1_boundary_bytes > 0 and c.t_comm > 0

    with pytest.raises(ValueError, match="no comm profile"):
        LayerCommProfile.for_segment("laser", cfg)

    # segment_workloads covers every kind in the zoo without error
    from repro.configs.registry import ARCHS
    for name, acfg in ARCHS.items():
        ws = segment_workloads(acfg)
        assert sum(w.layers for w in ws) >= 1
        assert all(w.profile.hidden for w in ws)


def test_moe_dispatch_bytes_priced_into_cost():
    cfg = mixed_cfg()
    prof = LayerCommProfile.for_segment("moe", cfg)
    with_flat = t_comm_overlap(cm.PRESETS["ic3"](), 2, 4, layers=4, batch=8,
                               seq=256, profile=prof)
    without = t_comm_overlap(
        cm.PRESETS["ic3"](), 2, 4, layers=4, batch=8, seq=256,
        profile=dataclasses.replace(prof, flat_dispatch_out=0.0))
    assert with_flat.t_comm > without.t_comm
    assert with_flat.t_exposed > without.t_exposed
    assert with_flat.flat_dispatch_bytes > 0 == without.flat_dispatch_bytes


# ---------------------------------------------------------------------------
# Measured alpha_s (per-step latency) -> chunk-count choice.
# ---------------------------------------------------------------------------


def test_calibrate_mesh_measures_alpha(devices8):
    tab = calibrate_mesh(2, payload_kb=4, repeats=1)
    for _, e in tab.entries:
        assert e.alpha_s is not None and e.alpha_s >= 0.0
    assert CalibrationTable.from_dict(tab.to_dict()) == tab
    assert tab.alpha(2, 1) == tab.get(2, 1).alpha_s


def test_measured_alpha_steers_chunk_count():
    prof = LayerCommProfile.gpt(8192)
    m = cm.PRESETS["ic4"]()

    def best_chunks(alpha):
        tab = CalibrationTable(
            entries=tuple(((d1, d2), CalibEntry(b1=5.0, b2=5.0,
                                                alpha_s=alpha))
                          for d1, d2 in ((1, 16), (2, 8), (4, 4), (8, 2),
                                         (16, 1))),
            source="unit")
        return search_strategy_overlap(
            m, 16, layers=4, batch=4, seq=2048, profile=prof,
            calibration=tab).best.chunks

    # latency-free chunking always pays; a huge measured per-step latency
    # (each chunk re-pays alpha) must push the choice back to 1
    assert best_chunks(0.0) > 1
    assert best_chunks(10.0) == 1


# ---------------------------------------------------------------------------
# Per-segment knob threading: builders + execution.
# ---------------------------------------------------------------------------


def test_builders_thread_per_segment_knobs(devices8):
    from repro.launch.steps import build_decode_step, build_train_step

    cfg = mixed_cfg()
    plan = mixed_plan(chunks=2, seq_parallel=True)
    _, t_info = build_train_step(cfg, plan=plan)
    dense = t_info.ctx.for_segment("dense")
    moe = t_info.ctx.for_segment("moe")
    assert (dense.chunks, dense.seq_parallel) == (2, True)
    assert (moe.chunks, moe.seq_parallel) == (1, False)
    # decode masks seq_parallel in EVERY segment entry but keeps chunks
    _, d_info = build_decode_step(cfg, B=4, s_max=16, plan=plan)
    assert all(not s.seq_parallel for s in d_info.ctx.segment_plans)
    assert d_info.ctx.for_segment("dense").chunks == 2
    assert d_info.ctx.for_segment("dense").seq_parallel is False


def test_mixed_plan_prefill_logits_match_replicated(devices8):
    """Different per-segment knobs must reach execution without changing
    the math: greedy prefill tokens agree between the heterogeneous plan
    (dense segment seq-parallel + chunked) and the all-replicated one."""
    import numpy as np

    from repro.launch.steps import build_prefill
    from repro.models import lm

    cfg = mixed_cfg()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)

    def run(plan):
        fn, info = build_prefill(cfg, plan=plan)
        p = jax.device_put(params, info.sharding(info.pspecs))
        batch = jax.device_put({"tokens": tokens},
                               info.sharding(info.bspecs))
        return np.asarray(fn(p, batch))

    base = run(mixed_plan())
    het = run(mixed_plan(chunks=2, seq_parallel=True))
    assert (base == het).all()


def test_mixed_plan_decode_runs_with_per_segment_chunks(devices8):
    from repro.launch.steps import build_decode_step
    from repro.models import lm

    cfg = mixed_cfg()
    plan = mixed_plan(chunks=2, seq_parallel=True)
    step, info = build_decode_step(cfg, B=4, s_max=16, plan=plan)
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    params = jax.device_put(params, info.sharding(info.pspecs))
    caches, cache_specs = lm.init_decode_caches(cfg, info.ctx, 4, 16)
    caches = jax.device_put(caches, info.sharding(cache_specs))
    toks = jnp.zeros((4, 1), jnp.int32)
    out, caches = step(params, toks, jnp.int32(0), caches)
    assert out.shape == (4,)
    assert jnp.all((out >= 0) & (out < cfg.vocab_size))


def test_deepseek_style_mla_dense_prefix_trains_seq_parallel(devices8):
    """DeepSeek-shaped stack (mla_dense prefix + mla_moe + MTP head): the
    prefix runs sequence-parallel while the MoE segment masks it, through
    the real train builder."""
    from repro.data.pipeline import DataConfig, TokenSource
    from repro.launch.steps import build_train_step
    from repro.models import lm
    from repro.optim import adamw

    cfg = get_config("deepseek-v3-671b").reduced()
    kinds = [s.kind for s in segments(cfg)]
    assert kinds == ["mla_dense", "mla_moe"]
    plan = ParallelPlan(
        d1=2, d2=2, dp=2,
        segments=(SegmentPlan("mla_dense", seq_parallel=True),
                  SegmentPlan("mla_moe", chunks=2)))
    step, info = build_train_step(cfg, plan=plan)
    assert info.ctx.for_segment("mla_dense").seq_parallel is True
    assert info.ctx.for_segment("mla_moe").seq_parallel is False
    src = TokenSource(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                 global_batch=4))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    opt = adamw.init_opt_state(params, info.pspecs, info.ctx, "zero1")
    params = jax.device_put(params, info.sharding(info.pspecs))
    opt = jax.device_put(opt, info.sharding(info.ospecs))
    batch = jax.device_put(
        {k: jnp.asarray(v) for k, v in src.global_batch(0).items()},
        info.sharding(info.bspecs))
    _, _, metrics = step(params, opt, batch)
    assert jnp.isfinite(metrics["loss"])


def test_embeds_entry_respects_masked_first_segment(devices8):
    """Regression: a global seq_parallel=True knob on a model whose first
    segment masks it (pure-MoE stack) must NOT seq-slice externally
    supplied embeds — the entry follows the first segment's masked view."""
    import numpy as np

    from jax import shard_map
    from repro.core.mesh import MeshTopo
    from repro.models import lm

    cfg = mixed_cfg()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, first_dense_layers=0),
        num_layers=2)
    assert [s.kind for s in segments(cfg)] == ["moe"]
    topo = MeshTopo((("tp1", 2), ("tp2", 2)))
    ctx = make_context(topo, seq_parallel=True)   # v1-style global knob
    mesh = topo.build(jax.devices()[:4])
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    pspecs = lm.param_specs(cfg, ctx)
    b, s = 2, 8
    embeds = jax.random.normal(jax.random.PRNGKey(1),
                               (b, s, cfg.d_model), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def local(p, e):
        h, _, _, _ = lm.forward(ctx, cfg, p, None, positions, embeds=e)
        return h

    from jax.sharding import PartitionSpec as P

    fn = shard_map(local, mesh=mesh,
                   in_specs=(pspecs, P(None, None, "tp2")),
                   out_specs=P(None, None, "tp2"), check_vma=False)
    h = fn(params, embeds)
    # full sequence out (the bug sliced it to s/d1) and finite values
    assert h.shape == (b, s, cfg.d_model)
    assert np.isfinite(np.asarray(h)).all()


def test_for_segment_fallback_and_ring_summary():
    topo = atp_topo(1, 2, 2)
    ctx = make_context(topo, chunks=3, seq_parallel=True)
    # no segment entries: the view is the context itself (v1 behavior)
    assert ctx.for_segment("dense") == ctx
    assert ctx.for_segment("moe").seq_parallel is False
    assert not ctx.any_ring
    ctx2 = dataclasses.replace(ctx, segment_plans=(
        SegmentPlan("moe", boundary_mode="ring"),))
    assert ctx2.any_ring
    # an entry-less kind under segment plans falls back to global knobs
    assert ctx2.for_segment("dense").chunks == 3
    assert ctx2.for_segment("dense").segment_plans == ()


# ---------------------------------------------------------------------------
# Elastic re-plan: calibration kept but visibly stale.
# ---------------------------------------------------------------------------


def _calibrated_plan() -> ParallelPlan:
    tab = CalibrationTable.from_pairs(
        {(2, 4): (1.2, 4.95), (8, 1): (0.97, 0.97)}, source="unit")
    return ParallelPlan(d1=4, d2=2, dp=1, calibration=tab)


def test_replan_elastic_keeps_calibration_tagged_stale():
    plan = _calibrated_plan()             # 8 devices
    new = replan_elastic(plan, 4)         # tp halves -> table is stale
    assert new.tp == 4
    assert new.calibration == plan.calibration   # kept, not dropped
    assert new.calibration_stale
    assert "[calibration:stale]" in new.describe()
    # dp-only shrink does NOT stale the table
    same_tp = replan_elastic(ParallelPlan(d1=2, d2=2, dp=2,
                                          calibration=plan.calibration), 4)
    assert not same_tp.calibration_stale


def test_replan_elastic_researched_plan_keeps_stale_tag():
    cfg = get_config("llama3-8b")
    plan = plan_search("ic4", 16, model=cfg, batch=4, seq=2048,
                       calibration=CalibrationTable.from_pairs(
                           {(4, 4): (10.0, 10.0)}, source="unit")).best
    new = replan_elastic(plan, 8, model=cfg, batch=4, seq=2048)
    assert new.tp == 8
    assert new.calibration == plan.calibration
    assert new.calibration_stale
    assert any(k == "elastic" for k, _ in new.provenance)
    # the re-searched plan still carries per-segment knobs
    assert [s.kind for s in new.segments] == ["dense"]

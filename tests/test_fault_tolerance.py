"""Checkpoint atomic commit, elastic reshard-on-restore, trainer failure
recovery, straggler watchdog (deliverable: large-scale runnability)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.core.mesh import make_mesh
from repro.data.pipeline import DataConfig, TokenSource
from repro.runtime.trainer import StragglerWatchdog, Trainer, TrainerConfig


class TestOrphanTmpSweep:
    """A crashed/killed save() must not leak .tmp_* staging dirs forever."""

    def _orphan(self, tmp_path):
        d = os.path.join(str(tmp_path), ".tmp_dead")
        os.makedirs(d)
        with open(os.path.join(d, "arr_0.npy"), "w") as f:
            f.write("junk")
        return d

    def test_save_sweeps_orphans_on_entry(self, tmp_path):
        d = self._orphan(tmp_path)
        ckpt.save(str(tmp_path), 1, {"a": jnp.ones(3)})
        assert not os.path.exists(d)
        assert ckpt.latest_step(str(tmp_path)) == 1

    def test_prune_sweeps_orphans(self, tmp_path):
        ckpt.save(str(tmp_path), 1, {"a": jnp.ones(3)})
        d = self._orphan(tmp_path)
        ckpt.prune(str(tmp_path), keep=1)
        assert not os.path.exists(d)
        assert ckpt.latest_step(str(tmp_path)) == 1

    def test_sweep_missing_dir_is_noop(self, tmp_path):
        assert ckpt.sweep_orphan_tmps(os.path.join(str(tmp_path), "no")) == 0

    def test_failed_save_cleans_its_tmp(self, tmp_path):
        class Boom:
            def __array__(self, *a, **k):
                raise RuntimeError("boom")  # fails mid-save, inside try

        with pytest.raises(Exception):
            ckpt.save(str(tmp_path), 1, {"a": jnp.ones(3), "b": Boom()})
        assert not [n for n in os.listdir(str(tmp_path))
                    if n.startswith(".tmp_")]


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.ones(5)}}
        ckpt.save(str(tmp_path), 7, tree)
        out, meta = ckpt.restore(str(tmp_path), tree)
        assert meta["step"] == 7
        np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))

    def test_uncommitted_checkpoint_is_invisible(self, tmp_path):
        tree = {"a": jnp.ones(3)}
        d = ckpt.save(str(tmp_path), 1, tree)
        os.remove(os.path.join(d, "COMMITTED"))
        assert ckpt.latest_step(str(tmp_path)) is None

    def test_latest_and_prune(self, tmp_path):
        tree = {"a": jnp.ones(3)}
        for s in (1, 2, 3, 4):
            ckpt.save(str(tmp_path), s, tree)
        assert ckpt.latest_step(str(tmp_path)) == 4
        ckpt.prune(str(tmp_path), keep=2)
        assert ckpt.latest_step(str(tmp_path)) == 4
        assert not os.path.exists(os.path.join(str(tmp_path), "step_00000001"))

    def test_elastic_reshard_on_restore(self, tmp_path, devices8):
        """Save under one mesh, restore under a different one."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh4 = make_mesh((4,), ("data",))
        x = jax.device_put(jnp.arange(16.0),
                           NamedSharding(mesh4, P("data")))
        ckpt.save(str(tmp_path), 1, {"x": x})
        mesh8 = make_mesh((8,), ("data",))
        tgt = NamedSharding(mesh8, P("data"))
        out, _ = ckpt.restore(str(tmp_path), {"x": jnp.zeros(16)},
                              shardings={"x": tgt})
        assert out["x"].sharding == tgt
        np.testing.assert_array_equal(np.asarray(out["x"]), np.arange(16.0))

    def test_sharded_restore_casts_to_template_dtype(self, tmp_path, devices8):
        """The on-disk npy dtype must not leak through device_put: a bf16
        template restores at bf16 on BOTH branches (the sharded path used
        to skip the cast the unsharded path applies)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        ckpt.save(str(tmp_path), 1, {"x": jnp.arange(16.0),  # f32 on disk
                                     "y": jnp.arange(8.0)})
        mesh = make_mesh((4,), ("data",))
        tgt = NamedSharding(mesh, P("data"))
        tmpl = {"x": jnp.zeros(16, jnp.bfloat16),
                "y": jnp.zeros(8, jnp.bfloat16)}
        out, _ = ckpt.restore(str(tmp_path), tmpl,
                              shardings={"x": tgt, "y": None})
        assert out["x"].dtype == jnp.bfloat16     # sharded branch
        assert out["y"].dtype == jnp.bfloat16     # unsharded branch
        assert out["x"].sharding == tgt
        np.testing.assert_array_equal(np.asarray(out["x"], np.float32),
                                      np.arange(16.0))

    def test_bf16_checkpoint_round_trips(self, tmp_path, devices8):
        """np.save writes bf16 as raw void bytes; restore must
        reinterpret via the recorded dtype (both branches), and casting
        to a different template dtype still works."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        x = jnp.arange(16.0, dtype=jnp.bfloat16) / 3
        ckpt.save(str(tmp_path), 1, {"x": x, "y": x})
        mesh = make_mesh((4,), ("data",))
        tgt = NamedSharding(mesh, P("data"))
        tmpl = {"x": jnp.zeros(16, jnp.bfloat16),
                "y": jnp.zeros(16, jnp.bfloat16)}
        out, meta = ckpt.restore(str(tmp_path), tmpl,
                                 shardings={"x": tgt, "y": None})
        assert meta["dtypes"] == ["bfloat16", "bfloat16"]
        assert out["x"].dtype == jnp.bfloat16 and out["x"].sharding == tgt
        np.testing.assert_array_equal(np.asarray(out["x"], np.float32),
                                      np.asarray(x, np.float32))
        np.testing.assert_array_equal(np.asarray(out["y"], np.float32),
                                      np.asarray(x, np.float32))
        # bf16 on disk -> f32 template: bits recovered, then cast
        out32, _ = ckpt.restore(str(tmp_path),
                                {"x": jnp.zeros(16), "y": jnp.zeros(16)})
        np.testing.assert_array_equal(np.asarray(out32["x"]),
                                      np.asarray(x, np.float32))


class _Clock:
    def __init__(self):
        self.t = 0.0
        self.step_cost = 1.0

    def __call__(self):
        self.t += self.step_cost / 2
        return self.t


def _fail_at(step, exc):
    """A fail_injector that raises ``exc`` once, at ``step``."""
    fired = []

    def injector(s):
        if s == step and not fired:
            fired.append(s)
            raise exc
    return injector


class _Refused:
    """A jitted-step stand-in whose compile fails as the compiler's does."""

    def __init__(self, msg):
        self.msg = msg

    def lower(self, *args):
        return self

    def compile(self):
        raise jax.errors.JaxRuntimeError(self.msg)

    def __call__(self, *args):
        raise AssertionError("a step the compiler refused was run")


class TestTrainer:
    def _mk(self, tmp_path, total=8, ckpt_every=2):
        src = TokenSource(DataConfig(vocab_size=10, seq_len=4, global_batch=2))
        cfg = TrainerConfig(total_steps=total, ckpt_dir=str(tmp_path),
                            ckpt_every=ckpt_every, max_failures=3)
        state = {"w": jnp.zeros(())}

        def build_step():
            def step(params, opt, batch):
                w = params["w"] + jnp.sum(batch["tokens"]) * 0 + 1.0
                return {"w": w}, opt, {"loss": 1.0 / (w + 1)}
            return step

        def init_state():
            return dict(state), {"n": jnp.zeros(())}

        return Trainer(cfg, build_step, src, init_state, lambda b: {
            "tokens": jnp.asarray(b["tokens"])})

    def test_runs_to_completion(self, tmp_path):
        tr = self._mk(tmp_path)
        params, _ = tr.run()
        assert float(params["w"]) == 8.0
        assert ckpt.latest_step(str(tmp_path)) == 8

    def test_recovers_from_injected_failure(self, tmp_path):
        tr = self._mk(tmp_path)
        fired = {"n": 0}

        def injector(step):
            if step == 5 and fired["n"] == 0:
                fired["n"] = 1
                raise RuntimeError("simulated device failure")

        params, _ = tr.run(fail_injector=injector)
        assert tr.total_failures == 1
        assert tr.failures == 0  # consecutive counter decayed on recovery
        assert float(params["w"]) == 8.0  # deterministic replay -> same result

    _OOM = ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "17179869184 bytes.")
    _REFUSED = "INTERNAL: Mosaic failed to compile TPU kernel: block shape"

    @pytest.mark.parametrize("msg", [_OOM, _REFUSED])
    def test_oom_and_compile_errors_propagate_without_restore(
            self, tmp_path, msg):
        """An out-of-memory while the step runs, or a program the compiler
        refuses, is not a device fault: the first one raises, with no
        restore and no failure counted."""
        tr = self._mk(tmp_path)
        restores = []
        orig = tr._restore_or_init
        tr._restore_or_init = lambda *a: restores.append(a) or orig(*a)
        if msg == self._OOM:
            def injector(step):
                if step == 3:
                    raise jax.errors.JaxRuntimeError(msg)
        else:   # a rebuilt step the compiler refuses
            injector = _fail_at(3, RuntimeError("transient device fault"))
            plain = tr.build_step
            builds = []

            def build_step():
                builds.append(1)
                return plain() if len(builds) == 1 else _Refused(msg)
            tr.build_step = build_step
        with pytest.raises(jax.errors.JaxRuntimeError, match=msg[:12]):
            tr.run(fail_injector=injector)
        assert len(restores) == 1 + (msg == self._REFUSED)
        assert tr.total_failures == (msg == self._REFUSED)
        assert [h["step"] for h in tr.history] == [0, 1, 2]

    def test_invalid_argument_while_running_is_retried(self, tmp_path):
        """Only RESOURCE_EXHAUSTED is final among errors raised while a
        step runs: INVALID_ARGUMENT (what the runtime reports for a step
        that touches a buffer a failed step had donated) is restored and
        retried like any device fault."""
        tr = self._mk(tmp_path)
        tr.run(fail_injector=_fail_at(5, jax.errors.JaxRuntimeError(
            "INVALID_ARGUMENT: Buffer has been deleted or donated.")))
        assert tr.total_failures == 1
        assert [h["step"] for h in tr.history][-3:] == [5, 6, 7]

    def test_jitted_step_is_compiled_once_before_it_runs(self, tmp_path):
        """compile_ahead's executable is the one the jitted step runs: the
        loop compiles each built step once, and the call reuses it."""
        from jax._src import compiler

        tr = self._mk(tmp_path, total=3, ckpt_every=0)
        plain = tr.build_step
        tr.build_step = lambda: jax.jit(plain())
        seen = []
        orig = compiler.backend_compile_and_load

        def counting(*a, **k):
            seen.append(1)
            return orig(*a, **k)
        compiler.backend_compile_and_load = counting
        try:
            params, _ = tr.run()
        finally:
            compiler.backend_compile_and_load = orig
        assert float(params["w"]) == 3.0
        assert len(seen) == 1

    def test_failure_counter_decays_after_recovery(self, tmp_path):
        """Sporadic transient faults over a long run must not accumulate
        into max_failures — the consecutive counter resets once a
        post-recovery step commits."""
        tr = self._mk(tmp_path)
        tr.cfg.max_failures = 1
        fails = {s: 1 for s in (2, 5, 7)}  # 3 separate transient faults

        def injector(step):
            if fails.get(step):
                fails[step] = 0
                raise RuntimeError("transient fault")

        params, _ = tr.run(fail_injector=injector)   # must NOT raise
        assert tr.total_failures == 3
        assert tr.failures == 0
        assert float(params["w"]) == 8.0

    def test_consecutive_failures_still_give_up(self, tmp_path):
        """Decay must not defeat max_failures for a persistent fault."""
        tr = self._mk(tmp_path)
        tr.cfg.max_failures = 2
        with pytest.raises(RuntimeError):
            tr.run(fail_injector=lambda step: (_ for _ in ()).throw(
                RuntimeError("persistent")))
        assert tr.failures == 3  # never decayed: no step ever committed

    def test_transient_fault_is_not_a_replan(self, tmp_path):
        """A replan hook that returns the live step unchanged (intact
        mesh) must not be recorded as a re-plan nor reset the watchdog."""
        tr = self._mk(tmp_path)
        step_fn = tr.build_step()
        tr.replan = lambda: (step_fn, None)
        tr.build_step = lambda: step_fn
        fired = {"n": 0}

        def injector(step):
            if step == 5 and fired["n"] == 0:
                fired["n"] = 1
                raise RuntimeError("transient fault, pool intact")

        tr.watchdog.ema = 123.0  # sentinel: must survive the recovery
        tr.run(fail_injector=injector)
        assert tr.replans == []
        assert tr.total_failures == 1

    def test_restore_threads_shardings(self, tmp_path, devices8):
        """_restore_or_init passes the current plan's shardings into
        ckpt.restore, so resumed state lands sharded, not replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = make_mesh((4,), ("data",))
        tgt = NamedSharding(mesh, P("data"))
        ckpt.save(str(tmp_path), 3, ({"w": jnp.arange(8.0)}, {}))
        src = TokenSource(DataConfig(vocab_size=10, seq_len=4, global_batch=2))
        tr = Trainer(
            TrainerConfig(total_steps=3, ckpt_dir=str(tmp_path)),
            build_step=lambda: None, source=src,
            init_state=lambda: ({"w": jnp.zeros(8)}, {}),
            put_batch=lambda b: b,
            restore_shardings=lambda: ({"w": tgt}, {}))
        params, _, step = tr._restore_or_init()
        assert step == 3
        assert params["w"].sharding == tgt
        np.testing.assert_array_equal(np.asarray(params["w"]), np.arange(8.0))

    def test_gives_up_after_max_failures(self, tmp_path):
        tr = self._mk(tmp_path)
        tr.cfg.max_failures = 1

        def injector(step):
            raise RuntimeError("persistent failure")

        with pytest.raises(RuntimeError):
            tr.run(fail_injector=injector)


class TestStragglerWatchdog:
    def test_flags_slow_step(self):
        wd = StragglerWatchdog(factor=3.0, beta=0.5)
        for _ in range(5):
            assert not wd.observe(0, 1.0)
        assert wd.observe(5, 10.0)       # 10x the EMA
        assert wd.events and wd.events[0][0] == 5

    def test_outliers_do_not_poison_ema(self):
        wd = StragglerWatchdog(factor=3.0, beta=0.5)
        for _ in range(5):
            wd.observe(0, 1.0)
        wd.observe(5, 100.0)
        assert wd.ema == pytest.approx(1.0, rel=0.01)

    def test_reset_forgets_ema_keeps_events(self):
        wd = StragglerWatchdog(factor=3.0, beta=0.5)
        for _ in range(5):
            wd.observe(0, 1.0)
        wd.observe(5, 10.0)
        assert wd.events
        wd.reset()
        assert wd.ema is None and wd.events
        # the first post-reset step re-seeds the EMA instead of being
        # judged against the old mesh's timing
        assert not wd.observe(6, 5.0)
        assert wd.ema == 5.0

    def test_replan_resets_watchdog(self, tmp_path):
        """Slower steps on the surviving mesh must not be flagged against
        the pre-failure EMA (nor skew it) after an elastic re-plan."""
        src = TokenSource(DataConfig(vocab_size=10, seq_len=4, global_batch=2))
        cfg = TrainerConfig(total_steps=8, ckpt_dir=str(tmp_path),
                            ckpt_every=2, max_failures=2,
                            straggler_factor=3.0)
        clock = _Clock()
        fired = {"n": 0}

        def injector(step):
            if step == 4 and fired["n"] == 0:
                fired["n"] = 1
                clock.step_cost = 10.0   # surviving mesh is 10x slower
                raise RuntimeError("device loss")

        def build_step():
            def step(params, opt, batch):
                return {"w": params["w"] + 1}, opt, {"loss": 0.0}
            return step

        hooks = []
        tr = Trainer(cfg, build_step, src,
                     lambda: ({"w": jnp.zeros(())}, {}),
                     lambda b: b, mitigation_hook=hooks.append,
                     time_fn=clock, replan=build_step)
        tr.run(fail_injector=injector)
        assert tr.replans == [4]
        assert not hooks and not tr.watchdog.events, \
            "post-replan steps falsely flagged as stragglers"
        assert tr.watchdog.ema == pytest.approx(5.0), \
            "EMA must be re-seeded from surviving-mesh timings"

    def test_trainer_fires_mitigation_hook(self, tmp_path):
        src = TokenSource(DataConfig(vocab_size=10, seq_len=4, global_batch=2))
        cfg = TrainerConfig(total_steps=8, ckpt_dir=str(tmp_path), ckpt_every=100)
        clock = _Clock()
        hooks = []

        def build_step():
            def step(params, opt, batch):
                if int(params["w"]) == 5:
                    clock.step_cost = 50.0   # one slow step
                else:
                    clock.step_cost = 1.0
                return {"w": params["w"] + 1}, opt, {"loss": 0.0}
            return step

        tr = Trainer(cfg, build_step, src,
                     lambda: ({"w": jnp.zeros(())}, {}),
                     lambda b: b, mitigation_hook=hooks.append,
                     time_fn=clock)
        tr.run()
        assert hooks, "straggler mitigation hook should have fired"


# ---------------------------------------------------------------------------
# Elastic restart done right (PR 4): plan-independent zero1 checkpoints,
# surviving-mesh recalibration, and the failure -> shrink -> reshard loop.
# ---------------------------------------------------------------------------

from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.atp import make_context  # noqa: E402
from repro.core.calibrate import (CalibEntry, CalibrationTable,  # noqa: E402
                                  recalibrate_surviving, surviving_tp)
from repro.core.mesh import MeshTopo, atp_topo  # noqa: E402
from repro.core.plan import ParallelPlan, replan_elastic  # noqa: E402
from repro.optim import adamw  # noqa: E402


def _fake_entry(d1, d2):
    return CalibEntry(b1=10.0 * d1, b2=5.0 * d2, t_psum=1e-5, t_ring=2e-5,
                      alpha_s=1e-6)


class TestZero1CheckpointLayout:
    """zero1 state is checkpointed param-shaped; rebank restores the
    runtime layout on ANY plan (the (d1,d2)-change reshard path)."""

    PARAMS = {"W": jnp.arange(128.0).reshape(8, 16),
              "b": jnp.arange(16.0),
              "r": jnp.arange(24.0).reshape(4, 6)}  # TP-replicated leaf
    SPECS = {"W": P(None, "tp1"), "b": P("tp1"), "r": P(None, None)}

    def _rand_canonical(self, seed=0):
        rng = np.random.RandomState(seed)
        leaves = {k: {"m": rng.rand(*v.shape).astype(np.float32),
                      "v": rng.rand(*v.shape).astype(np.float32)}
                  for k, v in self.PARAMS.items()}
        return {"step": jnp.int32(7), "leaves": leaves}

    def test_rebank_unbank_round_trip_same_plan(self, devices8):
        ctx = make_context(atp_topo(2, 2, 1))
        canon = self._rand_canonical()
        banked = adamw.rebank_opt_state(self.PARAMS, canon, self.SPECS, ctx)
        assert banked["leaves"]["W"]["m"].shape == (2, 2, 32)  # [dp,tp,k]
        back = adamw.unbank_opt_state(self.PARAMS, banked, self.SPECS, ctx)
        for k in self.PARAMS:
            np.testing.assert_array_equal(back["leaves"][k]["m"],
                                          canon["leaves"][k]["m"])
            np.testing.assert_array_equal(back["leaves"][k]["v"],
                                          canon["leaves"][k]["v"])

    def test_rebank_across_plans_preserves_moments(self, devices8):
        """canonical -> bank on (dp=2, tp1=2) -> unbank -> bank on
        (dp=4, tp1=1)... every layout reads back the same moments."""
        canon = self._rand_canonical()
        specs_b = {"W": P(None, None), "b": P(None), "r": P(None, None)}
        for topo, specs in [(atp_topo(2, 2, 1), self.SPECS),
                            (atp_topo(4, 1, 1), specs_b),
                            (atp_topo(2, 1, 2),
                             {"W": P(None, "tp2"), "b": P("tp2"),
                              "r": P(None, None)})]:
            ctx = make_context(topo)
            banked = adamw.rebank_opt_state(self.PARAMS, canon, specs, ctx)
            back = adamw.unbank_opt_state(self.PARAMS, banked, specs, ctx)
            for k in self.PARAMS:
                np.testing.assert_array_equal(
                    back["leaves"][k]["m"], canon["leaves"][k]["m"],
                    err_msg=f"{topo} leaf {k}")

    def test_unbank_matches_plain_state_after_training(self, devices8):
        """The canonical view of trained zero1 state equals the plain-mode
        state for the same trajectory (moments preserved exactly where the
        parity test pins the updates)."""
        from jax import shard_map
        topo = MeshTopo((("data", 4), ("tp1", 2)))
        mesh = topo.build(jax.devices()[:8])
        ctx = make_context(topo)
        X = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        Y = jax.random.normal(jax.random.PRNGKey(2), (16, 16))
        W = jax.random.normal(jax.random.PRNGKey(0), (8, 16)) * 0.1
        params = {"W": W, "b": jnp.zeros((16,))}
        pspecs = {"W": P(None, "tp1"), "b": P("tp1")}
        states = {}
        for mode in ("plain", "zero1"):
            cfg = adamw.AdamWConfig(lr=1e-2, mode=mode, warmup_steps=1,
                                    total_steps=100)
            opt = adamw.init_opt_state(params, pspecs, ctx, mode)
            ospecs = adamw.opt_state_specs(pspecs, ctx, mode)
            rep = adamw.replication_factors(pspecs, ctx)

            def step(p, o, X, Y):
                def loss(q):
                    pred = X @ q["W"] + q["b"]
                    return jax.lax.psum(jnp.sum((pred - Y) ** 2),
                                        ("data", "tp1"))
                _, g = jax.value_and_grad(loss)(p)
                np_, no_, _ = adamw.apply_adamw(cfg, ctx, p, g, o, rep)
                return np_, no_

            f = jax.jit(shard_map(step, mesh=mesh,
                                  in_specs=(pspecs, ospecs,
                                            P("data", None), P("data", "tp1")),
                                  out_specs=(pspecs, ospecs),
                                  check_vma=True))
            p, o = params, opt
            for _ in range(3):
                p, o = f(p, o, X, Y)
            states[mode] = (p, o)
        canon = adamw.unbank_opt_state(states["zero1"][0], states["zero1"][1],
                                       pspecs, ctx, "zero1")
        for k in ("W", "b"):
            np.testing.assert_allclose(
                np.asarray(canon["leaves"][k]["m"]),
                np.asarray(states["plain"][1]["leaves"][k]["m"]),
                rtol=1e-5, atol=1e-6, err_msg=f"m[{k}]")

    def test_zero1_without_dp_mirrors_params(self, devices8):
        """mode=zero1 with no data-parallel axis takes apply_adamw's
        full-state path, so the state must mirror the params (banking it
        crashed the elastic shrink-to-dp=1 recovery)."""
        ctx = make_context(atp_topo(1, 2, 1))
        opt = adamw.init_opt_state(self.PARAMS, self.SPECS, ctx, "zero1")
        assert opt["leaves"]["W"]["m"].shape == (8, 16)
        specs = adamw.opt_state_specs(self.SPECS, ctx, "zero1")
        assert specs["leaves"]["W"]["m"] == P(None, "tp1")
        # and the codec is the identity there
        assert adamw.unbank_opt_state(self.PARAMS, opt, self.SPECS,
                                      ctx, "zero1") is opt


class TestRecalibrateSurviving:
    def _plan(self):
        tab = CalibrationTable.from_pairs(
            {(2, 2): (1.0, 2.0), (4, 1): (0.5, 0.5)}, source="unit")
        return ParallelPlan(d1=2, d2=2, dp=2, topology="ic3",
                            calibration=tab)

    def test_surviving_tp_halves_until_fit(self):
        assert surviving_tp(8, 8) == 8
        assert surviving_tp(8, 5) == 4
        assert surviving_tp(8, 2) == 2
        assert surviving_tp(4, 1) == 1
        with pytest.raises(ValueError):
            surviving_tp(4, 0)

    def test_covers_tp(self):
        tab = CalibrationTable.from_pairs({(2, 2): (1.0, 2.0)})
        assert tab.covers_tp(4) and not tab.covers_tp(2)

    def test_recalibrate_merges_and_clears_stale(self):
        plan = self._plan()
        stale = replan_elastic(plan, 2)          # tp 4 -> 2: tagged stale
        assert stale.calibration_stale
        fresh = recalibrate_surviving(stale, devices=list(range(2)),
                                      measure=_fake_entry)
        assert not fresh.calibration_stale
        assert fresh.calibration.covers_tp(2)    # fresh surviving entries
        assert fresh.calibration.get(2, 2) is not None  # old keys kept
        assert any(k == "calibration" and v.startswith("recalibrated")
                   for k, v in fresh.provenance)

    def test_recalibrated_replan_is_not_stale(self):
        """The complete loop: shrink -> recalibrate -> re-plan carries a
        fresh table and no stale tag (the acceptance criterion)."""
        plan = self._plan()
        fresh = recalibrate_surviving(plan, devices=list(range(2)),
                                      measure=_fake_entry)
        new = replan_elastic(fresh, 2)
        assert new.tp == 2
        assert not new.calibration_stale
        assert new.calibration.covers_tp(2)

    def test_unrecalibrated_replan_still_stale(self):
        new = replan_elastic(self._plan(), 2)
        assert new.tp == 2 and new.calibration_stale

    def test_fresh_measurements_override_old_keys(self):
        plan = self._plan().with_(d1=4, d2=1, dp=1)  # tp=4 on 4 devices
        fresh = recalibrate_surviving(plan, devices=list(range(4)),
                                      measure=_fake_entry)
        # same tp: the (2,2)/(4,1) keys are re-measured, new values win
        assert fresh.calibration.get(2, 2).b1 == pytest.approx(20.0)
        assert fresh.calibration.get(4, 1).b1 == pytest.approx(40.0)


class TestElasticReshardRoundTrip:
    def test_failure_shrink_reshard_round_trip(self, tmp_path, devices8):
        """End-to-end: fail at step 3, lose 4 of 8 devices, recover under
        a re-searched plan across a (d1,d2) change with the checkpoint
        re-banked + resharded, and match the uninterrupted trajectory."""
        from repro.configs.base import ModelConfig
        from repro.launch.train import make_elastic_trainer
        from repro.runtime.trainer import TrainerConfig

        # num_heads must cover the initial tp=4 (fewer heads than TP
        # ranks takes a padded attention path whose loss is not
        # factorization-invariant — not this test's subject)
        cfg = ModelConfig(name="rt", family="dense", num_layers=1,
                          d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                          vocab_size=64, head_dim=16, dtype="float32")
        plan = ParallelPlan(
            d1=2, d2=2, dp=2, topology="ic3",
            calibration=CalibrationTable.from_pairs({(2, 2): (1.0, 1.0)},
                                                    source="unit"))

        def one_run(ckpt_dir, shrink):
            pool = {"n": 8}
            fired = {"n": 0}

            def injector(step):
                if shrink and step == 3 and fired["n"] == 0:
                    fired["n"] = 1
                    pool["n"] = 2  # dp absorbs 8->4; 2 forces a TP change
                    raise RuntimeError("injected device loss")

            src = TokenSource(DataConfig(vocab_size=cfg.vocab_size,
                                         seq_len=16, global_batch=4))
            trainer, live = make_elastic_trainer(
                cfg, plan,
                adamw.AdamWConfig(lr=1e-3, mode="zero1", total_steps=5),
                TrainerConfig(total_steps=5, ckpt_dir=str(ckpt_dir),
                              ckpt_every=2, max_failures=2),
                src, batch=4, seq=16,
                devices_fn=lambda: jax.devices()[: pool["n"]],
                measure=_fake_entry)
            params, _ = trainer.run(fail_injector=injector)
            return trainer, live, params, \
                {h["step"]: h["loss"] for h in trainer.history}

        _, _, _, base = one_run(tmp_path / "base", shrink=False)
        tr, live, params, elas = one_run(tmp_path / "elastic", shrink=True)

        new_plan = live["plan"]
        assert tr.replans == [3]
        assert new_plan.tp == 2 and (new_plan.d1, new_plan.d2) != (2, 2)
        assert not new_plan.calibration_stale
        assert new_plan.calibration.covers_tp(2)
        # restored + trained state carries the new plan's shardings
        inf = live["info"]
        want = jax.tree.leaves(inf.sharding(inf.pspecs))
        for got, w in zip(jax.tree.leaves(params), want):
            assert got.sharding == w
        # loss continuity: deterministic replay across the (d1,d2) change
        for s, l in base.items():
            assert abs(elas[s] - l) <= 5e-4 * max(1.0, abs(l)), \
                f"step {s}: {elas[s]} vs {l}"

    def test_dead_mesh_device_with_spares_triggers_rebuild(self, tmp_path,
                                                           devices8):
        """'Intact' is membership, not head-count: losing a device the
        live mesh runs on must rebuild onto the spares even when the pool
        is still large enough."""
        from repro.configs.base import ModelConfig
        from repro.launch.train import make_elastic_trainer
        from repro.runtime.trainer import TrainerConfig

        cfg = ModelConfig(name="mb", family="dense", num_layers=1,
                          d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                          vocab_size=64, head_dim=16, dtype="float32")
        plan = ParallelPlan(d1=2, d2=2, dp=1, topology="ic3")
        pool = {"lo": 0}
        fired = {"n": 0}

        def injector(step):
            if step == 1 and fired["n"] == 0:
                fired["n"] = 1
                pool["lo"] = 1   # device 0 (in the live mesh) died
                raise RuntimeError("device 0 lost")

        src = TokenSource(DataConfig(vocab_size=cfg.vocab_size,
                                     seq_len=16, global_batch=4))
        trainer, live = make_elastic_trainer(
            cfg, plan,
            adamw.AdamWConfig(lr=1e-3, mode="zero1", total_steps=3),
            TrainerConfig(total_steps=3, ckpt_dir=str(tmp_path),
                          ckpt_every=1, max_failures=2),
            src, batch=4, seq=16,
            devices_fn=lambda: jax.devices()[pool["lo"]:],
            recalibrate=False)
        trainer.run(fail_injector=injector)
        assert trainer.replans == [1]        # rebuilt despite 7 >= 4
        assert live["plan"].tp == 4          # strategy itself unchanged
        used = {d.id for d in live["info"].mesh.devices.flat}
        assert 0 not in used, "rebuilt mesh must avoid the dead device"

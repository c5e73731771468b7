"""Job ``train``: the program's fault-tolerant train loop, timed.

One ``Trainer`` (``launch.train.make_elastic_trainer``) is built with the
cell's plan, its weights drawn by the benchmark from the seed, and runs
one ``Trainer.run`` from set-up through the window: the first
``compare_steps + 1`` steps are set-up (they compile and warm up, and
feed the correctness comparison), then the window runs steps until
``--seconds`` have passed.  The window closes at a step boundary; every
step ends in the loop's host read of the loss, so each step counted has
finished.

Correctness: the reference (``bench.reference``) runs the first
``compare_steps`` steps in float32 on the same weights and batches, and
three numbers are read against the program's, and those the cell's
``limits`` name are compared: the largest gap between the step losses;
the worst per-layer gap between the norms of the first
clipped gradient (the program's read from AdamW's first moment after one
step, ``m / (1 - b1)``); and the worst per-layer gap between the norms of
the weights' change over the compared steps (read from the weights step
``compare_steps`` is given).  A per-layer gap is measured against the
larger of that layer's reference norm and the median one.  Layers whose
reference gradient is under a thousandth of the median are left out of
the change: they move by round-off alone.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from bench.model import Sizes, make_weights, program_config, seed_key
from bench.traffic import TrainTokens


class WindowClosed(Exception):
    """Ends ``Trainer.run`` at the window's close (not a RuntimeError, so
    the loop's fault handling lets it through)."""


def worst_gap(prog: dict, ref: dict, keep: dict | None = None):
    """(worst per-layer |prog - ref| / max(ref, median ref), its leaf)."""
    med = float(np.median(np.concatenate([np.ravel(v) for v in ref.values()])))
    worst, where = 0.0, ""
    for name, r in ref.items():
        p = np.asarray(prog[name], np.float64)
        r = np.asarray(r, np.float64)
        gap = np.abs(p - r) / np.maximum(r, med)
        if keep is not None:
            gap = np.where(keep[name], gap, 0.0)
        i = int(np.argmax(gap))
        if gap[i] > worst:
            worst, where = float(gap[i]), f"{name}[{i}]"
    return worst, where


def moved(ref_grad: dict) -> dict:
    """Per-layer mask of the layers the reference's gradient moves: those
    at or over a thousandth of the median layer's gradient norm."""
    med = float(np.median(np.concatenate([np.ravel(v)
                                          for v in ref_grad.values()])))
    return {k: np.asarray(v) >= 1e-3 * med for k, v in ref_grad.items()}


def compare(prog: dict, ref: dict) -> list[tuple[str, float, str]]:
    """(name, value, where) of the three numbers compared."""
    loss = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    grad, gw = worst_gap(prog["grad"], ref["grad"])
    change, cw = worst_gap(prog["change"], ref["change"], moved(ref["grad"]))
    return [("loss_gap", loss, "max over steps"), ("grad_gap", grad, gw),
            ("change_gap", change, cw)]


def build_plan(spec, chips: int, cfg, batch: int, seq: int):
    """The cell's plan: ``"search"`` takes what ATP's search returns for
    this job on ``chips`` chips; otherwise a fixed ``{d1, d2, dp}``."""
    from repro.core.plan import ParallelPlan
    from repro.launch.train import pick_plan

    if spec == "search":
        return pick_plan(cfg, chips, seq, batch).best
    return ParallelPlan(d1=spec["d1"], d2=spec["d2"], dp=spec["dp"],
                        provenance=(("searcher", "benchmark"),))


def run(cell, profile=None):
    import jax

    from bench import flops
    from bench.reference import per_layer_norms, train_reference, weights
    from bench.run import JobResult, log, memory_peak
    from repro.launch.train import make_elastic_trainer
    from repro.optim import adamw
    from repro.runtime.membership import SingleObserverMembership
    from repro.runtime.trainer import TrainerConfig

    w = cell.workload
    sizes = Sizes.from_file(cell.config)
    cfg = program_config(cell.config)
    batch, seq = cell.traffic["batch"], cell.traffic["seq"]
    compare_steps = w["compare_steps"]
    opt = w["optimizer"]
    plan = build_plan(w["plan"], cell.chips, cfg, batch, seq)
    log(f"plan {plan.describe()}; predicted "
        f"{plan.predicted.to_dict() if plan.predicted else None}")
    log(flops.describe(sizes, seq))
    opt_cfg = adamw.AdamWConfig(**opt)
    source = TrainTokens(cell.traffic, cell.seed, sizes.vocab)

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")   # ckpt_every=0: unused
    trainer, live = make_elastic_trainer(
        cfg, plan, opt_cfg,
        TrainerConfig(total_steps=10**9, ckpt_dir=ckpt_dir, ckpt_every=0,
                      log_every=10**9),
        source, batch=batch, seq=seq,
        membership=SingleObserverMembership(lambda: list(cell.devices)))
    info = live["info"]
    key = seed_key(cell.seed)
    shard = info.sharding(info.pspecs)

    def init_state():
        params = make_weights(sizes, key, out_shardings=shard)
        state = jax.jit(
            lambda p: adamw.init_opt_state(p, info.pspecs, info.ctx,
                                           opt_cfg.mode),
            out_shardings=info.sharding(info.ospecs))(params)
        return params, state

    trainer.init_state = init_state

    # the program's side of the comparison, read from the state the loop
    # hands the step: the first moment after one step, the weights after
    # compare_steps
    prog = {"grad": None, "change": None}
    norms = jax.jit(per_layer_norms)
    change = jax.jit(lambda p, p0: per_layer_norms(
        jax.tree.map(lambda a, b: a.astype("float32") - b.astype("float32"),
                     p, p0)))
    step = live["step"]
    calls = [0]

    def stepped(params, state, batch_):
        k = calls[0]
        if k == 1:
            m = jax.tree.map(lambda d: d["m"], state["leaves"],
                             is_leaf=lambda d: isinstance(d, dict) and "m" in d)
            prog["grad"] = {n: v / (1 - opt["b1"])
                            for n, v in jax.device_get(norms(m)).items()}
        if k == compare_steps:
            prog["change"] = jax.device_get(
                change(params, make_weights(sizes, key, out_shardings=shard)))
        calls[0] += 1
        with jax.profiler.TraceAnnotation("train.step"):
            return step(params, state, batch_)

    if hasattr(step, "lower"):       # the loop compiles ahead through it
        stepped.lower = step.lower
    live["step"] = stepped

    put = trainer.put_batch

    def put_batch(b):
        with jax.profiler.TraceAnnotation("train.put"):
            return put(b)

    trainer.put_batch = put_batch

    warm = compare_steps + 1
    trace_steps = w["trace_steps"]
    clock = {}

    class Windowed:
        """The loop's source: opens the window at step ``warm``, closes it
        at the first step boundary past ``--seconds``, and runs the traced
        sub-window over ``trace_steps`` steps from the window's second."""

        def global_batch(self, k):
            now = time.perf_counter()
            if k == warm:
                clock["open"] = now
            elif k > warm and now >= clock["open"] + cell.seconds:
                clock["close"], clock["steps"] = now, k - warm
                raise WindowClosed
            if profile is not None:
                if k == warm + 1:
                    profile.start()
                elif k == warm + 1 + trace_steps:
                    profile.stop()
                    clock["traced"] = trace_steps
            with jax.profiler.TraceAnnotation("train.batch"):
                return source.global_batch(k)

    trainer.source = Windowed()
    try:
        trainer.run()
    except WindowClosed:
        pass
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    if profile is not None and "traced" not in clock:
        raise RuntimeError(f"the window ended before {trace_steps} traced "
                           f"steps: give --seconds more room")
    peak = memory_peak(cell.devices)
    losses = [h["loss"] for h in trainer.history[:compare_steps]]
    failed = trainer.total_failures
    del trainer, live, step, stepped
    gc.collect()

    steps = clock["steps"]
    window_s = clock["close"] - clock["open"]
    tokens = steps * batch * seq
    e2e = {"train_tokens_per_s": tokens / window_s,
           "setup_s": clock["open"] - cell.t_start}
    pred = plan.predicted
    log(f"window {window_s:.3f} s, {steps} steps, {tokens} tokens; set-up "
        f"{e2e['setup_s']:.3f} s; step {window_s / steps:.4f} s measured, "
        f"{pred.t_gemm + pred.t_exposed if pred else None} s predicted "
        f"(t_gemm + t_exposed)")

    t0 = time.perf_counter()
    ref = train_reference(sizes, weights(sizes, key, cell.devices),
                          [source.global_batch(k) for k in range(compare_steps)],
                          opt)
    prog["losses"] = losses
    found = compare(prog, ref)
    log(f"reference {time.perf_counter() - t0:.1f} s; losses program "
        f"{losses} reference {ref['losses']}")
    for name, v, where in found:
        log(f"{name} {v!r} at {where}")
    lim = w["limits"]
    counters = {"flops": flops.train_flops_per_token(sizes, seq)
                * clock.get("traced", 0) * batch * seq}
    return JobResult(
        attempted=steps, failed=failed, e2e=e2e,
        checks=[(n, v, lim[n]) for n, v, _ in found if n in lim],
        memory_peak_bytes=peak, counters=counters,
        trace_dir=str(profile.logdir) if profile is not None else None)

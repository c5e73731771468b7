"""Fault-tolerant training loop: checkpoint/restart, failure recovery,
straggler watchdog, deterministic data replay.

Hardware failures on a real pod surface as raised exceptions from the jit'd
step (XLA device errors).  The loop's contract:

  - every step is a pure function of (params, opt_state, batch(step))
  - batches are pure functions of (seed, step)   -> replay is exact
  - on failure: restore last committed checkpoint, rebuild the step
    (possibly on a new mesh — elastic), continue from ckpt step.  An
    out-of-memory or a program the compiler refused is not a device
    fault: a retry would fail the same way, so it propagates at once.
    Each built step is compiled before it first runs, so compile errors
    are told apart by where they are raised, not by their text
  - stragglers: per-step wall time is tracked with an EMA; a step slower
    than `straggler_factor` x EMA fires the mitigation hook (on a real
    cluster: re-shard away from the slow host / preemptively checkpoint)

Each phase of a step runs inside a profiler span ``train.loop.<phase>``
(``step=`` in its arguments): fetch, transfer, compile, dispatch,
loss_read, bookkeep, checkpoint, recover; ``train.loop.recompile`` marks
a dispatch that compiled.  With the profiler off a span costs about a
microsecond.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable

import jax

from repro.checkpoint import manager as ckpt
from repro.data.pipeline import TokenSource
from repro.launch.compile_cache import compiles, dispatch_span

log = logging.getLogger("repro.trainer")
span = jax.profiler.TraceAnnotation


def is_device_fault(e: Exception) -> bool:
    """Whether a step that raised ``e`` while running is worth a restore
    and a retry.  Out of memory (status ``RESOURCE_EXHAUSTED``) is not: the
    same program fails the same way again.  Compile errors never reach
    here: :func:`compile_ahead` raises them before the step runs."""
    return not (isinstance(e, jax.errors.JaxRuntimeError)
                and str(e).startswith("RESOURCE_EXHAUSTED"))


def compile_ahead(step_fn, *args) -> None:
    """Compile a jitted step for ``args`` before its first call, so an
    error of the compiler surfaces here and not as a failed step.  The
    jitted function reuses the executable on its next call with the same
    argument types.  Plain Python steps (no ``lower``) are left alone."""
    lower = getattr(step_fn, "lower", None)
    if lower is not None:
        lower(*args).compile()


class _CompileError(Exception):
    """Carries a compile error past the step's fault handler."""


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: str  # no default: a shared path would resume a stale run
    total_steps: int = 100
    ckpt_every: int = 50   # 0: never checkpoint (a run nothing resumes)
    keep_ckpts: int = 3
    max_failures: int = 3
    straggler_factor: float = 3.0
    ema_beta: float = 0.9
    log_every: int = 10


@dataclasses.dataclass
class StragglerWatchdog:
    factor: float = 3.0
    beta: float = 0.9
    ema: float | None = None
    events: list = dataclasses.field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        """Returns True if this step is flagged as a straggler."""
        if self.ema is None:
            self.ema = dt
            return False
        flagged = dt > self.factor * self.ema and self.ema > 0
        if flagged:
            self.events.append((step, dt, self.ema))
        else:
            # only fold non-outlier steps into the EMA
            self.ema = self.beta * self.ema + (1 - self.beta) * dt
        return flagged

    def reset(self):
        """Forget the EMA (keep the event log).

        Must be called when the per-step cost legitimately changes — e.g.
        an elastic re-plan onto a smaller/slower surviving mesh — or every
        first step on the new mesh is falsely flagged against the old
        mesh's EMA (and, flagged or not, the old EMA skews forever).
        """
        self.ema = None


class Trainer:
    def __init__(
        self,
        cfg: TrainerConfig,
        build_step: Callable[[], Callable],  # returns jitted train_step
        source: TokenSource,
        init_state: Callable[[], tuple[Any, Any]],  # -> (params, opt_state)
        put_batch: Callable[[dict], Any],    # host batch -> device arrays
        mitigation_hook: Callable[[int], None] | None = None,
        time_fn: Callable[[], float] = time.monotonic,
        replan: Callable[[], Any] | None = None,
        restore_shardings: Callable[[], Any] | None = None,
        encode_ckpt: Callable[[Any, Any], Any] | None = None,
        decode_ckpt: Callable[[Any], tuple[Any, Any]] | None = None,
        ckpt_template: Callable[[], Any] | None = None,
    ):
        self.cfg = cfg
        self.build_step = build_step
        self.source = source
        self.init_state = init_state
        self.put_batch = put_batch
        self.watchdog = StragglerWatchdog(cfg.straggler_factor, cfg.ema_beta)
        self.mitigation_hook = mitigation_hook or (lambda step: None)
        self.time_fn = time_fn
        # elastic recovery: re-derive the ParallelPlan on the surviving mesh
        # and return a fresh step built from it (launch.train wires
        # plan.replan_elastic here); None keeps the rebuild-same-plan path.
        # The hook may return either a step, or (step, restore_shardings):
        # the sharding tree places the restored checkpoint directly onto
        # the re-planned mesh instead of replicated on the default device.
        self.replan = replan
        # current-plan sharding provider for every checkpoint restore
        # (resume-at-start included) — a zero-arg callable returning the
        # sharding tree of the CHECKPOINTED (encoded) state, or None for
        # host placement.
        self.restore_shardings = restore_shardings
        # state <-> checkpoint-tree codec.  encode maps (params, opt) to
        # the tree written to disk; decode inverts it after restore.
        # launch.train uses these to checkpoint the zero1 optimizer state
        # in its plan-independent param-shaped layout, so a restart can
        # re-bank it onto ANY surviving (d1, d2, dp) — without a codec the
        # raw (plan-dependent) state is written as-is.
        self.encode_ckpt = encode_ckpt or (lambda params, opt: (params, opt))
        self.decode_ckpt = decode_ckpt or (lambda tree: tree)
        # optional abstract (shape/dtype-only) view of the encoded tree:
        # restore only reads shapes and dtypes from its template, so this
        # avoids materializing (and device-placing) throwaway state on
        # every restore.  Fallback: encode a real init_state().
        self.ckpt_template = ckpt_template
        self.failures = 0        # consecutive: decays once recovery sticks
        self.total_failures = 0  # lifetime count (reporting only)
        self.replans: list[int] = []  # steps at which a re-plan happened
        self.history: list[dict] = []
        self._recovering = False

    @property
    def compiles(self) -> int:
        """Executables the process has built or loaded
        (:func:`repro.launch.compile_cache.compiles`): it should stop
        rising once the first step has run."""
        return compiles()

    def _restore_or_init(self, shardings=None):
        step = ckpt.latest_step(self.cfg.ckpt_dir)
        if step is None:
            params, opt_state = self.init_state()
            return params, opt_state, 0
        template = (self.ckpt_template() if self.ckpt_template is not None
                    else self.encode_ckpt(*self.init_state()))
        if shardings is None and self.restore_shardings is not None:
            shardings = self.restore_shardings()
        tree, meta = ckpt.restore(self.cfg.ckpt_dir, template,
                                  shardings=shardings)
        params, opt_state = self.decode_ckpt(tree)
        log.info("restored checkpoint at step %d%s", meta["step"],
                 " (resharded)" if shardings is not None else "")
        return params, opt_state, meta["step"]

    def _checkpoint(self, step: int, params, opt_state):
        """Save + prune under the SAME consecutive-failure budget as the
        train step.  A torn write (fail-injected OSError, device error
        while materializing leaves) used to escape ``run``'s guard and
        kill the job even though ``ckpt.save`` is atomic (tmp + rename:
        the committed checkpoint set is never corrupted, only the attempt
        is lost).  Here each failed attempt is counted, its orphan tmp is
        swept, and the save retries immediately — same-process retry is
        correct because the state being written is host-reachable and
        committed checkpoints are untouched.  Budget exhaustion raises,
        exactly like a step that cannot recover.
        """
        while True:
            try:
                ckpt.save(self.cfg.ckpt_dir, step,
                          self.encode_ckpt(params, opt_state))
                ckpt.prune(self.cfg.ckpt_dir, self.cfg.keep_ckpts)
                return
            except (RuntimeError, OSError,
                    jax.errors.JaxRuntimeError) as e:
                self.failures += 1
                self.total_failures += 1
                log.error("checkpoint at step %d failed (%s); retrying "
                          "(%d/%d)", step, e, self.failures,
                          self.cfg.max_failures)
                if self.failures > self.cfg.max_failures:
                    raise
                swept = ckpt.sweep_orphan_tmps(self.cfg.ckpt_dir)
                if swept:
                    log.info("swept %d torn checkpoint tmp(s)", swept)
                # like step recovery, the budget only decays once the
                # NEXT train step commits — a flapping disk still trips
                # max_failures
                self._recovering = True

    def run(self, fail_injector: Callable[[int], None] | None = None):
        train_step = self.build_step()
        params, opt_state, start = self._restore_or_init()
        step = start
        compiled = None     # the step compile_ahead last ran for
        while step < self.cfg.total_steps:
            try:
                if fail_injector is not None:
                    fail_injector(step)
                with span("train.loop.fetch", step=step):
                    host_batch = self.source.global_batch(step)
                with span("train.loop.transfer", step=step):
                    batch = self.put_batch(host_batch)
                if compiled is not train_step:
                    with span("train.loop.compile", step=step):
                        try:
                            compile_ahead(train_step, params, opt_state, batch)
                        except Exception as e:  # refused by the compiler
                            raise _CompileError() from e
                    compiled = train_step
                t0 = self.time_fn()
                with dispatch_span("train.loop.dispatch",
                                   "train.loop.recompile", step=step):
                    params, opt_state, metrics = train_step(
                        params, opt_state, batch)
                with span("train.loop.loss_read", step=step):
                    loss = float(metrics["loss"])
                dt = self.time_fn() - t0
                with span("train.loop.bookkeep", step=step):
                    self._bookkeep(step, loss, dt)
            except _CompileError as e:
                raise e.__cause__ from None
            except RuntimeError as e:  # JaxRuntimeError included
                if not is_device_fault(e):
                    raise
                self.failures += 1
                self.total_failures += 1
                log.error("step %d failed (%s); recovering (%d/%d)",
                          step, e, self.failures, self.cfg.max_failures)
                if self.failures > self.cfg.max_failures:
                    raise
                with span("train.loop.recover", step=step):
                    train_step, params, opt_state, step = self._recover(
                        step, train_step)
                continue
            step += 1
            every = self.cfg.ckpt_every
            if every and (step % every == 0 or step == self.cfg.total_steps):
                with span("train.loop.checkpoint", step=step):
                    self._checkpoint(step, params, opt_state)
        return params, opt_state

    def _bookkeep(self, step: int, loss: float, dt: float):
        """Straggler watchdog, history and log after a committed step."""
        if self.watchdog.observe(step, dt):
            log.warning("straggler at step %d (%.3fs vs EMA %.3fs)",
                        step, dt, self.watchdog.ema)
            self.mitigation_hook(step)
        self.history.append({"step": step, "loss": loss, "dt": dt})
        if self._recovering:
            # a post-recovery step committed: the fault was transient, so
            # the consecutive-failure budget resets (a long run with
            # sporadic recovered faults must not eventually trip
            # max_failures)
            self._recovering = False
            self.failures = 0
        if step % self.cfg.log_every == 0:
            log.info("step %d loss %.4f (%.3fs)", step, loss, dt)

    def _recover(self, step: int, train_step):
        """Full recovery path: rebuild the step (fresh executables,
        possibly a new mesh) and restore the last committed state,
        resharded onto whatever mesh the step now targets.  Returns
        (train_step, params, opt_state, step)."""
        shardings = None
        if self.replan is not None:
            out = self.replan()
            new_step, shardings = (
                out if isinstance(out, tuple) else (out, None))
            if new_step is not train_step:
                # an actual re-plan (the hook returns the live step
                # unchanged for a transient fault on an intact mesh — that
                # must not count as one)
                self.replans.append(step)
                # the surviving mesh's step cost is a new distribution;
                # judging it against the old mesh's EMA would flag every
                # first step (and skew the EMA permanently)
                self.watchdog.reset()
                log.info("elastic re-plan applied at step %d", step)
            train_step = new_step
        else:
            train_step = self.build_step()
        self._recovering = True
        params, opt_state, step = self._restore_or_init(shardings)
        return train_step, params, opt_state, step

"""Training launcher: ATP plan search -> mesh -> fault-tolerant loop.

The strategy is a ParallelPlan artifact end to end:

    # search (optionally after on-mesh calibration), save, train
    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --steps 200 --dp 2 --d1 2 --d2 2 --seq 128 --batch 8 \
        [--auto-atp [--calibrate]] [--save-plan plan.json]

    # re-apply a saved plan bit-for-bit (train or serve)
    ... -m repro.launch.train --arch llama3-8b --plan plan.json

The mesh is built over ``jax.devices()``: the attached chips, or on a
CPU host the virtual devices that
XLA_FLAGS=--xla_force_host_platform_device_count=N gives.
"""
from __future__ import annotations

import argparse
import logging
import tempfile
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_config
from repro.core import comm_matrix
from repro.core.calibrate import calibrate_mesh, recalibrate_surviving
from repro.core.cost_model import LayerCommProfile
from repro.core.plan import ParallelPlan, plan_search, replan_elastic
from repro.data.pipeline import DataConfig, TokenSource
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import build_train_step, init_params_sharded
from repro.models import lm
from repro.optim import adamw
from repro.runtime.membership import SingleObserverMembership
from repro.runtime.trainer import Trainer, TrainerConfig

log = logging.getLogger("repro.train")


def comm_profile(cfg) -> LayerCommProfile:
    """Generalized Eq.2 coefficients for this architecture's dense block
    (one source of truth: the per-kind constructor in the cost model)."""
    return LayerCommProfile.dense(cfg)


def pick_plan(cfg, tp: int, seq: int, batch: int, topology: str = "v5e",
              dp: int = 1, calibrate: bool = False, overlap: bool = True):
    """Search the plan space for this workload (optionally calibrated).

    The default path is the heterogeneous per-segment search
    (``plan_search(model=cfg)``): each model segment gets its own
    (chunks, seq_parallel) against its per-kind comm profile over the
    shared mesh.  ``overlap=False`` restricts to the seed Eq. 2 space —
    the exact degradation path the acceptance tests pin down.
    """
    calib = None
    if calibrate:
        matrix = comm_matrix.PRESETS[topology]()
        calib = calibrate_mesh(tp, matrix)
        log.info("on-mesh calibration (%d factorizations): %s",
                 len(calib), {k: (round(e.b1, 2), round(e.b2, 2))
                              for k, e in calib.entries})
    if not overlap:
        return plan_search(topology, tp, layers=cfg.num_layers, batch=batch,
                           seq=seq, profile=comm_profile(cfg), dp=dp,
                           calibration=calib, chunks_options=(1,),
                           seq_parallel_options=(False,),
                           algo="rabenseifner", alpha_s=0.0)
    return plan_search(topology, tp, model=cfg, batch=batch, seq=seq,
                       dp=dp, calibration=calib)


def make_elastic_trainer(cfg, plan: ParallelPlan, opt_cfg, trainer_cfg,
                         source, *, batch: int, seq: int,
                         membership=None, devices_fn=None,
                         recalibrate: bool = True, measure=None,
                         recalib_deadline_s: float | None = None):
    """Wire plan -> builders -> fault-tolerant Trainer, elastic end to end.

    The recovery loop on a shrunken device pool is *complete* (the PR-2/3
    deferral): (1) ``recalibrate_surviving`` re-measures (B1,B2)/alpha_s/
    boundary latency for factorizations of the surviving TP degree and
    merges them into the carried table, (2) ``replan_elastic`` re-searches
    the surviving mesh ranking with those fresh numbers (the re-planned
    artifact carries no ``calibration: stale`` tag), (3) the rebuilt step's
    shardings are returned to the Trainer so the checkpoint restore lands
    params/opt_state sharded on the new (d1, d2) mesh instead of
    replicated on the default device.

    ``membership`` answers *what pool survived, and is this host the
    elected re-planner* — a ``runtime.membership.MembershipRuntime`` over
    a lease/heartbeat fabric (recovery waits for a converged, epoch-
    numbered, quorum-committed view, and only the elected planner runs
    the re-search), or any object with the same ``converged_view()/
    devices()/is_planner()`` surface.  ``devices_fn`` is the DEPRECATED
    PR-4 single-observer poll, kept behind
    ``SingleObserverMembership`` with a loud warning; default (neither
    given) is the single-observer view of ``jax.devices``.

    ``recalibrate=False`` skips the on-mesh micro-benchmarks (the
    re-search then ranks with the stale-tagged table, the pre-PR-4
    behavior).  ``measure`` forwards to ``recalibrate_surviving``
    (injectable benchmark for tests).  ``recalib_deadline_s`` budgets the
    recovery micro-benchmarks: most-sensitive factorizations measured
    first, the rest degraded to carried/analytic entries when the
    deadline runs out (provenance recorded in the plan).

    Returns ``(trainer, live)`` — ``live`` is the mutable holder the
    closures read, so callers can observe the post-recovery plan/step/info.
    """
    if membership is not None and devices_fn is not None:
        raise TypeError("pass membership= or devices_fn=, not both")
    if membership is None:
        if devices_fn is not None:
            warnings.warn(
                "devices_fn= is deprecated: it is the PR-4 single-"
                "observer poll — one omniscient host, no leases, no "
                "quorum, no planner election.  Pass membership= "
                "(runtime.membership.MembershipRuntime over a "
                "MembershipFabric) instead.",
                DeprecationWarning, stacklevel=2)
        membership = SingleObserverMembership(devices_fn or jax.devices)
    topo = plan.topo()
    devs = membership.devices()
    assert topo.size <= len(devs), \
        f"need {topo.size} devices, have {len(devs)}"
    mesh = topo.build(devs)
    step_fn, info = build_train_step(cfg, topo, opt_cfg, mesh=mesh, plan=plan)

    # live holder so the elastic re-plan path can swap plan/step/shardings
    # under the closures the Trainer holds
    live = {"plan": plan, "step": step_fn, "info": info, "ctx": info.ctx}

    def init_state():
        # created in place, shard by shard (see init_params_sharded)
        inf, c = live["info"], live["ctx"]
        params = init_params_sharded(cfg, jax.random.PRNGKey(0), inf.mesh,
                                     inf.pspecs)
        opt = jax.jit(
            lambda p: adamw.init_opt_state(p, inf.pspecs, c, opt_cfg.mode),
            out_shardings=inf.sharding(inf.ospecs))(params)
        return params, opt

    def put_batch(host_batch):
        inf = live["info"]
        return jax.device_put(
            {k: jnp.asarray(v) for k, v in host_batch.items()},
            inf.sharding(inf.bspecs))

    def encode_ckpt(params, opt_state):
        """Checkpoint tree: params as-is + the opt state in its
        plan-independent param-shaped layout (zero1 banks unbanked), so
        any restart can re-bank onto whatever plan survives."""
        inf = live["info"]
        return (params, adamw.unbank_opt_state(
            params, opt_state, inf.pspecs, live["ctx"], opt_cfg.mode))

    def decode_ckpt(tree):
        params, canonical = tree
        inf = live["info"]
        opt = adamw.rebank_opt_state(params, canonical, inf.pspecs,
                                     live["ctx"], opt_cfg.mode)
        return params, jax.device_put(opt, inf.sharding(inf.ospecs))

    # the checkpoint's canonical (plan-independent) opt layout: zero1's
    # banked state unbanks to "plain"; compressed keeps its own mode so
    # the error-feedback residual ("err") rides the checkpoint too
    canon_mode = "plain" if opt_cfg.mode == "zero1" else opt_cfg.mode

    def ckpt_template():
        """Abstract shape/dtype view of the checkpoint tree (params +
        canonical opt) — restore needs no materialized throwaway state."""
        inf = live["info"]
        params = jax.eval_shape(
            lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
        canon = adamw.init_opt_state(params, inf.pspecs, live["ctx"],
                                     canon_mode, abstract=True)
        return (params, canon)

    def restore_shardings():
        """The *current* plan's shardings for the CHECKPOINTED tree —
        every restore (resume at start, recovery) places params directly
        onto the mesh the live step expects.  The canonical opt state
        stays host-side (``ckpt.HOST``): decode_ckpt re-banks it on the
        host anyway, and device-placing the param-shaped fp32 moments
        first would be a wasted full round trip."""
        from repro.checkpoint import manager as ckpt

        inf = live["info"]
        canon_specs = adamw.opt_state_specs(inf.pspecs, live["ctx"],
                                            canon_mode)
        canon_host = jax.tree.map(lambda _: ckpt.HOST, canon_specs,
                                  is_leaf=lambda x: isinstance(x, P))
        return (inf.sharding(inf.pspecs), canon_host)

    def replan_step():
        """Elastic restart: re-plan only if the device pool actually
        changed.  A transient step failure on an intact mesh must NOT
        change the strategy — the executed plan stays the artifact the
        user saved.  'Intact' is membership, not a head-count: enough
        spare devices with a dead one still in the live mesh would
        otherwise hand back a step bound to the dead device forever.

        The pool itself comes from the membership layer: recovery blocks
        on a CONVERGED, quorum-committed view (a glitchy lease cannot
        trigger a reshard — the fabric needs ``quorum_views`` stable
        reviews plus a majority ack before any view commits), and only
        the view's elected planner may run the re-search."""
        view = membership.converged_view()
        surviving = membership.devices(view)
        alive = {d.id for d in surviving}
        mesh_alive = all(d.id in alive
                         for d in live["info"].mesh.devices.flat)
        if mesh_alive and len(surviving) >= live["plan"].devices:
            return live["step"], restore_shardings()
        if not membership.is_planner(view):
            # a real non-planner host would wait for the planner's plan
            # artifact; the single-process simulation has no one to wait
            # for, so losing the planner role is a scenario bug
            raise RuntimeError(
                f"epoch {view.epoch}: this host is not the elected "
                f"re-planner (view {view.alive}, planner {view.planner})")
        log.info("membership epoch %d committed view %s; this host is "
                 "the elected re-planner", view.epoch, view.alive)
        old = live["plan"]
        if recalibrate:
            old = recalibrate_surviving(old, devices=surviving,
                                        measure=measure,
                                        deadline_s=recalib_deadline_s,
                                        model=cfg, batch=batch, seq=seq)
            log.info("recalibrated on surviving mesh: %d entries (%s)",
                     len(old.calibration), old.calibration.source)
        new_plan = replan_elastic(old, len(surviving), model=cfg,
                                  batch=batch, seq=seq)
        log.info("elastic re-plan: %s -> %s",
                 live["plan"].describe(), new_plan.describe())
        new_topo = new_plan.topo()
        new_mesh = new_topo.build(surviving)
        new_step, new_info = build_train_step(cfg, new_topo, opt_cfg=opt_cfg,
                                              mesh=new_mesh, plan=new_plan)
        live.update(plan=new_plan, step=new_step, info=new_info,
                    ctx=new_info.ctx)
        return new_step, restore_shardings()

    trainer = Trainer(
        trainer_cfg,
        build_step=lambda: live["step"],
        source=source, init_state=init_state, put_batch=put_batch,
        replan=replan_step, restore_shardings=restore_shardings,
        encode_ckpt=encode_ckpt, decode_ckpt=decode_ckpt,
        ckpt_template=ckpt_template)
    return trainer, live


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the tiny smoke config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--d1", type=int, default=2)
    ap.add_argument("--d2", type=int, default=1)
    ap.add_argument("--auto-atp", action="store_true",
                    help="search a ParallelPlan (paper §3.5 + overlap knobs)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="restrict --auto-atp to the seed Eq. 2 space")
    ap.add_argument("--calibrate", action="store_true",
                    help="micro-benchmark (B1,B2) on the attached mesh and "
                         "re-rank with the measured table (paper §5.3)")
    ap.add_argument("--plan", default=None,
                    help="load a saved ParallelPlan JSON instead of searching")
    ap.add_argument("--save-plan", default=None,
                    help="write the executed plan JSON here")
    ap.add_argument("--topology", default="v5e", choices=list(comm_matrix.PRESETS))
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--opt-mode", default="zero1",
                    choices=["plain", "zero1", "compressed"])
    ap.add_argument("--chunks", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint (and resume) directory; default: a "
                         "fresh temporary directory, so nothing is resumed")
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    if args.plan:
        plan = ParallelPlan.load(args.plan)
        log.info("loaded plan %s: %s", args.plan, plan.describe())
    elif args.auto_atp:
        res = pick_plan(cfg, args.d1 * args.d2, args.seq, args.batch,
                        args.topology, dp=args.dp,
                        calibrate=args.calibrate,
                        overlap=not args.no_overlap)
        plan = res.best
        log.info("ATP plan search on %s picked %s; top of ranking: %s",
                 args.topology, plan.describe(),
                 [(c.d1, c.d2, c.chunks, c.seq_parallel,
                   round(c.t_exposed * 1e3, 2)) for c in res.costs[:4]])
    else:
        # manual knobs still produce a plan: one artifact, one code path
        plan = ParallelPlan(d1=args.d1, d2=args.d2, dp=args.dp,
                            chunks=args.chunks,
                            provenance=(("searcher", "manual-cli"),))
    if args.save_plan:
        plan.save(args.save_plan)
        log.info("saved plan -> %s", args.save_plan)

    opt_cfg = adamw.AdamWConfig(lr=args.lr, mode=args.opt_mode,
                                total_steps=args.steps)
    source = TokenSource(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch))
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    trainer, live = make_elastic_trainer(
        cfg, plan, opt_cfg,
        TrainerConfig(total_steps=args.steps, ckpt_dir=ckpt_dir,
                      ckpt_every=args.ckpt_every),
        source, batch=args.batch, seq=args.seq)
    params, _ = trainer.run()
    losses = [h["loss"] for h in trainer.history]
    log.info("done: first loss %.4f -> last loss %.4f (%d steps)",
             losses[0], losses[-1], len(losses))
    return params


if __name__ == "__main__":
    main()

"""Drive ATP's train and serve paths once on a TPU and check the results.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips of one host

One chip: ``qwen1.5-0.5b`` at its published width (24 layers, d_model
1024, vocab 151936), random weights from a fixed key.

  train  five steps through ``launch.train.make_elastic_trainer``; every
         loss finite, no failure, no re-plan; the step-0 per-token losses
         of the first batch row on the chip agree with the same losses in
         float32 on the host's CPU backend, and a forward with one layer's
         attention output zeroed does not.
  serve  sixteen mixed-length requests through ``launch.serve.
         make_paged_server``; every request completes and the page pool
         drains.  Eight equal-length prompts then go through the paged
         server and through the wave loop (``launch.serve.serve``), and
         their greedy tokens must agree (in float32; the bf16 agreement is
         printed).

Four chips (``--chips 4``): ``qwen3-8b`` served at full depth on the
decode mesh ``plan_search`` picks, then in float32 on that mesh and on a
second factorization, tokens compared; then trained, cut in depth, for
three steps on each factorization of 4, step-0 per-token losses
compared between factorizations (and against one with a layer zeroed).

Timings and peak device memory are printed as readings, not claims.  The
last line of stdout is ``{"ok": true, "device": {...}}``; any failed check
or error exits non-zero before it.  Without a TPU the script exits
non-zero at once.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

MAX_NEW = 32
PAGE = 16
PAGES_PER_SLOT = 64       # 1024 positions per slot: prompt <= 512 + MAX_NEW
SLOTS = 8

#: --chips 4 trains qwen3-8b cut to this depth (published: 36): the
#: deepest cut whose train step, compiled for a v5e, stays under 14 GB per
#: device at batch 4 x seq 1024 on every factorization of 4 (14 layers:
#: 13.85 GB at (1, 4); 16 layers: 14.85 GB)
TRAIN4_LAYERS = 14

#: max |chip - cpu| bound over the step-0 per-token losses of one
#: 512-token row.  The chip runs bf16 params and activations (8-bit
#: mantissa: ~0.4% per rounding) through 24 layers; the CPU reference runs
#: the same params in float32 at "highest" matmul precision.  Read on a
#: v5e: 0.035 for the sound forward, 1.41 with layer FAULT_LAYER's
#: attention output projection zeroed (every run prints both and checks
#: the second lies above the bound).
TOKEN_ATOL = 0.1

#: max |a - b| bound over the step-0 per-token losses of one 1024-token
#: row between factorizations of 4 (same params, same batch, all bf16:
#: only the reduction order of the boundary collectives differs; 4096-wide
#: sums round more than the one-chip model's 1024-wide ones), placed like
#: TOKEN_ATOL between the sound reading and the zeroed-layer one.
FACTOR_TOKEN_ATOL = 0.2

#: the layer whose attention output projection the planted fault zeroes
FAULT_LAYER = 6

#: floor on the mean fraction of each request's greedy tokens that match
#: the reference before the first mismatch.  The comparisons run in
#: float32 at "highest" matmul precision, where an argmax near-tie that
#: flips one token (and ends that request's agreement early) is rare; in
#: bf16 the same flips are common at this vocabulary and the bf16
#: agreement is printed as a reading only.  A cache that mixes pages
#: between slots corrupts every request from its first decode step on,
#: which puts the agreement near 1/32.
AGREE_FLOOR = 0.75


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, phase: str, what: str) -> None:
    if not ok:
        raise SystemExit(f"[{phase}] FAIL: {what}")
    log(phase, f"ok: {what}")


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def agreement(got, ref) -> tuple[float, int]:
    """(mean matched-prefix fraction, requests matching exactly)."""
    fracs, exact = [], 0
    for g, r in zip(got, ref):
        n = 0
        while n < len(r) and n < len(g) and g[n] == r[n]:
            n += 1
        fracs.append(n / len(r))
        exact += n == len(r) == len(g)
    return sum(fracs) / len(fracs), exact


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def token_losses(ctx, cfg, params, pspecs, batch, devices) -> np.ndarray:
    """Per-token losses [b, s] of ``batch`` through the train path's
    forward and head (``lm.forward``, ``lm.lm_logits``,
    ``lm.vocab_parallel_ce``: what ``lm.train_loss`` averages), no update,
    on ``ctx``'s mesh over ``devices``."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.models import lm

    def local(p, b):
        tokens = b["tokens"]
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None],
                                     tokens.shape)
        h = lm.forward(ctx, cfg, p, tokens, positions, remat=False)[0]
        return lm.vocab_parallel_ce(ctx, lm.lm_logits(ctx, cfg, p, h),
                                    b["labels"])

    fn = shard_map(local, mesh=ctx.topo.build(devices),
                   in_specs=(pspecs, P()), out_specs=P())
    return np.asarray(jax.jit(fn)(params, batch), np.float64)


def zero_attn_out(params, layer: int):
    """``params`` with one layer's attention output projection zeroed: the
    planted fault that shows a per-token bound can tell a wrong forward
    from rounding."""
    import jax

    seg = dict(params["seg0"])
    wo = seg["attn"]["wo"]
    seg["attn"] = dict(seg["attn"], wo=jax.jit(
        lambda w: w.at[layer].set(0), out_shardings=wo.sharding)(wo))
    return dict(params, seg0=seg)


def max_diff(a, b) -> float:
    return float(np.max(np.abs(a - b)))


def train_run(cfg, plan, *, batch, seq, steps, ckpt_dir, ckpt_every):
    """``make_elastic_trainer`` end to end; returns (trainer, live)."""
    from repro.data.pipeline import DataConfig, TokenSource
    from repro.launch.train import make_elastic_trainer
    from repro.optim import adamw
    from repro.runtime.trainer import TrainerConfig

    source = TokenSource(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                    global_batch=batch))
    t0 = time.perf_counter()
    trainer, live = make_elastic_trainer(
        cfg, plan, adamw.AdamWConfig(lr=3e-4, total_steps=steps),
        TrainerConfig(total_steps=steps, ckpt_dir=ckpt_dir,
                      ckpt_every=ckpt_every, log_every=1),
        source, batch=batch, seq=seq)
    trainer.run()
    trainer.wall_s = time.perf_counter() - t0
    return trainer, live, source


def check_trainer(phase, trainer, steps):
    import math

    losses = [h["loss"] for h in trainer.history]
    dts = [h["dt"] for h in trainer.history]
    log(phase, f"losses {losses}")
    log(phase, f"set-up, init and compile {trainer.wall_s - sum(dts):.3f} "
               f"s; step 0 {dts[0]:.4f} s; median step after warm-up "
               f"{statistics.median(dts[1:]):.4f} s")
    check(len(trainer.history) == steps, phase, f"{steps} steps ran")
    check(all(math.isfinite(x) for x in losses), phase, "every loss finite")
    check(trainer.total_failures == 0, phase, "no step failed")
    check(trainer.replans == [], phase, "no re-plan")
    return losses


def train_phase(dev, cfg, *, batch=8, seq=512, steps=5):
    import jax

    from repro.core.plan import ParallelPlan
    from repro.launch.steps import init_params_sharded

    phase = "train"
    log(phase, f"{cfg.name}: {cfg.num_layers} layers, d_model "
               f"{cfg.d_model}, vocab {cfg.vocab_size}; batch {batch} x "
               f"seq {seq}; plan (1,1,1)")
    plan = ParallelPlan(d1=1, d2=1, dp=1,
                        provenance=(("searcher", "chip-smoke"),))
    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        trainer, live, source = train_run(
            cfg, plan, batch=batch, seq=seq, steps=steps,
            ckpt_dir=ckpt_dir, ckpt_every=steps)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    check_trainer(phase, trainer, steps)
    log(phase, f"peak device bytes {peak_bytes(dev)}")

    # step-0 per-token losses of the first batch row: chip (bf16) vs host
    # CPU (f32), and the chip with a planted fault vs the same reference
    info = live["info"]
    params = init_params_sharded(cfg, jax.random.PRNGKey(0), info.mesh,
                                 info.pspecs)   # the trainer's step-0 bits
    row = {k: v[:1] for k, v in source.global_batch(0).items()}
    chip = token_losses(info.ctx, cfg, params, info.pspecs, row, [dev])
    fault = token_losses(info.ctx, cfg, zero_attn_out(params, FAULT_LAYER),
                         info.pspecs, row, [dev])
    cpu = jax.devices("cpu")[0]
    host = jax.tree.map(lambda x: np.asarray(x, np.float32),
                        jax.device_get(params))
    del params
    with jax.default_matmul_precision("highest"):
        ref = token_losses(info.ctx, f32(cfg), jax.device_put(host, cpu),
                           info.pspecs, jax.device_put(row, cpu), [cpu])
    sound, planted = max_diff(chip, ref), max_diff(fault, ref)
    log(phase, f"step-0 row loss (mean of {ref.size} tokens) chip "
               f"{chip.mean():.6f} vs cpu f32 {ref.mean():.6f}")
    log(phase, f"max |chip - cpu f32| over per-token losses: sound "
               f"{sound:.6f}, layer {FAULT_LAYER} attention zeroed "
               f"{planted:.6f}")
    check(sound <= TOKEN_ATOL, phase,
          f"per-token losses chip vs cpu f32 within {TOKEN_ATOL}")
    check(planted > TOKEN_ATOL, phase,
          f"the zeroed-layer forward exceeds {TOKEN_ATOL}")


def factor_train_phase(dev, cfg, *, batch=4, seq=1024, steps=3):
    import jax

    from repro.core.mesh import factorizations
    from repro.core.plan import ParallelPlan
    from repro.launch.steps import init_params_sharded

    phase = "train4"
    log(phase, f"{cfg.name} cut in depth to {cfg.num_layers} layers "
               f"(published 36), d_model {cfg.d_model}, vocab "
               f"{cfg.vocab_size}; batch {batch} x seq {seq}; {steps} steps "
               f"per factorization of 4")
    key = jax.random.PRNGKey(0)
    meshes = factorizations(4)
    tokens = {}
    for d1, d2 in meshes:
        plan = ParallelPlan(d1=d1, d2=d2, dp=1,
                            provenance=(("searcher", "chip-smoke"),))
        ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
        try:   # ckpt_every=0: nothing is written
            trainer, live, source = train_run(
                cfg, plan, batch=batch, seq=seq, steps=steps,
                ckpt_dir=ckpt_dir, ckpt_every=0)
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        check_trainer(f"{phase} ({d1},{d2})", trainer, steps)
        del trainer
        # step-0 per-token losses of the first batch row on this mesh
        info = live["info"]
        params = init_params_sharded(cfg, key, info.mesh, info.pspecs)
        row = {k: v[:1] for k, v in source.global_batch(0).items()}
        tokens[(d1, d2)] = token_losses(info.ctx, cfg, params, info.pspecs,
                                        row, jax.devices()[:4])
        if (d1, d2) == meshes[-1]:
            fault = token_losses(info.ctx, cfg,
                                 zero_attn_out(params, FAULT_LAYER),
                                 info.pspecs, row, jax.devices()[:4])
        del params
    a = tokens[meshes[0]]
    sound = max(max_diff(tokens[m], a) for m in meshes[1:])
    planted = max_diff(fault, a)
    log(phase, "step-0 row loss (mean of 1 x %d tokens) per factorization "
               "%s" % (seq, {m: round(float(t.mean()), 6)
                            for m, t in tokens.items()}))
    log(phase, f"max |per-token loss - {meshes[0]}'s| over the other "
               f"factorizations: sound {sound:.6f}, on {meshes[-1]} with "
               f"layer {FAULT_LAYER} attention zeroed {planted:.6f}")
    log(phase, f"peak device bytes {peak_bytes(dev)}")
    check(sound <= FACTOR_TOKEN_ATOL, phase,
          f"per-token losses agree across factorizations within "
          f"{FACTOR_TOKEN_ATOL}")
    check(planted > FACTOR_TOKEN_ATOL, phase,
          f"the zeroed-layer forward exceeds {FACTOR_TOKEN_ATOL}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def paged_config():
    from repro.models.paging import PagedConfig
    from repro.runtime.server import ServerConfig

    # the pool holds every slot at its table's full width: admission never
    # waits for pages
    return ServerConfig(
        batch_slots=SLOTS, prefill_chunk=128,
        paged=PagedConfig(page_size=PAGE,
                          num_pages=1 + SLOTS * PAGES_PER_SLOT,
                          pages_per_slot=PAGES_PER_SLOT))


def run_requests(server, prompts, rid0=0):
    """Submit, drain, and return (tokens per request, ticks, seconds)."""
    from repro.runtime.server import Request

    for i, p in enumerate(prompts):
        server.submit(Request(rid=rid0 + i, prompt=p, max_new=MAX_NEW))
    t0 = time.perf_counter()
    ticks = server.run_until_drained()
    dt = time.perf_counter() - t0
    done = sorted((r for r in server.completed if r.rid >= rid0),
                  key=lambda r: r.rid)
    return [list(r.out) for r in done], ticks, dt


def prompts_from(rng, vocab, lens):
    return [rng.integers(0, vocab, size=n, dtype=np.int32) for n in lens]


def f32(cfg):
    return dataclasses.replace(cfg, dtype="float32")


def serve_phase(dev, cfg, seed, *, requests=16, wave=8):
    import jax

    from repro.core.mesh import atp_topo
    from repro.launch.serve import (init_serving_params, make_paged_server,
                                    serve)

    phase = "serve"
    topo = atp_topo(1, 1, 1)
    scfg = paged_config()
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(seed)
    lens = [int(n) for n in rng.integers(64, 513, size=requests)]
    prompts = prompts_from(rng, cfg.vocab_size, lens)
    L = int(rng.integers(64, 513))
    eq = prompts_from(rng, cfg.vocab_size, [L] * wave)
    log(phase, f"{cfg.name}: {SLOTS} slots, page {PAGE}, prefill chunk "
               f"{scfg.prefill_chunk}; {requests} requests, prompt lengths "
               f"{lens}, max_new {MAX_NEW}")
    params = init_serving_params(cfg, key, topo=topo)
    server, _ = make_paged_server(cfg, scfg, params, topo=topo)
    outs, ticks, dt = run_requests(server, prompts)
    log(phase, f"{requests} requests in {ticks} ticks, {dt:.3f} s "
               f"(compile included)")
    check(len(outs) == requests and all(len(o) == MAX_NEW for o in outs),
          phase, f"all {requests} requests completed with {MAX_NEW} tokens")
    check(server.alloc.free_pages == scfg.paged.num_pages - 1, phase,
          "page pool drained")
    got, ticks, dt = run_requests(server, eq, rid0=requests)
    log(phase, f"{wave} requests of length {L} in {ticks} ticks, {dt:.3f} s "
               f"(warm: {dt / ticks * 1e3:.2f} ms per tick)")
    ref = serve(cfg, topo, params, eq, MAX_NEW, L + MAX_NEW).tolist()
    frac, exact = agreement(got, ref)
    log(phase, f"bf16 paged vs wave loop: agreement {frac:.4f} "
               f"({exact}/{wave} identical), a reading")
    log(phase, f"peak device bytes {peak_bytes(dev)}")
    del server, params

    # the oracle check: same prompts, float32 at "highest" precision
    cfg32 = f32(cfg)
    with jax.default_matmul_precision("highest"):
        params = init_serving_params(cfg32, key, topo=topo)
        server, _ = make_paged_server(cfg32, scfg, params, topo=topo)
        got, _, _ = run_requests(server, eq)
        ref = serve(cfg32, topo, params, eq, MAX_NEW, L + MAX_NEW).tolist()
    frac, exact = agreement(got, ref)
    check(frac >= AGREE_FLOOR, phase,
          f"f32 paged vs wave loop: agreement {frac:.4f} ({exact}/{wave} "
          f"identical) >= {AGREE_FLOOR}")


def mesh_serve_phase(dev, cfg, seed, *, requests=8):
    import jax

    from repro.core.plan import ParallelPlan, plan_search
    from repro.launch.serve import init_serving_params, make_paged_server

    phase = "serve4"
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(seed)
    lens = [int(n) for n in rng.integers(64, 513, size=requests)]
    prompts = prompts_from(rng, cfg.vocab_size, lens)
    view = plan_search("v5e", 4, model=cfg, batch=SLOTS, seq=512 + MAX_NEW,
                       decode_batch=SLOTS).best.decode_view()
    other = ((4, 1) if (view.d1, view.d2) != (4, 1) else (2, 2))
    log(phase, f"{cfg.name}: {cfg.num_layers} layers, d_model "
               f"{cfg.d_model}; {requests} requests, prompt lengths {lens}, "
               f"max_new {MAX_NEW}; searched decode view {view.describe()}")

    def run(c, plan, what):
        params = init_serving_params(c, key, plan=plan)
        server, info = make_paged_server(c, paged_config(), params,
                                         plan=plan)
        mesh = (info.ctx.d1, info.ctx.d2)
        got, ticks, dt = run_requests(server, prompts)
        log(phase, f"{what} on mesh {mesh}: {ticks} ticks, {dt:.3f} s "
                   f"(compile included)")
        check(len(got) == requests and all(len(o) == MAX_NEW for o in got)
              and server.alloc.free_pages == server.cfg.paged.num_pages - 1,
              phase, f"{what} {mesh}: all requests completed, pages drained")
        return got

    run(cfg, view, "bf16")
    log(phase, f"peak device bytes {peak_bytes(dev)}")
    # the oracle check: the same prompts in float32 at "highest" precision
    # on the searched mesh and on a second factorization (the Megatron
    # (4, 1), or (2, 2) where the search picked (4, 1) itself)
    second = ParallelPlan(d1=other[0], d2=other[1], dp=1,
                          provenance=(("searcher", "chip-smoke"),))
    with jax.default_matmul_precision("highest"):
        a = run(f32(cfg), view, "f32")
        b = run(f32(cfg), second, "f32")
    frac, exact = agreement(a, b)
    log(phase, f"peak device bytes {peak_bytes(dev)}")
    check(frac >= AGREE_FLOOR, phase,
          f"f32 mesh {(view.d1, view.d2)} vs {other}: agreement {frac:.4f} "
          f"({exact}/{requests} identical) >= {AGREE_FLOOR}")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="draws the serve prompts")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX's first device is "
                         f"{dev.platform})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: {SRC / 'repro'} not found: run from a "
                         f"checkout of the repository")
    sys.path.insert(0, str(SRC))

    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log("device", f"{dev.platform} {dev.device_kind} x {len(devices)}; "
                  f"compile cache {enable_compile_cache()}")
    if args.chips == 1:
        cfg = get_config("qwen1.5-0.5b")
        train_phase(dev, cfg)
        serve_phase(dev, cfg, args.seed)
    else:
        cfg = get_config("qwen3-8b")
        mesh_serve_phase(dev, cfg, args.seed)
        factor_train_phase(dev, dataclasses.replace(
            cfg, num_layers=TRAIN4_LAYERS))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

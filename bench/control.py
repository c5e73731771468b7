"""Read the control and the planted faults of a cell's correctness check,
at the cell's own size, on several seeds.  Not part of a benchmark run.

    python bench/control.py --workload <cell> --seeds 11 12 13 [--seconds 8]

A ``train`` cell needs no program: for each seed the reference runs the
compared steps in float32, in float8 (the control: the precision under the
configuration's bfloat16), and in float32 on half of each batch (the fault
"half the batch left out, the mean over the rest"); the control and the
fault are compared with the float32 run exactly as the program is.  A
step that returns its state unchanged reads 1 on the gradient and the
change by construction.

A ``serve_closed`` cell runs the program for a short window, as a
benchmark run does, and at the sampled requests' positions reads both the
served tokens' gaps and the gap of the token that the float8 reference
puts first.  With ``--exchange`` (a train cell on several chips) the
program runs, as a benchmark run does, with every ATP boundary's exchange
left out (``bench/faults.py``).  Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path[0] = str(CHECKOUT)
sys.path.insert(1, str(CHECKOUT / "src"))


def train_readings(cell, seed: int, devices) -> dict:
    from bench.jobs.train import compare
    from bench.model import Sizes, seed_key
    from bench.reference import train_reference, weights
    from bench.traffic import TrainTokens

    w = cell["workload"]
    s = Sizes.from_file(cell["config"])
    src = TrainTokens(cell["traffic"], seed, s.vocab)
    batches = [src.global_batch(k) for k in range(w["compare_steps"])]
    w0 = weights(s, seed_key(seed), devices)
    ref = train_reference(s, w0, batches, w["optimizer"])
    out = {}
    ctl = train_reference(s, w0, batches, w["optimizer"], mode="fp8")
    out["fp8"] = {n: v for n, v, _ in compare(ctl, ref)}
    half = [{k: v[: len(v) // 2] for k, v in b.items()} for b in batches]
    hb = train_reference(s, w0, half, w["optimizer"])
    out["half_batch"] = {n: v for n, v, _ in compare(hb, ref)}
    return out


def serve_readings(cell, seed: int, seconds: float, devices) -> dict:
    import numpy as np

    from bench import reference
    from bench.jobs import serve_closed
    from bench.run import Cell

    real = reference.serve_gaps
    seen = {}

    def both(*a, **k):
        served, ctl = real(*a, control="fp8", **k)
        seen["served"] = max(float(np.max(g)) for g in served)
        seen["fp8"] = max(float(np.max(g)) for g in ctl)
        seen["tokens"] = sum(len(g) for g in served)
        return served

    reference.serve_gaps = both
    try:
        serve_closed.run(Cell(
            name=cell["name"], entry=cell["entry"], workload=cell["workload"],
            config=cell["config"], traffic=cell["traffic"], seed=seed,
            seconds=seconds, trace=False, devices=devices,
            t_start=time.perf_counter()))
    finally:
        reference.serve_gaps = real
    return seen


def exchange_readings(cell, seed: int, seconds: float, devices) -> dict:
    """The program with every boundary's exchange left out
    (``bench.faults``), run as a benchmark run is: its compared numbers."""
    from bench.faults import exchange_left_out
    from bench.jobs import train
    from bench.run import Cell

    with exchange_left_out():
        res = train.run(Cell(
            name=cell["name"], entry=cell["entry"], workload=cell["workload"],
            config=cell["config"], traffic=cell["traffic"], seed=seed,
            seconds=seconds, trace=False, devices=devices,
            t_start=time.perf_counter()))
    return {"exchange": {n: v for n, v, _ in res.checks}}


def load_cell(name: str) -> dict:
    def j(*p):
        with open(CHECKOUT.joinpath(*p)) as f:
            return json.load(f)

    bench = j("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    return {"name": name, "entry": entry,
            "workload": j("bench", "workloads", f"{name}.json"),
            "config": j("bench", "configs", f"{entry['config']}.json"),
            "traffic": j("bench", "traffic", f"{entry['traffic']}.json")}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--exchange", action="store_true",
                    help="read the program with its boundary exchange left "
                         "out (train cells on several chips)")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = load_cell(args.workload)
    devices = jax.devices()[: cell["entry"]["chips"]]
    for seed in args.seeds:
        if args.exchange:
            r = exchange_readings(cell, seed, args.seconds, devices)
        elif cell["workload"]["job"] == "train":
            r = train_readings(cell, seed, devices)
        else:
            r = serve_readings(cell, seed, args.seconds, devices)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

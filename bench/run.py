"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a cell is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``bench/configs/<name>.json``)
and traffic (``bench/traffic/<name>.json``); ``bench/workloads/<cell>.json``
names the job kind (``bench/jobs/<kind>.py``) and its settings and limits;
each per-layer metric is read by ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared with its
limit.  The checks are also the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
# the checkout, not bench/, is where imports start (bench/trace.py is no
# stdlib ``trace``)
sys.path[0] = str(CHECKOUT)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402

BENCH = CHECKOUT / "bench"
TRACE_DIR = CHECKOUT / ".bench_trace"
SPAN_PREFIXES = ("bench.", "train.", "serve.")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    """What a job is given: the cell's files, the run's arguments and the
    devices it runs on."""

    name: str
    entry: dict          # the cell's entry in BENCHMARK.json
    workload: dict       # bench/workloads/<cell>.json
    config: dict         # bench/configs/<config>.json
    traffic: dict        # bench/traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float = T_START

    @property
    def chips(self) -> int:
        return self.entry["chips"]


@dataclasses.dataclass
class JobResult:
    attempted: int
    failed: int
    e2e: dict                      # end-to-end metric name -> value
    checks: list                   # (name, value, limit): correct iff <=
    memory_peak_bytes: int
    counters: dict = dataclasses.field(default_factory=dict)
    trace_dir: str | None = None   # the traced sub-window's profile

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.checks) and not self.failed


class Profile:
    """The traced sub-window: the profiler on, and one host span
    ``bench.window`` over exactly the part the per-layer metrics read."""

    def __init__(self, logdir: Path):
        self.logdir = logdir
        self._span = None

    def start(self):
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        jax.profiler.start_trace(str(self.logdir))
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def stop(self):
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str, e2e: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def execute(cell: Cell, bench: dict, peak: dict):
    """Run the cell's job and build the result line (everything but the
    look for a chip).  Returns (result dict, the job's JobResult)."""
    job = importlib.import_module(f"bench.jobs.{cell.workload['job']}")
    res: JobResult = job.run(cell, Profile(TRACE_DIR) if cell.trace else None)

    e2e_names = {m["name"] for m in bench["end_to_end"]
                 if "workloads" not in m or cell.name in m["workloads"]}
    dev = cell.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": res.memory_peak_bytes}
    out: dict = {"correct": res.correct, "attempted": res.attempted,
                 "failed": res.failed}
    if not cell.trace:
        missing = e2e_names - set(res.e2e)
        if missing:
            raise RuntimeError(f"job gave no {sorted(missing)}")
        out["metrics"] = {m["name"]: {"value": res.e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in bench["end_to_end"]
                          if m["name"] in e2e_names}
    else:
        from bench import trace as tr

        t = tr.load(tr.find_trace_file(res.trace_dir), SPAN_PREFIXES)
        window = tr.window_of(t, "bench.window")
        view = Reading(trace=t, window=window, counters=res.counters,
                       peak=peak, chips=cell.chips)
        metrics = {}
        for m in bench["per_layer"]:
            if applies(m, cell.name, e2e_names):
                v = reader(m["name"])(view)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = tr.busy_s(t, window)
        device["window_s"] = (window[1] - window[0]) * 1e-6
        out["breakdown"] = {
            "device_ops": [list(x) for x in tr.top_ops(t, window)],
            "idle_gaps": [list(x) for x in tr.idle_gaps(t, window)]}
        shutil.rmtree(res.trace_dir, ignore_errors=True)
    out["device"] = device
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in res.checks}
    return out, res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench = load_json(CHECKOUT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        log(f"no cell {args.workload!r} in BENCHMARK.json")
        return 2
    if not (CHECKOUT / "src" / "repro").is_dir():
        log(f"the program is not in this checkout ({CHECKOUT / 'src'})")
        return 2
    sys.path.insert(1, str(CHECKOUT / "src"))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devices[0].platform}")
        return 3
    if len(devices) < entry["chips"]:
        log(f"the cell needs {entry['chips']} chips, JAX sees {len(devices)}")
        return 3
    peaks = load_json(BENCH / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        log(f"device kind {kind!r} is not in bench/peaks.json")
        return 3

    from repro.launch.compile_cache import enable_compile_cache

    log(f"{kind} x {len(devices)}; compile cache {enable_compile_cache()}")
    workload = load_json(BENCH / "workloads" / f"{args.workload}.json")
    cell = Cell(
        name=args.workload, entry=entry, workload=workload,
        config=load_json(BENCH / "configs" / f"{entry['config']}.json"),
        traffic=load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices[:entry["chips"]])
    out, res = execute(cell, bench, peaks[kind])
    for n, v, lim in res.checks:
        log(f"check {n} = {v!r} (limit {lim!r}) "
            f"{'ok' if v <= lim else 'FAILED'}")
    print(json.dumps(out), flush=True)
    return 0


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader is given."""

    trace: object        # bench.trace.Trace of the traced sub-window
    window: tuple        # its (start, end), microseconds on the trace clock
    counters: dict       # the job's counts over the same sub-window
    peak: dict           # this device kind's row of bench/peaks.json
    chips: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-6


if __name__ == "__main__":
    sys.exit(main())

"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.trace.json.gz``
beside the ``.xplane.pb``; the JSON carries what the reduction needs on
every device op (``hlo_category``, ``tf_op`` with the JAX name stack,
``long_name``), which the xplane reader does not expose.  On a TPU the
device ops are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` process; on the CPU backend (the tests) they are the
host-thread events that carry an ``hlo_op``, grouped by
``device_ordinal``.  Host spans are the ``TraceAnnotation`` events of the
host process.

All times here are microseconds on the trace's own clock.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

#: hlo categories whose event spans the ops inside it: counting them would
#: count the loop's own gaps as busy time
CONTAINERS = frozenset({"while", "conditional", "call"})

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")

Interval = tuple[float, float]


@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str
    category: str
    scope: str          # the JAX name stack (``tf_op``), "" if absent
    long_name: str

    @property
    def collective(self) -> bool:
        return bool(COLLECTIVE.search(self.name)
                    or COLLECTIVE.search(self.category)
                    or COLLECTIVE.search(_opcode(self.long_name)))


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Op]]       # device -> ops, sorted by start
    spans: list[Span]              # host spans, sorted by start


def _opcode(long_name: str) -> str:
    """The HLO opcode of ``%x = <shape> opcode(...)``."""
    m = re.match(r"%\S+ = .*?\s([a-z][a-z0-9-]*)\(", long_name)
    return m.group(1) if m else ""


def find_trace_file(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.trace.json.gz"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one trace under {logdir}, "
                                f"found {files}")
    return files[0]


def load(path: str, span_prefixes: tuple[str, ...] = ()) -> Trace:
    """Device ops and host spans (names starting with one of
    ``span_prefixes``) of one ``.trace.json.gz``."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    devices = {pid: name for pid, name in procs.items()
               if re.match(r"/device:(TPU|GPU):\d+$", name)}
    ops: dict[str, list[Op]] = {}
    spans: list[Span] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args", {})
        start, end = float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0))
        pid = e["pid"]
        if pid in devices:
            if threads.get((pid, e["tid"])) != "XLA Ops":
                continue
            dev = devices[pid]
        elif not devices and "hlo_op" in args:
            dev = f"/device:CPU:{args.get('device_ordinal', '0')}"
        else:
            if span_prefixes and e["name"].startswith(span_prefixes):
                spans.append(Span(start, end, e["name"]))
            continue
        cat = args.get("hlo_category", "")
        if cat in CONTAINERS:
            continue
        ops.setdefault(dev, []).append(Op(
            start, end, e["name"], cat, args.get("tf_op", ""),
            args.get("long_name", "")))
    for lst in ops.values():
        lst.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return Trace(ops=ops, spans=spans)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals) -> list[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals, window: Interval) -> list[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, minus) -> list[Interval]:
    """The parts of ``intervals`` that no interval of ``minus`` covers."""
    minus = union(minus)
    out = []
    for a, b in union(intervals):
        cur = a
        for c, d in minus:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def intersect(a, b) -> list[Interval]:
    a, b = union(a), union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def window_of(trace: Trace, name: str) -> Interval | None:
    """The one host span called ``name`` (the traced sub-window)."""
    found = [s for s in trace.spans if s.name == name]
    return (found[0].start, found[-1].end) if found else None


def busy(trace: Trace, window: Interval) -> dict[str, list[Interval]]:
    """Per device: the union of its op intervals inside ``window``."""
    return {d: union(clip([(o.start, o.end) for o in ops], window))
            for d, ops in trace.ops.items()}


def busy_s(trace: Trace, window: Interval) -> float:
    """Seconds in which an op ran, averaged over the devices."""
    b = busy(trace, window)
    return sum(length(v) for v in b.values()) / max(1, len(b)) * 1e-6


def idle_share(trace: Trace, window: Interval) -> float:
    """1 - busy / window, averaged over the devices."""
    return 1.0 - busy_s(trace, window) / ((window[1] - window[0]) * 1e-6)


def collective_shares(trace: Trace, window: Interval) -> tuple[float, float]:
    """(collective time, exposed collective time) over the window, averaged
    over devices: the union of collective op intervals, and the part of it
    in which no other op runs on that device."""
    total = exposed = 0.0
    for ops in trace.ops.values():
        coll = clip([(o.start, o.end) for o in ops if o.collective], window)
        other = clip([(o.start, o.end) for o in ops if not o.collective],
                     window)
        total += length(coll)
        exposed += length(subtract(coll, other))
    n = max(1, len(trace.ops)) * (window[1] - window[0])
    return total / n, exposed / n


def scope_share(trace: Trace, window: Interval, scopes) -> float | None:
    """Device time of ops whose name stack holds one of ``scopes`` (as a
    path component, forward or under a transform such as
    ``transpose(jvp(shell:head))``) over device busy time; None when no op
    carries a name stack at all."""
    pat = re.compile(r"(^|[/(])(" + "|".join(re.escape(s) for s in scopes)
                     + r")($|[/)])")
    any_scope = False
    hit, total = [], 0.0
    for ops in trace.ops.values():
        inside = [o for o in ops if o.end > window[0] and o.start < window[1]]
        any_scope |= any(o.scope for o in inside)
        hit += clip([(o.start, o.end) for o in inside if pat.search(o.scope)],
                    window)
        total += length(clip([(o.start, o.end) for o in inside], window))
    if not any_scope or total <= 0:
        return None
    return sum(b - a for a, b in hit) / total


def device_time_in(trace: Trace, name: str, window: Interval):
    """(device busy seconds inside host spans called ``name``, averaged
    over devices; number of such spans) within the window."""
    spans = [(s.start, s.end) for s in trace.spans if s.name == name
             and s.start >= window[0] and s.end <= window[1]]
    b = busy(trace, window)
    t = sum(length(intersect(v, spans)) for v in b.values()) / max(1, len(b))
    return t * 1e-6, len(spans)


def op_key(o: Op) -> str:
    """What instances of one op share across layers and steps: its name
    stack without the jitted function's name (``jit(f)/``), or its HLO
    name without the numeric suffix where it has none, with its category."""
    scope = re.sub(r"^jit\([^)]*\)/", "", o.scope)
    return f"{scope or re.sub(r'[.]\d+$', '', o.name)} [{o.category}]"


def top_ops(trace: Trace, window: Interval, n: int = 10):
    """[(op, seconds)] of the ops that took most device time inside the
    window, instances added up (:func:`op_key`), averaged over devices."""
    acc: dict[str, float] = {}
    for ops in trace.ops.values():
        for o in ops:
            a, b = max(o.start, window[0]), min(o.end, window[1])
            if b > a:
                key = op_key(o)
                acc[key] = acc.get(key, 0.0) + (b - a) * 1e-6
    k = max(1, len(trace.ops))
    return sorted(((nm, t / k) for nm, t in acc.items()),
                  key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: Trace, window: Interval, n: int = 10):
    """[(host span, seconds)]: device idle time inside the window, by the
    innermost host span it falls in ("none" where no span of the program's
    layers covers it; the ``bench.*`` spans mark windows, not layers),
    averaged over devices, the largest first."""
    acc: dict[str, float] = {}
    b = busy(trace, window)
    spans = [s for s in trace.spans if s.end > window[0]
             and s.start < window[1] and not s.name.startswith("bench.")]
    for dev_busy in b.values():
        for a, z in subtract([window], dev_busy):
            # walk the gap, giving each piece to the shortest span over it
            cuts = sorted({a, z} | {x for s in spans for x in (s.start, s.end)
                                    if a < x < z})
            for lo, hi in zip(cuts, cuts[1:]):
                mid = (lo + hi) / 2
                over = [s for s in spans if s.start <= mid < s.end]
                name = (min(over, key=lambda s: s.end - s.start).name
                        if over else "none")
                acc[name] = acc.get(name, 0.0) + (hi - lo) * 1e-6
    k = max(1, len(b))
    return sorted(((nm, t / k) for nm, t in acc.items()),
                  key=lambda kv: -kv[1])[:n]

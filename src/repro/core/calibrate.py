"""On-mesh calibration of the ATP cost model (paper §5.3).

The analytic hierarchical comm matrix (Eq. 3/4) predicts per-mesh-dim
algorithm bandwidths; §5.3 shows the prediction can be badly wrong on
messy fabrics (IC1: PCIe ACS/NUMA effects), and that re-ranking with
*measured* (B1, B2) recovers the right strategy.  This module produces
those measurements as a ``CalibrationTable``: for each (d1, d2)
factorization of the TP degree that fits the available devices, it
micro-benchmarks

  - the all-reduce over each mesh dim  -> effective algorithm bandwidths
    (B1, B2) in the seed convention (payload_bytes / measured_seconds),
    directly substitutable for Eq. 4's values in ``t_comm`` /
    ``t_comm_overlap``;
  - the psum vs explicit-ring boundary  -> preferred ``boundary_mode``.

Tables are plain data (JSON round-trippable) so a ``ParallelPlan`` can
carry them: a plan searched on one machine records exactly which measured
numbers drove the choice.  Measurement is injectable (``measure=``) so
tests and the cost-model path stay deterministic.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Mapping

from repro.core.comm_matrix import HierarchicalCommMatrix
from repro.core.mesh import atp_topo, factorizations


@dataclasses.dataclass(frozen=True)
class CalibEntry:
    """Measured numbers for one (d1, d2) factorization.

    b1 / b2 are *algorithm* bandwidths in GB/s (the seed ``calibration``
    convention: all-reduce time = payload_bytes / (B * 1e9)); inf means
    the dim is singleton.  t_psum / t_ring are measured seconds of one
    boundary all-reduce in each implementation (None when unmeasured).
    alpha_s is the measured per-collective-step latency in seconds (ring
    step convention: a d-rank all-reduce runs 2(d-1) steps), extracted
    from a latency-bound tiny-payload all-reduce; it feeds
    ``t_comm_overlap``'s ring-vs-Rabenseifner and chunk-count choices —
    chunking amortizes bandwidth but pays alpha per chunk, so a measured
    alpha is what keeps the search from over-chunking on real fabrics.

    chunk_eff holds the chunked-overlap *effective bandwidth* micro-
    benchmark (ROADMAP open item): tuples ``(chunks, eff1, eff2)`` where
    eff_i is the measured PURE-bandwidth efficiency of splitting one
    boundary all-reduce on mesh dim i into ``chunks`` back-to-back
    collectives of payload/chunks each —
    ``t_whole / (t_chunked - (chunks-1) * launch_s)``, 1.0 = free
    splitting.  The per-extra-chunk software launch cost is measured
    separately as ``launch_s`` (from the c=2 split: t_2 - t_whole) and
    charged additively by ``t_comm_overlap(chunk_launch_s=...)``; folding
    it into the bandwidth number — the pre-fix behavior — double-counted
    launch overhead against the alpha_s term.  A slow measured chunk path
    (either number) still steers the search back to chunks=1.

    ``provenance`` records where THIS entry's numbers came from:
    ``"measured"`` (on-mesh micro-benchmark), ``"carried"`` (copied from
    the pre-shrink table when a recovery deadline ran out before this
    factorization's turn), or ``"analytic"`` (Eq. 3/4 model values — the
    budget-exhausted fallback when there is nothing to carry).  Deadline-
    budgeted recovery (``recalibrate_surviving(deadline_s=...)``) is the
    writer; ``CalibrationTable.provenance_counts`` and
    ``ParallelPlan.describe`` surface it so a partially-calibrated
    recovery is visible in the artifact.

    b1_q / b2_q are the *quantized-collective* algorithm bandwidths: the
    same micro-benchmark run over the int8 wire
    (``overlap.quant_psum``), in the WIRE-byte convention — a quantized
    all-reduce of N elements takes ``N * 1 byte / (b_q * 1e9)`` seconds.
    They pair with ``t_comm_overlap(wire_dtype=..., calibrated=...)``:
    the search substitutes (b1_q, b2_q) for (b1, b2) when pricing a
    quantized plan, which is how measured quant/dequant overhead (or a
    fabric that accelerates small payloads sub-linearly) can flip the
    chosen factorization or chunk count.  None = unmeasured (the search
    falls back to the full-width bandwidths over the halved byte count).
    """

    b1: float
    b2: float
    t_psum: float | None = None
    t_ring: float | None = None
    alpha_s: float | None = None
    chunk_eff: tuple[tuple[int, float, float], ...] | None = None
    launch_s: float | None = None
    b1_q: float | None = None
    b2_q: float | None = None
    provenance: str = "measured"

    @property
    def boundary_mode(self) -> str | None:
        if self.t_psum is None or self.t_ring is None:
            return None
        return "ring" if self.t_ring < self.t_psum else "psum"

    def chunk_efficiency(self) -> dict[int, tuple[float, float]] | None:
        """{chunks: (eff1, eff2)} view for ``t_comm_overlap`` (None when
        the chunked micro-benchmark was not run)."""
        if self.chunk_eff is None:
            return None
        return {int(c): (e1, e2) for c, e1, e2 in self.chunk_eff}

    def to_dict(self) -> dict:
        return {"b1": _enc_inf(self.b1), "b2": _enc_inf(self.b2),
                "t_psum": self.t_psum, "t_ring": self.t_ring,
                "alpha_s": self.alpha_s,
                "chunk_eff": (None if self.chunk_eff is None
                              else [list(t) for t in self.chunk_eff]),
                "launch_s": self.launch_s,
                "b1_q": (None if self.b1_q is None else _enc_inf(self.b1_q)),
                "b2_q": (None if self.b2_q is None else _enc_inf(self.b2_q)),
                "provenance": self.provenance}

    @staticmethod
    def from_dict(d: Mapping) -> "CalibEntry":
        ce = d.get("chunk_eff")
        b1_q, b2_q = d.get("b1_q"), d.get("b2_q")
        return CalibEntry(b1=_dec_inf(d["b1"]), b2=_dec_inf(d["b2"]),
                          t_psum=d.get("t_psum"), t_ring=d.get("t_ring"),
                          alpha_s=d.get("alpha_s"),
                          chunk_eff=(None if ce is None else tuple(
                              (int(c), float(e1), float(e2))
                              for c, e1, e2 in ce)),
                          launch_s=d.get("launch_s"),
                          b1_q=(None if b1_q is None else _dec_inf(b1_q)),
                          b2_q=(None if b2_q is None else _dec_inf(b2_q)),
                          # absent in pre-v5 files: every entry was a
                          # real on-mesh measurement back then
                          provenance=d.get("provenance", "measured"))


def _enc_inf(v: float):
    return "inf" if math.isinf(v) else v


def _dec_inf(v) -> float:
    return math.inf if v == "inf" else float(v)


@dataclasses.dataclass(frozen=True)
class CalibrationTable:
    """Per-factorization measured entries; JSON round-trippable.

    ``source`` records where the numbers came from ("measured", "model",
    or a free-form label such as the paper's published IC1 values).
    """

    entries: tuple[tuple[tuple[int, int], CalibEntry], ...] = ()
    source: str = "measured"

    def get(self, d1: int, d2: int) -> CalibEntry | None:
        for (a, b), e in self.entries:
            if (a, b) == (d1, d2):
                return e
        return None

    def bandwidths(self, d1: int, d2: int) -> tuple[float, float] | None:
        e = self.get(d1, d2)
        return (e.b1, e.b2) if e is not None else None

    def boundary_mode(self, d1: int, d2: int) -> str | None:
        e = self.get(d1, d2)
        return e.boundary_mode if e is not None else None

    def alpha(self, d1: int, d2: int) -> float | None:
        """Measured per-step collective latency (None when unmeasured)."""
        e = self.get(d1, d2)
        return e.alpha_s if e is not None else None

    def chunk_efficiency(self, d1: int, d2: int) \
            -> dict[int, tuple[float, float]] | None:
        """Measured chunked-collective bandwidth efficiencies (or None)."""
        e = self.get(d1, d2)
        return e.chunk_efficiency() if e is not None else None

    def launch(self, d1: int, d2: int) -> float | None:
        """Measured per-extra-chunk launch cost (None when unmeasured)."""
        e = self.get(d1, d2)
        return e.launch_s if e is not None else None

    def quant_bandwidths(self, d1: int, d2: int) \
            -> tuple[float, float] | None:
        """Measured quantized-collective bandwidths (b1_q, b2_q) in the
        wire-byte convention, or None when the quantized micro-benchmark
        did not run for this factorization."""
        e = self.get(d1, d2)
        if e is None or (e.b1_q is None and e.b2_q is None):
            return None
        return (e.b1_q if e.b1_q is not None else e.b1,
                e.b2_q if e.b2_q is not None else e.b2)

    def provenance_counts(self) -> dict[str, int]:
        """Entry counts by provenance (measured / carried / analytic) —
        how calibrated this table actually is.  A deadline-budgeted
        recovery that ran out of time shows up here (and in
        ``ParallelPlan.describe``) instead of masquerading as fully
        measured."""
        out: dict[str, int] = {}
        for _, e in self.entries:
            out[e.provenance] = out.get(e.provenance, 0) + 1
        return out

    def covers_tp(self, tp_degree: int) -> bool:
        """True if any entry measures a factorization of ``tp_degree``.

        Necessary (not sufficient) evidence of a surviving-mesh
        recalibration: ``replan_elastic`` requires it together with the
        provenance tag ``recalibrate_surviving`` writes, since an
        external table may key several degrees without any having been
        measured on this mesh.
        """
        return any(d1 * d2 == tp_degree for (d1, d2), _ in self.entries)

    def merged(self, other: "CalibrationTable") -> "CalibrationTable":
        """This table with ``other``'s entries layered on top.

        ``other`` wins on key collisions — it is the *fresher* measurement
        (the elastic recalibration path merges surviving-mesh numbers into
        the carried table this way, keeping still-valid old keys around
        for audit).
        """
        d = dict(self.entries)
        d.update(dict(other.entries))
        source = (other.source if other.source == self.source
                  else f"{self.source}+{other.source}")
        return CalibrationTable(entries=tuple(sorted(d.items())),
                                source=source)

    def __len__(self) -> int:
        return len(self.entries)

    @staticmethod
    def from_pairs(pairs: Mapping[tuple[int, int], tuple[float, float]],
                   source: str = "external") -> "CalibrationTable":
        """Lift a seed-style {(d1,d2): (B1,B2)} dict into a table."""
        return CalibrationTable(
            entries=tuple(((d1, d2), CalibEntry(b1=b1, b2=b2))
                          for (d1, d2), (b1, b2) in sorted(pairs.items())),
            source=source)

    @staticmethod
    def coerce(calibration) -> "CalibrationTable | None":
        """Accept a table, a seed-style {(d1,d2): (B1,B2)} dict, or None —
        the one dispatch point for every calibration-taking API."""
        if calibration is None or isinstance(calibration, CalibrationTable):
            return calibration
        return CalibrationTable.from_pairs(calibration)

    def as_pairs(self) -> dict[tuple[int, int], tuple[float, float]]:
        """Seed-style {(d1,d2): (B1,B2)} view (for ``search_strategy``)."""
        return {(d1, d2): (e.b1, e.b2) for (d1, d2), e in self.entries}

    def to_dict(self) -> dict:
        return {"source": self.source,
                "entries": {f"{d1}x{d2}": e.to_dict()
                            for (d1, d2), e in self.entries}}

    @staticmethod
    def from_dict(d: Mapping) -> "CalibrationTable":
        entries = []
        for key, ed in d.get("entries", {}).items():
            d1, d2 = (int(p) for p in key.split("x"))
            entries.append(((d1, d2), CalibEntry.from_dict(ed)))
        return CalibrationTable(entries=tuple(sorted(entries)),
                                source=d.get("source", "measured"))


# ---------------------------------------------------------------------------
# On-mesh micro-benchmarks.
# ---------------------------------------------------------------------------


#: samples above this multiple of the raw median are treated as outliers
_TRIM_FACTOR = 2.5


def robust_seconds(samples) -> float:
    """Median-of-k with high-side outlier trimming.

    The pre-fix statistic was best-of-N (min) — robust against slow
    outliers but maximally credulous of FAST ones: a single spuriously
    quick sample (clock glitch, coalesced dispatch) becomes the measured
    time, inflates the derived bandwidth, and can flip ``plan_search``
    to a mesh the fabric cannot actually sustain (the ic1 pin in
    tests/test_robustness.py).  The median is robust on both sides as
    long as fewer than half the samples are outliers; samples more than
    ``_TRIM_FACTOR``x the raw median (stragglers: GC pause, scheduler
    preemption) are dropped first so they cannot drag the median of a
    small k either.
    """
    xs = sorted(float(s) for s in samples)
    if not xs:
        raise ValueError("no timing samples")
    med = xs[len(xs) // 2]
    kept = [x for x in xs if x <= _TRIM_FACTOR * med] or xs
    n = len(kept)
    return kept[n // 2] if n % 2 else 0.5 * (kept[n // 2 - 1] + kept[n // 2])


def _time_fn(fn, *args, repeats: int = 3,
             timer: Callable[[], float] = time.perf_counter,
             budget_s: float | None = None) -> float:
    """Robust wall time of a blocking call: up to ``repeats`` samples,
    stopping early once ``budget_s`` is spent (always at least one —
    a deadline bounds the repeat count k, never the truth of a sample),
    reduced by :func:`robust_seconds`."""
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm up
    t_start = timer()
    samples = []
    for _ in range(max(1, repeats)):
        t0 = timer()
        jax.block_until_ready(fn(*args))
        samples.append(timer() - t0)
        if budget_s is not None and timer() - t_start >= budget_s:
            break
    return robust_seconds(samples)


def _measure_factorization(d1: int, d2: int, payload_bytes: int,
                           repeats: int, devices=None,
                           budget_s: float | None = None,
                           timer: Callable[[], float] = time.perf_counter
                           ) -> CalibEntry:
    """All-reduce timing over each TP mesh dim + psum-vs-ring boundary.

    ``budget_s`` (deadline-budgeted recovery) caps the wall time spent
    here: every inner timing loop sees the remaining budget and stops
    sampling once it is gone — k shrinks before coverage does, and the
    overrun is bounded by one sample per measurement kind.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.core import overlap
    from jax import shard_map
    from repro.core.mesh import tp_axis_names

    topo = atp_topo(1, d1, d2)
    devices = devices if devices is not None else jax.devices()
    mesh = topo.build(devices[: topo.size])
    ax1, ax2 = tp_axis_names(topo)
    elems = max(1, payload_bytes // 4)
    t_begin = timer()

    def rem() -> float | None:
        if budget_s is None:
            return None
        return max(0.0, budget_s - (timer() - t_begin))

    def time_allreduce(axis: str, d: int, ring: bool = False,
                       n_elems: int | None = None,
                       quant: bool = False) -> float:
        x = jnp.ones((d, n_elems or elems), jnp.float32)
        if quant:
            red = lambda v: overlap.quant_psum(v, axis, "int8")  # noqa: E731
        elif ring:
            red = lambda v: overlap.ring_all_reduce(v, axis, d)  # noqa: E731
        else:
            red = lambda v: lax.psum(v, axis)  # noqa: E731
        f = jax.jit(shard_map(red, mesh=mesh, in_specs=P(axis),
                              out_specs=P(axis), check_vma=True))
        return _time_fn(f, x, repeats=repeats, budget_s=rem())

    def quant_bw(axis: str | None, d: int) -> float | None:
        """Quantized-collective bandwidth in the WIRE-byte convention:
        the int8 wire moves 1 byte per element, so b_q = elems / t — the
        number ``t_comm_overlap(wire_dtype="int8")`` divides its 1-byte
        volumes by.  Quant/dequant overhead lands in t, which is the
        point: a fabric (or emulation) where quantization does not pay
        shows up as b_q < b/2 and the search prices it honestly."""
        if axis is None:
            return None
        t = time_allreduce(axis, d, quant=True)
        return elems / t / 1e9 if t > 0.0 else None

    def alpha_from_tiny(axis: str, d: int) -> float:
        """Per-step latency: a 64-element all-reduce is latency-bound, so
        its wall time over the ring step count is alpha_s (ROADMAP open
        item — previously analytic-only)."""
        return max(0.0, time_allreduce(axis, d, n_elems=64)) / (2 * (d - 1))

    def time_chunked(axis: str, d: int, c: int) -> float:
        """One boundary payload split into c back-to-back collectives of
        payload/c each — the wire pattern the chunk-overlap engine issues
        per boundary (repro.core.atp._chunked_boundary_matmul)."""
        per = max(1, elems // c)
        x = jnp.ones((d, c, per), jnp.float32)

        def red(v):
            return jnp.stack([lax.psum(v[:, i], axis) for i in range(c)],
                             axis=1)

        f = jax.jit(shard_map(red, mesh=mesh, in_specs=P(axis),
                              out_specs=P(axis), check_vma=True))
        return _time_fn(f, x, repeats=repeats, budget_s=rem())

    def launch_axis(axis: str | None, d: int,
                    whole: float | None) -> float | None:
        """Per-extra-chunk software launch cost: the c=2 split issues
        exactly one extra collective, so t_2 - t_whole isolates it from
        the bandwidth term (the satellite fix for the chunk-eff
        double-count)."""
        if axis is None or whole is None or whole <= 0.0:
            return None
        return max(0.0, time_chunked(axis, d, 2) - whole)

    def chunk_eff_axis(axis: str | None, d: int, whole: float, c: int,
                       launch: float | None) -> float:
        """Measured PURE-bandwidth efficiency of splitting into c chunks
        on one axis (1.0 for singleton dims): the measured per-extra-chunk
        launch cost is subtracted from the chunked time first, so this
        number no longer double-counts what ``launch_s`` (and the alpha
        term) already charge."""
        if axis is None or whole is None or whole <= 0.0:
            return 1.0
        tc = time_chunked(axis, d, c) - (c - 1) * (launch or 0.0)
        return min(1.0, whole / tc) if tc > 0.0 else 1.0

    b1 = b2 = math.inf
    b1_q = b2_q = None
    t_psum = t_ring = alpha_s = None
    t1_whole = t2_whole = None
    if ax1 is not None:
        t_psum = time_allreduce(ax1, d1)
        t_ring = time_allreduce(ax1, d1, ring=True)
        b1 = payload_bytes / t_psum / 1e9
        alpha_s = alpha_from_tiny(ax1, d1)
        b1_q = quant_bw(ax1, d1)
        t1_whole = t_psum
        if ax2 is not None:
            t2_whole = time_allreduce(ax2, d2)
            b2 = payload_bytes / t2_whole / 1e9
            b2_q = quant_bw(ax2, d2)
            # one alpha serves every collective of this factorization —
            # keep the slower axis's latency (conservative: the cost model
            # must not over-chunk the slow axis on a two-level fabric)
            alpha_s = max(alpha_s, alpha_from_tiny(ax2, d2))
    elif ax2 is not None:
        # boundary collectives live on the only non-trivial dim here, so
        # the psum timing doubles as the b2 measurement
        t_psum = time_allreduce(ax2, d2)
        t_ring = time_allreduce(ax2, d2, ring=True)
        b2 = payload_bytes / t_psum / 1e9
        alpha_s = alpha_from_tiny(ax2, d2)
        b2_q = quant_bw(ax2, d2)
        t2_whole = t_psum
    launch1 = launch_axis(ax1, d1, t1_whole)
    launch2 = launch_axis(ax2, d2, t2_whole)
    launch_s = max((v for v in (launch1, launch2) if v is not None),
                   default=None)
    chunk_eff = tuple(
        (c,
         chunk_eff_axis(ax1, d1, t1_whole, c, launch1),
         chunk_eff_axis(ax2, d2, t2_whole, c, launch2))
        for c in (2, 4))
    return CalibEntry(b1=b1, b2=b2, t_psum=t_psum, t_ring=t_ring,
                      alpha_s=alpha_s, chunk_eff=chunk_eff,
                      launch_s=launch_s, b1_q=b1_q, b2_q=b2_q)


def calibrate_mesh(
    tp_degree: int,
    matrix: HierarchicalCommMatrix | None = None,
    *,
    payload_kb: int = 256,
    repeats: int = 3,
    measure: Callable[[int, int], CalibEntry] | None = None,
    devices=None,
) -> CalibrationTable:
    """Measure (B1, B2) + boundary latency for every runnable (d1, d2).

    ``matrix`` (optional) restricts the sweep to factorizations that embed
    into the modelled topology — the same filter the search applies — so
    the table's keys line up with the strategy space.  Factorizations
    needing more devices than are attached are skipped (the table is
    partial; the search falls back to the analytic model for missing
    keys).  ``measure`` overrides the on-mesh micro-benchmark with an
    arbitrary (d1, d2) -> CalibEntry function (tests, simulators).
    ``devices`` restricts the benchmark to a device subset (the elastic
    recovery path passes the surviving pool; default: all attached).
    """
    import jax

    devices = devices if devices is not None else jax.devices()
    ndev = len(devices)
    entries = []
    for d1, d2 in factorizations(tp_degree):
        if matrix is not None:
            try:
                matrix.axis_bandwidths(d1, d2)
            except ValueError:
                continue
        if measure is None and d1 * d2 > ndev:
            continue
        fn = measure or (lambda a, b: _measure_factorization(
            a, b, payload_kb * 1024, repeats, devices))
        entries.append(((d1, d2), fn(d1, d2)))
    return CalibrationTable(entries=tuple(entries), source="measured")


# ---------------------------------------------------------------------------
# Elastic recovery: recalibrate on the surviving mesh.
# ---------------------------------------------------------------------------


def surviving_tp(tp_degree: int, n_devices: int) -> int:
    """The TP degree an elastic shrink keeps on ``n_devices``.

    Mirrors ``plan.replan_elastic``: data-parallel replicas absorb device
    loss first, so TP only halves when even dp=1 no longer fits.
    """
    if n_devices < 1:
        raise ValueError("no surviving devices")
    tp = tp_degree
    while tp > n_devices:
        tp //= 2
    return tp


def analytic_entry(matrix: HierarchicalCommMatrix | None, d1: int,
                   d2: int) -> CalibEntry:
    """Eq. 3/4 model bandwidths lifted into a ``CalibEntry`` (provenance
    ``"analytic"``) — the budget-exhausted fallback when a recovery
    deadline leaves a factorization unmeasured and the carried table has
    nothing for it.  Only (b1, b2) are filled: the model has no opinion
    on boundary-mode timings or chunk efficiencies, and pretending it
    did would defeat the provenance record."""
    if matrix is None:
        return CalibEntry(b1=math.inf, b2=math.inf, provenance="analytic")
    from repro.core.cost_model import axis_algorithm_bw

    _, _, b1, b2 = axis_algorithm_bw(matrix, d1, d2)
    return CalibEntry(b1=b1, b2=b2, provenance="analytic")


def sensitivity_order(keys, matrix: HierarchicalCommMatrix | None, *,
                      model=None, batch: int | None = None,
                      seq: int | None = None) -> list[tuple[int, int]]:
    """Order factorization keys by descending cost-model sensitivity
    (``cost_model.factorization_sensitivity``): the entries whose
    bandwidth numbers move the strategy ranking most get measured first,
    so a recovery deadline degrades the *least important* entries to
    carried/analytic.  Without a matrix the natural order stands (there
    is no model to rank by); without a workload a generic dense block is
    assumed — the ordering across factorizations is dominated by the
    fabric's bandwidths, not the exact layer shape."""
    keys = list(keys)
    if matrix is None or len(keys) < 2:
        return keys
    from repro.core.cost_model import (LayerCommProfile, SegmentWorkload,
                                       factorization_sensitivity,
                                       segment_workloads)

    if model is not None:
        workloads = segment_workloads(model)
    else:
        workloads = (SegmentWorkload(kind="dense", layers=1,
                                     profile=LayerCommProfile.gpt(4096)),)
    b = batch if batch is not None else 8
    s = seq if seq is not None else 512
    return sorted(keys, key=lambda k: (-factorization_sensitivity(
        matrix, k[0], k[1], workloads=workloads, batch=b, seq=s), k))


def recalibrate_surviving(
    plan,
    devices=None,
    *,
    payload_kb: int = 256,
    repeats: int = 3,
    measure: Callable[[int, int], CalibEntry] | None = None,
    deadline_s: float | None = None,
    model=None,
    batch: int | None = None,
    seq: int | None = None,
    timer: Callable[[], float] = time.perf_counter,
):
    """Re-measure a plan's calibration on the surviving mesh (paper §5.3).

    After an elastic shrink the carried table is tagged
    ``calibration: stale`` — its (B1, B2)/alpha_s/boundary numbers were
    measured on a mesh the job no longer runs on, and §5.3 is exactly the
    story of how badly a mis-priced table can mis-rank strategies.  This
    re-runs the micro-benchmarks for every factorization of the
    *surviving* TP degree (``surviving_tp`` of the surviving pool), merges
    the fresh entries into the carried table (fresh keys win; old keys
    stay for audit), clears the stale tag and records the recalibration in
    provenance.  The returned plan is ready for ``replan_elastic``: the
    re-search ranks the surviving factorizations with fresh measurements
    and — because the provenance records this pass for the surviving
    degree (and the merged table covers its factorizations) — the
    re-planned artifact is not re-tagged stale.

    **Deadline budget** (``deadline_s``): recovery time is downtime, so
    instead of fixed repeat counts the micro-benchmarks spend a wall-
    clock budget — factorizations are visited in descending cost-model
    sensitivity (``sensitivity_order``, using ``model``/``batch``/``seq``
    when the caller knows the workload), each measurement's repeat count
    k shrinks as the budget drains (``_time_fn(budget_s=...)``), and once
    the budget is gone the remaining factorizations fall back to the
    carried table's entry (provenance ``"carried"``) or the analytic
    model (``"analytic"``).  The per-entry provenance rides the table,
    the plan's provenance records the budget spend, and the
    ``recalibrated tp=`` tag — what lets ``replan_elastic`` skip the
    stale tag — is only written when at least one entry was actually
    measured: a fully-exhausted budget yields a usable but honestly
    stale-tagged plan.

    ``plan`` is any ParallelPlan-shaped object (duck-typed to avoid a
    module cycle: plan.py imports this module).  ``measure`` injects the
    per-factorization benchmark (tests, simulators); ``devices`` is the
    surviving pool (default: all attached); ``timer`` injects the budget
    clock (tests script deterministic deadlines with it).
    """
    import jax

    from repro.core import comm_matrix

    devs = list(devices) if devices is not None else jax.devices()
    tp = surviving_tp(plan.tp, len(devs))
    matrix = None
    if plan.topology is not None:
        preset = comm_matrix.PRESETS.get(plan.topology)
        matrix = preset() if preset is not None else None
    keys = []
    for d1, d2 in factorizations(tp):
        if matrix is not None:
            try:
                matrix.axis_bandwidths(d1, d2)
            except ValueError:
                continue
        if measure is None and d1 * d2 > len(devs):
            continue
        keys.append((d1, d2))
    if deadline_s is not None:
        keys = sensitivity_order(keys, matrix, model=model, batch=batch,
                                 seq=seq)
    t0 = timer()
    entries = []
    counts = {"measured": 0, "carried": 0, "analytic": 0}
    # adaptive gate: once one factorization has been timed, a later one is
    # only measured if the remaining budget covers what the last one cost
    # — so the deadline is respected even through an injected ``measure``
    # that cannot see the budget (the real path additionally threads
    # budget_s down to every sampling loop).
    last_cost = 0.0
    for d1, d2 in keys:
        remaining = (None if deadline_s is None
                     else deadline_s - (timer() - t0))
        if remaining is not None and (remaining <= 0.0
                                      or remaining < last_cost):
            old = (plan.calibration.get(d1, d2)
                   if plan.calibration is not None else None)
            e = (dataclasses.replace(old, provenance="carried")
                 if old is not None else analytic_entry(matrix, d1, d2))
        else:
            t_meas = timer()
            if measure is not None:
                e = dataclasses.replace(measure(d1, d2),
                                        provenance="measured")
            else:
                e = dataclasses.replace(
                    _measure_factorization(d1, d2, payload_kb * 1024,
                                           repeats, devs,
                                           budget_s=remaining, timer=timer),
                    provenance="measured")
            last_cost = timer() - t_meas
        counts[e.provenance] += 1
        entries.append(((d1, d2), e))
    entries.sort()
    source = ("measured" if counts["measured"] == len(entries)
              else "deadline-budgeted")
    fresh = CalibrationTable(entries=tuple(entries), source=source)
    merged = fresh if plan.calibration is None \
        else plan.calibration.merged(fresh)
    prov = tuple(p for p in plan.provenance
                 if p != ("calibration", "stale"))
    if counts["measured"] > 0:
        prov += (("calibration",
                  f"recalibrated tp={tp} on {len(devs)} devices"),)
    if deadline_s is not None:
        spent = timer() - t0
        # key "calibration" so replan_elastic's re-search carries it
        prov += (("calibration",
                  f"budget deadline_s={deadline_s:g} spent_s={spent:.3f} "
                  f"measured={counts['measured']} "
                  f"carried={counts['carried']} "
                  f"analytic={counts['analytic']}"),)
    return plan.with_(calibration=merged, provenance=prov)

"""Collective signature extraction: jaxpr -> what the program really emits.

The extractor traces a built step (any callable — the jitted functions
from ``launch.steps`` builders, or a bare shard_map'd function) with
abstract arguments and recursively walks the jaxpr: through ``pjit`` /
``shard_map`` bodies, ``scan`` bodies multiplied by their trip count,
``remat2`` / checkpoint replays, ``custom_vjp`` call jaxprs (forward-only
steps; AD inlines them in differentiated ones) and ``cond`` branches.

Every collective primitive is recorded with its mesh axes, payload
element count, dtype and an attribution read from the jaxpr name stack:

  - ``seg{i}:{kind}`` / ``shell:*`` scopes (``models/lm.py``) attribute a
    collective to a plan segment or to the model shell;
  - ``transpose(...)`` entries mark the backward (cotangent) region;
  - ``ring_rs/ring_ag/ring_ar/cm_rs/cm_ag[axis]`` scopes
    (``core/overlap.py``) mark ppermutes belonging to a ring schedule;
  - ``quant[axis]`` scopes mark payloads that ride the quantized wire —
    the grid values are *held* in f32 (so the unmodified collectives sum
    them exactly) but each element carries 1 byte of information, which
    is what ``wire_bytes`` prices (and what the cost model priced).

Byte conventions match ``launch/hlo_analysis.py`` so the two extraction
backends cross-check: all-reduce/all-gather/permute/all-to-all count
result bytes, reduce-scatter counts result x group (== operand) bytes.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Any, Callable, Iterable

import jax
import numpy as np
from jax.extend import core as jcore

#: primitives the extractor records (axis_index is free; pmean lowers to
#: psum + divide so it never appears as its own primitive)
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "all_gather", "reduce_scatter",
    "all_to_all",
})

#: vma-typed spellings of the same collectives (``lax.psum`` of a varying
#: value traces to ``psum_invariant`` under check_vma)
_PRIM_ALIASES = {"psum_invariant": "psum",
                 "all_gather_invariant": "all_gather"}


def prim_name(eqn) -> str:
    """The eqn's primitive name, with vma spellings folded to the plain
    collective they lower to."""
    name = eqn.primitive.name
    return _PRIM_ALIASES.get(name, name)


_SEG_RE = re.compile(r"^seg\d+:[a-z_]+$")
_SITE_RE = re.compile(r"^(ring_rs|ring_ag|ring_ar|cm_rs|cm_ag|quant|wireq)\[")


@dataclasses.dataclass(frozen=True)
class Collective:
    """One aggregated collective: ``count`` invocations of ``op`` over
    ``axes`` moving ``elems`` elements of ``dtype`` each."""

    op: str
    axes: tuple[str, ...]
    elems: int
    dtype: str
    quant: bool
    region: str          # "seg0:dense", "shell:embed", ... ("" = outside)
    backward: bool
    site: str            # innermost ring/quant scope ("" = monolithic)
    count: int = 1

    @property
    def raw_bytes(self) -> int:
        """Wire bytes at the dtype the payload is held in."""
        return self.count * self.elems * _dtype_bytes(self.dtype)

    @property
    def wire_bytes(self) -> int:
        """Information bytes on the wire: quantized payloads carry one
        byte per element regardless of the f32 container."""
        per = 1 if self.quant else _dtype_bytes(self.dtype)
        return self.count * self.elems * per

    @property
    def key(self):
        return (self.region, self.backward, self.op, self.axes)

    def describe(self) -> str:
        ax = "+".join(self.axes) or "-"
        q = " quant" if self.quant else ""
        bwd = " bwd" if self.backward else ""
        return (f"{self.count}x{self.op}[{ax}] {self.elems}elem "
                f"{self.dtype}{q}{bwd}")


@dataclasses.dataclass
class StepSignature:
    """All collectives of one traced step, scan-trip multiplied."""

    collectives: tuple[Collective, ...]
    warnings: tuple[str, ...] = ()

    def filter(self, region: str | None = None,
               backward: bool | None = None,
               op: str | None = None) -> "StepSignature":
        out = [c for c in self.collectives
               if (region is None or c.region == region)
               and (backward is None or c.backward == backward)
               and (op is None or c.op == op)]
        return StepSignature(tuple(out), self.warnings)

    def regions(self) -> tuple[str, ...]:
        return tuple(sorted({c.region for c in self.collectives}))

    def count(self, op: str | None = None) -> int:
        return sum(c.count for c in self.collectives
                   if op is None or c.op == op)

    def raw_bytes(self, op: str | None = None) -> int:
        return sum(c.raw_bytes for c in self.collectives
                   if op is None or c.op == op)

    def wire_bytes(self) -> int:
        return sum(c.wire_bytes for c in self.collectives)

    def by_key(self) -> dict[tuple, tuple[int, int, int]]:
        """{(region, backward, op, axes): (count, raw_bytes, wire_bytes)}."""
        agg: dict[tuple, list[int]] = defaultdict(lambda: [0, 0, 0])
        for c in self.collectives:
            a = agg[c.key]
            a[0] += c.count
            a[1] += c.raw_bytes
            a[2] += c.wire_bytes
        return {k: tuple(v) for k, v in agg.items()}

    def op_bytes(self) -> dict[str, int]:
        """{op: raw bytes} — the cross-check currency vs the HLO backend
        (XLA's all-reduce combiner merges ops, so counts don't compare)."""
        agg: dict[str, int] = defaultdict(int)
        for c in self.collectives:
            agg[c.op] += c.raw_bytes
        return dict(agg)

    def describe(self, prefix: str = "") -> str:
        lines = []
        for key, (n, rb, wb) in sorted(self.by_key().items()):
            region, bwd, op, axes = key
            ax = "+".join(axes) or "-"
            lines.append(f"{prefix}{region or '<top>'}"
                         f"{'.bwd' if bwd else '.fwd'}: {n}x{op}[{ax}] "
                         f"raw={rb} wire={wb}")
        return "\n".join(lines)


def _dtype_bytes(name: str) -> float:
    return np.dtype(name).itemsize


def _axes_of(params: dict) -> tuple[str, ...]:
    for k in ("axes", "axis_name"):
        if k in params:
            ax = params[k]
            return tuple(ax) if isinstance(ax, (tuple, list)) else (str(ax),)
    return ()


def _aval_elems(var) -> int:
    return int(np.prod(var.aval.shape)) if var.aval.shape else 1


def _payload(eqn) -> tuple[int, str]:
    """(elements, dtype) under the HLO-matching byte convention."""
    name = prim_name(eqn)
    if name in ("psum", "pmax", "pmin", "reduce_scatter"):
        # all-reduce: result == operand; reduce-scatter: result x group
        elems = sum(_aval_elems(v) for v in eqn.invars
                    if hasattr(v.aval, "shape"))
        dt = eqn.invars[0].aval.dtype.name
        return elems, dt
    elems = sum(_aval_elems(v) for v in eqn.outvars)
    return elems, eqn.outvars[0].aval.dtype.name


def _stack_components(eqn) -> tuple[str, ...]:
    ns = getattr(eqn.source_info, "name_stack", None)
    s = str(ns) if ns is not None else ""
    return tuple(p for p in s.split("/") if p)


def _attribution(path: tuple[str, ...]) -> tuple[str, bool, bool, str]:
    """(region, backward, quant, site) from a composed scope path."""
    region, site, quant = "", "", False
    backward = any("transpose(" in p for p in path)
    for p in path:
        bare = _strip_transforms(p)
        if _SEG_RE.match(bare) or bare.startswith("shell:"):
            region = bare
        if bare.startswith("quant["):
            quant = True
        if _SITE_RE.match(bare):
            site = bare
    return region, backward, quant, site


def _strip_transforms(comp: str) -> str:
    """'transpose(jvp(seg0:dense))' -> 'seg0:dense'."""
    out = comp
    while True:
        m = re.match(r"^[a-z_0-9]+\((.*)\)$", out)
        if not m:
            return out
        out = m.group(1)


def _sub_jaxpr(x):
    if isinstance(x, jcore.ClosedJaxpr):
        return x.jaxpr
    if isinstance(x, jcore.Jaxpr):
        return x
    return None


class _Walker:
    def __init__(self):
        self.hits: list[Collective] = []
        self.warnings: list[str] = []

    def walk(self, jaxpr: jcore.Jaxpr, mult: int,
             path: tuple[str, ...]) -> None:
        for eqn in jaxpr.eqns:
            p = path + _stack_components(eqn)
            name = prim_name(eqn)
            if name in COLLECTIVE_PRIMS:
                elems, dtype = _payload(eqn)
                region, backward, quant, site = _attribution(p)
                self.hits.append(Collective(
                    op=name, axes=_axes_of(eqn.params), elems=elems,
                    dtype=dtype, quant=quant, region=region,
                    backward=backward, site=site, count=mult))
            elif name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                self.walk(body, mult * int(eqn.params["length"]), p)
            elif name == "while":
                # trip count is dynamic at the jaxpr level; record once and
                # flag it (the HLO backend reads known_trip_count instead)
                if self._has_collectives(eqn.params["body_jaxpr"].jaxpr):
                    self.warnings.append(
                        f"while loop with collectives at {'/'.join(p)}: "
                        f"counted for ONE trip")
                self.walk(eqn.params["body_jaxpr"].jaxpr, mult, p)
                self.walk(eqn.params["cond_jaxpr"].jaxpr, mult, p)
            elif name == "cond":
                self._walk_cond(eqn, mult, p)
            else:
                self._walk_generic(eqn, mult, p)

    def _walk_cond(self, eqn, mult: int, path: tuple[str, ...]) -> None:
        branches = eqn.params["branches"]
        sub = []
        for br in branches:
            w = _Walker()
            w.walk(br.jaxpr, mult, path)
            sub.append(w)
        sigs = [StepSignature(tuple(w.hits)).by_key() for w in sub]
        if any(s != sigs[0] for s in sigs[1:]):
            self.warnings.append(
                f"cond branches disagree on collectives at "
                f"{'/'.join(path)}: counted branch 0 only")
        self.hits.extend(sub[0].hits)
        for w in sub:
            self.warnings.extend(w.warnings)

    def _walk_generic(self, eqn, mult: int, path: tuple[str, ...]) -> None:
        for v in eqn.params.values():
            j = _sub_jaxpr(v)
            if j is not None:
                self.walk(j, mult, path)

    def _has_collectives(self, jaxpr: jcore.Jaxpr) -> bool:
        w = _Walker()
        w.walk(jaxpr, 1, ())
        return bool(w.hits)


def trace_jaxpr(fn: Callable, *abstract_args) -> jcore.ClosedJaxpr:
    """Trace a built step (jitted or bare) with ShapeDtypeStruct args."""
    if hasattr(fn, "trace"):  # jitted
        return fn.trace(*abstract_args).jaxpr
    return jax.make_jaxpr(fn)(*abstract_args)


def extract(fn_or_jaxpr: Any, *abstract_args) -> StepSignature:
    """Extract the collective signature of a built step.

    Accepts a (jitted or bare) callable plus its abstract arguments, or a
    ready ClosedJaxpr/Jaxpr.
    """
    j = _sub_jaxpr(fn_or_jaxpr)
    if j is None:
        j = trace_jaxpr(fn_or_jaxpr, *abstract_args).jaxpr
    w = _Walker()
    w.walk(j, 1, ())
    return StepSignature(tuple(w.hits), tuple(w.warnings))


def aggregate(collectives: Iterable[Collective]) -> StepSignature:
    """Merge identical entries (same full identity) summing counts."""
    agg: dict[tuple, int] = defaultdict(int)
    for c in collectives:
        k = (c.op, c.axes, c.elems, c.dtype, c.quant, c.region,
             c.backward, c.site)
        agg[k] += c.count
    return StepSignature(tuple(
        Collective(op=k[0], axes=k[1], elems=k[2], dtype=k[3], quant=k[4],
                   region=k[5], backward=k[6], site=k[7], count=n)
        for k, n in agg.items()))

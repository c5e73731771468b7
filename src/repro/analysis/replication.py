"""Replication (vma) lint: prove shard_map ``out_specs`` honest.

ATP runs some build paths with jax's own per-eqn replication checker
disabled (``check_vma=False``): every ring/collective-matmul plan, whose
ppermute rings the upstream checker cannot certify.  This module closes
that gap: it walks the traced jaxpr of a built step, finds each
``shard_map`` eqn, and data-flows a *replication set* — the mesh axes
over which a value is guaranteed identical across ranks — from the
``in_specs`` to every output, then checks each output's ``out_specs``:
an axis the spec does NOT mention is a claim of replication, and the
lint errors if the value may actually vary over it.

Transfer rules (``rep`` = set of axes a value is replicated over):

  - default eqn: intersection of the operands' sets (a value derived
    from inputs is replicated over an axis only if all inputs are);
  - ``psum/pmax/pmin`` over ``axes``: union in ``axes`` (reduction
    restores invariance); ``all_gather``: union in its axis;
  - ``reduce_scatter/all_to_all/ppermute/pvary/pbroadcast``: difference
    with their axes (ranks now hold different data);
  - ``axis_index``: everything but its axis;
  - HOPs recurse (``pjit``/``remat2``/``custom_*`` map operands 1:1;
    ``scan``/``while`` iterate the carry to a fixpoint — monotone
    decreasing, so it terminates; ``cond`` intersects branches and the
    predicate); unknown sub-jaxpr shapes fall back to the permissive
    operand intersection.

Ring schedules need one extra ingredient: a completed ppermute ring IS
an all-reduce/all-gather, but per-hop data flow only ever sees the
varying intermediates.  The named scopes ``core.overlap`` wraps every
ring in (``ring_ar[ax]``/``ring_ag[ax]``/``ring_rs[ax]``/``cm_rs[ax]``/
``cm_ag[ax]``) mark the algebra: values are tagged with the scopes that
produced them, and when a value ESCAPES a ring scope the scope's net
effect is applied once — ``ring_ar``/``ring_ag`` restore the axis (up
to reduction reassociation, the same equivalence the cost model prices),
``ring_rs``/``cm_rs`` scatter over it.  Quantized wires need nothing
special: ``quant[ax]`` payloads flow through the same psum / ring /
scatter rules on the grid values, and the shared scale is a ``pmax``.
"""
from __future__ import annotations

import dataclasses
import re
from itertools import chain
from typing import Any

from jax.extend import core as jcore

from repro.analysis.signature import prim_name

#: scope -> net effect on the replication set when a value escapes it
_SCOPE_RE = re.compile(r"^(ring_ar|ring_ag|ring_rs|cm_rs|cm_ag)\[(.+)\]$")
_SCOPE_EFFECT = {"ring_ar": "add", "ring_ag": "add",
                 "ring_rs": "drop", "cm_rs": "drop", "cm_ag": "none"}

_REDUCE_PRIMS = frozenset({"psum", "pmax", "pmin"})
_VARY_PRIMS = frozenset({"reduce_scatter", "all_to_all", "ppermute",
                         "pvary", "pbroadcast"})


@dataclasses.dataclass(frozen=True)
class ReplicationError:
    out_index: int
    axis: str
    claimed: tuple[str, ...]
    actual: tuple[str, ...]

    def __str__(self) -> str:
        return (f"shard_map out[{self.out_index}]: out_spec claims "
                f"replication over '{self.axis}' but the value may vary "
                f"over it (proven replicated: "
                f"{sorted(self.actual) or ['<none>']})")


@dataclasses.dataclass
class ShardMapReport:
    """Lint result for one shard_map eqn inside a traced step."""

    mesh_axes: tuple[str, ...]
    errors: tuple[ReplicationError, ...]
    out_rep: tuple[frozenset, ...]
    check_vma: bool

    @property
    def ok(self) -> bool:
        return not self.errors


def _axes_param(params: dict) -> tuple[str, ...]:
    for k in ("axes", "axis_name"):
        if k in params:
            ax = params[k]
            return tuple(ax) if isinstance(ax, (tuple, list)) else (str(ax),)
    return ()


def _sub_jaxpr(x):
    if isinstance(x, jcore.ClosedJaxpr):
        return x.jaxpr
    if isinstance(x, jcore.Jaxpr):
        return x
    return None


def _stack_components(eqn) -> tuple[str, ...]:
    ns = getattr(eqn.source_info, "name_stack", None)
    s = str(ns) if ns is not None else ""
    return tuple(p for p in s.split("/") if p)


def _scopes_in(path: tuple[str, ...]) -> tuple[str, ...]:
    return tuple(p for p in path if _SCOPE_RE.match(p))


@dataclasses.dataclass(frozen=True)
class _Val:
    """A replication fact: the base set + the ring scopes that produced
    the value (applied lazily when the value escapes them)."""

    rep: frozenset
    tags: tuple[str, ...] = ()

    def read(self, consumer_scopes: tuple[str, ...]) -> frozenset:
        rep = self.rep
        for tag in self.tags:
            if tag in consumer_scopes:
                continue
            m = _SCOPE_RE.match(tag)
            effect = _SCOPE_EFFECT[m.group(1)]
            if effect == "add":
                rep = rep | {m.group(2)}
            elif effect == "drop":
                rep = rep - {m.group(2)}
        return rep

    def escaped(self, consumer_scopes: tuple[str, ...]) -> "_Val":
        kept = tuple(t for t in self.tags if t in consumer_scopes)
        return _Val(self.read(consumer_scopes), kept)


class _RepWalker:
    """Forward data-flow of replication sets over one jaxpr."""

    def __init__(self, axes: frozenset):
        self.axes = axes
        self.full = _Val(frozenset(axes))

    def run(self, jaxpr: jcore.Jaxpr, in_vals: list[_Val],
            path: tuple[str, ...]) -> list[_Val]:
        env: dict[Any, _Val] = {}
        for v in jaxpr.constvars:
            env[v] = self.full
        for v, val in zip(jaxpr.invars, in_vals):
            env[v] = val

        def read(v, scopes) -> frozenset:
            if isinstance(v, jcore.Literal):
                return frozenset(self.axes)
            return env.get(v, self.full).read(scopes)

        for eqn in jaxpr.eqns:
            p = path + _stack_components(eqn)
            scopes = _scopes_in(p)
            name = prim_name(eqn)
            ins = [read(v, scopes) for v in eqn.invars]
            inter = frozenset.intersection(*ins) if ins \
                else frozenset(self.axes)
            if name in _REDUCE_PRIMS:
                out = inter | set(_axes_param(eqn.params))
                outs = [_Val(out, scopes)] * len(eqn.outvars)
            elif name == "all_gather":
                outs = [_Val(inter | set(_axes_param(eqn.params)), scopes)]
            elif name in _VARY_PRIMS:
                out = inter - set(_axes_param(eqn.params))
                outs = [_Val(out, scopes)] * len(eqn.outvars)
            elif name == "axis_index":
                outs = [_Val(frozenset(self.axes)
                             - set(_axes_param(eqn.params)), scopes)]
            elif name == "scan":
                outs = self._scan(eqn, p, scopes, env, read)
            elif name == "while":
                outs = self._while(eqn, p, scopes, read)
            elif name == "cond":
                outs = self._cond(eqn, p, scopes, read)
            else:
                outs = self._generic(eqn, p, scopes, env, inter, read)
            for v, val in zip(eqn.outvars, outs):
                env[v] = val
        return [_Val(read(v, ()), ()) if isinstance(v, jcore.Literal)
                else env.get(v, self.full).escaped(())
                for v in jaxpr.outvars]

    # -- HOPs ---------------------------------------------------------------

    def _scan(self, eqn, path, scopes, env, read):
        body = eqn.params["jaxpr"].jaxpr
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        ins = [_Val(read(v, scopes), scopes) for v in eqn.invars]
        consts, carry, xs = ins[:nc], ins[nc:nc + ncar], ins[nc + ncar:]
        for _ in range(len(self.axes) * max(1, ncar) + 2):
            outs = self.run(body, consts + carry + xs, path)
            new_carry = [_Val(c.read(scopes) & o.read(scopes), scopes)
                         for c, o in zip(carry, outs[:ncar])]
            if all(n.rep == c.read(scopes) for n, c in zip(new_carry, carry)):
                carry = new_carry
                break
            carry = new_carry
        outs = self.run(body, consts + carry + xs, path)
        return [_Val(o.read(scopes), scopes) for o in outs]

    def _while(self, eqn, path, scopes, read):
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        body = eqn.params["body_jaxpr"].jaxpr
        ins = [_Val(read(v, scopes), scopes) for v in eqn.invars]
        bconsts = ins[cn:cn + bn]
        carry = ins[cn + bn:]
        for _ in range(len(self.axes) * max(1, len(carry)) + 2):
            outs = self.run(body, bconsts + carry, path)
            new_carry = [_Val(c.read(scopes) & o.read(scopes), scopes)
                         for c, o in zip(carry, outs)]
            if all(n.rep == c.read(scopes) for n, c in zip(new_carry, carry)):
                return new_carry
            carry = new_carry
        return carry

    def _cond(self, eqn, path, scopes, read):
        pred = read(eqn.invars[0], scopes)
        ops = [_Val(read(v, scopes), scopes) for v in eqn.invars[1:]]
        per_branch = [self.run(br.jaxpr, ops, path)
                      for br in eqn.params["branches"]]
        outs = []
        for i in range(len(eqn.outvars)):
            rep = frozenset.intersection(
                pred, *[b[i].read(scopes) for b in per_branch])
            outs.append(_Val(rep, scopes))
        return outs

    def _generic(self, eqn, path, scopes, env, inter, read):
        # single-sub-jaxpr HOPs whose operands map 1:1 (pjit, remat2,
        # custom_jvp/vjp call jaxprs, closed_call) recurse; anything else
        # falls back to the permissive operand intersection
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            body = _sub_jaxpr(eqn.params.get(key))
            if body is not None and len(body.invars) == len(eqn.invars):
                ins = [_Val(read(v, scopes), scopes) for v in eqn.invars]
                outs = self.run(body, ins, path)
                return [_Val(o.read(scopes), scopes) for o in outs]
        return [_Val(inter, scopes)] * len(eqn.outvars)


def _spec_axes(spec) -> frozenset:
    """Mesh axes a PartitionSpec shards over (entries: None, a name, or a
    tuple of names)."""
    return frozenset(chain.from_iterable(
        e if isinstance(e, tuple) else (e,) for e in spec if e is not None))


def _find_shard_maps(jaxpr: jcore.Jaxpr, out: list) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            out.append(eqn)
            continue
        for v in eqn.params.values():
            j = _sub_jaxpr(v)
            if j is not None:
                _find_shard_maps(j, out)
        if "branches" in eqn.params:
            for br in eqn.params["branches"]:
                _find_shard_maps(br.jaxpr, out)


def analyze_shard_maps(fn_or_jaxpr: Any, *abstract_args) -> list[ShardMapReport]:
    """Find every shard_map in a built step and lint its out_specs."""
    from repro.analysis.signature import trace_jaxpr

    j = _sub_jaxpr(fn_or_jaxpr)
    if j is None:
        j = trace_jaxpr(fn_or_jaxpr, *abstract_args).jaxpr
    eqns: list = []
    _find_shard_maps(j, eqns)
    reports = []
    for eqn in eqns:
        mesh = eqn.params["mesh"]
        axes = tuple(mesh.axis_names)
        manual = frozenset(eqn.params["manual_axes"])
        # a size-1 mesh axis cannot carry variance: specs may still name
        # it (they are written against the axis NAMES, not the degrees),
        # so it is replicated by construction everywhere
        trivial = frozenset(a for a in manual
                            if dict(mesh.shape).get(a, 1) == 1)
        body = _sub_jaxpr(eqn.params["jaxpr"])
        in_vals = [_Val((manual - _spec_axes(sp)) | trivial)
                   for sp in eqn.params["in_specs"]]
        walker = _RepWalker(manual)
        out_vals = walker.run(body, in_vals, ())
        errors = []
        out_rep = []
        for i, (sp, val) in enumerate(zip(eqn.params["out_specs"], out_vals)):
            rep = val.read(()) | trivial
            out_rep.append(rep)
            claimed = manual - _spec_axes(sp)
            for ax in sorted(claimed - rep):
                errors.append(ReplicationError(
                    out_index=i, axis=ax,
                    claimed=tuple(sorted(claimed)),
                    actual=tuple(sorted(rep))))
        reports.append(ShardMapReport(
            mesh_axes=axes, errors=tuple(errors), out_rep=tuple(out_rep),
            check_vma=bool(eqn.params["check_vma"])))
    return reports


def verify_replication(fn_or_jaxpr: Any, *abstract_args,
                       strict: bool = True) -> list[str]:
    """Lint every shard_map out_spec in a built step.

    Returns error strings (empty == every replication claim is proven);
    raises AssertionError when ``strict`` and a claim fails.  This is the
    checker that stands in for jax's ``check_vma`` on the build paths
    where that one is off — all ppermute-ring / collective-matmul plans
    (see module docstring for the ring algebra).
    """
    reports = analyze_shard_maps(fn_or_jaxpr, *abstract_args)
    if not reports:
        errs = ["no shard_map found in traced step"]
    else:
        errs = [str(e) for r in reports for e in r.errors]
    if errs and strict:
        raise AssertionError("replication lint failed:\n  "
                             + "\n  ".join(errs))
    return errs

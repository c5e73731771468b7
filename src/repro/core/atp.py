"""ATP row/column-first tensor-parallel layers (paper §3.2, Fig. 5/6).

Everything here runs *inside* ``jax.shard_map`` with ``check_vma=True``:
tensors are local shards, collectives are explicit, and JAX's
varying-manual-axes type system transposes them exactly (the backward
all-reduce of each boundary is mathematically forced — the cotangent
arrives Partial on the same mesh dim because the neighbouring GEMM's
contraction dim is sharded there).

Communication schedule per transformer layer (== paper Fig. 6 / Eq. 2):

    column-first GEMM -> boundary psum over mesh dim 2 (f1 fwd / f3 fwd)
    row-first GEMM    -> boundary psum over mesh dim 1 (f2 fwd / f4 fwd)
    + the mirrored backward psums inserted by AD

Summed per layer this is Eq. 2: 2Lbs*(7h/(d1 B2) + 2h/(d2 B1)) for GPT.

Activations between blocks carry the paper's spec [Replicate, Shard(1)]:
replicated over tp1 (mesh dim 1), feature-sharded over tp2 (mesh dim 2).

Beyond-paper boundary modes (see docs/overlap.md):

``boundary_mode``
    "psum"  — monolithic lax collectives at every boundary (paper Fig. 6).
    "ring"  — boundaries run as explicit ppermute rings from
              repro.core.overlap: the chunked GEMM is software-pipelined
              against the ring steps (a collective-matmul, §4.1 made
              structural), and jax.custom_vjp gives the backward pass the
              mirrored ring schedule instead of AD-inserted monolithic
              psums.

``seq_parallel``
    Opt-in sequence-parallel block I/O spec [Shard(seq)@ax1, Shard(f)@ax2]:
    the f2/f4 row boundaries become psum_scatter over ax1 along the
    sequence dim (half the wire bytes of the all-reduce they replace) and
    the block-entry norms fold the conjugate all-gather (rms_norm /
    layer_norm `gather_seq=True`).  Activation memory between blocks drops
    by d1.  Eq. 2's row term keeps its volume across fwd+bwd but every
    boundary op halves, which is what the overlap engine pipelines against.

Name scopes (:func:`atp_scope`): each collective site runs under
``atp:<site>[<axes>]``, which reaches every device op as its name stack:
``atp:col[..]`` / ``atp:row[..]`` for the linear boundaries in every form
(AD keeps the scope on the backward ops, under ``transpose(..)`` of the
enclosing scope), ``atp:norm[..]`` for the feature-sharded norms,
``atp:heads[..]`` for the attention core's head split and output gather,
``atp:grad_sync[..]`` for the explicit gradient reductions.  ``-`` stands
for no axis: a column boundary on a mesh with d2 = 1 still reduces its
backward over ax1.  Where ``ctx.tp`` is 1 none of these collectives runs
and no scope opens.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import overlap
from repro.core.mesh import MeshTopo, dp_axis_names, tp_axis_names


class _Removed:
    """Sentinel singleton for retired knobs (copies compare identical)."""

    def __repr__(self):
        return "<removed>"

    def __deepcopy__(self, memo):
        return self

    def __copy__(self):
        return self

    def __eq__(self, other):
        return isinstance(other, _Removed)

    def __hash__(self):
        return hash(_Removed)


#: Segment kinds whose block I/O can run the sequence-parallel spec
#: [Shard(seq)@ax1, Shard(f)@ax2]: their block-entry norms fold the
#: conjugate all-gather and their row boundaries psum_scatter back.  MoE
#: dispatch, SSM scans and the zamba/xlstm super-blocks assume
#: ax1-replicated full-sequence I/O, so their segments mask seq_parallel
#: (per-segment gating, not a whole-network error).
SEQ_PARALLEL_KINDS = frozenset({"dense", "mla_dense"})


@dataclasses.dataclass(frozen=True)
class SegmentPlan:
    """Per-segment execution knobs over the shared (d1, d2, dp) mesh.

    One entry per model segment kind (plan format_version 2): the mesh is
    global — activation layouts must agree at segment boundaries — but
    chunking, boundary implementation and the sequence-parallel spec are
    per-segment properties of each segment's communication profile.
    """

    kind: str
    chunks: int = 1
    boundary_mode: str = "psum"
    seq_parallel: bool = False
    #: boundary-collective payload dtype (plan format_version 4): "bf16"
    #: full width, "int8"/"fp8" quantized wire (overlap.WIRE_DTYPES)
    wire_dtype: str = "bf16"

    def __post_init__(self):
        if self.chunks < 1:
            raise ValueError(
                f"segment {self.kind!r}: chunks must be >= 1, got {self.chunks}")
        if self.boundary_mode not in ("psum", "ring"):
            raise ValueError(
                f"segment {self.kind!r}: boundary_mode must be 'psum' or "
                f"'ring', got {self.boundary_mode!r}")
        if self.wire_dtype not in overlap.WIRE_DTYPES:
            raise ValueError(
                f"segment {self.kind!r}: wire_dtype must be one of "
                f"{overlap.WIRE_DTYPES}, got {self.wire_dtype!r}")

    def describe(self) -> str:
        sp = "+sp" if self.seq_parallel else ""
        wd = "" if self.wire_dtype == "bf16" else f"@{self.wire_dtype}"
        return f"{self.kind}:ck{self.chunks}{self.boundary_mode}{sp}{wd}"

    def to_dict(self) -> dict:
        return {"kind": self.kind, "chunks": self.chunks,
                "boundary_mode": self.boundary_mode,
                "seq_parallel": self.seq_parallel,
                "wire_dtype": self.wire_dtype}

    @staticmethod
    def from_dict(d) -> "SegmentPlan":
        return SegmentPlan(kind=str(d["kind"]),
                           chunks=int(d.get("chunks", 1)),
                           boundary_mode=d.get("boundary_mode", "psum"),
                           seq_parallel=bool(d.get("seq_parallel", False)),
                           wire_dtype=d.get("wire_dtype", "bf16"))


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """Decode-time (serving) knobs of a ParallelPlan (format_version 3).

    Decode boundary all-reduces move ``[B, 1, h]`` activations — latency-
    bound, not bandwidth-bound — so the serve objective
    (``core.search.search_strategy_decode``) may pick a *different*
    (d1, d2) factorization and boundary implementation than the
    train/prefill search did.  ``chunks`` is pinned to 1: there is no
    per-boundary payload worth splitting at seq=1, and the chunk engine's
    per-chunk alpha would be pure overhead.  ``seq_parallel`` is
    structurally absent (a one-token step has no sequence to shard).

    Executing a decode factorization that differs from the plan's mesh
    requires building the serving stack on the decode mesh up front
    (``ParallelPlan.decode_view``); ``resolve_ctx(decode=True)`` applies
    the mesh-layout-neutral knobs (boundary_mode, chunks) either way.
    """

    d1: int
    d2: int
    boundary_mode: str = "psum"
    chunks: int = 1
    #: boundary wire dtype for decode steps (format_version 4)
    wire_dtype: str = "bf16"
    #: MTP self-speculative decode pays on this interconnect: the tick
    #: costs one extra token of boundary traffic but amortizes over
    #: 1 + accept_rate tokens (format_version 5)
    speculate: bool = False
    #: copy-on-write prefix sharing at admission (format_version 5)
    prefix_cache: bool = False
    #: modelled seconds per generated token behind the choice (provenance)
    predicted_t_step: float | None = None

    def __post_init__(self):
        if self.d1 < 1 or self.d2 < 1:
            raise ValueError(f"decode plan degrees must be >= 1: {self}")
        if self.chunks != 1:
            raise ValueError(
                f"decode plans are chunks=1 by construction (got "
                f"{self.chunks}): one-token boundaries have nothing to "
                f"pipeline and pay alpha per chunk")
        if self.boundary_mode not in ("psum", "ring"):
            raise ValueError(
                f"decode boundary_mode must be 'psum' or 'ring', got "
                f"{self.boundary_mode!r}")
        if self.wire_dtype not in overlap.WIRE_DTYPES:
            raise ValueError(
                f"decode wire_dtype must be one of {overlap.WIRE_DTYPES}, "
                f"got {self.wire_dtype!r}")

    @property
    def tp(self) -> int:
        return self.d1 * self.d2

    def describe(self) -> str:
        wd = "" if self.wire_dtype == "bf16" else f" @{self.wire_dtype}"
        sp = " +spec" if self.speculate else ""
        pc = " +pfx" if self.prefix_cache else ""
        return f"decode[({self.d1},{self.d2}) {self.boundary_mode}{wd}{sp}{pc}]"

    def to_dict(self) -> dict:
        return {"d1": self.d1, "d2": self.d2,
                "boundary_mode": self.boundary_mode, "chunks": self.chunks,
                "wire_dtype": self.wire_dtype,
                "speculate": self.speculate,
                "prefix_cache": self.prefix_cache,
                "predicted_t_step": self.predicted_t_step}

    @staticmethod
    def from_dict(d) -> "DecodePlan":
        ts = d.get("predicted_t_step")
        return DecodePlan(d1=int(d["d1"]), d2=int(d["d2"]),
                          boundary_mode=d.get("boundary_mode", "psum"),
                          chunks=int(d.get("chunks", 1)),
                          wire_dtype=d.get("wire_dtype", "bf16"),
                          speculate=bool(d.get("speculate", False)),
                          prefix_cache=bool(d.get("prefix_cache", False)),
                          predicted_t_step=(None if ts is None
                                            else float(ts)))


_USE_REDUCE_SCATTER_REMOVED = _Removed()
_USE_REDUCE_SCATTER_MSG = (
    "ATPContext.use_reduce_scatter was retired: the fused psum+slice "
    "boundary it named is exactly the reduce-scatter row boundary of the "
    "sequence-parallel block I/O spec.  Pass seq_parallel=True (or a "
    "ParallelPlan with seq_parallel=True) instead; the strategy search "
    "ranks it as part of the plan space (core.plan.plan_search)."
)


@dataclasses.dataclass(frozen=True)
class ATPContext:
    """Static distribution context threaded through all model code."""

    topo: MeshTopo
    ax1: str | None          # device-mesh dim 1 (size d1)
    ax2: str | None          # device-mesh dim 2 (size d2)
    dp_axes: tuple[str, ...]  # data-parallel axes (pod, data)
    chunks: int = 1           # chunk-based overlapping factor (paper §4.1)
    boundary_mode: Literal["psum", "ring"] = "psum"  # see module docstring
    seq_parallel: bool = False  # block I/O [Shard(seq)@ax1, Shard(f)@ax2]
    wire_dtype: str = "bf16"  # boundary payload dtype (overlap.WIRE_DTYPES)
    # per-segment knob overrides (plan format_version 2): model code asks
    # for its segment's view via ``for_segment(kind)``; the scalar knobs
    # above are the defaults for kinds with no dedicated entry
    segment_plans: tuple[SegmentPlan, ...] = ()
    # retired knob: any explicit value raises (subsumed by seq_parallel)
    use_reduce_scatter: object = dataclasses.field(
        default=_USE_REDUCE_SCATTER_REMOVED, repr=False, compare=False)

    def __post_init__(self):
        if self.use_reduce_scatter is not _USE_REDUCE_SCATTER_REMOVED:
            raise TypeError(_USE_REDUCE_SCATTER_MSG)
        if self.boundary_mode not in ("psum", "ring"):
            # a bool here is almost certainly a seed-era positional
            # use_reduce_scatter (this slot used to hold that knob)
            if isinstance(self.boundary_mode, bool):
                raise TypeError(_USE_REDUCE_SCATTER_MSG)
            raise ValueError(
                f"boundary_mode must be 'psum' or 'ring', got "
                f"{self.boundary_mode!r}")
        if self.wire_dtype not in overlap.WIRE_DTYPES:
            raise ValueError(
                f"wire_dtype must be one of {overlap.WIRE_DTYPES}, got "
                f"{self.wire_dtype!r}")

    @property
    def d1(self) -> int:
        return self.topo.axis_size(self.ax1) if self.ax1 else 1

    @property
    def d2(self) -> int:
        return self.topo.axis_size(self.ax2) if self.ax2 else 1

    @property
    def tp(self) -> int:
        return self.d1 * self.d2

    @property
    def tp_axes(self) -> tuple[str, ...]:
        """Combined TP axes, mesh-dim-1 major (for EP / head sharding)."""
        return tuple(a for a in (self.ax1, self.ax2) if a)

    @property
    def dp(self) -> int:
        return math.prod(self.topo.axis_size(a) for a in self.dp_axes) if self.dp_axes else 1

    def index1(self):
        return lax.axis_index(self.ax1) if self.ax1 else 0

    def index2(self):
        return lax.axis_index(self.ax2) if self.ax2 else 0

    def tp_index(self):
        """Flattened TP rank, mesh-dim-1 major."""
        return self.index1() * self.d2 + self.index2()

    def dp_index(self):
        idx = 0
        for a in self.dp_axes:
            idx = idx * self.topo.axis_size(a) + lax.axis_index(a)
        return idx

    # -- per-segment views (plan format_version 2) -------------------------

    def for_segment(self, kind: str) -> "ATPContext":
        """This segment kind's execution view: same mesh, per-segment
        (chunks, boundary_mode, seq_parallel).

        Falls back to the context's scalar knobs when no dedicated
        :class:`SegmentPlan` entry exists (v1 plans broadcast their global
        knobs to every segment), and masks ``seq_parallel`` for kinds
        outside :data:`SEQ_PARALLEL_KINDS` — the per-segment replacement
        for the retired whole-network "seq_parallel is dense-only" error.
        The returned view carries no ``segment_plans`` of its own.
        """
        base = self
        for seg in self.segment_plans:
            if seg.kind == kind:
                base = dataclasses.replace(
                    self, chunks=seg.chunks, boundary_mode=seg.boundary_mode,
                    seq_parallel=seg.seq_parallel,
                    wire_dtype=seg.wire_dtype, segment_plans=())
                break
        else:
            if self.segment_plans:
                base = dataclasses.replace(self, segment_plans=())
        if base.seq_parallel and kind not in SEQ_PARALLEL_KINDS:
            base = dataclasses.replace(base, seq_parallel=False)
        return base

    @property
    def any_ring(self) -> bool:
        """True if any segment (or the default knobs) runs ring boundaries."""
        return (self.boundary_mode == "ring"
                or any(s.boundary_mode == "ring" for s in self.segment_plans))

    @property
    def any_quant_wire(self) -> bool:
        """True if any segment (or the default knobs) quantizes its wire."""
        return (self.wire_dtype != "bf16"
                or any(s.wire_dtype != "bf16" for s in self.segment_plans))

    @property
    def any_seq_parallel(self) -> bool:
        """True if any knob — the scalar default (which broadcasts to
        uncovered kinds) or any per-segment entry — requests the
        sequence-parallel spec.  Capability gating is ``for_segment``'s
        job; this only answers "could any segment's view ask for it"."""
        return (self.seq_parallel
                or any(s.seq_parallel for s in self.segment_plans))


def make_context(
    topo: MeshTopo | None = None,
    chunks: int = 1,
    boundary_mode: Literal["psum", "ring"] = "psum",
    seq_parallel: bool = False,
    wire_dtype: str = "bf16",
    *,
    plan=None,
    **retired,
) -> ATPContext:
    """Build the execution context — from loose knobs or a ParallelPlan.

    ``make_context(plan=p)`` is the canonical path: the plan's topology
    (or an explicitly passed ``topo``, e.g. the dryrun's dp=16 mesh) plus
    the plan's chunks / boundary_mode / seq_parallel.  A plan whose
    (d1, d2) disagrees with the topology's TP axes is a hard error — the
    searched strategy and the executed mesh must be the same artifact.
    """
    if "use_reduce_scatter" in retired:
        raise TypeError(_USE_REDUCE_SCATTER_MSG)
    if retired:
        raise TypeError(f"make_context got unexpected kwargs "
                        f"{sorted(retired)}")
    segment_plans: tuple[SegmentPlan, ...] = ()
    if plan is not None:
        if topo is None:
            topo = plan.topo()
        chunks = plan.chunks
        boundary_mode = plan.boundary_mode
        seq_parallel = plan.seq_parallel
        wire_dtype = getattr(plan, "wire_dtype", "bf16")
        segment_plans = tuple(getattr(plan, "segments", ()) or ())
    if topo is None:
        raise TypeError("make_context needs a MeshTopo or a plan")
    ax1, ax2 = tp_axis_names(topo)
    ctx = ATPContext(
        topo=topo, ax1=ax1, ax2=ax2, dp_axes=dp_axis_names(topo),
        chunks=chunks, boundary_mode=boundary_mode, seq_parallel=seq_parallel,
        wire_dtype=wire_dtype, segment_plans=segment_plans,
    )
    if plan is not None and (ctx.d1, ctx.d2) != (plan.d1, plan.d2):
        raise ValueError(
            f"plan/topology mismatch: plan prescribes DeviceMesh"
            f"({plan.d1},{plan.d2}) but mesh TP axes give "
            f"({ctx.d1},{ctx.d2}) on {topo.axes}")
    return ctx


# ---------------------------------------------------------------------------
# Boundary collectives (f1..f4).
# ---------------------------------------------------------------------------

def atp_scope(ctx, site: str, axes):
    """``jax.named_scope("atp:<site>[<axes>]")`` (``-`` for no axis) on a
    tensor-parallel mesh; no scope where ``ctx.tp`` is 1 (no collective
    runs)."""
    if ctx.tp == 1:
        return contextlib.nullcontext()
    names = (axes if isinstance(axes, str) else "+".join(axes or ())) or "-"
    return jax.named_scope(f"atp:{site}[{names}]")


def atp_boundary(x, axis: str | None):
    """Resolve a Partial(axis) activation: all-reduce over one mesh dim.

    This is the forward of the paper's conjugate f operator; AD inserts the
    conjugate backward all-reduce automatically (vma typing)."""
    if axis is None:
        return x
    return lax.psum(x, axis)


def vma_rewrite_active(ctx) -> bool:
    """True when jax's vma rewrite types this build's shard_map bodies.

    With the rewrite active, jax inserts ``pvary`` casts wherever a
    replicated value meets varying data, and the transpose of ``pvary``
    is exactly the gradient psum that :func:`grad_sync` supplies by hand.
    It is active unless some segment's plan runs a ring boundary or a
    quantized wire: both are custom-VJP collectives whose backward runs a
    mirrored (rank-partial) cotangent reduction, which vma typing cannot
    express, so those builds pass ``check_vma=False``.  Callers use this
    to avoid double-reducing on rewrite builds and to decide
    ``check_vma`` for whole-step shard_maps (one source of truth).
    """
    return not (ctx.any_ring or ctx.any_quant_wire)


def grad_sync(ctx, x, axes):
    """Identity forward, ``psum(ct, axes)`` backward.

    TP-replicated params whose cotangent is rank-partial (norm scales and
    biases — every norm feeds a column boundary whose output is
    ax1-sharded, so the scale grad sums only the local columns' / local
    tokens' contributions; MoE router and qk-norm gains, whose cotangent
    flows back from rank-local experts/heads) drift apart across ranks
    without an explicit gradient all-reduce.  Wrapping the param in this
    barrier at its use site restores the reduction the vma replication
    lint (``repro.analysis.replication``) demands — it is the classic
    Megatron sequence-parallel "grads of RMSNorm need all-reduce" fix,
    which applies to ATP's ax2-sharded-feature norms on every mesh with
    d1 > 1, sequence-parallel or not.

    No-op when the vma rewrite is active (see :func:`vma_rewrite_active`):
    there jax's own ``pvary`` transpose performs the identical reduction,
    and stacking this barrier on top would double-count the gradient.
    """
    if not axes or vma_rewrite_active(ctx):
        return x
    with atp_scope(ctx, "grad_sync", axes):
        return _grad_sync(x, axes if isinstance(axes, str) else tuple(axes))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_sync(x, axes):
    return x


def _grad_sync_fwd(x, axes):
    return x, None


def _grad_sync_bwd(axes, _res, ct):
    return (lax.psum(ct, axes),)


_grad_sync.defvjp(_grad_sync_fwd, _grad_sync_bwd)


def atp_gather(x, axis: str | None, dim: int):
    """all-gather fwd (reduce-scatter bwd): the paper's 'gather the output
    tensor before the Output Linear'."""
    if axis is None:
        return x
    return lax.all_gather(x, axis, axis=dim, tiled=True)


def atp_reduce_scatter(x, axis: str | None, dim: int):
    """Beyond-paper fused boundary: psum+shard_slice as one reduce-scatter."""
    if axis is None:
        return x
    return lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)


# ---------------------------------------------------------------------------
# Sequence-parallel block I/O helpers (spec [Shard(seq)@ax1, Shard(f)@ax2]).
# ---------------------------------------------------------------------------

def seq_scatter(ctx: ATPContext, x, dim: int = 1):
    """Free slice of an ax1-replicated activation to this rank's seq shard
    (entry into the sequence-parallel domain, e.g. after the embedding)."""
    if not ctx.seq_parallel or ctx.ax1 is None:
        return x
    if x.shape[dim] % ctx.d1:
        raise ValueError(
            f"seq_parallel requires seq ({x.shape[dim]}) divisible by d1={ctx.d1}")
    return shard_slice(x, ctx.index1(), ctx.d1, dim)


def seq_gather(ctx: ATPContext, x, dim: int = 1):
    """all-gather a seq-sharded activation back to full sequence over ax1
    (the conjugate of the psum_scatter row boundary)."""
    if not ctx.seq_parallel or ctx.ax1 is None:
        return x
    if ctx.boundary_mode == "ring":
        return overlap.ring_all_gather(x, ctx.ax1, ctx.d1, dim)
    return lax.all_gather(x, ctx.ax1, axis=dim, tiled=True)


# ---------------------------------------------------------------------------
# Row/column-first linear layers.
# ---------------------------------------------------------------------------

def _chunked_boundary_matmul(ctx: ATPContext, x, w, axis, b=None):
    """Chunk-based overlapping (paper §4.1).

    Split the leading (batch) dim into `ctx.chunks` chunks (uneven leading
    dims use jnp.array_split); each chunk's GEMM + all-reduce chain is
    data-independent of the others.  In "psum" mode the overlap is left to
    XLA's latency-hiding scheduler; in "ring" mode the collective is an
    explicit ppermute ring issued between consecutive chunk GEMMs
    (overlap.overlap_matmul_ar).  The bias add is fused into each chunk's
    post-boundary epilogue rather than a separate full-tensor add.
    Semantically identical to the unchunked op.  ``ctx.wire_dtype`` swaps
    every boundary for its quantized-wire variant (scale-per-chunk; see
    overlap.wire_quantize).
    """
    d = ctx.d2 if axis == ctx.ax2 else ctx.d1
    if ctx.boundary_mode == "ring":
        return overlap.overlap_matmul_ar(x, w, axis, d, ctx.chunks, b=b,
                                         wire_dtype=ctx.wire_dtype)
    quant = ctx.wire_dtype != "bf16" and axis is not None

    def _boundary(y):
        if quant:
            return overlap.quant_psum(y, axis, ctx.wire_dtype)
        return atp_boundary(y, axis)

    c = max(1, min(ctx.chunks, x.shape[0]))
    if c <= 1:
        y = _boundary(jnp.einsum("...k,kn->...n", x, w))
        return y + b if b is not None else y
    xs = (jnp.split(x, c, axis=0) if x.shape[0] % c == 0
          else jnp.array_split(x, c, axis=0))
    ys = []
    for xc in xs:
        yc = _boundary(jnp.einsum("...k,kn->...n", xc, w))
        ys.append(yc + b if b is not None else yc)
    return jnp.concatenate(ys, axis=0)


def atp_linear(
    ctx: ATPContext,
    x,
    w,
    b=None,
    *,
    kind: Literal["col", "row"],
    chunked: bool = True,
):
    """Distributed Y = XW (+b) with ATP sharding.

    column-first (paper Fig. 5 right):
        W global [K, N] sharded [Shard(1)@ax1, Shard(0)@ax2]
        (local shard [K/d2, N/d1]); X local [..., K/d2] (block I/O spec
        [Replicate, Shard(-1)]); local GEMM output is Partial over ax2 ->
        boundary psum(ax2) -> [..., N/d1]: ax1-feature-sharded,
        ax2-replicated.
    row-first (paper Fig. 5 left):
        W global [K, N] sharded [Shard(0)@ax1, Shard(1)@ax2]
        (local [K/d1, N/d2]); X local [..., K/d1]; local GEMM output is
        Partial over ax1 -> boundary psum(ax1) -> [..., N/d2]: back to the
        block I/O spec [Replicate, Shard(-1)].

    With ``ctx.seq_parallel`` the row boundary becomes a psum_scatter over
    ax1 along the sequence dim (x.ndim - 2), leaving the output in the
    sequence-parallel block I/O spec [Shard(seq)@ax1, Shard(-1)@ax2].

    Bias is sharded like the GEMM output dim and applied in the boundary
    epilogue (psum is linear; keeps the bias gradient exact and local).
    The GEMM and its boundary run under ``atp:<kind>[<axis>]``.
    """
    axis = ctx.ax2 if kind == "col" else ctx.ax1
    with atp_scope(ctx, kind, axis):
        return _linear(ctx, x, w, b, kind, axis, chunked)


def _linear(ctx: ATPContext, x, w, b, kind: str, axis, chunked: bool):
    quant = ctx.wire_dtype != "bf16" and axis is not None
    if (ctx.seq_parallel and kind == "row" and axis is not None
            and x.ndim >= 3):
        seq_dim = x.ndim - 2
        ring = ctx.boundary_mode == "ring" and x.shape[seq_dim] % ctx.d1 == 0
        if quant:
            y = overlap.quant_reduce_scatter(
                jnp.einsum("...k,kn->...n", x, w), axis, ctx.d1, seq_dim,
                ctx.wire_dtype, ring)
        elif ring:
            y = overlap.overlap_matmul_rs(x, w, axis, ctx.d1, seq_dim)
        else:
            y = atp_reduce_scatter(
                jnp.einsum("...k,kn->...n", x, w), axis, seq_dim)
        return y + b if b is not None else y
    if chunked and ctx.chunks > 1 and x.ndim >= 2:
        return _chunked_boundary_matmul(ctx, x, w, axis, b)
    if ctx.boundary_mode == "ring" and axis is not None:
        d = ctx.d2 if kind == "col" else ctx.d1
        g = jnp.einsum("...k,kn->...n", x, w)
        y = (overlap.quant_ring_all_reduce(g, axis, d, ctx.wire_dtype)
             if quant else overlap.ring_all_reduce(g, axis, d))
    elif quant:
        y = overlap.quant_psum(jnp.einsum("...k,kn->...n", x, w), axis,
                               ctx.wire_dtype)
    else:
        y = atp_boundary(jnp.einsum("...k,kn->...n", x, w), axis)
    if b is not None:
        y = y + b
    return y


def gather_features(ctx, x, invariant: bool):
    """Tiled all-gather of ``x``'s last dim over ax2.

    ``invariant`` types the result replicated over ax2, as a recurrence
    that carries a cached state needs.  jax 0.9 has no public invariant
    all-gather, so that case places the local shard in zeros and psums:
    an all-reduce of the full width, about twice an all-gather's bytes.
    :func:`gather_features_op` names the collective each case emits."""
    if not invariant:
        return lax.all_gather(x, ctx.ax2, axis=-1, tiled=True)
    full = jnp.zeros(x.shape[:-1] + (x.shape[-1] * ctx.d2,), x.dtype)
    return lax.psum(lax.dynamic_update_slice_in_dim(
        full, x, ctx.index2() * x.shape[-1], axis=x.ndim - 1), ctx.ax2)


def gather_features_op(invariant: bool) -> str:
    """The collective :func:`gather_features` emits."""
    return "psum" if invariant else "all_gather"


def shard_slice(x, index, nshards: int, dim: int):
    """Local slice of dim `dim` into `nshards` parts at `index` (the paper's
    free 'scatter' of a replicated tensor)."""
    if nshards == 1:
        return x
    size = x.shape[dim] // nshards
    return lax.dynamic_slice_in_dim(x, index * size, size, axis=dim)


# ---------------------------------------------------------------------------
# Attention-core scatter/gather (paper §3.2.1): fully shard the core over
# the *combined* d1*d2 ranks.  Head-count shortfall is covered by also
# sharding the batch dim (policy: DESIGN.md §6).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CoreSharding:
    """How the attention/SSM core shards over ax2 (it is already sharded
    over ax1 by the column-first QKV projection): d2 = h2 (more heads) *
    b2 (batch)."""

    h2: int
    b2: int


def plan_core_sharding(ctx: ATPContext, heads_after_ax1: int, batch_local: int) -> CoreSharding:
    h2 = math.gcd(heads_after_ax1, ctx.d2)
    b2 = ctx.d2 // h2
    if batch_local % b2:
        raise ValueError(
            f"cannot shard attention core: {heads_after_ax1} heads vs d2={ctx.d2} "
            f"leaves batch factor {b2}, but local batch is {batch_local}"
        )
    return CoreSharding(h2=h2, b2=b2)


def core_scatter(ctx: ATPContext, x, cs: CoreSharding, head_dim: int, batch_dim: int = 0):
    """Slice (free) the ax2-replicated tensor to this rank's core shard."""
    if ctx.ax2 is None:
        return x
    i2 = ctx.index2()
    x = shard_slice(x, i2 // cs.b2, cs.h2, head_dim)
    x = shard_slice(x, i2 % cs.b2, cs.b2, batch_dim)
    return x


def core_gather(ctx: ATPContext, y, cs: CoreSharding, head_dim: int, batch_dim: int = 0):
    """all-gather the core output back to ax2-replicated layout."""
    if ctx.ax2 is None:
        return y
    if cs.b2 == 1:
        return atp_gather(y, ctx.ax2, head_dim)
    if cs.h2 == 1:
        return atp_gather(y, ctx.ax2, batch_dim)
    g = lax.all_gather(y, ctx.ax2, axis=0, tiled=False)  # [d2, ...]
    g = g.reshape((cs.h2, cs.b2) + y.shape)
    parts_b = jnp.concatenate([g[:, i] for i in range(cs.b2)], axis=batch_dim + 1)
    return jnp.concatenate([parts_b[i] for i in range(cs.h2)], axis=head_dim)

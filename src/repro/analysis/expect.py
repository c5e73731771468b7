"""Expected collective signatures: what a plan SHOULD emit.

This module is the other half of the conformance linter: given a
ModelConfig + :class:`~repro.core.plan.ParallelPlan` + phase it derives,
in pure Python (no tracing), the exact collective inventory the cost
model priced — per region (``seg{i}:{kind}`` / ``shell:*``), per op, per
mesh axes, with payload element counts at the dtype each payload is held
in.  ``check_conformance`` diffs it against an extracted
:class:`~repro.analysis.signature.StepSignature` and reports
segment-specific errors, e.g.::

    seg1:moe fwd: expected 2x all_to_all[tp1+tp2], found 4
    seg0:dense fwd: psum[tp2] raw bytes 32768 != expected 16384
    seg0:dense fwd: expected quantized psum[tp2] (int8 wire), found
    full-width

The emitters mirror the execution dispatch *decision for decision*:
``ATPContext.for_segment`` (per-segment knob views, seq_parallel
masking), ``resolve_ctx(decode=True)`` (decode sub-plan knob
application), ``atp_linear`` (sp-row reduce-scatter vs ring vs quant;
chunk clamp ``c = min(chunks, local_batch)``), ``overlap.ring_all_reduce``
(``_pick_ring_dim`` + the bidirectional split rule) and every model
block's boundary schedule.  Payload byte conventions match
``analysis.signature`` / ``launch.hlo_analysis``: all-reduce counts
operand bytes, all-gather/all-to-all/ppermute count result bytes,
reduce-scatter counts operand (result x group) bytes.  Quantized
payloads are *held* in f32 (the grid trick in ``core.overlap``) — the
expectation prices them at f32 raw bytes with ``quant=True``, exactly
like the extractor.

Forward regions are checked exactly (counts + bytes); the backward pass
is checked structurally (a ring-planned segment must run ppermute rings
backward, a psum-planned one must not, a quantized boundary's cotangent
must ride the quantized wire) — AD owns the exact backward schedule.
"""
from __future__ import annotations

import dataclasses
import math
from collections import defaultdict

from repro.configs.base import ModelConfig, segments
from repro.core.atp import gather_features_op

ACT = "bfloat16"
F32 = "float32"
I32 = "int32"

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int32": 4}

#: phases the expectation engine understands (paged steps carry extra
#: scheduler plumbing and are covered by the byte-drift benchmarks, not
#: the exact linter)
PHASES = ("train", "prefill", "decode")


class PlanConformanceError(AssertionError):
    """A compiled step's collectives disagree with the plan that priced it."""


@dataclasses.dataclass(frozen=True)
class Exp:
    """One expected line item: ``count`` invocations of ``op`` over
    ``axes`` moving ``elems`` elements TOTAL (summed across the count) of
    ``dtype``.  ``elems=None`` is the pressure valve: count is checked,
    bytes are not."""

    op: str
    axes: tuple[str, ...]
    count: int
    elems: int | None
    dtype: str = ACT
    quant: bool = False

    @property
    def raw_bytes(self) -> int:
        if self.elems is None:
            return 0
        return self.elems * _DTYPE_BYTES[self.dtype]


@dataclasses.dataclass(frozen=True)
class View:
    """Pure-Python mirror of one segment's ``ATPContext.for_segment``
    view: mesh degrees + effective knobs (after per-segment override,
    seq-parallel masking and decode sub-plan application)."""

    d1: int
    d2: int
    dp: int
    chunks: int = 1
    boundary_mode: str = "psum"
    seq_parallel: bool = False
    wire_dtype: str = "bf16"
    act: str = ACT

    @property
    def ax1(self) -> str | None:
        return "tp1" if self.d1 > 1 else None

    @property
    def ax2(self) -> str | None:
        return "tp2" if self.d2 > 1 else None

    @property
    def tp(self) -> int:
        return self.d1 * self.d2

    @property
    def tp_axes(self) -> tuple[str, ...]:
        return tuple(a for a in (self.ax1, self.ax2) if a)

    @property
    def quant(self) -> bool:
        return self.wire_dtype != "bf16"


@dataclasses.dataclass(frozen=True)
class Expectation:
    """Per-region expected collectives + structural backward rules."""

    regions: dict[str, tuple[Exp, ...]]
    phase: str
    notes: tuple[str, ...] = ()

    def by_key(self) -> dict[tuple, tuple[int, int, bool]]:
        """{(region, op, axes, quant): (count, raw_bytes, bytes_known)}."""
        agg: dict[tuple, list] = defaultdict(lambda: [0, 0, True])
        for region, exps in self.regions.items():
            for e in exps:
                a = agg[(region, e.op, e.axes, e.quant)]
                a[0] += e.count
                a[1] += e.raw_bytes
                if e.elems is None:
                    a[2] = False
        return {k: (v[0], v[1], v[2]) for k, v in agg.items()}

    def op_bytes(self) -> dict[str, int]:
        """{op: raw bytes} — comparable with StepSignature.op_bytes()."""
        agg: dict[str, int] = defaultdict(int)
        for exps in self.regions.values():
            for e in exps:
                agg[e.op] += e.raw_bytes
        return dict(agg)

    def describe(self) -> str:
        lines = []
        for key, (n, rb, known) in sorted(self.by_key().items()):
            region, op, axes, quant = key
            ax = "+".join(axes) or "-"
            q = " quant" if quant else ""
            b = f"raw={rb}" if known else "raw=?"
            lines.append(f"{region}: {n}x{op}[{ax}]{q} {b}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Knob resolution (mirrors ATPContext.for_segment + resolve_ctx).
# ---------------------------------------------------------------------------

#: kinds whose block I/O may run the sequence-parallel spec — must match
#: repro.core.atp.SEQ_PARALLEL_KINDS (asserted by tests)
SEQ_PARALLEL_KINDS = frozenset({"dense", "mla_dense"})


def _segment_view(plan, kind: str, decode: bool, act: str = ACT) -> View:
    sp = plan.segment_plan(kind)
    chunks, bm, seqp, wd = (sp.chunks, sp.boundary_mode, sp.seq_parallel,
                            sp.wire_dtype)
    if decode and getattr(plan, "decode", None) is not None:
        dec = plan.decode
        chunks, bm, wd = dec.chunks, dec.boundary_mode, dec.wire_dtype
    if decode or kind not in SEQ_PARALLEL_KINDS:
        seqp = False
    return View(d1=plan.d1, d2=plan.d2, dp=plan.dp * plan.pods,
                chunks=chunks, boundary_mode=bm, seq_parallel=seqp,
                wire_dtype=wd, act=act)


def _shell_view(plan, decode: bool, act: str = ACT) -> View:
    """The scalar-knob context the model shell (embed/exit/head/mtp) runs
    under — the plan's global knobs with decode overrides, sp as-is (the
    shell consults per-site sp decisions separately)."""
    chunks, bm, wd = plan.chunks, plan.boundary_mode, plan.wire_dtype
    if decode and getattr(plan, "decode", None) is not None:
        dec = plan.decode
        chunks, bm, wd = dec.chunks, dec.boundary_mode, dec.wire_dtype
    return View(d1=plan.d1, d2=plan.d2, dp=plan.dp * plan.pods,
                chunks=chunks, boundary_mode=bm, seq_parallel=False,
                wire_dtype=wd, act=act)


# ---------------------------------------------------------------------------
# Low-level boundary emitters (mirror core.atp / core.overlap dispatch).
# ---------------------------------------------------------------------------


def _pick_ring_dim(shape, d: int):
    best, best_size = None, 0
    for i, s in enumerate(shape):
        if s % d == 0 and s > best_size:
            best, best_size = i, s
    return best


def _prod(shape) -> int:
    return math.prod(shape)


def _wireq(ax: str) -> Exp:
    """wire_quantize's shared-scale pmax (scalar amax, f32).  It runs
    inside the ``quant[axis]`` scope so the extractor tags it quantized —
    mirror that here so the keys line up."""
    return Exp("pmax", (ax,), 1, 1, F32, quant=True)


def _ring_ar(shape, d: int, ax: str, dtype: str, quant: bool) -> list[Exp]:
    """overlap.ring_all_reduce on a local tensor of ``shape``."""
    E = _prod(shape)
    dim = _pick_ring_dim(shape, d)
    if dim is None:  # monolithic fallback inside the ring_ar scope
        return [Exp("psum", (ax,), 1, E, dtype, quant)]
    if shape[dim] % (2 * d) == 0:  # bidirectional: halves circle both ways
        return [Exp("ppermute", (ax,), 4 * (d - 1),
                    4 * (d - 1) * (E // (2 * d)), dtype, quant)]
    return [Exp("ppermute", (ax,), 2 * (d - 1), 2 * (d - 1) * (E // d),
                dtype, quant)]


def _one_boundary(v: View, shape, ax: str, d: int) -> list[Exp]:
    """One monolithic boundary all-reduce of a local ``shape`` payload
    (atp_linear's non-sp tail: ring / quant / plain psum)."""
    E = _prod(shape)
    if v.boundary_mode == "ring":
        out = [_wireq(ax)] if v.quant else []
        return out + _ring_ar(shape, d, ax, F32 if v.quant else v.act, v.quant)
    if v.quant:
        return [_wireq(ax), Exp("psum", (ax,), 1, E, F32, True)]
    return [Exp("psum", (ax,), 1, E, v.act)]


def _chunk_sizes(b: int, chunks: int) -> list[int]:
    """jnp.split / jnp.array_split sizes for the leading (batch) dim."""
    c = max(1, min(chunks, b))
    if b % c == 0:
        return [b // c] * c
    hi, rem = divmod(b, c)
    return [hi + 1] * rem + [hi] * (c - rem)


def _linear(v: View, b: int, s: int, out_loc: int, kind: str) -> list[Exp]:
    """atp_linear's boundary collectives for a [b, s, K_loc] @ W GEMM with
    local output width ``out_loc``."""
    ax = v.ax2 if kind == "col" else v.ax1
    d = v.d2 if kind == "col" else v.d1
    if ax is None:
        return []
    E = b * s * out_loc
    if v.seq_parallel and kind == "row":
        ring = v.boundary_mode == "ring" and s % v.d1 == 0
        if v.quant:
            out = [_wireq(ax)]
            if ring:  # quant ring reduce-scatter: d-1 hops of one block
                out.append(Exp("ppermute", (ax,), d - 1,
                               (d - 1) * (E // d), F32, True))
            else:
                out.append(Exp("reduce_scatter", (ax,), 1, E, F32, True))
            return out
        if ring:  # collective matmul (cm_rs): d-1 hops of the acc block
            return [Exp("ppermute", (ax,), d - 1, (d - 1) * (E // d), v.act)]
        return [Exp("reduce_scatter", (ax,), 1, E, v.act)]
    if v.chunks > 1:
        out = []
        for bc in _chunk_sizes(b, v.chunks):
            out += _one_boundary(v, (bc, s, out_loc), ax, d)
        return out
    return _one_boundary(v, (b, s, out_loc), ax, d)


def _norm(v: View, cfg: ModelConfig, b: int, s_norm: int,
          gather: bool = False, feat: int | None = None) -> list[Exp]:
    """layers.norm: 1 (rms) / 2 (layernorm) tiny f32 psum(ax2) over the
    keepdims reduction, optionally folding the conjugate seq all-gather."""
    out = []
    n_psum = 2 if cfg.norm_kind == "layernorm" else 1
    if v.ax2:
        out.append(Exp("psum", (v.ax2,), n_psum, n_psum * b * s_norm, F32))
    if gather:
        out += _seq_gather(v, b, s_norm, feat)
    return out


def _seq_gather(v: View, b: int, s_loc: int, feat: int) -> list[Exp]:
    """atp.seq_gather: AG(ax1) back to full sequence (ring_ag when the
    segment runs ring boundaries)."""
    if not v.seq_parallel or v.ax1 is None:
        return []
    if v.boundary_mode == "ring":
        return [Exp("ppermute", (v.ax1,), v.d1 - 1,
                    (v.d1 - 1) * b * s_loc * feat, v.act)]
    return [Exp("all_gather", (v.ax1,), 1, b * s_loc * v.d1 * feat, v.act)]


# ---------------------------------------------------------------------------
# Attention / block emitters (mirror models.*).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _AttnPlan:
    g: int
    q_loc: int
    r: int
    h2: int
    q_regroup: bool
    kv_regroup: bool


def _attn_plan(H: int, KV: int, d1: int, d2: int) -> _AttnPlan:
    n = d1 * d2
    q_regroup = H % d1 != 0
    if q_regroup:
        g = math.gcd(H, n)
        h2 = 1
    else:
        h2 = math.gcd(H // d1, d2)
        g = d1 * h2
    return _AttnPlan(g=g, q_loc=H // g, r=n // g, h2=h2,
                     q_regroup=q_regroup, kv_regroup=KV % d1 != 0)


def _attn(v: View, cfg: ModelConfig, b: int, s: int, decode: bool) -> list[Exp]:
    """transformer.attn_block: fused f1 psum, head-regroup gathers, the
    core output gather, the f2 row boundary."""
    out = []
    hd = cfg.hd
    if v.ax2:  # f1: fused qkv boundary (always a plain psum)
        out.append(Exp("psum", (v.ax2,), 1,
                       b * s * (cfg.q_dim + 2 * cfg.kv_dim) // v.d1, v.act))
    ap = _attn_plan(cfg.num_heads, cfg.num_kv_heads, v.d1, v.d2)
    if ap.q_regroup and v.ax1:
        out.append(Exp("all_gather", (v.ax1,), 1, b * s * cfg.q_dim, v.act))
    if ap.kv_regroup and v.ax1:
        out.append(Exp("all_gather", (v.ax1,), 2, 2 * b * s * cfg.kv_dim, v.act))
    # core output gather (layers.core_output_gather)
    seq_split = not decode
    s_r = s // ap.r if (seq_split and ap.r > 1) else s
    F = ap.q_loc * hd
    if v.tp > 1:
        if ap.q_regroup:  # untiled AG over BOTH tp axes
            out.append(Exp("all_gather", v.tp_axes, 1,
                           v.tp * b * s_r * F, v.act))
        elif v.ax2:       # untiled AG over ax2
            out.append(Exp("all_gather", (v.ax2,), 1,
                           v.d2 * b * s_r * F, v.act))
    # f2: row-first output projection
    out += _linear(v, b, s, cfg.d_model // v.d2, "row")
    return out


def _mlp(v: View, cfg: ModelConfig, b: int, s: int,
         d_ff: int | None = None) -> list[Exp]:
    """transformer.mlp_block: fused up(+gate) col boundary + row down."""
    ff = d_ff if d_ff is not None else cfg.d_ff
    n_cols = 2 * ff if cfg.mlp_kind in ("swiglu", "geglu") else ff
    out = _linear(v, b, s, n_cols // v.d1, "col")
    out += _linear(v, b, s, cfg.d_model // v.d2, "row")
    return out


def _dense_layer(v: View, cfg: ModelConfig, b: int, S: int, decode: bool,
                 d_ff: int | None = None) -> list[Exp]:
    sp = v.seq_parallel and not decode
    s_norm = S // v.d1 if sp else S
    hl = cfg.d_model // v.d2
    nv = dataclasses.replace(v, seq_parallel=sp)
    out = _norm(nv, cfg, b, s_norm, gather=sp, feat=hl)
    out += _attn(nv, cfg, b, S, decode)
    if cfg.post_block_norms:
        out += _norm(nv, cfg, b, s_norm)
    out += _norm(nv, cfg, b, s_norm, gather=sp, feat=hl)
    out += _mlp(nv, cfg, b, S, d_ff)
    if cfg.post_block_norms:
        out += _norm(nv, cfg, b, s_norm)
    return out


def _moe_ffn(v: View, cfg: ModelConfig, b: int, s: int) -> list[Exp]:
    """moe.moe_block: EP dispatch over the flat TP group."""
    mc = cfg.moe
    n, h = v.tp, cfg.d_model
    hl = h // v.d2
    T = b * s
    out = []
    if T % n != 0 or T // n == 0:  # replicated dispatch (decode-sized)
        if v.ax2:
            out.append(Exp("all_gather", (v.ax2,), 1, T * h, v.act))
        if v.tp_axes:
            out.append(Exp("psum", v.tp_axes, 1, 1, F32))        # aux loss
            out.append(Exp("psum", v.tp_axes, 1, T * h, v.act))    # combine
    else:
        if v.ax2:  # token scatter: swap token-shard for feature-gather
            out.append(Exp("all_to_all", (v.ax2,), 1, T * hl, v.act))
        if v.tp_axes:
            out.append(Exp("psum", v.tp_axes, 1, 1, F32))        # aux loss
            tn = T // n
            cap = max(1, int(mc.capacity_factor * tn * mc.top_k
                             / mc.num_experts))
            e_loc = max(1, mc.num_experts // n)
            buf = n * e_loc * cap * h
            out.append(Exp("all_to_all", v.tp_axes, 2, 2 * buf, v.act))
        if v.ax1:  # token gather back: place + psum (ax1-invariant)
            out.append(Exp("psum", (v.ax1,), 1, (T // v.d2) * h, v.act))
        if v.ax2:
            out.append(Exp("all_to_all", (v.ax2,), 1, T * hl, v.act))
    if mc.num_shared:
        out += _mlp(v, cfg, b, s, d_ff=mc.d_ff_expert * mc.num_shared)
    return out


def _moe_layer(v: View, cfg: ModelConfig, b: int, S: int,
               decode: bool) -> list[Exp]:
    out = _norm(v, cfg, b, S)
    out += _attn(v, cfg, b, S, decode)
    out += _norm(v, cfg, b, S)
    out += _moe_ffn(v, cfg, b, S)
    return out


def _mla_layer(v: View, cfg: ModelConfig, b: int, S: int, decode: bool,
               moe: bool) -> list[Exp]:
    m = cfg.mla
    sp = v.seq_parallel and not decode
    s_norm = S // v.d1 if sp else S
    hl = cfg.d_model // v.d2
    nv = dataclasses.replace(v, seq_parallel=sp)
    out = _norm(nv, cfg, b, s_norm, gather=sp, feat=hl)
    if v.ax2:  # latent down-projections: replicated outputs via psum(ax2)
        out.append(Exp("psum", (v.ax2,), 1, b * S * m.q_lora_rank, v.act))
        out.append(Exp("psum", (v.ax2,), 1,
                       b * S * (m.kv_lora_rank + m.qk_rope_head_dim), v.act))
    if v.ax2:  # core output gather back to ax1-sharded layout
        out.append(Exp("all_gather", (v.ax2,), 1,
                       b * S * (cfg.num_heads // v.d1) * m.v_head_dim, v.act))
    out += _linear(nv, b, S, cfg.d_model // v.d2, "row")   # wo
    out += _norm(nv, cfg, b, s_norm, gather=sp, feat=hl)
    if moe:
        out += _moe_ffn(v, cfg, b, S)
    else:
        out += _mlp(nv, cfg, b, S)
    return out


def _mamba_layer(v: View, cfg: ModelConfig, b: int, S: int) -> list[Exp]:
    sc = cfg.ssm
    d_inner = sc.expand * cfg.d_model
    nheads = d_inner // sc.head_dim
    out = []
    if v.ax2:
        out.append(Exp("psum", (v.ax2,), 1, b * S, F32))              # rms
        out.append(Exp("psum", (v.ax2,), 1,
                       b * S * 2 * d_inner // v.d1, v.act))             # z|x
        out.append(Exp("psum", (v.ax2,), 1,
                       b * S * (2 * sc.d_state + nheads), v.act))       # B/C/dt
        out.append(Exp("all_gather", (v.ax2,), 1,
                       b * S * d_inner // v.d1, v.act))                 # heads
    out += _linear(v, b, S, cfg.d_model // v.d2, "row")               # w_out
    return out


def _zamba_super(v: View, cfg: ModelConfig, b: int, S: int, decode: bool,
                 inner: int) -> list[Exp]:
    h = cfg.d_model
    out = []
    if v.ax2:  # shared-attn entry: two fused column projections, one psum
        out.append(Exp("psum", (v.ax2,), 1, b * S * h // v.d1, v.act))
    if v.ax1:  # _gather_ax1_invariant: place + psum
        out.append(Exp("psum", (v.ax1,), 1, b * S * h, v.act))
    out += _dense_layer(v, cfg, b, S, decode)
    for _ in range(inner - 1):
        out += _mamba_layer(v, cfg, b, S)
    return out


def _xlstm_super(v: View, cfg: ModelConfig, b: int, S: int,
                 inner: int, decode: bool) -> list[Exp]:
    d_inner = int(cfg.ssm.proj_factor * cfg.d_model)
    nh = cfg.num_heads
    dk = (d_inner // nh) // 2
    h = cfg.d_model
    mlstm: list[Exp] = []
    if v.ax2:
        mlstm.append(Exp("psum", (v.ax2,), 1, b * S, F32))            # rms
        mlstm.append(Exp("psum", (v.ax2,), 1,
                         b * S * 2 * d_inner // v.d1, v.act))           # up|z
        mlstm.append(Exp("psum", (v.ax2,), 1,
                         b * S * 2 * nh * dk // v.d1, v.act))           # q|k
        mlstm.append(Exp("psum", (v.ax2,), 1, b * S * 2 * nh, v.act))   # i|f
    if v.ax1:
        mlstm.append(Exp("all_gather", (v.ax1,), 1,
                         b * S * 2 * nh * dk, v.act))                   # q|k
    if v.tp_axes:  # down projection: all-reduce over BOTH mesh dims
        mlstm.append(Exp("psum", v.tp_axes, 1, b * S * h, v.act))
    out = _times(mlstm, inner - 1)
    if v.ax2:  # sLSTM runs on full-h replicated activations (invariant
        # over ax2 in decode, where a cached state meets them)
        out.append(Exp(gather_features_op(decode), (v.ax2,), 1, b * S * h,
                       v.act))
    return out


def _layer_exps(seg, v: View, cfg: ModelConfig, b: int, S: int,
                decode: bool) -> list[Exp]:
    if seg.kind == "dense":
        return _dense_layer(v, cfg, b, S, decode)
    if seg.kind == "moe":
        return _moe_layer(v, cfg, b, S, decode)
    if seg.kind in ("mla_dense", "mla_moe"):
        return _mla_layer(v, cfg, b, S, decode, moe=seg.kind == "mla_moe")
    if seg.kind == "mamba":
        return _mamba_layer(v, cfg, b, S)
    if seg.kind == "zamba":
        return _zamba_super(v, cfg, b, S, decode, seg.inner)
    if seg.kind == "xlstm":
        return _xlstm_super(v, cfg, b, S, seg.inner, decode)
    raise ValueError(seg.kind)


def _times(exps: list[Exp], k: int) -> list[Exp]:
    if k <= 0:
        return []
    return [dataclasses.replace(
        e, count=e.count * k,
        elems=None if e.elems is None else e.elems * k) for e in exps]


# ---------------------------------------------------------------------------
# Whole-step expectation.
# ---------------------------------------------------------------------------


def expected_signature(cfg: ModelConfig, plan, phase: str, batch: int,
                       seq: int) -> Expectation:
    """Derive the collective signature a built step SHOULD have.

    ``batch`` is the GLOBAL batch (the builders shard it over the data
    axes); ``seq`` is the full sequence for train/prefill and the token
    step width (normally 1) for decode.  Decode expectations mirror
    ``resolve_ctx(decode=True)``: the plan's :class:`DecodePlan` knobs
    replace chunks/boundary_mode/wire_dtype in every segment view and
    seq_parallel is masked everywhere.  A deployment serving on the
    decode mesh passes ``plan.decode_view()`` here, exactly as it does to
    the builders.
    """
    if phase not in PHASES:
        raise ValueError(f"phase must be one of {PHASES}, got {phase!r}")
    decode = phase == "decode"
    segs = segments(cfg)
    # activations are held at the model compute dtype (the reduced smoke
    # configs run float32; everything production-sized runs bf16)
    act = cfg.dtype if cfg.dtype in _DTYPE_BYTES else ACT
    views = [_segment_view(plan, s.kind, decode, act) for s in segs]
    sv = _shell_view(plan, decode, act)
    dpn = plan.dp * plan.pods
    b = batch // dpn if (dpn > 1 and batch % dpn == 0) else batch
    S = seq
    hl = cfg.d_model // plan.d2
    notes = []

    regions: dict[str, list[Exp]] = {}
    entry_view = views[0] if views else sv
    entry_sp = entry_view.seq_parallel

    # -- shell:embed -------------------------------------------------------
    emb: list[Exp] = []
    if cfg.frontend != "vision_patches":
        if entry_sp and entry_view.ax1:
            emb.append(Exp("reduce_scatter", (entry_view.ax1,), 1,
                           b * S * hl, entry_view.act))
        elif entry_view.ax1:
            emb.append(Exp("psum", (entry_view.ax1,), 1, b * S * hl, entry_view.act))
    regions["shell:embed"] = emb

    # -- segments + transitions -------------------------------------------
    cur_sp = entry_sp
    last_sp_view = entry_view if entry_sp else None
    for i, (seg, v) in enumerate(zip(segs, views)):
        trans: list[Exp] = []
        if cur_sp and not v.seq_parallel:
            trans = _seq_gather(last_sp_view, b, S // last_sp_view.d1, hl)
        regions[f"shell:trans{i}"] = trans
        cur_sp = v.seq_parallel
        if cur_sp:
            last_sp_view = v
        regions[f"seg{i}:{seg.kind}"] = _times(
            _layer_exps(seg, v, cfg, b, S, decode), seg.count)

    # -- shell:exit --------------------------------------------------------
    ex: list[Exp] = []
    s_loc = S // last_sp_view.d1 if cur_sp else S
    n_psum = 2 if cfg.norm_kind == "layernorm" else 1
    if sv.ax2:
        ex.append(Exp("psum", (sv.ax2,), n_psum, n_psum * b * s_loc, F32))
    if cur_sp:
        ex += _seq_gather(last_sp_view, b, s_loc, hl)
    regions["shell:exit"] = ex

    # -- shell:head / shell:loss / shell:pick ------------------------------
    v_loc = cfg.vocab_size // plan.d1
    head: list[Exp] = []
    s_head = S if phase == "train" else 1
    if sv.ax2:
        head.append(Exp("psum", (sv.ax2,), 1, b * s_head * v_loc, sv.act))
    if phase == "train" and sv.ax1:  # vocab-parallel CE
        head.append(Exp("pmax", (sv.ax1,), 1, b * S, F32))
        head.append(Exp("psum", (sv.ax1,), 2, 2 * b * S, F32))
    regions["shell:head"] = head

    if phase == "train":
        loss: list[Exp] = []
        dp_axes = ("data",) if dpn > 1 else ()
        if dp_axes:
            loss.append(Exp("psum", dp_axes, 2, 2, F32))
            if cfg.moe is not None:  # pmean of the aux loss lowers to psum
                loss.append(Exp("psum", dp_axes, 1, 1, F32))
        regions["shell:loss"] = loss
        if cfg.mtp and cfg.frontend != "vision_patches":
            regions["shell:mtp"] = _mtp_exps(sv, cfg, b, S, dp_axes)
    else:
        regions["shell:pick"] = _pick_exps(sv, b)

    if any("while" in n for n in notes):
        pass
    return Expectation(regions={k: tuple(vv) for k, vv in regions.items()},
                       phase=phase, notes=tuple(notes))


def _pick_exps(sv: View, b: int) -> list[Exp]:
    """launch.steps._greedy_pick: vocab-parallel argmax over ax1."""
    if sv.ax1 is None:
        return []
    return [Exp("pmax", (sv.ax1,), 1, b, F32),
            Exp("pmin", (sv.ax1,), 1, b, I32)]


def _mtp_exps(sv: View, cfg: ModelConfig, b: int, S: int,
              dp_axes: tuple[str, ...]) -> list[Exp]:
    """models.lm train MTP head: embed + fused proj + ax1 regather + one
    dense/mla block on the GLOBAL scalar knobs + norm + logits + CE."""
    h = cfg.d_model
    hl = h // sv.d2
    out: list[Exp] = []
    if sv.ax1:
        out.append(Exp("psum", (sv.ax1,), 1, b * S * hl, sv.act))   # emb(t+1)
    if sv.ax2:
        out.append(Exp("psum", (sv.ax2,), 1, b * S * h // sv.d1, sv.act))
    if sv.ax1:
        out.append(Exp("all_gather", (sv.ax1,), 1, b * S * h, sv.act))
    seg = _FakeSeg("mla_dense" if cfg.mla else "dense")
    out += _layer_exps(seg, sv, cfg, b, S, False)
    n_psum = 2 if cfg.norm_kind == "layernorm" else 1
    if sv.ax2:
        out.append(Exp("psum", (sv.ax2,), n_psum, n_psum * b * S, F32))
        out.append(Exp("psum", (sv.ax2,), 1,
                       b * S * cfg.vocab_size // sv.d1, sv.act))
    if sv.ax1:
        out.append(Exp("pmax", (sv.ax1,), 1, b * S, F32))
        out.append(Exp("psum", (sv.ax1,), 2, 2 * b * S, F32))
    if dp_axes:
        out.append(Exp("psum", dp_axes, 1, 1, F32))
    return out


@dataclasses.dataclass(frozen=True)
class _FakeSeg:
    kind: str
    count: int = 1
    inner: int = 1


# ---------------------------------------------------------------------------
# Diff engine.
# ---------------------------------------------------------------------------


def _fmt_key(op: str, axes: tuple[str, ...], quant: bool) -> str:
    ax = "+".join(axes) or "-"
    return f"{'quant ' if quant else ''}{op}[{ax}]"


def check_conformance(sig, exp: Expectation) -> list[str]:
    """Diff an extracted StepSignature against an Expectation.

    Returns a list of human-readable errors (empty == conformant):
    forward regions are compared exactly by (op, axes, quantized) —
    counts and raw payload bytes — and the backward pass is checked
    structurally against rules derived from the forward expectation
    (ring segments must run ppermutes backward, psum segments must not,
    quantized boundaries must quantize the cotangent).
    """
    errors: list[str] = []

    # ---- forward: exact ---------------------------------------------------
    found: dict[tuple, list[int]] = defaultdict(lambda: [0, 0])
    fwd_regions = set()
    for c in sig.collectives:
        if c.backward or not c.region:
            continue
        fwd_regions.add(c.region)
        a = found[(c.region, c.op, c.axes, c.quant)]
        a[0] += c.count
        a[1] += c.raw_bytes
    want = exp.by_key()

    for key in sorted(set(found) | set(want)):
        region, op, axes, quant = key
        if region not in exp.regions:
            continue  # whole-region mismatch reported below
        fc, fb = found.get(key, (0, 0))
        wc, wb, known = want.get(key, (0, 0, True))
        if fc == wc and (not known or fb == wb or wc == 0):
            continue
        k = _fmt_key(op, axes, quant)
        if wc == 0:
            # special-case the quant-flag flip for a sharper diagnostic
            flip = (region, op, axes, not quant)
            if flip in want and flip not in found:
                wire = "full-width" if quant else "quantized"
                have = "quantized" if quant else "full-width"
                errors.append(
                    f"{region} fwd: expected {wire} {_fmt_key(op, axes, False)}"
                    f" payloads, found {have}")
                continue
            errors.append(f"{region} fwd: unexpected {fc}x {k}")
        elif fc != wc:
            errors.append(f"{region} fwd: expected {wc}x {k}, found {fc}")
        else:
            errors.append(
                f"{region} fwd: {k} raw bytes {fb} != expected {wb}")
    for region in sorted(set(exp.regions) - fwd_regions):
        if any(e.count for e in exp.regions[region]):
            errors.append(
                f"{region} fwd: region missing from trace (expected "
                + ", ".join(f"{e.count}x {_fmt_key(e.op, e.axes, e.quant)}"
                            for e in exp.regions[region]) + ")")
    for region in sorted(fwd_regions - set(exp.regions)):
        errors.append(f"{region} fwd: unexpected region in trace")

    # ---- backward: structural --------------------------------------------
    bwd_ppermute: dict[str, int] = defaultdict(int)
    bwd_quant: dict[str, int] = defaultdict(int)
    bwd_any: dict[str, int] = defaultdict(int)
    for c in sig.collectives:
        if not c.backward or not c.region:
            continue
        bwd_any[c.region] += c.count
        if c.op == "ppermute":
            bwd_ppermute[c.region] += c.count
        if c.quant:
            bwd_quant[c.region] += c.count
    if any(bwd_any.values()):  # differentiated step: apply structural rules
        for region, exps in exp.regions.items():
            ring = any(e.op == "ppermute" for e in exps)
            quant = any(e.quant for e in exps)
            if ring and bwd_any[region] and not bwd_ppermute[region]:
                errors.append(
                    f"{region} bwd: ring-planned segment ran no ppermute "
                    f"ring in the backward pass")
            if not ring and bwd_ppermute[region]:
                errors.append(
                    f"{region} bwd: psum-planned segment ran "
                    f"{bwd_ppermute[region]}x ppermute in the backward pass")
            if quant and bwd_any[region] and not bwd_quant[region]:
                errors.append(
                    f"{region} bwd: quantized-wire segment sent a "
                    f"full-width cotangent")
    return errors


def lint_conformance(sig, cfg: ModelConfig, plan, phase: str, batch: int,
                     seq: int, strict: bool = True) -> list[str]:
    """Expected-vs-extracted diff for one built step; raises
    :class:`PlanConformanceError` on mismatch when ``strict``."""
    exp = expected_signature(cfg, plan, phase, batch, seq)
    errors = check_conformance(sig, exp)
    if errors and strict:
        raise PlanConformanceError(
            f"{cfg.name} [{phase}] does not conform to its plan "
            f"({plan.describe()}):\n  " + "\n  ".join(errors))
    return errors


def assert_step_conforms(fn, cfg: ModelConfig, plan, phase: str, batch: int,
                         seq: int, *abstract_args) -> None:
    """One-call gate for the smokes: trace a built step, then require
    BOTH plan conformance (extracted == expected collectives) and proven
    out_spec replication.  Raises on the first violation."""
    from repro.analysis.replication import verify_replication
    from repro.analysis.signature import extract, trace_jaxpr

    jaxpr = trace_jaxpr(fn, *abstract_args)
    lint_conformance(extract(jaxpr), cfg, plan, phase, batch, seq)
    verify_replication(jaxpr)

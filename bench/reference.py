"""The plain reference: a Qwen2 / Qwen3 dense decoder written from the
published description, in float32 at the highest matmul precision, with
no kernels, no cache, no sharding and no batching tricks.  It imports
nothing of the program; it reads the weight tree that ``bench.model``
draws from the seed.

Layer equations (Hugging Face ``Qwen2`` / ``Qwen3`` modeling code):

    h  = x + o_proj(attn(rope(qk_norm(q_proj(rms(x)))), rope(qk_norm(k..)), v))
    y  = h + down(silu(gate(rms(h))) * up(rms(h)))

``rms(x) = x / sqrt(mean(x^2) + eps) * g``; RoPE rotates the two halves of
each head (``rotate_half``); attention is causal with grouped k/v heads and
scale ``1/sqrt(head_dim)``; Qwen2 has q/k/v biases, Qwen3 RMS-normalises q
and k per head before RoPE.  The logits are the final RMS-normed hidden
times the head (the embedding's transpose when tied).

``mode="fp8"`` is the control: every matmul, forward and backward, takes
its operands rounded to float8 e4m3 with a per-tensor scale.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Sizes

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0   # largest finite float8 e4m3fn


def _f8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(spec, a, b):
    return _einsum(spec, _f8(a), _f8(b))


def _einsum8_fwd(spec, a, b):
    a8, b8 = _f8(a), _f8(b)
    return _einsum(spec, a8, b8), (a8, b8)


def _einsum8_bwd(spec, res, g):
    a8, b8 = res
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), a8, b8)
    return vjp(_f8(g))


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


def dot_for(mode: str):
    if mode == "f32":
        return _einsum
    if mode == "fp8":
        return _einsum8
    raise ValueError(f"unknown reference mode {mode!r}")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x [b, s, heads, hd]; pos [s]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(s: Sizes, dot, w, x, pos):
    """One decoder layer.  w: this layer's weights (f32); x [b, t, hidden]."""
    b, t, _ = x.shape
    a = w["attn"]
    h = _rms(x, w["ln_attn"]["scale"], s.eps)
    q = dot("btk,kn->btn", h, a["wq"])
    k = dot("btk,kn->btn", h, a["wk"])
    v = dot("btk,kn->btn", h, a["wv"])
    if s.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = q.reshape(b, t, s.heads, s.head_dim)
    k = k.reshape(b, t, s.kv_heads, s.head_dim)
    v = v.reshape(b, t, s.kv_heads, s.head_dim)
    if s.qk_norm:
        q = _rms(q, a["q_norm"], s.eps)
        k = _rms(k, a["k_norm"], s.eps)
    q, k = _rope(q, pos, s.rope_theta), _rope(k, pos, s.rope_theta)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = dot("bqhd,bkhd->bhqk", q, k) / math.sqrt(s.head_dim)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = dot("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + dot("btk,kn->btn", o.reshape(b, t, -1), a["wo"])
    m = w["mlp"]
    h = _rms(x, w["ln_mlp"]["scale"], s.eps)
    g = jax.nn.silu(dot("btk,kn->btn", h, m["w_gate"]))
    u = dot("btk,kn->btn", h, m["w_up"])
    return x + dot("btk,kn->btn", g * u, m["w_down"])


def hidden(s: Sizes, dot, w, tokens):
    """Final RMS-normed hidden states [b, t, hidden] of tokens [b, t]."""
    pos = jnp.arange(tokens.shape[1])
    x = jnp.take(w["embed"], tokens, axis=0)

    def body(x, lw):
        return jax.checkpoint(lambda x, lw: layer(s, dot, lw, x, pos))(x, lw), None

    x, _ = jax.lax.scan(body, x, w["seg0"])
    return _rms(x, w["final_norm"]["scale"], s.eps)


def head(s: Sizes, w):
    return w["embed"].T if s.tied else w["lm_head"]


def to_f32(w):
    """A float32 copy (never the same buffers: the train steps donate it)."""
    return jax.tree.map(lambda x: jnp.array(x, jnp.float32, copy=True), w)


# ---------------------------------------------------------------------------
# training: three AdamW steps
# ---------------------------------------------------------------------------


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay to
    ``min_lr_ratio * lr`` at ``total_steps``."""
    lr, warm = opt["lr"], opt["warmup_steps"]
    if step < warm:
        return lr * min(1.0, (step + 1) / max(1, warm))
    prog = min(1.0, max(0.0, (step - warm) / max(1, opt["total_steps"] - warm)))
    r = opt["min_lr_ratio"]
    return lr * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * prog)))


def per_layer_norms(tree) -> dict:
    """{leaf path: [per-layer L2 norms]} of a weight-shaped tree (stacked
    leaves give one norm per layer, the others one)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        x = x.astype(jnp.float32)
        if name.startswith("seg0/"):
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def _loss(s, dot, w, tokens, labels):
    """Mean cross-entropy of a batch [b, t].  The head and its logits run
    one row at a time (and are recomputed in the backward pass), so only
    one row's logits are ever held."""
    h = hidden(s, dot, w, tokens)
    hw = head(s, w)

    def row_ce(hr, yr):
        logits = dot("tk,kn->tn", hr, hw)
        picked = jnp.take_along_axis(logits, yr[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    def body(total, xs):
        return total + jax.checkpoint(row_ce)(*xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, labels))
    return total / tokens.size


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 9), donate_argnums=(3, 4, 5))
def _ref_step(s, mode, last, w, m, v, tokens, labels, lr, t, hyper):
    """One AdamW step.  ``m is None`` on the first step; ``v is None`` on
    the second, where it follows from the first step's ``m`` (one step in,
    ``v = (1 - b2) g^2`` and ``m = (1 - b1) g``), so the first step keeps
    one moment and not two.  On the ``last`` step no moment is returned.
    Returns (w, m, v, loss, per-layer norms of the clipped gradient)."""
    b1, b2, eps, wd, clip = hyper
    loss, g = jax.value_and_grad(
        lambda w: _loss(s, dot_for(mode), w, tokens, labels))(w)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (gnorm + 1e-9)), g)
    norms = per_layer_norms(g)
    if m is None:
        m = jax.tree.map(jnp.zeros_like, w)
        v = m
    elif v is None:
        v = jax.tree.map(lambda m: (1 - b2) / (1 - b1) ** 2 * m * m, m)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    w = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                  + wd * p), w, m, v)
    if last:
        m = v = None
    elif t == 1:
        v = None
    return w, m, v, loss, norms


@jax.jit
def _change_norms(w, w0):
    return per_layer_norms(jax.tree.map(lambda a, b: a - b.astype(a.dtype),
                                        w, w0))


def shardings(w, devices):
    """Spread each leaf of a weight tree over ``devices`` along its largest
    dimension that they divide (None on one device).  The reference is
    plain code; this only lets an 8B model's float32 state fit."""
    if len(devices) == 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("x",))
    n = len(devices)

    def spec(x):
        dims = [d for d in np.argsort(x.shape)[::-1] if x.shape[d] % n == 0]
        if not dims:
            return NamedSharding(mesh, P())
        parts = [None] * x.ndim
        parts[int(dims[0])] = "x"
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(spec, w)


def weights(s: Sizes, key, devices):
    """The seed's weight tree, spread over ``devices`` as :func:`shardings`
    says."""
    from bench.model import make_weights

    shape = jax.eval_shape(lambda: make_weights(s, key))
    return make_weights(s, key, out_shardings=shardings(shape, devices))


def train_reference(s: Sizes, w0, batches, opt: dict, mode: str = "f32"):
    """``len(batches)`` AdamW steps from the weights ``w0`` (laid out as
    :func:`shardings` gives, or on one device).

    Returns {"losses": [...], "grad": {leaf: norms} (step 0's clipped
    gradient), "change": {leaf: norms} (weights after the last step minus
    ``w0``)}, all as numpy."""
    hyper = tuple(float(opt[k]) for k in ("b1", "b2", "eps", "weight_decay",
                                          "grad_clip"))
    w = to_f32(w0)
    m = v = None
    losses, grad = [], None
    for k, bt in enumerate(batches):
        t = k + 1
        w, m, v, loss, gn = _ref_step(
            s, mode, k == len(batches) - 1, w, m, v,
            jnp.asarray(bt["tokens"]), jnp.asarray(bt["labels"]),
            jnp.float32(lr_at(opt, k)), t, hyper)
        losses.append(float(loss))
        if grad is None:
            grad = jax.device_get(gn)
    change = jax.device_get(_change_norms(w, w0))
    return {"losses": losses, "grad": grad, "change": change}


# ---------------------------------------------------------------------------
# serving: how far below the reference's best each served token lies
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits_at(s, mode, w, tokens, at):
    """Logits [n, vocab] at positions ``at`` [n] of the row tokens [1, t]."""
    h = hidden(s, dot_for(mode), w, tokens)[0]
    return dot_for(mode)("tk,kn->tn", jnp.take(h, at, axis=0), head(s, w))


def serve_gaps(s: Sizes, w, seqs, width: int, slots: int,
               control: str | None = None):
    """For each (tokens, first, served) in ``seqs`` -- a request's prompt
    and served tokens run as one row, the position whose logits gave the
    first served token, and the served tokens -- the gap by which each
    served token's reference logit lies below the reference's best.

    Rows are padded to ``width`` and positions to ``slots``, so every
    request runs one compiled program.  With ``control`` the same is read
    for the token that the control's own logits put first.  Returns a list
    of numpy arrays (one per request), or (served, control) lists."""
    w = to_f32(w)
    out, ctrl = [], []
    for tokens, first, served in seqs:
        n = len(served)
        row = np.zeros((1, width), np.int32)
        row[0, : len(tokens)] = tokens
        at = np.zeros((slots,), np.int32)
        at[:n] = np.arange(first, first + n)
        ref = _logits_at(s, "f32", w, row, at)[:n]
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, jnp.asarray(served)[:, None],
                                  axis=-1)[:, 0]
        out.append(np.asarray(best - got))
        if control is not None:
            lo = _logits_at(s, control, w, row, at)[:n]
            pick = jnp.argmax(lo, axis=-1)
            ctrl.append(np.asarray(
                best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]))
    return (out, ctrl) if control is not None else out

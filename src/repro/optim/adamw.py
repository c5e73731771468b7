"""AdamW with optional ZeRO-1 sharding over the data-parallel axes.

Runs INSIDE shard_map.  Three gradient-reduction modes:

  plain   : psum(grads, dp) then full AdamW on every DP rank (ZeRO-0)
  zero1   : psum_scatter(grads) -> shard-local AdamW -> all_gather(updates).
            Optimizer state (m, v) lives only on the owning DP shard:
            1/dp of the fp32 state memory per rank.
  compressed : int8-quantized gradient all-reduce with error feedback
            (distributed-optimization trick; see grad_compress.py)

Every param leaf is flattened and padded to a multiple of the DP degree so
psum_scatter has a clean scatter dim.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.atp import ATPContext


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    mode: str = "zero1"          # plain | zero1 | compressed
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def lr_at(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    warm = cfg.lr * jnp.minimum(1.0, (step + 1) / max(1, cfg.warmup_steps))
    prog = jnp.clip((step - cfg.warmup_steps)
                    / max(1, cfg.total_steps - cfg.warmup_steps), 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.lr * cos)


def _pad_to(x, mult):
    flat = x.reshape(-1)
    pad = (-flat.size) % mult
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


def _tp_axes_in_spec(spec, ctx: ATPContext) -> tuple[str, ...]:
    """TP axes this leaf is actually sharded over (in (ax1, ax2) order)."""
    found = set()
    for entry in spec:
        names = entry if isinstance(entry, tuple) else (entry,)
        for nm in names:
            if nm is not None and nm in (ctx.ax1, ctx.ax2):
                found.add(nm)
    return tuple(a for a in (ctx.ax1, ctx.ax2) if a is not None and a in found)


def _shard_factor(spec, ctx: ATPContext) -> int:
    f = 1
    for entry in spec:
        names = entry if isinstance(entry, tuple) else (entry,)
        for nm in names:
            if nm:
                f *= ctx.topo.axis_size(nm)
    return f


def zero1_banked(mode: str, ctx: ATPContext) -> bool:
    """True when the zero1 banked [DP, TPs, k] state layout is in effect.

    Must agree with ``apply_adamw``'s dispatch: with no data-parallel axis
    the zero1 step degenerates to full-state AdamW, so the state must
    mirror the params there (banking it was a latent recovery-path bug —
    an elastic shrink to dp=1 handed banked state to the full-state path).
    """
    return mode == "zero1" and bool(ctx.dp_axes)


def init_opt_state(params, param_specs_tree, ctx: ATPContext,
                   mode: str = "zero1", abstract: bool = False):
    """fp32 m/v per leaf (GLOBAL arrays).

    plain/compressed (and zero1 at dp=1): m/v mirror the param shape and
    sharding.
    zero1: banked [DP, TPs, k] with k = ceil(local_param_size / DP); each
    (dp, tp) rank owns one bank — 1/DP of the fp32 state per rank.  The
    bank's TP dim only spans axes the param is sharded over, so banks of
    TP-replicated leaves stay provably replicated (vma invariance).
    """
    dp = ctx.dp
    banked = zero1_banked(mode, ctx)

    def mk(shape, dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    def leaf_state(x, spec):
        if not banked:
            return {"m": mk(x.shape, jnp.float32), "v": mk(x.shape, jnp.float32)}
        axes = _tp_axes_in_spec(spec, ctx)
        tpn = math.prod(ctx.topo.axis_size(a) for a in axes) if axes else 1
        local = x.size // _shard_factor(spec, ctx)
        k = math.ceil(local / dp)
        return {"m": mk((dp, tpn, k), jnp.float32),
                "v": mk((dp, tpn, k), jnp.float32)}

    leaves = jax.tree.map(leaf_state, params, param_specs_tree)
    state = {"step": mk((), jnp.int32), "leaves": leaves}
    if mode == "compressed":
        # persistent error-feedback residual: what int8 rounding dropped
        # this step is added back before quantizing the next step.  Param-
        # shaped f32 like plain m/v (compressed is never zero1-banked), so
        # it checkpoints and reshards exactly like the moments.
        state["err"] = jax.tree.map(
            lambda x: mk(x.shape, jnp.float32), params)
    return state


def opt_state_specs(param_specs_tree, ctx: ATPContext, mode: str = "zero1"):
    from jax.sharding import PartitionSpec as P
    dp_t = tuple(ctx.dp_axes) or None
    banked = zero1_banked(mode, ctx)

    def leaf_spec(spec):
        if not banked:
            return {"m": spec, "v": spec}
        axes = _tp_axes_in_spec(spec, ctx)
        s = P(dp_t, axes if axes else None, None)
        return {"m": s, "v": s}

    out = {"step": P(),
           "leaves": jax.tree.map(leaf_spec, param_specs_tree,
                                  is_leaf=lambda x: isinstance(x, P))}
    if mode == "compressed":
        out["err"] = param_specs_tree
    return out


# ---------------------------------------------------------------------------
# Checkpoint layout: the banked zero1 state is a *plan-dependent* runtime
# layout ([DP, TPs, k] depends on (d1, d2, dp)), so a checkpoint written in
# it cannot be restored under a different mesh.  unbank/rebank convert to
# and from the plan-independent param-shaped ("plain") layout on the host;
# the trainer checkpoints canonically and re-banks onto whatever plan is
# live at restore time (elastic reshard across a (d1, d2, dp) change).
# ---------------------------------------------------------------------------


def _tp_coord_of(j: int, axes, sizes) -> dict:
    """Bank index -> mesh coordinate.  The bank's TP dim is sharded
    P(..., axes, ...) with ``axes`` in (ax1, ax2) order, so j is row-major
    over them (first axis most significant)."""
    coord = {}
    for a, s in zip(reversed(axes), reversed(sizes)):
        coord[a] = j % s
        j //= s
    return coord


def _tp_block_slices(shape, spec, ctx: ATPContext, coord: dict):
    """The slices of the GLOBAL leaf owned by mesh coordinate ``coord``.

    A dim sharded over an axis tuple splits row-major in the tuple's own
    order (jax semantics), which need not match the bank's (ax1, ax2)
    order — hence the per-dim relinearization."""
    slices = []
    for d, size in enumerate(shape):
        entry = spec[d] if d < len(spec) else None
        names = entry if isinstance(entry, tuple) else \
            ((entry,) if entry is not None else ())
        names = [nm for nm in names if nm in coord]
        n, b = 1, 0
        for nm in names:
            s = ctx.topo.axis_size(nm)
            n *= s
            b = b * s + coord[nm]
        loc = size // n
        slices.append(slice(b * loc, (b + 1) * loc))
    return tuple(slices)


def unbank_opt_state(params, opt_state, param_specs_tree, ctx: ATPContext,
                     mode: str = "zero1"):
    """GLOBAL banked zero1 state -> param-shaped fp32 m/v (host numpy).

    Identity for layouts that already mirror the params (plain,
    compressed, zero1 at dp=1).  Bank [i, j, :] holds dp-rank i's slice of
    TP-shard j's padded flat moments; the pad region is provably zero
    (zero grads never move it), so unbank -> rebank round-trips exactly.
    """
    import numpy as np

    if not zero1_banked(mode, ctx):
        return opt_state

    def unbank_leaf(p, spec, st):
        axes = _tp_axes_in_spec(spec, ctx)
        sizes = [ctx.topo.axis_size(a) for a in axes]
        shape = tuple(np.shape(p))

        def one(banked):
            banked = np.asarray(jax.device_get(banked))
            dpn, tpn, k = banked.shape
            out = np.zeros(shape, np.float32)
            for j in range(tpn):
                sl = _tp_block_slices(shape, spec, ctx,
                                      _tp_coord_of(j, axes, sizes))
                block = out[sl]
                flat = banked[:, j, :].reshape(dpn * k)[: block.size]
                out[sl] = flat.reshape(block.shape)
            return out

        return {"m": one(st["m"]), "v": one(st["v"])}

    flat_p, tdef = jax.tree.flatten(params)
    flat_spec = tdef.flatten_up_to(param_specs_tree)
    flat_st = tdef.flatten_up_to(opt_state["leaves"])
    leaves = [unbank_leaf(p, s, st)
              for p, s, st in zip(flat_p, flat_spec, flat_st)]
    return {"step": opt_state["step"],
            "leaves": jax.tree.unflatten(tdef, leaves)}


def rebank_opt_state(params, canonical, param_specs_tree, ctx: ATPContext,
                     mode: str = "zero1"):
    """Param-shaped fp32 m/v -> the banked layout ``ctx``/``mode`` run
    under (host numpy; inverse of ``unbank_opt_state`` for the same plan,
    and the reshard path onto a *different* plan after an elastic resize).
    Identity when the runtime layout already mirrors the params."""
    import numpy as np

    if not zero1_banked(mode, ctx):
        return canonical
    dp = ctx.dp

    def rebank_leaf(p, spec, st):
        axes = _tp_axes_in_spec(spec, ctx)
        sizes = [ctx.topo.axis_size(a) for a in axes]
        tpn = math.prod(sizes) if sizes else 1
        shape = tuple(np.shape(p))
        local = int(np.prod(shape, dtype=np.int64)) // \
            (math.prod(sizes) if sizes else 1)
        k = math.ceil(local / dp)

        def one(canon):
            canon = np.asarray(jax.device_get(canon), np.float32)
            banked = np.zeros((dp, tpn, k), np.float32)
            for j in range(tpn):
                sl = _tp_block_slices(shape, spec, ctx,
                                      _tp_coord_of(j, axes, sizes))
                flat = canon[sl].reshape(-1)
                padded = np.zeros(dp * k, np.float32)
                padded[: flat.size] = flat
                banked[:, j, :] = padded.reshape(dp, k)
            return banked

        return {"m": one(st["m"]), "v": one(st["v"])}

    flat_p, tdef = jax.tree.flatten(params)
    flat_spec = tdef.flatten_up_to(param_specs_tree)
    flat_st = tdef.flatten_up_to(canonical["leaves"])
    leaves = [rebank_leaf(p, s, st)
              for p, s, st in zip(flat_p, flat_spec, flat_st)]
    return {"step": canonical["step"],
            "leaves": jax.tree.unflatten(tdef, leaves)}


def replication_factors(param_specs_tree, ctx: ATPContext):
    """Per-leaf TP replication factor = tp / prod(tp axis sizes in spec).

    Used to de-duplicate replicated leaves in the global grad norm."""
    from jax.sharding import PartitionSpec as P

    def factor(spec):
        sharded = 1
        for entry in spec:
            names = entry if isinstance(entry, tuple) else (entry,)
            for nm in names:
                if nm in (ctx.ax1, ctx.ax2):
                    sharded *= ctx.topo.axis_size(nm)
        return float(ctx.tp // sharded)

    return jax.tree.map(factor, param_specs_tree,
                        is_leaf=lambda x: isinstance(x, P))


def global_grad_norm(grads, ctx: ATPContext, rep=None):
    """L2 norm over the *global* gradient.  TP-sharded leaves contribute
    their shard once; replicated leaves are divided by their replication
    factor so the TP psum does not over-count them."""
    leaves = jax.tree.leaves(grads)
    reps = jax.tree.leaves(rep) if rep is not None else [1.0] * len(leaves)
    local = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) / r
                for g, r in zip(leaves, reps))
    axes = ctx.tp_axes
    if axes:
        local = lax.psum(local, axes)
    return jnp.sqrt(local)


def apply_adamw(
    cfg: AdamWConfig,
    ctx: ATPContext,
    params,
    grads,
    opt_state,
    replication_factor=None,
):
    """One optimizer step.  grads are LOCAL (pre-DP-reduction).  Its ops
    and collectives run under the ``opt:adamw`` name scope.

    Returns (new_params, new_opt_state, metrics)."""
    with jax.named_scope("opt:adamw"):
        return _apply_adamw(cfg, ctx, params, grads, opt_state,
                            replication_factor)


def _apply_adamw(cfg, ctx, params, grads, opt_state, replication_factor):
    dp_axes = ctx.dp_axes
    step = opt_state["step"]
    lr = lr_at(cfg, step)

    new_err = None
    if cfg.mode == "compressed":
        from repro.optim.grad_compress import (compressed_psum_mean,
                                               compressed_psum_mean_ef)
        err = opt_state.get("err")
        if err is None:
            # legacy state (pre-error-feedback checkpoint): memoryless path
            grads = jax.tree.map(
                lambda g: compressed_psum_mean(g, dp_axes), grads)
        else:
            flat_g, gdef = jax.tree.flatten(grads)
            flat_e = gdef.flatten_up_to(err)
            res = [compressed_psum_mean_ef(g, e, dp_axes)
                   for g, e in zip(flat_g, flat_e)]
            grads = jax.tree.unflatten(gdef, [r[0] for r in res])
            new_err = jax.tree.unflatten(gdef, [r[1] for r in res])
    elif dp_axes and cfg.mode == "plain":
        grads = jax.tree.map(lambda g: lax.pmean(g, dp_axes), grads)

    if cfg.mode == "zero1" and dp_axes:
        return _zero1_step(cfg, ctx, params, grads, opt_state, lr,
                           replication_factor)

    # full-state AdamW (grads already DP-reduced); m/v mirror param shapes
    gnorm = global_grad_norm(grads, ctx, replication_factor)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9)) if cfg.grad_clip else 1.0
    t = (step + 1).astype(jnp.float32)
    bc1 = 1 - cfg.b1 ** t
    bc2 = 1 - cfg.b2 ** t

    def upd(p, g, st):
        gf = g.astype(jnp.float32) * scale
        m = cfg.b1 * st["m"] + (1 - cfg.b1) * gf
        v = cfg.b2 * st["v"] + (1 - cfg.b2) * gf * gf
        u = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        pf = p.astype(jnp.float32)
        new = pf - lr * (u + cfg.weight_decay * pf)
        return new.astype(p.dtype), {"m": m, "v": v}

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_s = tdef.flatten_up_to(opt_state["leaves"])
    out = [upd(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)]
    new_params = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_leaves = jax.tree.unflatten(tdef, [o[1] for o in out])
    new_state = {"step": step + 1, "leaves": new_leaves}
    if new_err is not None:
        new_state["err"] = new_err
    return new_params, new_state, {"lr": lr, "grad_norm": gnorm}


def _zero1_step(cfg, ctx, params, grads, opt_state, lr, rep=None):
    """ZeRO-1: reduce-scatter grads over dp, shard-local Adam, all-gather."""
    dp_axes = ctx.dp_axes
    dp = ctx.dp
    step = opt_state["step"]
    t = (step + 1).astype(jnp.float32)
    bc1 = 1 - cfg.b1 ** t
    bc2 = 1 - cfg.b2 ** t

    # grad norm from local (unreduced) grads requires the DP mean first;
    # compute it on the scattered shards to stay memory-light.
    def scatter(g):
        flat, _ = _pad_to(g.astype(jnp.float32).reshape(-1), dp)
        shard = lax.psum_scatter(flat, dp_axes, scatter_dimension=0, tiled=True)
        return shard / dp                     # mean over DP

    g_shards = jax.tree.map(scatter, grads)
    leaves = jax.tree.leaves(g_shards)
    reps = jax.tree.leaves(rep) if rep is not None else [1.0] * len(leaves)
    sq = sum(jnp.sum(jnp.square(g)) / r for g, r in zip(leaves, reps))
    sq = lax.psum(sq, dp_axes)
    tp_ax = ctx.tp_axes
    if tp_ax:
        sq = lax.psum(sq, tp_ax)
    gnorm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9)) if cfg.grad_clip else 1.0

    def upd(p, gs, st):
        gs = gs * scale
        m0, v0 = st["m"][0, 0], st["v"][0, 0]     # local bank [k]
        m = cfg.b1 * m0 + (1 - cfg.b1) * gs
        v = cfg.b2 * v0 + (1 - cfg.b2) * gs * gs
        u = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        flat, pad = _pad_to(p.astype(jnp.float32).reshape(-1), dp)
        mine = lax.dynamic_slice_in_dim(
            flat, ctx.dp_index() * u.size, u.size, axis=0)
        new = mine - lr * (u + cfg.weight_decay * mine)
        # update-gather: each dp rank places its chunk, psum makes the
        # result provably dp-invariant under vma typing.  (an all_gather
        # would halve the bytes but its output cannot be typed invariant
        # without Explicit mesh axes; see DESIGN.md)
        placed = lax.dynamic_update_slice_in_dim(
            jnp.zeros_like(flat), new, ctx.dp_index() * u.size, axis=0)
        full = lax.psum(placed, ctx.dp_axes)
        if pad:
            full = full[: p.size]
        return (full.reshape(p.shape).astype(p.dtype),
                {"m": m[None, None], "v": v[None, None]})

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(g_shards)
    flat_s = tdef.flatten_up_to(opt_state["leaves"])
    out = [upd(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)]
    new_params = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_leaves = jax.tree.unflatten(tdef, [o[1] for o in out])
    return new_params, {"step": step + 1, "leaves": new_leaves}, \
        {"lr": lr, "grad_norm": gnorm}

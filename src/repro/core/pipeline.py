"""GPipe-style pipeline parallelism over a mesh axis (the `pod` axis).

Inter-pod DCN links are slow relative to ICI, which is the textbook place
for pipeline parallelism: only activations at stage boundaries cross pods
(vs full gradients for inter-pod DP).  This module implements the
microbatched forward schedule inside shard_map:

  - each rank of `axis` holds ONE stage's parameters
  - microbatches enter at stage 0; stage boundaries move activations with
    collective_permute (shift-by-one ring, no wraparound)
  - the classic GPipe bubble: S-1 warmup + S-1 drain ticks; every stage
    computes every tick (idle ticks process zeros — wasted FLOPs are the
    bubble, exactly as on real hardware)
  - fully differentiable (ppermute transposes to the reverse shift), so
    jax.grad implements the 1F1B-equivalent backward automatically

Used with DP/TP inside each stage: the pipeline axis composes with the
ATP mesh (`atp_topo(..., pods=S)` + stage_fn built from ATP layers).
"""
from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
from jax import lax


def gpipe_forward(
    stage_fn: Callable,       # (stage_params, x_micro) -> y_micro
    stage_params,             # this rank's stage params (sliced by spec)
    x_micro,                  # [M, ...] microbatches (read at stage 0)
    axis: str,
):
    """Returns [M, ...] pipeline outputs (valid on the LAST stage; other
    stages return zeros — callers typically ppermute/psum the result or
    compute the loss on the last stage and psum it)."""
    S = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    M = x_micro.shape[0]
    T = M + S - 1                      # total ticks incl. bubble
    micro_shape = x_micro.shape[1:]

    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        buf, outs = carry
        # stage 0 ingests microbatch t (zeros once drained)
        take = jnp.clip(t, 0, M - 1)
        first_in = jnp.where(t < M, 1.0, 0.0) * \
            lax.dynamic_index_in_dim(x_micro, take, axis=0, keepdims=False)
        inp = jnp.where(idx == 0, first_in, buf)
        y = stage_fn(stage_params, inp)
        # last stage emits microbatch t-(S-1)
        emit_t = t - (S - 1)
        ok = (emit_t >= 0) & (emit_t < M) & (idx == S - 1)
        outs = lax.dynamic_update_index_in_dim(
            outs,
            jnp.where(ok, y, lax.dynamic_index_in_dim(
                outs, jnp.clip(emit_t, 0, M - 1), axis=0, keepdims=False)),
            jnp.clip(emit_t, 0, M - 1), axis=0)
        # shift activations to the next stage
        buf = lax.ppermute(y, axis, fwd_perm)
        return (buf, outs), None

    # init carries varying over `axis` to match the tick outputs (vma)
    buf0 = lax.pcast(jnp.zeros(micro_shape, x_micro.dtype), axis, to="varying")
    outs0 = lax.pcast(jnp.zeros((M,) + micro_shape, x_micro.dtype), axis,
                      to="varying")
    (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(T))
    return outs


def gpipe_loss(
    stage_fn: Callable,
    loss_fn: Callable,        # (y_micro) -> scalar (computed on last stage)
    stage_params,
    x_micro,
    axis: str,
):
    """Pipeline forward + last-stage loss, psum'd to every stage (so
    jax.grad drives the full pipeline backward)."""
    S = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    outs = gpipe_forward(stage_fn, stage_params, x_micro, axis)
    local = jnp.where(idx == S - 1, loss_fn(outs), 0.0)
    return lax.psum(local, axis)

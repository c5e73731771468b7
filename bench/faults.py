"""A fault planted in the program, for the correctness check's tests and
for reading it on the chip (``bench/control.py --exchange``)."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def exchange_left_out():
    """Every ATP boundary all-reduce takes only the first rank's partial
    sum: what the other chips computed is never exchanged."""
    import jax.numpy as jnp
    from jax import lax

    import repro.core.atp as atp
    import repro.models.layers as layers
    import repro.models.lm as lm
    import repro.models.transformer as transformer

    def first_rank_only(x, axis):
        if axis is None:
            return x
        return lax.psum(jnp.where(lax.axis_index(axis) == 0, x,
                                  jnp.zeros_like(x)), axis)

    mods = (atp, layers, lm, transformer)
    saved = [m.atp_boundary for m in mods]
    for m in mods:
        m.atp_boundary = first_rank_only
    try:
        yield
    finally:
        for m, f in zip(mods, saved):
            m.atp_boundary = f

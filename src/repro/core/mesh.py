"""Device-mesh abstractions for ATP.

The paper factorizes the tensor-parallel degree N into a 2D device mesh
(d1, d2).  On top of that, a real training job adds data-parallel and
(multi-pod) pod axes.  We keep the *logical* mesh description separate from
the jax.sharding.Mesh so the strategy search can enumerate factorizations
without touching device state.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np

# Canonical axis names used throughout the framework.
AXIS_POD = "pod"      # across pods (DCN)
AXIS_DATA = "data"    # data parallel (within pod)
AXIS_TP1 = "tp1"      # first dim of the ATP 2D device mesh (d1)
AXIS_TP2 = "tp2"      # second dim of the ATP 2D device mesh (d2)
# The required production mesh uses a single "model" axis == ATP (N, 1).
AXIS_MODEL = "model"


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices: Sequence[jax.Device] | None = None):
    """``jax.make_mesh`` with every axis ``Auto``: the shard_map bodies
    here do their own collectives, so no axis may be Explicit."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=_auto(len(tuple(axis_shapes))),
                         devices=devices)


def factorizations(n: int) -> list[tuple[int, int]]:
    """All (d1, d2) with d1 * d2 == n, d1 and d2 >= 1.

    For n == 2**k this gives the paper's k+1 meshes.
    """
    out = []
    for d1 in range(1, n + 1):
        if n % d1 == 0:
            out.append((d1, n // d1))
    return out


@dataclasses.dataclass(frozen=True)
class MeshTopo:
    """Logical mesh: ordered (axis_name, size) pairs."""

    axes: tuple[tuple[str, int], ...]

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(a for a, _ in self.axes)

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(s for _, s in self.axes)

    @property
    def size(self) -> int:
        return math.prod(self.shape)

    def axis_size(self, name: str) -> int:
        for a, s in self.axes:
            if a == name:
                return s
        return 1  # absent axes behave as singleton

    def has_axis(self, name: str) -> bool:
        return any(a == name for a, _ in self.axes)

    @property
    def tp_degree(self) -> int:
        if self.has_axis(AXIS_MODEL):
            return self.axis_size(AXIS_MODEL)
        return self.axis_size(AXIS_TP1) * self.axis_size(AXIS_TP2)

    @property
    def dp_degree(self) -> int:
        d = self.axis_size(AXIS_DATA)
        if self.has_axis(AXIS_POD):
            d *= self.axis_size(AXIS_POD)
        return d

    def build(self, devices: Sequence[jax.Device] | None = None) -> jax.sharding.Mesh:
        """Materialize into a jax Mesh (touches device state)."""
        if devices is None:
            return make_mesh(self.shape, self.names)
        arr = np.asarray(devices)[: self.size].reshape(self.shape)
        return jax.sharding.Mesh(arr, self.names,
                                 axis_types=_auto(len(self.shape)))

    def abstract(self) -> jax.sharding.AbstractMesh:
        """AbstractMesh — enough for sharding specs / eval_shape, no devices."""
        return jax.sharding.AbstractMesh(self.shape, self.names)


def production_topo(multi_pod: bool = False) -> MeshTopo:
    """The assignment's required production mesh (ATP (16,1) baseline)."""
    if multi_pod:
        return MeshTopo(((AXIS_POD, 2), (AXIS_DATA, 16), (AXIS_MODEL, 16)))
    return MeshTopo(((AXIS_DATA, 16), (AXIS_MODEL, 16)))


def atp_topo(
    dp: int,
    d1: int,
    d2: int,
    pods: int = 1,
) -> MeshTopo:
    """ATP mesh: (pod?, data, tp1, tp2).  d1*d2 is the TP degree."""
    axes: list[tuple[str, int]] = []
    if pods > 1:
        axes.append((AXIS_POD, pods))
    axes.append((AXIS_DATA, dp))
    axes.append((AXIS_TP1, d1))
    axes.append((AXIS_TP2, d2))
    return MeshTopo(tuple(axes))


def tp_axis_names(topo: MeshTopo) -> tuple[str | None, str | None]:
    """(first, second) mesh-dim axis names for ATP collectives.

    On the required production mesh the single "model" axis is ATP (N, 1):
    tp1="model", tp2=None.  Size-1 axes are returned as None so collective
    code can skip no-op psums.
    """
    if topo.has_axis(AXIS_MODEL):
        return (AXIS_MODEL if topo.axis_size(AXIS_MODEL) > 1 else None, None)
    a1 = AXIS_TP1 if topo.axis_size(AXIS_TP1) > 1 else None
    a2 = AXIS_TP2 if topo.axis_size(AXIS_TP2) > 1 else None
    return (a1, a2)


def dp_axis_names(topo: MeshTopo) -> tuple[str, ...]:
    names = []
    if topo.has_axis(AXIS_POD) and topo.axis_size(AXIS_POD) > 1:
        names.append(AXIS_POD)
    if topo.has_axis(AXIS_DATA) and topo.axis_size(AXIS_DATA) > 1:
        names.append(AXIS_DATA)
    return tuple(names)

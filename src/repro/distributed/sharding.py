"""Mesh-axis conventions and sharding helpers.

Logical axes:
  dp   : data-parallel axes — ("data",) on a single pod, ("pod", "data") on the
         multi-pod mesh (pure DP across pods).
  tp   : tensor/model-parallel axis — "model".  Embedding tables are
         row-sharded over tp ("memory devices" in the PIFS mapping).
  ep   : expert-parallel axes for MoE — the combined (dp + tp) axes.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Logical->physical axis mapping for a given mesh."""
    dp: Tuple[str, ...]
    tp: str

    @property
    def ep(self) -> Tuple[str, ...]:
        return self.dp + (self.tp,)

    def dp_size(self, mesh: Mesh) -> int:
        return int(np.prod([mesh.shape[a] for a in self.dp]))

    def tp_size(self, mesh: Mesh) -> int:
        return int(mesh.shape[self.tp])

    def ep_size(self, mesh: Mesh) -> int:
        return self.dp_size(mesh) * self.tp_size(mesh)


def make_mesh(shape: Sequence[int], names: Sequence[str],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types: the engine's shard_map blocks
    and the models' ``with_sharding_constraint`` calls assume Auto
    semantics, which jax.make_mesh no longer defaults to."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         **kwargs)


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axes_for(mesh: Mesh) -> MeshAxes:
    names = mesh.axis_names
    if "pod" in names:
        return MeshAxes(dp=("pod", "data"), tp="model")
    if "data" in names:
        return MeshAxes(dp=("data",), tp="model")
    # single-axis test meshes
    return MeshAxes(dp=(), tp=names[0])


def ns(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def constrain(x, mesh: Mesh, *spec):
    return jax.lax.with_sharding_constraint(x, ns(mesh, *spec))

"""Mesh axes and helpers shared by the CT pipeline and the LM substrate.

Axis conventions (DESIGN.md §4):
  pod   : cross-pod data parallelism (DCN). iFDK: extra projection groups.
  data  : intra-pod data parallelism (ICI). iFDK: projection groups (paper C).
  model : tensor/expert parallelism   (ICI). iFDK: volume slabs (paper R).

`make_mesh` is a thin wrapper so importing this module never touches device
state; meshes are always built explicitly by launchers. Every axis is
`Auto`: the pipeline places data with shard_map and sharding constraints,
not with the `Explicit` sharding types `jax.make_mesh` defaults to.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"

# Axes over which data-parallel reductions run (pod present only multi-pod).
def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in (AXIS_POD, AXIS_DATA) if a in mesh.axis_names)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes),
                             axis_types=(AxisType.Auto,) * len(axes))
    devs = np.asarray(devices).reshape(tuple(shape))
    return Mesh(devs, tuple(axes))


def single_device_mesh() -> Mesh:
    """1x1 mesh over the default device — lets every shard_map program run
    unchanged on one chip (tests, smoke runs)."""
    devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
    return Mesh(devs, (AXIS_DATA, AXIS_MODEL))


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def axis_size(mesh: Mesh, *names: str) -> int:
    n = 1
    for name in names:
        if name in mesh.axis_names:
            n *= mesh.shape[name]
    return n

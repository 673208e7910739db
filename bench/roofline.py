"""The work an FDK scan needs, counted from its geometry, and the least time
a chip could take for it.

The count never looks at how a kernel does the work: an implementation that
spends more operations or bytes (the MXU u-interpolation, a gather, a
different tiling) reads a lower share of the same least time.

Voxel updates: U = N_x * N_y * N_z * N_p, one per voxel per projection.

Operations per update, from the paper's Alg. 4 (factorized back-projection):
  v coordinate      1.5   (y0 + k*dy) * f is an add and a multiply for the
                          front voxel; its Theorem-1 mirror takes
                          v~ = (N_v - 1) - v, one subtraction: 3 per pair
  tap fraction      2     floor(v) and v - floor(v); u's are per column
  bilinear blend    9     three lerps a + t*(b - a), 3 operations each
  weight and sum    2     acc += w * value
The per-column terms (u, the depth z, w = 1/z^2: two inner products and a
division per column and projection) are shared by N_z voxels and are left
out, as Alg. 4 amortises them.

Compulsory bytes: the filtered projections read once at the storage dtype,
plus the volume written once in float32.
"""
from __future__ import annotations

import json
import os

OPS_PER_UPDATE = {
    "v_coordinate": 1.5,
    "tap_fraction": 2.0,
    "bilinear_blend": 9.0,
    "weight_and_sum": 2.0,
}

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def updates(geom: dict) -> float:
    return (float(geom["n_x"]) * geom["n_y"] * geom["n_z"]
            * geom["n_proj"])


def operations(geom: dict) -> float:
    return updates(geom) * sum(OPS_PER_UPDATE.values())


def compulsory_bytes(geom: dict, storage_bytes: int) -> float:
    projections = float(geom["n_proj"]) * geom["n_v"] * geom["n_u"]
    volume = float(geom["n_x"]) * geom["n_y"] * geom["n_z"]
    return projections * storage_bytes + volume * 4


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of one chip of `device_kind`; an unknown kind
    is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def least_time(geom: dict, storage_bytes: int, peak: dict,
               n_chips: int = 1) -> tuple:
    """(seconds, bound): the larger of the operations over the chips'
    peak rate and the compulsory bytes over their bandwidth, and which of
    the two ("compute" or "memory") binds."""
    compute = operations(geom) / (n_chips * peak["flops_per_s"])
    memory = (compulsory_bytes(geom, storage_bytes)
              / (n_chips * peak["bytes_per_s"]))
    return (compute, "compute") if compute >= memory else (memory, "memory")

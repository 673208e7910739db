"""Plain FDK reconstruction of chosen voxels, in NumPy: the benchmark's
yardstick for `correct`.

The same mathematics as the program's `impl="reference"` path (paper Alg. 1
and Alg. 2), written out again so that no change to the program can move
it: the FDK cosine weight, the band-limited Ram-Lak ramp of Kak & Slaney
(ch. 3, eq. 61) applied as a convolution in the detector row (a Toeplitz
product, where the program takes an FFT), and the voxel-driven
back-projection with bilinear taps that read zero outside the detector,
weight 1/z^2 and the scale d^2 * dbeta / 2. The filtered projections are
kept in float32, the configuration's storage precision; coordinates and the
filter's sums are taken in float64.

Only the voxels asked for are back-projected, so a sample drawn from the
seed costs a few seconds on the host where the whole volume would cost an
hour. Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

# Projections filtered per matrix product, and per back-projection block.
_BLOCK = 16


def projection_matrices(geom: dict) -> np.ndarray:
    """(n_proj, 3, 4) float64 projection matrices P = (M1 Mrot M0)[:3], the
    paper's Eq. 2 with the conventions of `core/geometry.py`."""
    n_x, n_y, n_z = (int(geom[k]) for k in ("n_x", "n_y", "n_z"))
    m0 = np.diag([geom["d_x"], geom["d_y"], geom["d_z"], 1.0]) @ np.array([
        [1, 0, 0, -(n_x - 1) / 2.0],
        [0, -1, 0, (n_y - 1) / 2.0],
        [0, 0, -1, (n_z - 1) / 2.0],
        [0, 0, 0, 1]])
    cam = np.array([[1, 0, 0, 0], [0, 0, -1, 0], [0, 1, 0, geom["d"]],
                    [0, 0, 0, 1.0]])
    m1 = np.diag([1 / geom["d_u"], 1 / geom["d_v"], 1, 1]) @ np.array([
        [geom["dsd"], 0, (geom["n_u"] - 1) * geom["d_u"] / 2.0, 0],
        [0, geom["dsd"], (geom["n_v"] - 1) * geom["d_v"] / 2.0, 0],
        [0, 0, 1, 0], [0, 0, 0, 1]])
    out = []
    for beta in np.arange(int(geom["n_proj"])) * (2 * np.pi / geom["n_proj"]):
        c, s = np.cos(beta), np.sin(beta)
        rot = np.array([[c, -s, 0, 0], [s, c, 0, 0], [0, 0, 1, 0],
                        [0, 0, 0, 1.0]])
        out.append((m1 @ cam @ rot @ m0)[:3])
    return np.stack(out)


def cosine_weights(geom: dict) -> np.ndarray:
    """(n_v, n_u) FDK weights d / sqrt(d^2 + p^2 + zeta^2) on the detector
    rescaled to the rotation axis."""
    scale = geom["d"] / geom["dsd"]
    p = (np.arange(geom["n_u"]) - (geom["n_u"] - 1) / 2.0) * geom["d_u"] * scale
    z = (np.arange(geom["n_v"]) - (geom["n_v"] - 1) / 2.0) * geom["d_v"] * scale
    return geom["d"] / np.sqrt(geom["d"] ** 2 + p[None, :] ** 2
                               + z[:, None] ** 2)


def ramp_matrix(geom: dict) -> np.ndarray:
    """(n_u, n_u) Toeplitz matrix H with (row @ H)[u] = tau * sum_u'
    row[u'] h(u - u'): h(0) = 1/(4 tau^2), h(m) = -1/(m pi tau)^2 for odd m,
    0 for even m; tau is the detector pitch at the rotation axis."""
    n = int(geom["n_u"])
    tau = geom["d_u"] * geom["d"] / geom["dsd"]
    lag = np.abs(np.arange(n)[:, None] - np.arange(n)[None, :])
    h = np.zeros(lag.shape)
    h[lag == 0] = 1.0 / (4.0 * tau * tau)
    odd = lag % 2 == 1
    h[odd] = -1.0 / (lag[odd] * np.pi * tau) ** 2
    return h * tau


def filter_projections(geom: dict, proj: np.ndarray) -> np.ndarray:
    """Cosine weight and ramp filter along each detector row: (n_proj, n_v,
    n_u) -> float32 of the same shape."""
    weights = cosine_weights(geom)
    ramp = ramp_matrix(geom)
    out = np.empty(proj.shape, np.float32)
    for lo in range(0, proj.shape[0], _BLOCK):
        block = proj[lo:lo + _BLOCK].astype(np.float64) * weights
        out[lo:lo + _BLOCK] = block @ ramp
    return out


def backproject_voxels(geom: dict, filtered: np.ndarray,
                       voxels: np.ndarray) -> np.ndarray:
    """FDK values at `voxels` ((S, 3) integer (i, j, k) indices) from the
    filtered projections: paper Alg. 2 with bilinear taps, zero outside the
    detector, then the global FDK scale."""
    n_v, n_u = filtered.shape[1:]
    pm = projection_matrices(geom)
    hom = np.concatenate([voxels.astype(np.float64),
                          np.ones((len(voxels), 1))], axis=1).T   # (4, S)
    acc = np.zeros(len(voxels))
    for lo in range(0, len(pm), _BLOCK):
        xyz = pm[lo:lo + _BLOCK] @ hom                             # (B, 3, S)
        f = 1.0 / xyz[:, 2]
        u, v = xyz[:, 0] * f, xyz[:, 1] * f
        q = filtered[lo:lo + _BLOCK].reshape(len(xyz), n_v * n_u)
        u0, v0 = np.floor(u), np.floor(v)
        du, dv = u - u0, v - v0
        u0, v0 = u0.astype(np.int64), v0.astype(np.int64)
        val = np.zeros_like(u)
        for rv, cu, wgt in ((v0, u0, (1 - dv) * (1 - du)),
                            (v0, u0 + 1, (1 - dv) * du),
                            (v0 + 1, u0, dv * (1 - du)),
                            (v0 + 1, u0 + 1, dv * du)):
            inside = (rv >= 0) & (rv < n_v) & (cu >= 0) & (cu < n_u)
            flat = np.clip(rv, 0, n_v - 1) * n_u + np.clip(cu, 0, n_u - 1)
            tap = np.take_along_axis(q, flat, axis=1).astype(np.float64)
            val += np.where(inside, tap * wgt, 0.0)
        acc += np.sum(f * f * val, axis=0)
    return acc * (0.5 * geom["d"] ** 2 * 2 * np.pi / geom["n_proj"])


def fdk_voxels(geom: dict, proj: np.ndarray, voxels: np.ndarray) -> np.ndarray:
    """The reference FDK reconstruction of raw projections at `voxels`."""
    return backproject_voxels(geom, filter_projections(geom, proj), voxels)

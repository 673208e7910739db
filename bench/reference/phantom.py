"""Seeded ellipsoid phantoms and their analytic cone-beam projections.

The benchmark's inputs: a head-like phantom drawn from `--seed` (a skull,
the brain inside it and a fixed number of random inner ellipsoids), and its
exact line integrals through every detector pixel at every gantry angle.
The projector is a copy of the repository's analytic Shepp-Logan projector
(`core/phantom.py`), written so that one jitted call makes a whole scan on
the device; it imports nothing of the program. Every seed gives the same
shapes and the same work; only the ellipsoids move.

An ellipsoid row is (rho, a, b, c, x0, y0, z0, phi_deg): density, semi-axes,
centre in the gantry frame, and a rotation about z.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Ellipsoids besides the skull and the brain. Fixed, so that every seed
# makes the same work.
N_INNER = 10

# The modified (high-contrast) 3-D Shepp-Logan phantom, Kak-Slaney
# parameterisation: the seeded phantom's template, and the table that the
# agreement test feeds to both projectors.
SHEPP_LOGAN_3D = np.array([
    [1.00, 0.6900, 0.920, 0.810, 0.00, 0.000, 0.00, 0.0],
    [-0.80, 0.6624, 0.874, 0.780, 0.00, -0.0184, 0.00, 0.0],
    [-0.20, 0.1100, 0.310, 0.220, 0.22, 0.000, 0.00, -18.0],
    [-0.20, 0.1600, 0.410, 0.280, -0.22, 0.000, 0.00, 18.0],
    [0.10, 0.2100, 0.250, 0.410, 0.00, 0.350, -0.15, 0.0],
    [0.10, 0.0460, 0.046, 0.050, 0.00, 0.100, 0.25, 0.0],
    [0.10, 0.0460, 0.046, 0.050, 0.00, -0.100, 0.25, 0.0],
    [0.10, 0.0460, 0.023, 0.050, -0.08, -0.605, 0.00, 0.0],
    [0.10, 0.0230, 0.023, 0.020, 0.00, -0.606, 0.00, 0.0],
    [0.10, 0.0230, 0.046, 0.020, 0.06, -0.605, 0.00, 0.0],
])


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator `stream` of `seed`. Any whole number is a
    seed: negative and wide ones map into the 64-bit range."""
    return np.random.default_rng([seed % 2**64, stream])


def seeded_phantom(seed: int) -> np.ndarray:
    """(2 + N_INNER, 8) ellipsoid table drawn from `seed`: the Shepp-Logan
    skull and brain with their axes jittered by up to 5%, then N_INNER
    ellipsoids of density +-0.05..0.3 inside the brain."""
    r = rng(seed, 0)
    skull = SHEPP_LOGAN_3D[:2].copy()
    jitter = r.uniform(0.95, 1.0, size=3)
    skull[:, 1:4] *= jitter
    inner = np.empty((N_INNER, 8))
    inner[:, 0] = r.choice([-1.0, 1.0], N_INNER) * r.uniform(0.05, 0.3,
                                                             N_INNER)
    inner[:, 1:4] = r.uniform(0.03, 0.2, size=(N_INNER, 3))
    # Centres in a ball of radius 0.4: with semi-axes <= 0.2 every inner
    # ellipsoid stays inside the brain, whose semi-axes are >= 0.62.
    direction = r.normal(size=(N_INNER, 3))
    direction /= np.linalg.norm(direction, axis=1, keepdims=True)
    inner[:, 4:7] = direction * 0.4 * r.uniform(0, 1, (N_INNER, 1)) ** (1 / 3)
    inner[:, 7] = r.uniform(0.0, 180.0, N_INNER)
    return np.concatenate([skull, inner])


def fit(table: np.ndarray, geom: dict) -> np.ndarray:
    """`table`, drawn in the unit ball, scaled into the scan: by the
    radius of the field of view at the rotation axis or the volume's
    half extent, whichever is less (1 for the repository's default
    geometry, whose unit ball is its volume)."""
    half_fan = np.arctan(geom["n_u"] * geom["d_u"] / 2.0 / geom["dsd"])
    scale = min(geom["d"] * np.sin(half_fan),
                *(geom["n_" + a] * geom["d_" + a] / 2.0 for a in "xyz"))
    out = table.copy()
    out[:, 1:7] *= scale
    return out


def _frames(table: np.ndarray):
    """(rho, centre, M) per ellipsoid, M = diag(1/axes) @ Rz(-phi): the map
    of a gantry-frame offset to the unit sphere."""
    rho, axes, centres = table[:, 0], table[:, 1:4], table[:, 4:7]
    phi = np.deg2rad(table[:, 7])
    c, s = np.cos(phi), np.sin(phi)
    zero, one = np.zeros_like(c), np.ones_like(c)
    rot = np.stack([np.stack([c, s, zero], -1),
                    np.stack([-s, c, zero], -1),
                    np.stack([zero, zero, one], -1)], -2)
    return (rho.astype(np.float32), centres.astype(np.float32),
            (rot / axes[:, :, None]).astype(np.float32))


def _geometry_args(geom: dict) -> tuple:
    return tuple(float(geom[k]) for k in ("d_u", "d_v", "d", "dsd"))


@partial(jax.jit, static_argnames=("n_proj", "n_v", "n_u", "chunk"))
def _project(rho, centres, m, pitch_dist, *, n_proj: int, n_v: int,
             n_u: int, chunk: int):
    d_u, d_v, d, dsd = (pitch_dist[i] for i in range(4))
    cx = (jnp.arange(n_u, dtype=jnp.float32) - (n_u - 1) / 2.0) * d_u
    cy = (jnp.arange(n_v, dtype=jnp.float32) - (n_v - 1) / 2.0) * d_v
    cx, cy = jnp.meshgrid(cx, cy, indexing="xy")          # (n_v, n_u)
    theta = 2.0 * np.pi / n_proj

    def one_angle(index):
        beta = index.astype(jnp.float32) * theta
        src = jnp.stack([-d * jnp.sin(beta), -d * jnp.cos(beta),
                         jnp.zeros((), jnp.float32)])
        # Detector pixel centres in the gantry frame (camera frame rotated
        # by -beta), as core/geometry.detector_pixel_position places them.
        c, s = jnp.cos(-beta), jnp.sin(-beta)
        ry = dsd - d
        pix = jnp.stack([c * cx - s * ry, s * cx + c * ry, -cy], -1)
        ray = pix - src
        ray = ray / jnp.sqrt(jnp.sum(ray * ray, -1, keepdims=True))
        total = jnp.zeros((n_v, n_u), jnp.float32)
        for e in range(rho.shape[0]):      # a fixed, small number: unrolled
            me = m[e]
            o = [jnp.sum(me[a] * (src - centres[e])) for a in range(3)]
            dd = [jnp.sum(me[a] * ray, -1) for a in range(3)]
            qa = dd[0] * dd[0] + dd[1] * dd[1] + dd[2] * dd[2]
            qb = 2.0 * (o[0] * dd[0] + o[1] * dd[1] + o[2] * dd[2])
            qc = o[0] * o[0] + o[1] * o[1] + o[2] * o[2] - 1.0
            disc = qb * qb - 4.0 * qa * qc
            chord = jnp.where(disc > 0.0,
                              jnp.sqrt(jnp.maximum(disc, 0.0)) / qa, 0.0)
            total = total + rho[e] * chord
        return total

    def one_chunk(start):
        return jax.vmap(one_angle)(start + jnp.arange(chunk))

    starts = jnp.arange(0, n_proj, chunk)
    out = jax.lax.map(one_chunk, starts)
    return out.reshape((n_proj, n_v, n_u))


def project(table: np.ndarray, geom: dict, device=None) -> jax.Array:
    """Analytic projections (n_proj, n_v, n_u) float32 of the ellipsoid
    `table` under the scan geometry `geom` (the configuration's keys), made
    in one jitted call on `device` (default: JAX's default device)."""
    n_proj = int(geom["n_proj"])
    chunk = max(c for c in range(1, 9) if n_proj % c == 0)
    args = [jnp.asarray(a) for a in _frames(table)]
    args.append(jnp.asarray(_geometry_args(geom), jnp.float32))
    if device is not None:
        args = [jax.device_put(a, device) for a in args]
    return _project(*args, n_proj=n_proj, n_v=int(geom["n_v"]),
                    n_u=int(geom["n_u"]), chunk=chunk)

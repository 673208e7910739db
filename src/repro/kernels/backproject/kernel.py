"""Pallas TPU back-projection kernel (the paper's shflBP, TPU-adapted).

Design (see DESIGN.md §2 for the CUDA->TPU mapping):

  * Volume is produced in the *dual-slab* layout (nx, ny, 2, nz/2): slab 0 is
    the front half of z, slab 1 the z-reversed back half, so a Theorem-1
    mirror pair shares one index. z runs along the TPU **lane** dimension.
  * Grid = (nx/Bi, ny/Bj, Np/Bs). The output tile (Bi, Bj, nz) stays
    resident in VMEM across the innermost (projection-batch) grid dimension —
    the TPU analogue of the paper's "batch of 32 projections per kernel
    launch" that amortizes volume traffic (global memory there, HBM here).
  * Per voxel column (i, j): u, the depth z and w = 1/z^2 are computed once
    (Theorems 2/3); v is the affine ramp (y0 + k*dy) * f along the lanes.
  * Bilinear interpolation is split in two, with no 2-D gather (Mosaic
    lowers neither a dynamic slice of a loaded value nor a gather from a
    flattened projection):
      1. u-interpolation on the MXU: for the Bj columns of one i-row,
         rows = hat(a - u) @ Q^T, with hat(t) = max(0, 1 - |t|) over the
         detector columns a — two non-zero weights per row, so each row of
         `rows` is the detector line at the column's u (zero outside the
         detector for free). Q^T is (N_u, N_v): v contiguous, the paper's
         "L1-Tran" layout.
      2. v-interpolation on the VPU: each voxel reads its two taps along the
         line with lane gathers (`jnp.take_along_axis`), one 128-lane source
         block at a time — the width Mosaic's dynamic gather supports.
  * The symmetric (Theorem-1) half reuses the rows with v~ = (Nv-1) - v.

The projection batch may arrive in bf16/fp16/fp8 (the stream codec's wire
dtype); it is upcast to f32 in VMEM, the u-interpolation runs at f32
(HIGHEST) precision, the codec's per-projection scale (parameter column 12,
1.0 for scale-free codecs) multiplies the accumulation weight, and the
accumulator tile is always f32. The 13 per-projection parameters live in
SMEM.

`vmem_bytes()` is the working-set model the autotuner (tune.py) prunes block
candidates with; the kernel raises Mosaic's scoped-VMEM limit to the same
budget. Interpret mode (the Pallas interpreter, for CPU tests) runs only
when a caller asks for it: `interpret=True`, or `REPRO_PALLAS_INTERPRET=1`
in the environment. Off TPU without either, Pallas refuses to lower the
kernel instead of silently running somewhere else.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_LANES = 128
_N_PARAMS = 13  # 12 projection-matrix entries + the codec decode scale


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit flag wins; None defers to REPRO_PALLAS_INTERPRET=1 (the
    CPU test suite sets it). The backend never decides: off TPU without
    either, Pallas refuses to lower the compiled kernel — there is no
    silent fallback."""
    if interpret is None:
        return os.environ.get("REPRO_PALLAS_INTERPRET", "") not in ("", "0")
    return interpret


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tile_is_legal(bj: int, ny: int) -> bool:
    """The output tile's second-minor extent must be a multiple of the
    8-row sublane tile, or span the whole array."""
    return bj % 8 == 0 or bj == ny


def _row_chunk(bj: int) -> int:
    """Rows of one v-interpolation step: one sublane tile when it divides
    the column block, else the whole block."""
    return 8 if bj % 8 == 0 else bj


def _interp_lines(line: Array, v: Array) -> Array:
    """Linear interpolation of detector lines at positions v.

    line (rc, NVP) holds each column's line, zero in lanes [nv, NVP); v
    (rc, KP), KP a multiple of 128. Each tap is a lane gather from the one
    128-lane block that holds it; a tap outside [0, nv) reads zero (no block
    holds it, or a zero lane).
    """
    b0f = jnp.floor(v)
    dv = v - b0f
    b0 = b0f.astype(jnp.int32)
    kp = v.shape[1]
    t0 = jnp.zeros(v.shape, jnp.float32)
    t1 = jnp.zeros(v.shape, jnp.float32)
    for lo in range(0, line.shape[1], _LANES):
        src = line[:, lo:lo + _LANES]
        for b, t in ((b0, 0), (b0 + 1, 1)):
            li = b - lo
            tap = jnp.concatenate(
                [jnp.take_along_axis(
                    src, jnp.clip(li[:, o:o + _LANES], 0, _LANES - 1), axis=1)
                 for o in range(0, kp, _LANES)], axis=1)
            inb = (li >= 0) & (li < _LANES)
            if t == 0:
                t0 = jnp.where(inb, tap, t0)
            else:
                t1 = jnp.where(inb, tap, t1)
    return t0 * (1.0 - dv) + t1 * dv


def _bp_kernel(pm_ref, qt_ref, out_ref, line_ref, *, bs: int, nv: int):
    gi = pl.program_id(0)
    gj = pl.program_id(1)
    gs = pl.program_id(2)
    bi, bj, nz = out_ref.shape
    nzh = nz // 2
    nu = qt_ref.shape[1]
    kp = _round_up(nzh, _LANES)
    rc = _row_chunk(bj)

    @pl.when(gs == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)
        # lanes [nv, NVP) of the line buffer stay zero for the whole step
        line_ref[...] = jnp.zeros(line_ref.shape, jnp.float32)

    a = lax.broadcasted_iota(jnp.int32, (1, nu), 1).astype(jnp.float32)
    k = lax.broadcasted_iota(jnp.int32, (1, kp), 1).astype(jnp.float32)
    j_blk = lax.broadcasted_iota(jnp.int32, (bj, 1), 0).astype(jnp.float32)
    j_chk = lax.broadcasted_iota(jnp.int32, (rc, 1), 0).astype(jnp.float32)
    j_base = (gj * bj).astype(jnp.float32)

    def proj_step(s, carry):
        base = (gs * bs + s) * _N_PARAMS
        p = [pm_ref[base + t] for t in range(_N_PARAMS)]
        q = qt_ref[s].astype(jnp.float32)               # (nu, nv)

        def row_step(r, carry):
            i = (gi * bi + r).astype(jnp.float32)
            # Theorem 2: u is constant along the column; hat weights select
            # (and blend) the two detector columns around it.
            j = j_base + j_blk
            x0 = p[0] * i + p[1] * j + p[3]
            z = p[8] * i + p[9] * j + p[11]
            u = x0 * (1.0 / z)
            hat = jnp.maximum(0.0, 1.0 - jnp.abs(a - u))   # (bj, nu)
            line_ref[:, :nv] = jnp.dot(
                hat, q, precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

            def chunk_step(c, carry):
                j0 = c * rc
                if rc % 8 == 0:
                    j0 = pl.multiple_of(j0, 8)
                jj = j_base + j0.astype(jnp.float32) + j_chk
                y0 = p[4] * i + p[5] * jj + p[7]
                zz = p[8] * i + p[9] * jj + p[11]
                f = 1.0 / zz
                w = f * f * p[12]               # T3 weight x codec scale
                v = (y0 + p[6] * k) * f         # (rc, KP): one FMA per voxel
                line = line_ref[pl.ds(j0, rc), :]
                front = w * _interp_lines(line, v)
                # Theorem-1 mirror: reuse the lines, reflect v
                back = w * _interp_lines(line, (nv - 1.0) - v)
                rows = pl.ds(j0, rc)
                out_ref[r, rows, pl.ds(0, nzh)] += front[:, :nzh]
                out_ref[r, rows, pl.ds(nzh, nzh)] += back[:, :nzh]
                return carry

            return lax.fori_loop(0, bj // rc, chunk_step, carry)

        return lax.fori_loop(0, bi, row_step, carry)

    lax.fori_loop(0, bs, proj_step, 0)


def vmem_bytes(bi: int, bj: int, bs: int, nu: int, nv: int, nzh: int,
               qt_dtype=jnp.float32) -> int:
    """VMEM working set of one grid step: the double-buffered projection
    batch and output tile, the line buffer, and the per-row temporaries
    (f32 projection, hat matrix, matmul result),
    plus a quarter for what Mosaic adds that is not modelled term by term:
    compiled for v5e at the 512^3 clinical scan, its scoped allocation
    came out 6-16% above the plain sum."""
    qbytes = jnp.dtype(qt_dtype).itemsize
    nvp = _round_up(nv, _LANES)
    kp = _round_up(nzh, _LANES)
    qt_block = 2 * bs * nu * nv * qbytes
    out_tile = 2 * bi * bj * 2 * nzh * 4
    line = bj * nvp * 4
    temps = nu * nv * 4 + bj * nu * 4 + bj * nvp * 4
    rc = _row_chunk(bj)
    chunk = 16 * rc * kp * 4 + rc * nvp * 4
    return (qt_block + out_tile + line + temps + chunk) * 5 // 4


@functools.partial(
    jax.jit,
    static_argnames=("nx", "ny", "nz", "bi", "bj", "bs", "interpret",
                     "vmem_limit"))
def backproject_dual_pallas(pmats: Array, qt: Array,
                            nx: int, ny: int, nz: int,
                            bi: int = 1, bj: int = 8, bs: int = 1,
                            interpret: bool = False,
                            vmem_limit: int | None = None) -> Array:
    """pmats (Np, 13) f32 — 12 projection-matrix entries + the stream
    codec's per-projection decode scale (pass 1.0 for unscaled streams; a
    legacy (Np, 12) matrix is widened with unit scales) — and qt (Np, Nu,
    Nv) -> dual-slab volume (nx, ny, 2, nz/2).

    Np must be a multiple of bs, nx of bi, ny of bj (ops.py pads); bj must
    be a multiple of 8 or ny itself (the (8, 128) tiling of the output
    tile). `vmem_limit` is the scoped-VMEM limit handed to Mosaic (the
    tuner's budget).
    """
    n_p, nu, nv = qt.shape
    assert nz % 2 == 0 and n_p % bs == 0 and nx % bi == 0 and ny % bj == 0
    if not tile_is_legal(bj, ny):
        raise ValueError(
            f"bj={bj} must be a multiple of 8 or the whole N_y={ny} (the "
            "(8, 128) tiling of the output tile)")
    if pmats.shape[1] == 12:
        pmats = jnp.concatenate(
            [pmats, jnp.ones((n_p, 1), pmats.dtype)], axis=1)
    nvp = _round_up(nv, _LANES)
    kernel = functools.partial(_bp_kernel, bs=bs, nv=nv)
    # The parameter rows are scalar-prefetched: the whole (Np * 13,) table
    # sits in SMEM for the kernel's lifetime (52 B per projection).
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nx // bi, ny // bj, n_p // bs),
            in_specs=[pl.BlockSpec((bs, nu, nv),
                                   lambda gi, gj, gs, pm: (gs, 0, 0))],
            out_specs=pl.BlockSpec((bi, bj, nz),
                                   lambda gi, gj, gs, pm: (gi, gj, 0)),
            scratch_shapes=[pltpu.VMEM((bj, nvp), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nx, ny, nz), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="backproject_dual",
    )(pmats.astype(jnp.float32).reshape(-1), qt)
    return out.reshape(nx, ny, 2, nz // 2)

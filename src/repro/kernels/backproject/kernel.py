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
      1. u-interpolation on the MXU: for the Bi*Bj columns of the tile,
         rows = hat(a - u) @ Q^T, with hat(t) = max(0, 1 - |t|) over a
         window of K detector columns a = start + iota(K) — two non-zero
         weights per row, so each row of `rows` is the detector line at the
         column's u (zero outside the detector for free). Q^T is (N_u, N_v):
         v contiguous, the paper's "L1-Tran" layout. u is linear-fractional
         in (i, j) (Theorem 2), so over the tile it spans the range its four
         corners give: `window_starts` puts each (tile, view)'s window start
         there, aligned to the wire dtype's sublane packing, and the static K
         comes from the scan's matrices on the host (`u_spans`,
         `window_width`). K = N_u (start 0) contracts over the whole
         detector line.
      2. v-interpolation on the VPU: each voxel reads its two taps along the
         line with lane gathers (`jnp.take_along_axis`), one 128-lane source
         block at a time — the width Mosaic's dynamic gather supports.
  * The symmetric (Theorem-1) half reuses the rows with v~ = (Nv-1) - v.

The projection batch may arrive in bf16/fp16/fp8 (the stream codec's wire
dtype); it is upcast to f32 in VMEM, the u-interpolation runs at f32
(HIGHEST) precision, the codec's per-projection scale (parameter column 12,
1.0 for scale-free codecs) multiplies the accumulation weight, and the
accumulator tile is always f32. The 13 per-projection parameters live in
SMEM, and so does each output tile's row of window starts (one per view).

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
import numpy as np

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


def sublane_align(dtype) -> int:
    """Rows of one packed sublane tile of `dtype` (8 f32, 16 bf16/fp16, 32
    fp8): the alignment of a dynamic row offset into the projection block."""
    return 32 // jnp.dtype(dtype).itemsize


# Columns a tile's window keeps below its least tap (and twice that above
# its greatest), for the rounding between the f32 u of `u_spans`,
# `window_starts` and the kernel.
_WINDOW_MARGIN = 1


def u_spans(pmats, nx: int, ny: int, tiles) -> dict:
    """{(bi, bj): span} for each output tile shape in `tiles`: the widest
    floor(u_max) - floor(u_min) over one (bi, bj) tile of an (nx, ny)
    volume, over every tile and view of `pmats` (Np, 3, 4).

    Theorem 2 makes u a linear-fractional function of (i, j), so over a
    tile it spans the range of its four corners: u is evaluated (in f32,
    like the kernel) on every row and on the columns that are some tile's
    edge, once for every tile shape. Every span is None when a voxel
    there lies at or behind the source (depth <= 0: the corners no longer
    bound u).
    """
    pm = np.asarray(pmats, np.float32).reshape(-1, 3, 4)
    tiles = sorted({tuple(t) for t in tiles}, key=lambda t: (t[1], t[0]))
    spans = dict.fromkeys(tiles, 0)
    if not tiles:
        return spans
    edges = {bj: (np.arange(0, ny, bj), np.arange(bj - 1, ny, bj))
             for _, bj in tiles}
    at = np.unique(np.concatenate([e for pair in edges.values()
                                   for e in pair]))
    pos = np.zeros(ny, np.int64)
    pos[at] = np.arange(len(at))
    i = np.arange(nx, dtype=np.float32)[None, :, None]
    j = at.astype(np.float32)[None, None, :]
    step = max(1, 2**22 // (nx * len(at)))  # views per pass
    for lo in range(0, len(pm), step):
        p = pm[lo:lo + step, :, :, None, None]
        z = p[:, 2, 0] * i + p[:, 2, 1] * j + p[:, 2, 3]
        if not (z > 0).all():
            return dict.fromkeys(tiles)
        u = np.floor((p[:, 0, 0] * i + p[:, 0, 1] * j + p[:, 0, 3]) / z)
        hi_j = lo_j = None
        for n, (bi, bj) in enumerate(tiles):
            if n == 0 or tiles[n - 1][1] != bj:  # each column block once
                e0, e1 = (u[:, :, pos[e]] for e in edges[bj])
                hi_j, lo_j = np.maximum(e0, e1), np.minimum(e0, e1)
            hi = np.maximum(hi_j[:, ::bi], hi_j[:, bi - 1::bi])
            low = np.minimum(lo_j[:, ::bi], lo_j[:, bi - 1::bi])
            spans[bi, bj] = max(spans[bi, bj], int((hi - low).max()))
    return spans


def window_width(span: int | None, nu: int, qt_dtype=jnp.float32) -> int:
    """Static width K of the u-step's window for a tile whose u spans
    `span` (`u_spans`): its taps floor(u_min) .. floor(u_max) + 1, the
    alignment slack of `window_starts`, and room for the three f32
    evaluations of u (here, in the start table, in the kernel) to floor one
    column apart: the start table's margin below, and above it that margin
    plus one more. Rounded up to 128 lanes; N_u when that reaches N_u or
    the span is unbounded (None)."""
    if span is None:
        return nu
    need = span + 2 + 3 * _WINDOW_MARGIN + sublane_align(qt_dtype) - 1
    return min(_round_up(need, _LANES), nu)


def window_starts(pm: Array, nx: int, ny: int, bi: int, bj: int, nu: int,
                  kw: int, align: int) -> Array:
    """(nx/bi, ny/bj, Np) int32: the first detector column of each (tile,
    view)'s window of `kw` columns — floor of u's least corner value less
    the margin, aligned down to `align`, clamped to [0, nu - kw]. pm is the
    kernel's (Np, 13) parameter table."""
    ci = jnp.stack([jnp.arange(0, nx, bi), jnp.arange(bi - 1, nx, bi)])
    cj = jnp.stack([jnp.arange(0, ny, bj), jnp.arange(bj - 1, ny, bj)])
    i = ci.astype(jnp.float32)[:, :, None, None, None]
    j = cj.astype(jnp.float32)[None, None, :, :, None]
    x0 = pm[:, 0] * i + pm[:, 1] * j + pm[:, 3]
    z = pm[:, 8] * i + pm[:, 9] * j + pm[:, 11]
    lo = jnp.min(x0 / z, axis=(0, 2))                  # (nx/bi, ny/bj, Np)
    lo = jnp.where(jnp.isfinite(lo), lo, 0.0)
    lo = jnp.clip(lo, -1.0 - _WINDOW_MARGIN, float(nu))
    start = jnp.floor(lo).astype(jnp.int32) - _WINDOW_MARGIN
    return jnp.clip(start // align * align, 0, nu - kw)


def _bp_kernel(pm_ref, st_ref, qt_ref, out_ref, line_ref, *, bs: int,
               nv: int, kw: int, align: int):
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

    a = lax.broadcasted_iota(jnp.int32, (1, kw), 1).astype(jnp.float32)
    k = lax.broadcasted_iota(jnp.int32, (1, kp), 1).astype(jnp.float32)
    # The tile's columns, flattened: row r holds (i, j) = divmod(r, bj).
    r = lax.broadcasted_iota(jnp.int32, (bi * bj, 1), 0).astype(jnp.float32)
    i_loc = jnp.floor((r + 0.5) * (1.0 / bj))
    i_col = (gi * bi).astype(jnp.float32) + i_loc
    j_base = (gj * bj).astype(jnp.float32)
    j_col = j_base + (r - i_loc * bj)
    j_chk = lax.broadcasted_iota(jnp.int32, (rc, 1), 0).astype(jnp.float32)

    def proj_step(s, carry):
        view = gs * bs + s
        base = view * _N_PARAMS
        p = [pm_ref[base + t] for t in range(_N_PARAMS)]
        start = st_ref[view]
        if kw == nu:
            q = qt_ref[s]
        else:
            q = qt_ref[s, pl.ds(pl.multiple_of(start, align), kw), :]
        q = q.astype(jnp.float32)                       # (kw, nv)
        # Theorem 2: u is constant along the column; hat weights over the
        # window select (and blend) the two detector columns around it.
        x0 = p[0] * i_col + p[1] * j_col + p[3]
        z = p[8] * i_col + p[9] * j_col + p[11]
        u = x0 * (1.0 / z)
        col = a + start.astype(jnp.float32)
        hat = jnp.maximum(0.0, 1.0 - jnp.abs(col - u))  # (bi * bj, kw)
        line_ref[:, :nv] = jnp.dot(
            hat, q, precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

        def row_step(ri, carry):
            i = (gi * bi + ri).astype(jnp.float32)

            def chunk_step(c, carry):
                j0 = c * rc
                row0 = ri * bj + j0
                if rc % 8 == 0:
                    j0 = pl.multiple_of(j0, 8)
                    row0 = pl.multiple_of(row0, 8)
                jj = j_base + j0.astype(jnp.float32) + j_chk
                y0 = p[4] * i + p[5] * jj + p[7]
                zz = p[8] * i + p[9] * jj + p[11]
                f = 1.0 / zz
                w = f * f * p[12]               # T3 weight x codec scale
                v = (y0 + p[6] * k) * f         # (rc, KP): one FMA per voxel
                line = line_ref[pl.ds(row0, rc), :]
                front = w * _interp_lines(line, v)
                # Theorem-1 mirror: reuse the lines, reflect v
                back = w * _interp_lines(line, (nv - 1.0) - v)
                rows = pl.ds(j0, rc)
                out_ref[ri, rows, pl.ds(0, nzh)] += front[:, :nzh]
                out_ref[ri, rows, pl.ds(nzh, nzh)] += back[:, :nzh]
                return carry

            return lax.fori_loop(0, bj // rc, chunk_step, carry)

        return lax.fori_loop(0, bi, row_step, carry)

    lax.fori_loop(0, bs, proj_step, 0)


def _window_rows(nu: int, kw: int | None, qt_dtype) -> tuple[int, int]:
    """(K, projection rows in VMEM): K = `kw` capped at N_u (None = N_u);
    a window narrower than N_u pads the rows to the sublane alignment."""
    kw = nu if kw is None else min(kw, nu)
    return kw, nu if kw == nu else _round_up(nu, sublane_align(qt_dtype))


def vmem_bytes(bi: int, bj: int, bs: int, nu: int, nv: int, nzh: int,
               qt_dtype=jnp.float32, kw: int | None = None) -> int:
    """VMEM working set of one grid step with a u-window of `kw` columns
    (None = N_u): the double-buffered projection batch and output tile, the
    (bi*bj, NVP) line buffer, and the per-projection temporaries (f32
    window, (bi*bj, kw) hat matrix, matmul result),
    plus a quarter for what Mosaic adds that is not modelled term by term:
    compiled for v5e at the 512^3 clinical scan, its scoped allocation
    came out 6-16% above the plain sum."""
    qbytes = jnp.dtype(qt_dtype).itemsize
    kw, nu_rows = _window_rows(nu, kw, qt_dtype)
    nvp = _round_up(nv, _LANES)
    kp = _round_up(nzh, _LANES)
    rows = bi * bj
    qt_block = 2 * bs * nu_rows * nv * qbytes
    out_tile = 2 * rows * 2 * nzh * 4
    line = rows * nvp * 4
    temps = kw * nv * 4 + rows * kw * 4 + rows * nvp * 4
    rc = _row_chunk(bj)
    chunk = 16 * rc * kp * 4 + rc * nvp * 4
    return (qt_block + out_tile + line + temps + chunk) * 5 // 4


@functools.partial(
    jax.jit,
    static_argnames=("nx", "ny", "nz", "bi", "bj", "bs", "interpret",
                     "vmem_limit", "u_window"))
def backproject_dual_pallas(pmats: Array, qt: Array,
                            nx: int, ny: int, nz: int,
                            bi: int = 1, bj: int = 8, bs: int = 1,
                            interpret: bool = False,
                            vmem_limit: int | None = None,
                            u_window: int | None = None) -> Array:
    """pmats (Np, 13) f32 — 12 projection-matrix entries + the stream
    codec's per-projection decode scale (pass 1.0 for unscaled streams; a
    legacy (Np, 12) matrix is widened with unit scales) — and qt (Np, Nu,
    Nv) -> dual-slab volume (nx, ny, 2, nz/2).

    Np must be a multiple of bs, nx of bi, ny of bj (ops.py pads); bj must
    be a multiple of 8 or ny itself (the (8, 128) tiling of the output
    tile). `vmem_limit` is the scoped-VMEM limit handed to Mosaic (the
    tuner's budget). `u_window` is the u-step's window K (`window_width` of
    the scan's matrices at this tile; None = N_u, the whole detector line);
    a K below N_u must be a multiple of the wire dtype's sublane packing.
    """
    n_p, nu, nv = qt.shape
    assert nz % 2 == 0 and n_p % bs == 0 and nx % bi == 0 and ny % bj == 0
    if not tile_is_legal(bj, ny):
        raise ValueError(
            f"bj={bj} must be a multiple of 8 or the whole N_y={ny} (the "
            "(8, 128) tiling of the output tile)")
    align = sublane_align(qt.dtype)
    kw, nu_rows = _window_rows(nu, u_window, qt.dtype)
    if kw % align and kw < nu:
        raise ValueError(
            f"u_window={kw} must be a multiple of {align} (the sublane "
            f"packing of {qt.dtype}) or reach N_u={nu}")
    if nu_rows > nu:  # zero columns: the clamped window stays aligned
        qt = jnp.pad(qt, ((0, 0), (0, nu_rows - nu), (0, 0)))
    if pmats.shape[1] == 12:
        pmats = jnp.concatenate(
            [pmats, jnp.ones((n_p, 1), pmats.dtype)], axis=1)
    pm = pmats.astype(jnp.float32)
    # One row of starts per tile, flattened, each row padded to whole
    # 1024-entry tiles of XLA's 1-D int32 layout: the SMEM block of a tile
    # is its row.
    n_pad = _round_up(n_p, 1024)
    starts = window_starts(pm, nx, ny, bi, bj, nu_rows, kw, align)
    starts = jnp.pad(starts, ((0, 0), (0, 0), (0, n_pad - n_p))).reshape(-1)
    gj_n = ny // bj
    nvp = _round_up(nv, _LANES)
    kernel = functools.partial(_bp_kernel, bs=bs, nv=nv, kw=kw, align=align)
    # The parameter rows are scalar-prefetched: the whole (Np * 13,) table
    # sits in SMEM for the kernel's lifetime (52 B per projection). The
    # window starts go to SMEM one tile's row (Np int32) at a time.
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nx // bi, ny // bj, n_p // bs),
            in_specs=[
                pl.BlockSpec((n_pad,),
                             lambda gi, gj, gs, pm: (gi * gj_n + gj,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((bs, nu_rows, nv),
                             lambda gi, gj, gs, pm: (gs, 0, 0))],
            out_specs=pl.BlockSpec((bi, bj, nz),
                                   lambda gi, gj, gs, pm: (gi, gj, 0)),
            scratch_shapes=[pltpu.VMEM((bi * bj, nvp), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nx, ny, nz), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="backproject_dual",
    )(pm.reshape(-1), starts, qt)
    return out.reshape(nx, ny, 2, nz // 2)

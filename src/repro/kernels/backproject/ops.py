"""jit'd public wrappers around the back-projection kernel.

  backproject_pallas : drop-in replacement for core.backprojection.*
                       (handles layout, padding, block selection)
  backproject_mxu    : gather-free MXU formulation — bilinear interpolation
                       recast as two small matmuls with relu-hat weight
                       matrices (texture fetch -> systolic array; see
                       DESIGN.md §2). Exact same math, no dynamic indexing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.backprojection import _stream_scales, from_dual_slab
from .kernel import backproject_dual_pallas, resolve_interpret, vmem_bytes
from . import tune

Array = jax.Array


def backproject_pallas(pmats: Array, proj: Array,
                       nx: int, ny: int, nz: int,
                       bi: int | None = None, bj: int | None = None,
                       bs: int | None = None,
                       interpret: bool | None = None,
                       vmem_budget: int | None = None,
                       scales: Array | None = None,
                       u_window: int | None = None) -> Array:
    """Alg. 4 via the Pallas kernel. Same signature/result as the oracles.

    pmats: (Np, 3, 4); proj: (Np, N_v, N_u) filtered projections (row = v),
    in any wire dtype (fp32/bf16/fp16/fp8 — the stream codec's output);
    taps are upcast inside the kernel, `scales` (the codec's per-projection
    sidecar, None = unscaled) rides as column 12 of the parameter row and
    dequantizes at the accumulation weight, and accumulation is always f32.
    Returns (nx, ny, nz) float32.

    Block shapes not given explicitly come from the VMEM-budget autotuner
    (tune.pick_blocks): candidates that tile the problem, pruned against
    `vmem_budget` (default REPRO_BP_VMEM_BUDGET), model-ranked — or timed
    once per (geometry, dtype) when REPRO_BP_AUTOTUNE=time. The kernel's
    scoped-VMEM limit is that budget (or the tile's working set, if larger).

    `u_window` is the u-step's detector-column window K, derived on the
    host from the scan's matrices at these blocks (`tune.tile_window`; the
    engine passes it); None contracts over all N_u columns. The window
    starts are computed here, per call, from `pmats`.

    `interpret` runs the Pallas interpreter; None defers to
    REPRO_PALLAS_INTERPRET (kernel.resolve_interpret).
    """
    n_p = proj.shape[0]
    interpret = resolve_interpret(interpret)
    qt = jnp.swapaxes(proj, -1, -2)  # (Np, Nu, Nv): v contiguous
    nu, nv = qt.shape[1], qt.shape[2]
    if bi is None or bj is None or bs is None:
        bi, bj, bs = tune.pick_blocks(
            nx, ny, nz, n_p, nu, nv, qt_dtype=qt.dtype,
            budget=vmem_budget, interpret=interpret,
            fix_bi=bi, fix_bj=bj, fix_bs=bs,
        )
    pm = pmats.reshape(n_p, 12).astype(jnp.float32)
    sc = (jnp.ones((n_p, 1), jnp.float32) if scales is None
          else scales.reshape(n_p, 1).astype(jnp.float32))
    pm = jnp.concatenate([pm, sc], axis=1)
    if n_p % bs:
        pad = bs - n_p % bs
        qt = jnp.pad(qt, ((0, pad), (0, 0), (0, 0)))
        pm = jnp.pad(pm, ((0, pad), (0, 0)), constant_values=1.0)
    budget = tune.DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    limit = max(budget, vmem_bytes(bi, bj, bs, nu, nv, nz // 2, qt.dtype,
                                   kw=u_window))
    dual = backproject_dual_pallas(
        pm, qt, nx, ny, nz, bi=bi, bj=bj, bs=bs, interpret=interpret,
        vmem_limit=limit, u_window=u_window)
    return from_dual_slab(dual)


@functools.partial(jax.jit, static_argnames=("nx", "ny", "nz"))
def backproject_mxu(pmats: Array, proj: Array,
                    nx: int, ny: int, nz: int,
                    scales: Array | None = None) -> Array:
    """Gather-free back-projection: interpolation as relu-hat matmuls.

    For a voxel column (i,j):  val(k) = sum_{a,b} A[ij,a] * B[ij,k,b] * Q^T[a,b]
    with A[ij,a] = hat(a - u_ij), B[ij,k,b] = hat(b - v_ijk) and
    hat(t) = max(0, 1-|t|). Out-of-range coordinates get zero weight for free
    (no masking needed). Two einsums per projection:
        rows = A @ Q^T          (columns, N_v)   <- MXU
        val  = sum_b B * rows   (columns, nzh)   <- VPU reduction
    FLOP cost is ~N_u/4 + N_v/4 times the gather variant, but it maps onto
    the MXU and needs no dynamic addressing — the fallback documented in
    DESIGN.md for targets whose gather lowering is unavailable.
    """
    if nz % 2 != 0:
        raise ValueError("requires even N_z")
    nzh = nz // 2
    n_p, n_v, n_u = proj.shape
    qt = jnp.swapaxes(proj, -1, -2).astype(jnp.float32)  # (Np, Nu, Nv)
    i = jnp.arange(nx, dtype=jnp.float32)[:, None]
    j = jnp.arange(ny, dtype=jnp.float32)[None, :]
    k = jnp.arange(nzh, dtype=jnp.float32)
    ua = jnp.arange(n_u, dtype=jnp.float32)
    va = jnp.arange(n_v, dtype=jnp.float32)

    def hat(t):
        return jnp.maximum(0.0, 1.0 - jnp.abs(t))

    def body(acc, sp):
        p, q, s = sp
        x0 = p[0, 0] * i + p[0, 1] * j + p[0, 3]
        y0 = p[1, 0] * i + p[1, 1] * j + p[1, 3]
        z = p[2, 0] * i + p[2, 1] * j + p[2, 3]
        f = 1.0 / z
        u = x0 * f
        w = f * f * s                   # codec decode folded into the weight
        v = (y0[..., None] + p[1, 2] * k) * f[..., None]      # (nx, ny, nzh)
        a = hat(ua[None, None, :] - u[..., None])             # (nx, ny, Nu)
        rows = jnp.einsum("xyu,uv->xyv", a, q)                # MXU matmul
        b = hat(va[None, None, None, :] - v[..., None])       # (nx,ny,nzh,Nv)
        bm = hat(va[None, None, None, :] - ((n_v - 1.0) - v)[..., None])
        front = w[..., None] * jnp.einsum("xykv,xyv->xyk", b, rows)
        back = w[..., None] * jnp.einsum("xykv,xyv->xyk", bm, rows)
        return acc + jnp.stack([front, back], axis=-2), None

    init = jnp.zeros((nx, ny, 2, nzh), jnp.float32)
    dual, _ = jax.lax.scan(body, init, (pmats.astype(jnp.float32), qt,
                                        _stream_scales(proj, scales)))
    return from_dual_slab(dual)

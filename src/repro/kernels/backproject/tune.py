"""VMEM-budget-aware block autotuner for the Pallas back-projection kernel.

The (bi, bj, bs) tile shape sets the VMEM working set (kernel.vmem_bytes),
the u-step's MXU work — each tile contracts over a window of K(bi, bj)
detector columns (kernel.u_spans), so compact tiles keep K small — and the
HBM traffic: the projection batch is re-streamed once per (gi, gj) output
tile, (nx/bi)*(ny/bj) * Np*Nu*Nv*itemsize in all. The tuner

  1. enumerates candidates that tile the problem (bi | nx, bj | ny with bj
     a multiple of 8 or ny itself, bs a power of two — ops.py pads the
     projection axis),
  2. prunes them against a configurable VMEM budget with the kernel's own
     vmem_bytes() model at each tile's window (storage dtype aware:
     bf16/fp16 projections halve the projection blocks),
  3. ranks the survivors by modelled time (`model_seconds`), and — in
     measured mode — times the few best with the real kernel once per
     (geometry, dtype), memoized in an in-process cache.

Given the scan's projection matrices (`pmats`), K is derived per tile;
without them every tile contracts over all N_u columns.

Knobs:
  REPRO_BP_VMEM_BUDGET   VMEM budget in bytes (default 32 MiB). A v5e core
                         has 128 MiB of VMEM but Mosaic's default scoped
                         limit is 16 MiB, so the kernel raises its limit to
                         this budget (kernels/backproject/ops.py).
  REPRO_BP_AUTOTUNE      "time" to measure survivors on every first use of
                         a geometry (default: model-ranked pick, no timing
                         — interpret-mode timing is python-speed).
  REPRO_TUNE_CACHE       path of the file-backed tuning cache (JSON),
                         keyed by the full tuning key (geometry tile,
                         dtype, vmem budget, mode flags, cost model and a
                         digest of the scan's matrices) so tuning
                         survives across processes. Default
                         ~/.cache/repro/bp_tune_cache.json; "off"/"0"/""
                         disables persistence.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.filecache import JsonFileCache

from .kernel import (
    backproject_dual_pallas, resolve_interpret, tile_is_legal, u_spans,
    vmem_bytes, window_width,
)

DEFAULT_VMEM_BUDGET = int(os.environ.get("REPRO_BP_VMEM_BUDGET", 32 * 2**20))
_BLOCK_CAP = 64  # largest bi / projection batch considered
_BJ_CAP = 1024   # largest column block

# The cost model's name, part of every tuning key: a pick ranked by another
# model (or for the kernel before its u-window) is never reused.
_MODEL = "u-window-1"

# Modelled costs of one grid step on a TPU v5e (PERF.md section 5):
_MXU_F32_FLOPS = 197e12 / 6  # f32 HIGHEST = six bf16 MXU passes at 197 TF/s
_HBM_BYTES_S = 819e9         # projection blocks stream at HBM bandwidth
_STEP_S = 0.35e-6            # fixed cost of one grid step
# The v step's lane gathers, per voxel column, view and (128 output lanes x
# 128 source lanes): the kernel's time on a v5e less its u-step, which
# timing one tile at K = N_u and at a narrow window separates (PERF.md).
_V_UNIT_S = 2.9e-9


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """One kernel tiling: output tile (bi, bj), projection batch bs."""

    bi: int
    bj: int
    bs: int
    vmem: int            # working-set bytes under kernel.vmem_bytes()
    u_window: int        # the u-step's window K at this tile (N_u: none)
    elapsed: float = 0.0  # measured seconds/call (0.0 = model-ranked only)

    def as_tuple(self) -> Tuple[int, int, int]:
        return self.bi, self.bj, self.bs


_CACHE: Dict[tuple, BlockConfig] = {}

# File-backed persistence (tuning survives across processes): shared
# machinery with the planner's measurement cache (repro/filecache.py).
_FILE_CACHE = JsonFileCache("REPRO_TUNE_CACHE", "bp_tune_cache.json")


def clear_cache() -> None:
    """Drop the in-process memos (the file cache, if any, is untouched)."""
    _CACHE.clear()
    _SPANS.clear()


def cache_info() -> Dict[tuple, BlockConfig]:
    return dict(_CACHE)


def file_cache_hits() -> int:
    """How many tuning keys this process served from the file cache."""
    return _FILE_CACHE.hits


def cache_path() -> Optional[str]:
    """Resolved file-cache path, or None when persistence is disabled."""
    return _FILE_CACHE.path()


def _file_cache_get(key: tuple) -> Optional[BlockConfig]:
    entry = _FILE_CACHE.get(key)
    if entry is None:
        return None
    try:
        return BlockConfig(**entry)
    except TypeError:
        return None


def _file_cache_put(key: tuple, cfg: BlockConfig) -> None:
    _FILE_CACHE.put(key, dataclasses.asdict(cfg))


def _divisors(n: int, cap: int) -> List[int]:
    return [d for d in range(1, min(n, cap) + 1) if n % d == 0]


def _pow2_leq(n: int, cap: int) -> List[int]:
    out, b = [], 1
    while b <= min(n, cap):
        out.append(b)
        b *= 2
    return out


# u spans per (matrices' digest, extent, tile): one pass over the views
# fills every tile shape a tuning run asks for.
_SPANS: Dict[tuple, Optional[int]] = {}


def tile_windows(pmats, extent: Tuple[int, int], tiles, nu: int,
                 qt_dtype=jnp.float32) -> Dict[Tuple[int, int], int]:
    """{(bi, bj): K} — kernel.window_width of the scan's matrices `pmats`
    (Np, 3, 4) for tiles of the (nx, ny) `extent` the calls' tiles lie in
    (the whole volume: slabs and chunks shift P by whole tiles). Spans are
    memoized per (matrices, extent, tile)."""
    head = (_digest(pmats), tuple(extent))
    tiles = [tuple(t) for t in tiles]
    todo = [t for t in tiles if head + t not in _SPANS]
    if todo:
        for t, span in u_spans(pmats, *extent, todo).items():
            _SPANS[head + t] = span
    return {t: window_width(_SPANS[head + t], nu, qt_dtype) for t in tiles}


def tile_window(pmats, extent: Tuple[int, int], bi: int, bj: int, nu: int,
                qt_dtype=jnp.float32) -> int:
    """K of one tile shape (`tile_windows`)."""
    return tile_windows(pmats, extent, [(bi, bj)], nu, qt_dtype)[bi, bj]


def _digest(pmats) -> Optional[str]:
    if pmats is None:
        return None
    pm = np.ascontiguousarray(pmats, np.float32)
    return hashlib.sha1(pm.tobytes()).hexdigest()[:16]


def candidate_blocks(nx: int, ny: int, n_p: int, nu: int, nv: int, nzh: int,
                     qt_dtype=jnp.float32, budget: int | None = None,
                     fix_bi: int | None = None, fix_bj: int | None = None,
                     fix_bs: int | None = None, pmats=None,
                     extent: Tuple[int, int] | None = None
                     ) -> List[BlockConfig]:
    """All (bi, bj, bs) that tile the problem and fit the VMEM budget, each
    at its u-window: `tile_window` of `pmats` over `extent` (default the
    call's (nx, ny)), or N_u without matrices.

    fix_* pins a dimension the caller chose explicitly; the remaining
    dimensions are tuned around it so the joint config still fits.
    """
    budget = DEFAULT_VMEM_BUDGET if budget is None else budget
    extent = (nx, ny) if extent is None else extent
    bis = [fix_bi] if fix_bi else _divisors(nx, _BLOCK_CAP)
    bjs = ([fix_bj] if fix_bj else
           [d for d in _divisors(ny, _BJ_CAP) if tile_is_legal(d, ny)])
    bss = [fix_bs] if fix_bs else _pow2_leq(n_p, _BLOCK_CAP)
    tiles = [(bi, bj) for bi in bis for bj in bjs]
    kws = (dict.fromkeys(tiles, nu) if pmats is None else
           tile_windows(pmats, extent, tiles, nu, qt_dtype))
    out = []
    for bi, bj in tiles:
        kw = kws[bi, bj]
        for bs in bss:
            vm = vmem_bytes(bi, bj, bs, nu, nv, nzh, qt_dtype, kw=kw)
            if vm <= budget:
                out.append(BlockConfig(bi, bj, bs, vm, kw))
    return out


@functools.lru_cache(maxsize=None)
def min_vmem_bytes(nx: int, ny: int, n_p: int, nu: int, nv: int, nzh: int,
                   qt_dtype=jnp.float32) -> int:
    """Smallest achievable working set over all candidate tilings — the
    kernel-level feasibility floor (planner/feasibility.py): if even this
    exceeds the VMEM budget, no block choice can make the kernel fit.
    Counted at K = N_u, which no window exceeds. Memoized: the planner
    asks for the same per-call shape once per (reduce,
    precision-of-equal-width, grid) candidate."""
    cands = candidate_blocks(nx, ny, n_p, nu, nv, nzh, qt_dtype,
                             budget=2**62)
    return min(c.vmem for c in cands)


def model_seconds(c: BlockConfig, nx: int, ny: int, n_p: int, nu: int,
                  nv: int, nzh: int, qt_dtype=jnp.float32) -> float:
    """Modelled seconds of one kernel call at tile `c` on a v5e: per grid
    step, the u-step's windowed MXU work (bi*bj rows x K x N_v at f32
    HIGHEST) plus the v step's lane gathers, or the projection batch's
    stream from HBM if that is longer (the pipeline hides the shorter),
    plus a fixed step cost. Padded views (ops.py pads N_p to a multiple of
    bs) cost like real ones."""
    rows = c.bi * c.bj
    steps = (nx // c.bi) * (ny // c.bj) * -(-n_p // c.bs)
    mxu = 2 * rows * c.u_window * nv / _MXU_F32_FLOPS
    v_step = rows * -(-nzh // 128) * -(-nv // 128) * _V_UNIT_S
    stream = nu * nv * jnp.dtype(qt_dtype).itemsize / _HBM_BYTES_S
    return steps * (c.bs * max(mxu + v_step, stream) + _STEP_S)


def _rank_key(c: BlockConfig, shape: tuple, qt_dtype) -> tuple:
    """Sort key, smaller = better: modelled time, then the smaller working
    set."""
    return model_seconds(c, *shape, qt_dtype=qt_dtype), c.vmem


def _time_candidate(c: BlockConfig, nx: int, ny: int, nz: int, n_p: int,
                    nu: int, nv: int, qt_dtype, interpret: bool,
                    iters: int, budget: int) -> float:
    n_pad = -(-n_p // c.bs) * c.bs  # padding overhead is part of the cost
    pm = np.zeros((n_pad, 12), np.float32)
    pm[:, 11] = 1.0  # z == 1: no division hazard on synthetic data
    pm = jnp.asarray(pm)
    qt = jnp.zeros((n_pad, nu, nv), qt_dtype)
    run = lambda: backproject_dual_pallas(  # noqa: E731
        pm, qt, nx, ny, nz, bi=c.bi, bj=c.bj, bs=c.bs, interpret=interpret,
        vmem_limit=max(budget, c.vmem), u_window=c.u_window)
    jax.block_until_ready(run())  # compile / warm up
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(run())
    return (time.perf_counter() - t0) / iters


def autotune(nx: int, ny: int, nz: int, n_p: int, nu: int, nv: int,
             qt_dtype=jnp.float32, budget: int | None = None,
             interpret: bool | None = None, measure: bool = True,
             max_measure: int = 4, iters: int = 1,
             fix_bi: int | None = None, fix_bj: int | None = None,
             fix_bs: int | None = None, strict: bool = True,
             pmats=None, extent: Tuple[int, int] | None = None
             ) -> BlockConfig:
    """Best block config for one (geometry, dtype), memoized in-process and
    in the file-backed cache (REPRO_TUNE_CACHE) keyed by the tuning inputs.

    `pmats` (Np, 3, 4), the scan's matrices, and `extent`, the (nx, ny)
    the calls' tiles lie in, give each candidate its u-window
    (`candidate_blocks`); without them every tile contracts over N_u.

    With measure=True the top-`max_measure` model-ranked survivors are each
    timed once with the real kernel on synthetic data of the true shape;
    measure=False returns the model-ranked winner without running anything —
    unless a measured winner for the same inputs is already cached, which is
    always preferred (measured timings outrank the cost model).

    strict=True raises when nothing fits the budget; strict=False falls
    back to the minimal-working-set tiling with a warning (a detector so
    wide that even bs=1 overflows should still reconstruct, just slowly).
    """
    if nz % 2:
        raise ValueError("back-projection kernel requires even N_z")
    budget = DEFAULT_VMEM_BUDGET if budget is None else budget
    interpret = resolve_interpret(interpret)
    qt_dtype = jnp.dtype(qt_dtype)
    # The key is the tuning *problem*, not the tuning mode: a measured
    # winner (elapsed > 0) satisfies both measured and model-ranked
    # requests, so an expensive REPRO_BP_AUTOTUNE=time run is reused by
    # later default-mode calls (in-process and via the file cache). An
    # unmeasured entry only satisfies unmeasured requests — a measured
    # request upgrades it in place.
    extent = (nx, ny) if extent is None else tuple(extent)
    key = (nx, ny, nz, n_p, nu, nv, qt_dtype.str, budget, interpret,
           fix_bi, fix_bj, fix_bs, strict, _MODEL, _digest(pmats),
           extent)
    hit = _CACHE.get(key)
    from_file = False
    if hit is None:
        hit = _file_cache_get(key)
        from_file = hit is not None
    if hit is not None and (not measure or hit.elapsed > 0.0):
        if from_file:
            _FILE_CACHE.hits += 1
        _CACHE[key] = hit
        return hit

    shape = (nx, ny, n_p, nu, nv, nz // 2)
    cands = candidate_blocks(nx, ny, n_p, nu, nv, nz // 2, qt_dtype, budget,
                             fix_bi, fix_bj, fix_bs, pmats, extent)
    if not cands:
        if strict:
            raise ValueError(
                f"no (bi, bj, bs) tiling of ({nx}, {ny}, Np={n_p}) fits the "
                f"VMEM budget of {budget} bytes (detector {nu}x{nv}); "
                "raise REPRO_BP_VMEM_BUDGET or shrink the detector batch"
            )
        # The qt batch is what overflowed (it already does at bs=1): keep it
        # minimal and tune the rest normally, rather than refusing to run.
        unbounded = candidate_blocks(nx, ny, n_p, nu, nv, nz // 2, qt_dtype,
                                     2**62, fix_bi, fix_bj, fix_bs, pmats,
                                     extent)
        bs_min = min(c.bs for c in unbounded)
        pool = [c for c in unbounded if c.bs == bs_min]
        best = min(pool, key=lambda c: _rank_key(c, shape, qt_dtype))
        warnings.warn(
            f"back-projection working set exceeds the VMEM budget of "
            f"{budget} bytes even at bs={bs_min} (detector {nu}x{nv}); "
            f"proceeding with {best.as_tuple()} ({best.vmem} bytes)"
        )
        _CACHE[key] = best
        _file_cache_put(key, best)
        return best
    ranked = sorted(cands, key=lambda c: _rank_key(c, shape, qt_dtype))
    if measure and len(ranked) > 1:
        timed = [
            dataclasses.replace(
                c, elapsed=_time_candidate(c, nx, ny, nz, n_p, nu, nv,
                                           qt_dtype, interpret, iters,
                                           budget)
            )
            for c in ranked[:max_measure]
        ]
        best = min(timed, key=lambda c: c.elapsed)
    else:
        best = ranked[0]
    _CACHE[key] = best
    _file_cache_put(key, best)
    return best


def pick_blocks(nx: int, ny: int, nz: int, n_p: int, nu: int, nv: int,
                qt_dtype=jnp.float32, budget: int | None = None,
                interpret: bool | None = None,
                measure: bool | None = None,
                fix_bi: int | None = None, fix_bj: int | None = None,
                fix_bs: int | None = None, pmats=None,
                extent: Tuple[int, int] | None = None
                ) -> Tuple[int, int, int]:
    """ops.py / plan entry point: (bi, bj, bs) under the VMEM budget,
    ranked at each tile's u-window when `pmats` are given (`autotune`).

    measure=None defers to REPRO_BP_AUTOTUNE ("time" enables measured
    tuning); the default model-ranked pick costs one table scan, so it is
    safe on every call path (results are cached either way). fix_* pins
    dimensions the caller specified so the tuned remainder still respects
    the budget jointly.
    """
    if measure is None:
        measure = os.environ.get("REPRO_BP_AUTOTUNE", "") == "time"
    # An explicitly passed budget is a hard constraint; the env/default
    # budget degrades to minimal blocks + warning so oversized detectors
    # still reconstruct (the pre-autotuner behaviour).
    return autotune(nx, ny, nz, n_p, nu, nv, qt_dtype=qt_dtype,
                    budget=budget, interpret=interpret, measure=measure,
                    fix_bi=fix_bi, fix_bj=fix_bj, fix_bs=fix_bs,
                    strict=budget is not None, pmats=pmats,
                    extent=extent).as_tuple()

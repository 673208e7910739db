"""Declarative reconstruction plans + the staged engine (paper §4, unified).

The paper's framework is ONE pipeline — load/filter -> column AllGather ->
slab back-projection -> row Reduce — previously implemented four times
(`fdk.reconstruct`, `make_distributed_fdk`, `make_pipelined_fdk`,
`make_chunked_fdk`), each separately threading precision, filter, impl
dispatch, shard_map and reduce logic. This module replaces the fork with a
plan -> build -> run engine:

    plan = ReconstructionPlan(geometry=g, mesh=mesh, schedule="pipelined",
                              n_steps=4, reduce="scatter", precision="bf16")
    fdk = plan.build()          # validated, tuned, jitted — cached per plan
    volume = fdk(projections)

A `ReconstructionPlan` is a frozen dataclass capturing every degree of
freedom of the pipeline; `validate()` centralizes the divisibility checks
that used to live inline in each builder, and `build()` composes shared
stage primitives:

    filter stage         make_filter(window, storage dtype)   [per batch]
    gather schedule      column AllGather over the `model` axis
    slab back-projection shift_pmats_i (x-slab) / shift_pmats_j (y-chunk)
    reduce epilogue      psum (replicated) | psum_scatter (sharded store)

into one rank function, run under shard_map when a mesh is given and
directly on one device when not. The schedule x reduce x precision x impl
cross-product is fully available — including combinations the legacy
builders never offered (chunked+psum, pipelined single-device). Each
primitive runs under a `jax.named_scope` (`STAGE_SCOPES`), so every device
operation of every schedule names its stage in its `op_name` metadata.

Tuned Pallas block shapes for `impl="kernel"` are resolved ONCE at plan
time (kernels/backproject/tune.py, file-backed cache) instead of per-call
inside ops.py, and can be pinned explicitly via `blocks=(bi, bj, bs)`. So
is the kernel's u-step window K (`resolved_u_window`), from the scan's
projection matrices at those blocks.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Callable, Dict, Literal, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import numpy as np

from jax import shard_map
from repro.obs.trace import get_tracer
from repro.parallel.mesh import AXIS_DATA, AXIS_MODEL, AXIS_POD, axis_size
from .cache import CountingLRU
from .distributed import (
    IFDKGrid, SCATTER_REDUCES, _proj_spec, input_sharding, output_spec,
    shift_pmats_i,
)
from .fdk import BpImpl, _get_backprojector, fdk_scale
from .filtering import _WINDOWS, make_filter
from .geometry import CBCTGeometry, projection_matrices
from .precision import Precision, resolve_precision

Array = jax.Array

Schedule = Literal["fused", "pipelined", "chunked", "incremental"]
ReduceMode = Literal["psum", "scatter", "scatter_bf16"]

_SCHEDULES = ("fused", "pipelined", "chunked", "incremental")
_REDUCES = ("psum",) + SCATTER_REDUCES
_IMPLS = ("reference", "factorized", "kernel")
_PRECISIONS = ("fp32", "bf16", "fp16", "fp8_e4m3", "fp8_e5m2")
# Wire dtypes the compiled kernel cannot load on TPU (v5e: no f16 vectors).
_KERNEL_REFUSED_ON_TPU = ("fp16",)

# build()/build_batched() results, keyed by the (hashable) plan (plus batch
# size for batched engines): repeated builds of the same plan reuse the
# jitted function, so `reconstruct(...)`-style per-call wrappers never
# re-trace. Bounded LRU: engines pin compiled XLA executables, and a
# long-lived service seeing many scan families must not leak them; the
# hit/miss counters feed the service stats (repro/service).
_ENGINE_CACHE = CountingLRU(capacity=64, name="core.engine_cache")


def clear_engine_cache() -> None:
    _ENGINE_CACHE.clear()


def _traced_call(fn: Callable, name: str, attrs: dict) -> Callable:
    """Wrap an engine callable in a fenced span when the process tracer is
    on. The disabled path is ONE attribute load + branch per call (the
    <1%-overhead contract, tests/test_obs.py); `attrs` are fixed at build
    time so the hot path allocates nothing. The span's `dispatch_us` arg is
    the async-dispatch time, its total duration dispatch + device compute
    (`Span.fence` semantics)."""
    def call(*args, **kwargs):
        tracer = get_tracer()
        if not tracer.enabled:
            return fn(*args, **kwargs)
        with tracer.span(name, **attrs) as sp:
            out = fn(*args, **kwargs)
            sp.fence(out)
        return out
    call.__wrapped__ = fn
    return call


# The engine's stage scopes (`jax.named_scope`): metadata only, they reach
# the compiled module's `op_name` and change no instruction. A device trace
# attributes an operation to a stage by the scope in its op_name.
STAGE_SCOPES = ("fdk.filter", "fdk.encode", "fdk.gather", "fdk.backproject",
                "fdk.reduce")


def _scoped(scope: str, fn: Callable) -> Callable:
    """`fn` traced under the stage scope `scope`."""
    def run(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)
    return run


def engine_cache_stats() -> dict:
    """hit/miss/eviction/unhashable counters of the shared engine cache."""
    return _ENGINE_CACHE.stats()


def bp_call_shape(g: CBCTGeometry, r: int, c: int, schedule: str,
                  n_steps: int, y_chunks: Optional[int]
                  ) -> Tuple[int, int, int]:
    """(nx, ny, n_p) of ONE back-projection call under a plan point: the
    x-slab (and y-chunk, if chunked) of one gathered micro-batch. The one
    formula shared by the engine's block resolution and the planner's
    kernel-VMEM feasibility check (planner/feasibility.py)."""
    nx_call = g.n_x // r
    ny_call = (g.n_y // y_chunks if schedule == "chunked" and y_chunks
               else g.n_y)
    np_call = g.n_proj // (c * n_steps)
    return nx_call, ny_call, np_call


@lru_cache(maxsize=16)
def _scan_matrices(g: CBCTGeometry) -> np.ndarray:
    """projection_matrices(g), computed once per geometry (read-only)."""
    pm = projection_matrices(g)
    pm.setflags(write=False)
    return pm


def shift_pmats_j(pmats: Array, j0) -> Array:
    """Reparameterize P for a y-chunk starting at voxel index j0 (same trick
    as distributed.shift_pmats_i, on the j column)."""
    shift = pmats[..., :, 1] * j0
    return pmats.at[..., :, 3].add(shift)


@dataclasses.dataclass
class _Stages:
    """The engine's shared per-rank stage primitives, composed once per plan
    and reused by every schedule's rank function AND the incremental
    session (`build_incremental`) — the one place the filter/encode/gather,
    slab reparameterization and row-reduce logic is defined."""

    gather_batch: Callable   # (pm_b, raw_b) -> (pm_col, q_col, scales_col)
    filter_encode: Callable  # raw_b -> (data_b, scales_b)  [no collectives]
    gather_cols: Callable    # (pm_b, data_b, scales_b) -> gathered columns
    slab_pmats: Callable     # pm_col -> P shifted to this rank's x-slab
    reduce_slab: Callable    # full-slab row-reduce epilogue (fused/pipelined)
    backproject: Callable    # resolved impl (tuned blocks for "kernel")
    nx_slab: int
    scale: float             # fdk_scale(geometry)
    model_axis: Optional[str]
    data_axis: Optional[str]
    pod_axis: Optional[str]
    dp: Tuple[str, ...]      # row-reduce axes present on the mesh


@dataclasses.dataclass(frozen=True)
class ReconstructionPlan:
    """Everything that determines a reconstruction, in one declarative value.

    Fields
    ------
    geometry   : the CBCT scan geometry (paper Table 1).
    mesh       : device mesh; None = plain single-device execution (no
                 shard_map). The paper's R x C rank grid is derived from it:
                 R = `model` axis (volume slabs), C = `pod` x `data`
                 (projection groups) — see `grid`.
    impl       : back-projection implementation ("reference" | "factorized"
                 | "kernel").
    window     : ramp-filter apodization window.
    precision  : storage dtype policy of the filtered-projection stream
                 (core/precision.py): a Precision, a name, or None for the
                 backend default. Accumulation is always f32.
    schedule   : "fused"     — one gather, one slab back-projection;
                 "pipelined" — lax.scan over `n_steps` micro-batches, the
                               AllGather of batch s overlapping the
                               back-projection of batch s-1 (paper Fig. 4);
                 "chunked"   — pipelined + per-y-chunk reduce (streaming
                               output side; bounds the live slab state).
    n_steps    : projection micro-batches per rank (pipelined/chunked).
    y_chunks   : y-axis chunks (chunked only).
    reduce     : row-reduce epilogue. "psum" replicates the slab; "scatter"
                 leaves it sharded over `data` for the parallel store
                 (requires a mesh with a `data` axis); "scatter_bf16" is
                 scatter at half the reduce wire bytes — partial slabs are
                 quantized to bf16 before the psum_scatter and the result
                 upcast to f32, with an f32 error-feedback carry under the
                 chunked schedule (each step's quantization residual is
                 re-injected into the next step's partial, so the error
                 does not grow with n_steps). See DESIGN.md (codec layer)
                 for the error model.
    blocks     : explicit (bi, bj, bs) Pallas tile for impl="kernel";
                 None = resolve from the VMEM-budget autotuner at plan time.
    vmem_budget: byte budget handed to the autotuner (None = env default).
    """

    geometry: CBCTGeometry
    mesh: Optional[Mesh] = None
    impl: BpImpl = "factorized"
    window: str = "ramlak"
    precision: Precision | str | None = "fp32"
    schedule: Schedule = "fused"
    n_steps: int = 1
    y_chunks: Optional[int] = None
    reduce: ReduceMode = "psum"
    blocks: Optional[Tuple[int, int, int]] = None
    vmem_budget: Optional[int] = None

    # -- derived quantities -------------------------------------------------

    @property
    def grid(self) -> IFDKGrid:
        """The paper's R (slabs) x C (projection groups) rank grid."""
        if self.mesh is None:
            return IFDKGrid(r=1, c=1)
        return IFDKGrid(r=axis_size(self.mesh, AXIS_MODEL),
                        c=axis_size(self.mesh, AXIS_POD, AXIS_DATA))

    @property
    def _data_size(self) -> int:
        return axis_size(self.mesh, AXIS_DATA) if self.mesh is not None else 1

    def resolved_precision(self) -> Precision:
        return resolve_precision(self.precision)

    # -- validation ---------------------------------------------------------

    def validate(self) -> "ReconstructionPlan":
        """Centralized feasibility checks (every legacy builder's scattered
        divisibility tests live here, with uniform error messages)."""
        g = self.geometry
        if self.impl not in _IMPLS:
            raise ValueError(
                f"unknown back-projection impl {self.impl!r}; "
                f"choose from {_IMPLS}")
        if self.window not in _WINDOWS:
            raise ValueError(
                f"unknown window {self.window!r}; choose from {_WINDOWS}")
        if self.schedule not in _SCHEDULES:
            raise ValueError(
                f"unknown schedule {self.schedule!r}; "
                f"choose from {_SCHEDULES}")
        if self.reduce not in _REDUCES:
            raise ValueError(
                f"unknown reduce mode {self.reduce!r}; "
                f"choose from {_REDUCES}")
        resolve_precision(self.precision)  # raises on unknown storage
        if self.mesh is not None and AXIS_MODEL not in self.mesh.axis_names:
            raise ValueError(
                f"mesh axes {self.mesh.axis_names} lack the {AXIS_MODEL!r} "
                "axis that carries the paper's R volume slabs")
        grid = self.grid
        n_ranks = grid.n_ranks
        if g.n_proj % n_ranks:
            raise ValueError(
                f"N_p={g.n_proj} must divide over the {n_ranks} ranks of "
                f"the R={grid.r} x C={grid.c} grid")
        if g.n_x % grid.r:
            raise ValueError(
                f"N_x={g.n_x} must divide into R={grid.r} volume slabs")
        if self.n_steps < 1:
            raise ValueError(f"n_steps={self.n_steps} must be >= 1")
        if self.schedule == "fused" and self.n_steps != 1:
            raise ValueError(
                "the fused schedule has no micro-batching; use "
                "schedule='pipelined' (or 'chunked') for n_steps > 1")
        np_local = g.n_proj // n_ranks
        if np_local % self.n_steps:
            raise ValueError(
                f"per-rank N_p={np_local} must divide into "
                f"n_steps={self.n_steps} micro-batches")
        if self.schedule == "chunked":
            if self.y_chunks is None:
                raise ValueError("the chunked schedule requires y_chunks")
            if g.n_y % self.y_chunks:
                raise ValueError(
                    f"N_y={g.n_y} must divide into y_chunks={self.y_chunks}")
        elif self.y_chunks is not None:
            raise ValueError(
                "y_chunks only applies to the chunked schedule")
        if self.reduce in SCATTER_REDUCES:
            if self.mesh is None or AXIS_DATA not in self.mesh.axis_names:
                raise ValueError(
                    f"reduce={self.reduce!r} needs a mesh with a 'data' "
                    "axis to scatter over; use reduce='psum' on a single "
                    "device")
            scatter_extent = (g.n_y // self.y_chunks
                              if self.schedule == "chunked" else g.n_y)
            if scatter_extent % self._data_size:
                raise ValueError(
                    f"scatter extent {scatter_extent} (y) must divide over "
                    f"the data axis of size {self._data_size}")
        if self.blocks is not None and self.impl != "kernel":
            raise ValueError(
                "blocks=(bi, bj, bs) only applies to impl='kernel'")
        if self.impl == "kernel" and g.n_z % 2:
            raise ValueError(
                f"impl='kernel' requires even N_z (dual-slab layout), "
                f"got N_z={g.n_z}")
        if self.impl == "kernel" and self.resolved_precision().storage in \
                _KERNEL_REFUSED_ON_TPU:
            from repro.planner.feasibility import plan_device
            if plan_device(self.mesh).platform == "tpu":
                raise ValueError(
                    f"impl='kernel' cannot read {self.resolved_precision().storage} "
                    "streams on TPU: Mosaic has no vector load for that dtype "
                    "(tests/test_chip_compile.py); use bf16 or another impl")
        if self.blocks is not None:
            bi, bj, bs = self.blocks
            nx_call, ny_call, _ = self._bp_call_shape()
            if bi < 1 or bj < 1 or bs < 1:
                raise ValueError(f"blocks={self.blocks} must be positive")
            # bs need not divide the projection count (ops.py pads), but the
            # output tile must tile the per-call slab exactly.
            if nx_call % bi or ny_call % bj:
                raise ValueError(
                    f"blocks=(bi={bi}, bj={bj}) must tile the per-call "
                    f"back-projection slab ({nx_call}, {ny_call}) — the "
                    f"x-slab/y-chunk of one gathered micro-batch")
            from repro.kernels.backproject.kernel import tile_is_legal
            if not tile_is_legal(bj, ny_call):
                raise ValueError(
                    f"blocks=(bj={bj}) must be a multiple of 8 or the whole "
                    f"per-call N_y={ny_call} (the kernel's (8, 128) output "
                    "tiling)")
        return self

    # -- kernel block resolution (plan-time, not per-call) ------------------

    def _bp_call_shape(self) -> Tuple[int, int, int]:
        grid = self.grid
        return bp_call_shape(self.geometry, grid.r, grid.c, self.schedule,
                             self.n_steps, self.y_chunks)

    def resolved_blocks(self) -> Optional[Tuple[int, int, int]]:
        """The (bi, bj, bs) Pallas tile this plan will run with — explicit
        `blocks` if given, else the autotuner's pick for the per-call
        back-projection shape, ranked at each tile's u-window. None for
        non-kernel impls."""
        if self.impl != "kernel":
            return None
        if self.blocks is not None:
            return tuple(self.blocks)
        from repro.kernels.backproject import tune
        g = self.geometry
        nx_call, ny_call, np_call = self._bp_call_shape()
        prec = self.resolved_precision()
        return tune.pick_blocks(nx_call, ny_call, g.n_z, np_call,
                                g.n_u, g.n_v,
                                qt_dtype=prec.storage_dtype,
                                budget=self.vmem_budget,
                                pmats=_scan_matrices(g),
                                extent=(g.n_x, g.n_y))

    def resolved_u_window(self) -> Optional[int]:
        """The kernel's u-step window K at the resolved blocks: the widest
        detector-column span any output tile of the whole volume touches in
        any view (`tune.tile_window`), N_u when that reaches N_u. It holds
        for every call of every schedule: x-slabs and y-chunks shift P by
        whole tiles. None for non-kernel impls."""
        if self.impl != "kernel":
            return None
        from repro.kernels.backproject import tune
        g = self.geometry
        bi, bj, _ = self.resolved_blocks()
        return tune.tile_window(_scan_matrices(g), (g.n_x, g.n_y), bi, bj,
                                g.n_u, self.resolved_precision().storage_dtype)

    def _resolve_backprojector(self) -> Callable:
        if self.impl != "kernel":
            return _get_backprojector(self.impl)
        from repro.kernels.backproject.ops import backproject_pallas
        bi, bj, bs = self.resolved_blocks()
        return partial(backproject_pallas, bi=bi, bj=bj, bs=bs,
                       vmem_budget=self.vmem_budget,
                       u_window=self.resolved_u_window())

    def _window_attrs(self) -> dict:
        """`u_window` (K) and `u_window_share` (K / N_u): how much of the
        detector line the kernel's u-step contracts over. Empty for
        non-kernel impls."""
        kw = self.resolved_u_window()
        if kw is None:
            return {}
        return {"u_window": kw, "u_window_share": kw / self.geometry.n_u}

    def _exchange_attrs(self) -> dict:
        """What one rank's collectives move in one scan, at wire width, by
        the planner's own accounting (planner/cost.py): `gather_bytes` the
        column AllGather brings in, `reduce_bytes` the row reduce sends; 0
        on one chip. `slab_shape`: the rank's partial slab."""
        from repro.planner.cost import (
            allgather_wire_bytes, point_from_plan, reduce_wire_bytes,
        )
        g = self.geometry
        point = point_from_plan(self)
        n_ranks = self.grid.n_ranks
        return {
            "gather_bytes": allgather_wire_bytes(g, point) // n_ranks,
            "reduce_bytes": reduce_wire_bytes(g, point) // n_ranks,
            "slab_shape": (g.n_x // self.grid.r, g.n_y, g.n_z),
        }

    def _span_attrs(self) -> dict:
        """Fixed span args of this plan's engines (trace labels) — built
        once at build() time, JSON-plain for the Perfetto export."""
        grid = self.grid
        exchange = self._exchange_attrs()
        return {
            "schedule": self.schedule,
            "impl": self.impl,
            "reduce": self.reduce,
            "precision": self.resolved_precision().storage,
            "grid": f"{grid.r}x{grid.c}",
            "n_steps": self.n_steps,
            **self._window_attrs(),
            **exchange,
            "slab_shape": list(exchange["slab_shape"]),
        }

    def describe(self) -> dict:
        """Flat summary of the resolved plan (benchmark/report labels)."""
        grid = self.grid
        return {
            "schedule": self.schedule,
            "impl": self.impl,
            "window": self.window,
            "precision": self.resolved_precision().storage,
            "grid": (grid.r, grid.c),
            "n_steps": self.n_steps,
            "y_chunks": self.y_chunks,
            "reduce": self.reduce,
            "blocks": self.resolved_blocks(),
            **self._window_attrs(),
            **self._exchange_attrs(),
        }

    # -- engine -------------------------------------------------------------

    def _output_spec(self) -> Optional[P]:
        if self.mesh is None:
            return None
        if self.schedule == "chunked" and self.reduce in SCATTER_REDUCES:
            # (nx_slab, y_chunks, yc/dp, nz): x over model, chunk interior
            # scattered over data; reshape(nx, ny, nz) outside restores the
            # canonical volume.
            return P(AXIS_MODEL, None, AXIS_DATA, None)
        return output_spec(self.mesh, self.reduce)

    def _make_stages(self) -> _Stages:
        """Compose the shared stage primitives for this plan's mesh/precision
        — the building blocks both `_build_rank_fn` (batch schedules) and
        `IncrementalSession` (streaming) assemble their rank functions from."""
        g = self.geometry
        mesh = self.mesh
        grid = self.grid
        model_axis = (AXIS_MODEL if mesh is not None
                      and AXIS_MODEL in mesh.axis_names else None)
        data_axis = (AXIS_DATA if mesh is not None
                     and AXIS_DATA in mesh.axis_names else None)
        pod_axis = (AXIS_POD if mesh is not None
                    and AXIS_POD in mesh.axis_names else None)
        dp = tuple(a for a in (pod_axis, data_axis) if a is not None)
        nx_slab = g.n_x // grid.r
        prec = self.resolved_precision()
        codec = prec.codec
        # The filter emits f32; the stream codec owns the quantization to
        # the wire format (scale-free codecs are a plain cast — fused under
        # jit, byte-identical to casting inside the filter).
        filt = _scoped("fdk.filter",
                       make_filter(g, self.window, out_dtype=jnp.float32))
        encode = _scoped("fdk.encode", codec.encode)

        # --- stage: filter + encode + column AllGather (paper Fig. 3b) -----
        # The AllGather moves the codec's WIRE format: quantized data plus,
        # for scaled codecs (fp8), the per-projection f32 scale sidecar.
        # Split in two: `filter_encode` is per-projection-independent and
        # collective-free (the batched engine hoists it out of its vmap —
        # the FFT must not see a vmap batch dim, see build_batched), while
        # `gather_cols` moves the wire bytes over the model axis.
        def filter_encode(raw_b: Array):
            return encode(filt(raw_b))

        @partial(_scoped, "fdk.gather")
        def gather_cols(pm_b: Array, data: Array, scales):
            if model_axis is None:
                return pm_b, data, scales
            gathered_scales = (
                None if scales is None
                else lax.all_gather(scales, model_axis, axis=0, tiled=True))
            return (lax.all_gather(pm_b, model_axis, axis=0, tiled=True),
                    lax.all_gather(data, model_axis, axis=0, tiled=True),
                    gathered_scales)

        def gather_batch(pm_b: Array, raw_b: Array):
            return gather_cols(pm_b, *filter_encode(raw_b))

        # --- stage: x-slab reparameterization (offset folded into P) -------
        @partial(_scoped, "fdk.backproject")
        def slab_pmats(pm_col: Array) -> Array:
            if model_axis is None:
                return pm_col
            i0 = lax.axis_index(model_axis) * nx_slab
            return shift_pmats_i(pm_col, i0.astype(pm_col.dtype))

        # --- stage: row-reduce epilogue (fused/pipelined full slab) --------
        # "scatter_bf16" moves the partial slab at half width: quantize to
        # bf16, psum_scatter, upcast — ONE rounding per rank (relative error
        # <= C_data * eps_bf16/2 on the reduced slab); the cross-pod finish
        # stays f32. Plain "scatter"/"psum" paths are byte-identical to the
        # f32 collective (the astype(f32) is a no-op on an f32 slab).
        @partial(_scoped, "fdk.reduce")
        def reduce_slab(slab: Array) -> Array:
            if not dp:
                return slab
            if self.reduce in SCATTER_REDUCES:
                if self.reduce == "scatter_bf16":
                    slab = slab.astype(jnp.bfloat16)
                slab = lax.psum_scatter(slab, dp[-1], scatter_dimension=1,
                                        tiled=True).astype(jnp.float32)
                for a in dp[:-1]:  # multi-pod: finish across pods
                    slab = lax.psum(slab, a)
                return slab
            for a in dp:
                slab = lax.psum(slab, a)
            return slab

        return _Stages(
            gather_batch=gather_batch, filter_encode=filter_encode,
            gather_cols=gather_cols, slab_pmats=slab_pmats,
            reduce_slab=reduce_slab,
            backproject=_scoped("fdk.backproject",
                                self._resolve_backprojector()),
            nx_slab=nx_slab, scale=fdk_scale(g),
            model_axis=model_axis, data_axis=data_axis, pod_axis=pod_axis,
            dp=dp,
        )

    def _build_rank_fn(self, st: Optional[_Stages] = None,
                       encoded: bool = False) -> Callable:
        """Compose the shared stage primitives into one per-rank function.

        encoded=False (the build() path): rank_fn(pm_local, proj_local)
        takes RAW per-rank projections and runs filter + encode inline
        (inside the scan for the micro-batched schedules).

        encoded=True (the build_batched() path): rank_fn(pm_local,
        data_local, sc_local) takes the codec's WIRE-format stream (+ scale
        sidecar, or None) and starts at the column AllGather — the batched
        engine hoists filter_encode out of its vmap, because XLA's CPU FFT
        rejects the non-dim0-major layouts a vmap batch dim induces, and
        filtering/encoding are per-projection-independent anyway (bit-equal
        hoisted or inline). Both variants share ONE copy of each schedule
        body below.
        """
        g = self.geometry
        grid = self.grid
        st = st if st is not None else self._make_stages()
        gather_batch = st.gather_batch
        gather_cols = st.gather_cols
        slab_pmats = st.slab_pmats
        reduce_slab = st.reduce_slab
        backproject = st.backproject
        nx_slab = st.nx_slab
        scale = st.scale
        data_axis = st.data_axis
        pod_axis = st.pod_axis
        n_steps = self.n_steps
        nb = g.n_proj // grid.n_ranks // n_steps

        # Normalize both input shapes to (payload tuple, gather callable):
        # schedule bodies below are written once against `gath(pm_b, *pl)`.
        if encoded:
            def make_rank(schedule_fn):
                def rank_fn(pm_local, data_local, sc_local=None):
                    if sc_local is None:
                        return schedule_fn(
                            pm_local, (data_local,),
                            lambda pm_b, d_b: gather_cols(pm_b, d_b, None))
                    return schedule_fn(pm_local, (data_local, sc_local),
                                       gather_cols)
                return rank_fn
        else:
            def make_rank(schedule_fn):
                def rank_fn(pm_local, proj_local):
                    return schedule_fn(pm_local, (proj_local,), gather_batch)
                return rank_fn

        def split_steps(pm_local, payload):
            pm_steps = pm_local.reshape(n_steps, nb, 3, 4)
            steps = tuple(x.reshape((n_steps, nb) + x.shape[1:])
                          for x in payload)
            return pm_steps, steps

        if self.schedule == "fused":
            def fused(pm_local, payload, gath):
                pm_col, q_col, sc_col = gath(pm_local, *payload)
                slab = backproject(slab_pmats(pm_col), q_col,
                                   nx_slab, g.n_y, g.n_z, scales=sc_col)
                return reduce_slab(slab) * scale
            return make_rank(fused)

        if self.schedule == "pipelined":
            def pipelined(pm_local, payload, gath):
                pm_steps, steps = split_steps(pm_local, payload)
                buf = gath(pm_steps[0], *(x[0] for x in steps))  # prologue

                def step(carry, xs):
                    acc, (pm_prev, q_prev, sc_prev) = carry
                    nxt = gath(*xs)                # comm for batch s
                    acc = acc + backproject(        # compute for batch s-1
                        slab_pmats(pm_prev), q_prev, nx_slab, g.n_y, g.n_z,
                        scales=sc_prev)
                    return (acc, nxt), None

                init = (jnp.zeros((nx_slab, g.n_y, g.n_z), jnp.float32), buf)
                (acc, (pm_last, q_last, sc_last)), _ = lax.scan(
                    step, init,
                    (pm_steps[1:],) + tuple(x[1:] for x in steps))
                acc = acc + backproject(            # epilogue
                    slab_pmats(pm_last), q_last, nx_slab, g.n_y, g.n_z,
                    scales=sc_last)
                return reduce_slab(acc) * scale
            return make_rank(pipelined)

        # chunked: per-y-chunk back-projection with an immediate per-chunk
        # reduce, bounding the live slab state (output-side streaming).
        y_chunks = self.y_chunks
        yc = g.n_y // y_chunks
        scatter = self.reduce in SCATTER_REDUCES
        compensated = self.reduce == "scatter_bf16"
        yc_local = yc // self._data_size if scatter else yc
        shift_j = _scoped("fdk.backproject", shift_pmats_j)

        def chunk_reduce(part: Array) -> Array:
            if scatter:
                return lax.psum_scatter(part, data_axis, scatter_dimension=1,
                                        tiled=True)
            if data_axis is not None:
                part = lax.psum(part, data_axis)
            return part

        def chunked(pm_local, payload, gath):
            pm_steps, steps = split_steps(pm_local, payload)
            buf = gath(pm_steps[0], *(x[0] for x in steps))

            def bp_chunks(state, pm_col, q_col, sc_col):
                acc, err = state
                pm_slab = slab_pmats(pm_col)

                def one_chunk(ci, st):
                    a, e = st
                    pm_c = shift_j(pm_slab, (ci * yc).astype(pm_slab.dtype))
                    part = backproject(pm_c, q_col, nx_slab, yc, g.n_z,
                                       scales=sc_col)
                    with jax.named_scope("fdk.reduce"):
                        if compensated:
                            # error feedback: re-inject the residual this
                            # rank dropped when it quantized the SAME chunk
                            # last round, so quantization error does not
                            # accumulate over the n_steps micro-batches —
                            # only the final round's rounding survives (one
                            # per rank).
                            part = part + lax.dynamic_index_in_dim(
                                e, ci, axis=1, keepdims=False)
                            half = part.astype(jnp.bfloat16)
                            e = lax.dynamic_update_index_in_dim(
                                e, part - half.astype(jnp.float32), ci,
                                axis=1)
                            red = lax.psum_scatter(
                                half, data_axis, scatter_dimension=1,
                                tiled=True).astype(jnp.float32)
                        else:
                            red = chunk_reduce(part)
                    a = lax.dynamic_update_index_in_dim(
                        a, a[:, ci] + red, ci, axis=1)
                    return a, e

                return lax.fori_loop(0, y_chunks, one_chunk, (acc, err))

            def step(carry, xs):
                state, prev = carry
                nxt = gath(*xs)                    # comm for batch s
                state = bp_chunks(state, *prev)    # compute for batch s-1
                return (state, nxt), None

            acc0 = jnp.zeros((nx_slab, y_chunks, yc_local, g.n_z),
                             jnp.float32)
            err0 = (jnp.zeros((nx_slab, y_chunks, yc, g.n_z), jnp.float32)
                    if compensated else None)
            ((acc, err), last), _ = lax.scan(
                step, ((acc0, err0), buf),
                (pm_steps[1:],) + tuple(x[1:] for x in steps))
            acc, _ = bp_chunks((acc, err), *last)  # epilogue
            if pod_axis is not None:
                with jax.named_scope("fdk.reduce"):
                    acc = lax.psum(acc, pod_axis)
            if not scatter:
                # dims 1,2 are contiguous locally when nothing is scattered
                acc = acc.reshape(nx_slab, g.n_y, g.n_z)
            return acc * scale

        return make_rank(chunked)

    def build(self, source=None, sink=None) -> Callable[[Array], Array]:
        """Validated, tuned, jitted reconstruction: projections -> volume.

        Input : (N_p, N_v, N_u) projections — sharded with
                `input_sharding(mesh)` when the plan has a mesh.
        Output: (N_x, N_y, N_z) f32; x slab-sharded over `model` on a mesh,
                plus y sharded over `data` with reduce="scatter". The
                chunked+scatter combination returns the 4-D
                (N_x, y_chunks, N_y/y_chunks/C_data, N_z) store layout —
                reshape(N_x, N_y, N_z) restores the canonical volume.

        `source`/`sink` (repro/io/streams.py) close the pipeline at the
        filesystem like the paper's ranks do: with a `ProjectionSource` the
        returned callable may be invoked with no argument — each rank
        scatter-reads only its own projection slice; with a `VolumeSink`
        the sharded output volume is streamed shard-per-file to the store
        before being returned (the slice-per-rank PFS write).

        Results are cached per plan, so repeated builds (and the thin
        legacy wrappers that build per call) never re-trace.
        """
        if self.schedule == "incremental":
            raise ValueError(
                "schedule='incremental' is stateful (projections arrive as "
                "deltas); use plan.build_incremental() to obtain a "
                "streaming session instead of build()")
        if source is not None or sink is not None:
            return self._build_with_io(source, sink)
        # Counted LRU: unhashable keys (exotic meshes) are counted inside
        # and fall through to an uncached build.
        cached = _ENGINE_CACHE.get(self)
        if cached is not None:
            return cached
        self.validate()
        rank_fn = self._build_rank_fn()
        pmats_all = jnp.asarray(projection_matrices(self.geometry))
        if self.mesh is None:
            @jax.jit
            def reconstruct_fn(projections: Array) -> Array:
                return rank_fn(pmats_all, projections)
        else:
            mesh = self.mesh
            pspec = _proj_spec(mesh)
            out_sp = self._output_spec()

            @jax.jit
            def reconstruct_fn(projections: Array) -> Array:
                return shard_map(
                    rank_fn, mesh=mesh,
                    in_specs=(pspec, pspec),
                    out_specs=out_sp,
                    check_vma=False,
                )(pmats_all, projections)

        reconstruct_fn = _traced_call(
            reconstruct_fn, "engine.reconstruct", self._span_attrs())
        _ENGINE_CACHE.put(self, reconstruct_fn)
        return reconstruct_fn

    def build_batched(self, batch_size: int) -> Callable[[Array], Array]:
        """Batched engine: reconstruct `batch_size` same-geometry scans in
        ONE dispatch — the service layer's geometry-bucketed serving path.

        Input : (B, N_p, N_v, N_u) projections, B == batch_size. On a mesh
                each scan is sharded like build()'s input with the scan axis
                replicated — place with `batched_input_sharding(mesh)`.
        Output: (B, N_x, N_y, N_z) f32 (or B x the plan's 4-D chunked+
                scatter store layout), sharded per scan like build()'s.

        Exactness contract (tests/test_batched.py): lane b of the output is
        BIT-IDENTICAL to `self.build()(projections[b])` — padding a bucket
        with junk scans cannot perturb real ones, and a served scan equals
        the single-scan answer exactly. Two ingredients make this hold:
        filter+encode are hoisted out of the vmap and run on the flattened
        (B*N_p) projection axis (per-projection-independent ops, bit-equal
        to per-scan application; also keeps the FFT away from vmap batch
        dims, which XLA's CPU FFT thunk rejects), and the back-projectors
        pin their P-derived coordinate chains behind an optimization
        barrier so batched and unbatched compilations contract FMAs
        identically (core/backprojection.py).

        Engines are cached per (plan, batch_size) in the same counted LRU
        as build()'s.
        """
        if self.schedule == "incremental":
            raise ValueError(
                "schedule='incremental' is stateful; the batched serving "
                "path needs a batch schedule (fused/pipelined/chunked)")
        bsz = int(batch_size)
        if bsz < 1:
            raise ValueError(f"batch_size={batch_size} must be >= 1")
        key = (self, "batched", bsz)
        cached = _ENGINE_CACHE.get(key)
        if cached is not None:
            return cached
        self.validate()
        g = self.geometry
        grid = self.grid
        np_local = g.n_proj // grid.n_ranks
        st = self._make_stages()
        filter_encode = st.filter_encode
        rank_enc = self._build_rank_fn(st=st, encoded=True)

        def batched_rank(pm_local: Array, proj_b: Array) -> Array:
            # proj_b: (B, np_local, N_v, N_u) — this rank's block of every
            # scan. Filter+encode on the flattened projection axis, then
            # vmap the collective/back-projection half over the scan axis.
            flat = proj_b.reshape((bsz * np_local,) + proj_b.shape[2:])
            data, scales = filter_encode(flat)
            data = data.reshape((bsz, np_local) + data.shape[1:])
            if scales is not None:
                scales = scales.reshape((bsz, np_local) + scales.shape[1:])
                return jax.vmap(rank_enc, in_axes=(None, 0, 0))(
                    pm_local, data, scales)
            return jax.vmap(rank_enc, in_axes=(None, 0, None))(
                pm_local, data, None)

        pmats_all = jnp.asarray(projection_matrices(g))
        if self.mesh is None:
            @jax.jit
            def batched_fn(projections: Array) -> Array:
                return batched_rank(pmats_all, projections)
        else:
            mesh = self.mesh
            pspec = _proj_spec(mesh)
            out_sp = self._output_spec()

            @jax.jit
            def batched_fn(projections: Array) -> Array:
                return shard_map(
                    batched_rank, mesh=mesh,
                    in_specs=(pspec, P(None, *pspec)),
                    out_specs=P(None, *out_sp),
                    check_vma=False,
                )(pmats_all, projections)

        attrs = self._span_attrs()
        attrs["batch"] = bsz
        batched_fn = _traced_call(batched_fn, "engine.batched", attrs)
        _ENGINE_CACHE.put(key, batched_fn)
        return batched_fn

    def build_incremental(self, source=None, sink=None) -> "IncrementalSession":
        """Streaming reconstruction (the paper's *instant* CT): a stateful
        session that folds projection deltas into the per-rank slab
        accumulator as the scanner writes them, so time-from-last-projection
        is one delta's fold plus the reduce epilogue — not the full pipeline.

            plan = ReconstructionPlan(geometry=g, mesh=mesh,
                                      schedule="incremental", n_steps=8)
            sess = plan.build_incremental(source=src)
            while not sess.is_complete:
                sess.poll()          # discover + fold newly landed deltas
            volume = sess.finalize() # reduce epilogue + FDK scale only

        `n_steps` is the *nominal* delta count the planner prices; at run
        time any contiguous, disjoint angle slices whose length divides
        over the rank grid may be folded, in any order. See
        `IncrementalSession` for the state machine and exactness contract.
        """
        if self.schedule != "incremental":
            raise ValueError(
                f"build_incremental() needs schedule='incremental', got "
                f"{self.schedule!r} — batch schedules go through build()")
        return IncrementalSession(self, source=source, sink=sink)

    def _build_with_io(self, source, sink) -> Callable:
        """The engine with its filesystem endpoints attached: scatter-read
        projections from `source` when none are passed, stream the sharded
        output volume to `sink` shard-per-file. The core engine underneath
        comes from the per-plan cache, so attaching I/O never re-traces."""
        engine = self.build()
        # chunked+scatter emits the engine's internal 4-D y-chunk-major
        # layout (see _output_spec); record it in the sink's manifest so
        # VolumeSink.read() restores the canonical volume instead of
        # silently returning chunked axes.
        layout = None
        if self.schedule == "chunked" and self.reduce in SCATTER_REDUCES:
            layout = {"kind": "y_chunk_major", "y_chunks": self.y_chunks}

        def reconstruct_io(projections: Optional[Array] = None) -> Array:
            tracer = get_tracer()
            if projections is None:
                if source is None:
                    raise TypeError(
                        "this plan was built without a ProjectionSource; "
                        "pass the projections array")
                with tracer.span("stage.read") as sp:
                    projections = sp.fence(source.load(self.mesh))
            volume = engine(projections)
            if sink is not None:
                jax.block_until_ready(volume)
                with tracer.span("stage.write"):
                    sink.write(volume, layout=layout)
            return volume

        return reconstruct_io

    # -- traced engine (per-stage attribution) -------------------------------

    def build_traced(self, source=None, sink=None) -> Callable:
        """The engine cut at its stage seams, each stage a fenced span —
        the measurement counterpart of the planner's `PerfBreakdown`
        (obs/attribution.py joins the two).

        Every schedule runs the same FUSED stage decomposition here: one
        jitted dispatch per stage (filter+encode, column AllGather, slab
        back-projection, row-reduce epilogue; plus source read / sink write
        when wired), fenced with `block_until_ready` between stages so each
        span's duration is that stage's wall time — per-stage attribution
        trades away the overlap the pipelined schedules buy, so a traced
        run is a MEASUREMENT run, not a production configuration. Span
        names are the fixed ``stage.*`` vocabulary of
        `obs.attribution.STAGE_FIELDS`; output is always the canonical
        fused layout (chunked+scatter's y-chunk-major store layout does
        not apply).

        Works with the tracer disabled too (stages just run unfenced);
        enable via `obs.enable()` (or a local Tracer via obs.set_tracer)
        to collect the spans. With tracing enabled, every run also deposits
        its per-stage wall times into the calibration store
        (planner/calibrate.py) — traced runs are what anchors the planner's
        cost constants to this host.

        schedule="incremental" returns a `TracedIncrementalSession` instead
        of a callable: the same per-stage decomposition applied to the
        streaming session's stage()/fold path (its `session.stage`/
        `session.fold` work split into the ``stage.*`` vocabulary), feeding
        the same store.
        """
        if self.schedule == "incremental":
            return TracedIncrementalSession(self, source=source, sink=sink)
        self.validate()
        g = self.geometry
        mesh = self.mesh
        st = self._make_stages()
        has_scales = self.resolved_precision().codec.has_scales
        nx_slab, scale = st.nx_slab, st.scale
        attrs = self._span_attrs()

        def bp_rank(pm_col, q_col, sc_col):
            part = st.backproject(st.slab_pmats(pm_col), q_col,
                                  nx_slab, g.n_y, g.n_z, scales=sc_col)
            return part[None] if mesh is not None else part

        def reduce_rank(parts):
            slab = parts[0] if mesh is not None else parts
            return st.reduce_slab(slab) * scale

        if mesh is None:
            _filter = jax.jit(st.filter_encode)
            _gather = jax.jit(st.gather_cols)
            bp_fn = jax.jit(bp_rank)
            reduce_fn = jax.jit(reduce_rank)

            def run_filter(proj):
                return _filter(proj)           # (data, scales|None)

            def run_gather(data, scales):
                return _gather(pmats_all, data, scales)
        else:
            pspec = _proj_spec(mesh)
            gspec = P(_lead_axes(st.dp))
            # Un-reduced per-rank partial slabs: leading (pod x data) rank
            # dim so every rank's partial survives the stage boundary
            # (same trick as IncrementalSession's resident accumulator).
            part_spec = P(_lead_axes(st.dp), AXIS_MODEL, None, None)
            if has_scales:
                # plain tuple: shard_map's out_specs prefix does not match
                # the EncodedStream NamedTuple subtype.
                _filter = jax.jit(shard_map(
                    lambda raw: tuple(st.filter_encode(raw)), mesh=mesh,
                    in_specs=(pspec,), out_specs=(pspec, pspec),
                    check_vma=False))
                _gather = jax.jit(shard_map(
                    st.gather_cols, mesh=mesh,
                    in_specs=(pspec, pspec, pspec),
                    out_specs=(gspec, gspec, gspec), check_vma=False))

                def run_filter(proj):
                    return _filter(proj)

                def run_gather(data, scales):
                    return _gather(pmats_all, data, scales)
            else:
                _filter = jax.jit(shard_map(
                    lambda raw: st.filter_encode(raw)[0], mesh=mesh,
                    in_specs=(pspec,), out_specs=pspec, check_vma=False))
                _gather = jax.jit(shard_map(
                    lambda pm, d: st.gather_cols(pm, d, None)[:2],
                    mesh=mesh, in_specs=(pspec, pspec),
                    out_specs=(gspec, gspec), check_vma=False))

                def run_filter(proj):
                    return _filter(proj), None

                def run_gather(data, scales):
                    pm_col, q_col = _gather(pmats_all, data)
                    return pm_col, q_col, None
            # A None sc_col is an empty pytree: its gspec entry is simply
            # unused (same convention as IncrementalSession's fold fns).
            bp_fn = jax.jit(shard_map(
                bp_rank, mesh=mesh, in_specs=(gspec, gspec, gspec),
                out_specs=part_spec, check_vma=False))
            reduce_fn = jax.jit(shard_map(
                reduce_rank, mesh=mesh, in_specs=(part_spec,),
                out_specs=output_spec(mesh, self.reduce),
                check_vma=False))

        pmats_all = jnp.asarray(projection_matrices(g))
        if mesh is not None:
            pmats_all = jax.device_put(pmats_all, input_sharding(mesh))

        def reconstruct_traced(projections: Optional[Array] = None) -> Array:
            tracer = get_tracer()
            seconds: Dict[str, float] = {}
            with tracer.span("engine.traced", **attrs):
                if projections is None:
                    if source is None:
                        raise TypeError(
                            "this traced plan has no ProjectionSource; "
                            "pass the projections array")
                    with tracer.span("stage.read") as sp:
                        projections = sp.fence(source.load(mesh))
                    seconds["stage.read"] = sp.duration_s
                elif mesh is not None:
                    projections = jax.device_put(projections,
                                                 input_sharding(mesh))
                with tracer.span("stage.filter") as sp:
                    data, scales = sp.fence(run_filter(projections))
                seconds["stage.filter"] = sp.duration_s
                with tracer.span("stage.allgather") as sp:
                    pm_col, q_col, sc_col = sp.fence(
                        run_gather(data, scales))
                seconds["stage.allgather"] = sp.duration_s
                with tracer.span("stage.backproject") as sp:
                    parts = sp.fence(bp_fn(pm_col, q_col, sc_col))
                seconds["stage.backproject"] = sp.duration_s
                with tracer.span("stage.reduce") as sp:
                    volume = sp.fence(reduce_fn(parts))
                seconds["stage.reduce"] = sp.duration_s
                if sink is not None:
                    with tracer.span("stage.write") as sp:
                        sink.write(volume)
                    seconds["stage.write"] = sp.duration_s
            if tracer.enabled:
                # a traced run IS a calibration sample: feed the measured
                # stage times back into the planner's store. Disabled
                # tracer: spans are no-ops, there is nothing to record.
                from repro.planner.calibrate import record_traced_run
                record_traced_run(self, seconds)
            return volume

        return reconstruct_traced


def _lead_axes(axes: Tuple[str, ...]):
    """PartitionSpec entry for a leading state dim sharded over `axes`."""
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else tuple(axes)


class StagedDelta(NamedTuple):
    """One angle subset after the ARRIVAL-side stages — filtered, encoded
    and column-AllGathered, awaiting only its fold. Produced by
    `IncrementalSession.stage`, consumed by `IncrementalSession.update`."""

    lo: int
    hi: int
    pm_col: Array        # shifted-ready projection matrices, gathered
    q_col: Array         # filtered + encoded column batch (wire format)
    sc_col: Optional[Array]   # per-projection scale sidecar (scaled codecs)


class IncrementalSession:
    """Stateful streaming reconstruction — `plan.build_incremental()`.

    State machine (DESIGN.md, incremental schedule)::

        OPEN --update(delta, angles)--> OPEN    fold one angle subset
        OPEN --poll()-----------------> OPEN    discover + fold source deltas
        OPEN --finalize(partial=True)-> OPEN    peek: reduce a COPY of state
        OPEN --finalize()-------------> OPEN    full volume (all angles seen)

    `finalize` is pure — the resident accumulator is never consumed, so the
    session can keep folding after a peek. Each `update` filters, encodes
    and column-AllGathers ONE contiguous angle slice and folds it into the
    per-rank slab accumulator; `finalize` runs only the row-reduce epilogue
    and the FDK scale.

    Resident state (per rank): the f32 slab accumulator — full-width
    (nx_slab, N_y, N_z) under reduce="psum" (row-reduce deferred to
    finalize), or already scattered (nx_slab, N_y/C_data, N_z) under the
    scatter reduces (each update psum_scatters its partial, so state stays
    bounded exactly like the chunked schedule's output streaming). For
    "scatter_bf16" an f32 error-feedback carry of the full-width slab rides
    along: the quantization residual each update drops is re-injected into
    the next update's partial — the chunked schedule's carry, turned along
    the time axis — so only the final update's rounding survives per rank.

    Exactness contract (tests/test_streaming.py): with
    impl="reference"/"factorized" the fold threads the accumulator INTO the
    back-projection scan (`init=`), continuing the per-voxel addition
    sequence — so folding deltas in order is bit-identical to the fused
    batch engine on the same device count, and folding any permutation is
    bit-identical to the fused engine fed that same permuted projection
    stream (f32 addition does not commute, so no schedule can make every
    order bit-equal to the canonical one; permutations agree with it to
    f32 reassociation tolerance). impl="kernel" folds `acc + bp(delta)`
    (the Pallas kernel owns its accumulator) and matches to the same
    reassociation tolerance.
    """

    def __init__(self, plan: ReconstructionPlan, source=None, sink=None):
        plan.validate()
        self.plan = plan
        self._source = source
        self._sink = sink
        self._stages = plan._make_stages()
        self._scatter = plan.reduce in SCATTER_REDUCES
        self._compensated = plan.reduce == "scatter_bf16"
        g = plan.geometry
        self._covered = np.zeros(g.n_proj, dtype=bool)
        self._pmats = np.asarray(projection_matrices(g))
        self._update_fns: dict = {}
        self._stage_fns: dict = {}
        self._fold_fns: dict = {}
        self._finalize_fn = None
        self._init_state()

    # -- state --------------------------------------------------------------

    def _init_state(self) -> None:
        g = self.plan.geometry
        mesh = self.plan.mesh
        st = self._stages
        if mesh is None:
            self._acc_spec = self._carry_spec = None
            self._acc = jnp.zeros((g.n_x, g.n_y, g.n_z), jnp.float32)
            self._carry = None
            return
        # Global state arrays carry a leading rank-row dim so each rank-row
        # keeps its own partial under shard_map (block (1, nx_slab, ...)).
        dp = st.dp
        if self._scatter:
            lead = (st.pod_axis,) if st.pod_axis is not None else ()
            self._acc_spec = P(_lead_axes(lead), AXIS_MODEL, AXIS_DATA, None)
            acc_shape = (axis_size(mesh, AXIS_POD), g.n_x, g.n_y, g.n_z)
        else:
            self._acc_spec = P(_lead_axes(dp), AXIS_MODEL, None, None)
            acc_shape = (axis_size(mesh, AXIS_POD, AXIS_DATA),
                         g.n_x, g.n_y, g.n_z)
        self._acc = jax.device_put(
            jnp.zeros(acc_shape, jnp.float32),
            NamedSharding(mesh, self._acc_spec))
        if self._compensated:
            self._carry_spec = P(_lead_axes(dp), AXIS_MODEL, None, None)
            self._carry = jax.device_put(
                jnp.zeros((axis_size(mesh, AXIS_POD, AXIS_DATA),
                           g.n_x, g.n_y, g.n_z), jnp.float32),
                NamedSharding(mesh, self._carry_spec))
        else:
            self._carry_spec = None
            self._carry = None

    # -- bookkeeping --------------------------------------------------------

    @property
    def n_folded(self) -> int:
        """Angles folded so far."""
        return int(self._covered.sum())

    @property
    def is_complete(self) -> bool:
        return bool(self._covered.all())

    def pending_ranges(self) -> list:
        """Contiguous [lo, hi) angle ranges not folded yet."""
        missing = ~self._covered
        (idx,) = np.nonzero(np.diff(missing.astype(np.int8), prepend=0,
                                    append=0))
        return [(int(idx[i]), int(idx[i + 1]))
                for i in range(0, len(idx), 2)]

    def _check_slice(self, angle_slice) -> Tuple[int, int]:
        if isinstance(angle_slice, slice):
            if angle_slice.step not in (None, 1):
                raise ValueError("angle_slice must be contiguous (step 1)")
            lo, hi = angle_slice.start or 0, angle_slice.stop
        else:
            lo, hi = angle_slice
        n_proj = self.plan.geometry.n_proj
        if hi is None:
            hi = n_proj
        lo, hi = int(lo), int(hi)
        if not (0 <= lo < hi <= n_proj):
            raise ValueError(
                f"angle_slice [{lo}, {hi}) out of range for N_p={n_proj}")
        if self._covered[lo:hi].any():
            raise ValueError(
                f"angle_slice [{lo}, {hi}) overlaps angles already folded "
                "into this session — double-folding corrupts the volume")
        n_ranks = self.plan.grid.n_ranks
        if (hi - lo) % n_ranks:
            raise ValueError(
                f"delta of {hi - lo} angles must divide over the "
                f"{n_ranks} ranks of the grid")
        return lo, hi

    # -- the fold (one delta) -----------------------------------------------

    def _fold_closures(self, with_volume: bool):
        """(fold, rank_fold, accumulate): the per-delta fold shared by the
        raw-delta update path and the staged fold path.

        fold(acc_slab, pm_col, q_col, sc_col)       one rank's slab fold
        rank_fold(acc, carry, pm_col, q_col, sc_col)
            -> (new_acc, new_carry, volume|None)    leading-dim state block,
                                                    scatter reduce + carry,
                                                    fused epilogue when
                                                    with_volume
        accumulate(acc, carry, part)
            -> (new_acc, new_carry)                 the scatter branch's
                                                    reduce-into-state given
                                                    a PRECOMPUTED partial —
                                                    the seam the traced
                                                    session cuts at to time
                                                    back-projection apart
                                                    from the reduce
        """
        plan, st, g = self.plan, self._stages, self.plan.geometry
        slab_pmats = st.slab_pmats
        backproject = st.backproject
        nx_slab = st.nx_slab
        data_axis = st.data_axis
        scale = st.scale
        pod_axis = st.pod_axis
        dp = st.dp
        scatter, compensated = self._scatter, self._compensated
        # reference/factorized thread the accumulator INTO the scan (`init=`)
        # for the bit-exact fold; the Pallas kernel owns its accumulator, so
        # it falls back to `acc + bp(delta)`.
        threads_init = plan.impl in ("reference", "factorized")

        def fold(acc_slab, pm_col, q_col, sc_col):
            pm_s = slab_pmats(pm_col)
            if threads_init:
                return backproject(pm_s, q_col, nx_slab, g.n_y, g.n_z,
                                   scales=sc_col, init=acc_slab)
            return acc_slab + backproject(pm_s, q_col, nx_slab, g.n_y,
                                          g.n_z, scales=sc_col)

        @partial(_scoped, "fdk.reduce")
        def fin_slab(acc_new):
            """Per-rank finalize of the NEW accumulator block (epilogue of
            the fused last-delta dispatch) — mirrors _get_finalize_fn."""
            slab = acc_new[0]
            if scatter:
                if pod_axis is not None:  # cross-pod finish stays f32
                    slab = lax.psum(slab, pod_axis)
            else:
                for a in dp:
                    slab = lax.psum(slab, a)
            return slab * scale

        @partial(_scoped, "fdk.reduce")
        def accumulate(acc, carry, part):
            if compensated:
                # error feedback along the time axis: re-inject the
                # residual this rank dropped quantizing the PREVIOUS
                # delta before quantizing this one (cf. the chunked
                # schedule's per-chunk carry).
                part = part + carry[0]
                half = part.astype(jnp.bfloat16)
                new_carry = (part - half.astype(jnp.float32))[None]
                red = lax.psum_scatter(
                    half, data_axis, scatter_dimension=1,
                    tiled=True).astype(jnp.float32)
            else:
                new_carry = carry
                red = lax.psum_scatter(part, data_axis,
                                       scatter_dimension=1, tiled=True)
            return acc + red[None], new_carry

        def rank_fold(acc, carry, pm_col, q_col, sc_col):
            if not scatter:
                new = fold(acc[0], pm_col, q_col, sc_col)[None]
                new_carry = carry
            else:
                part = backproject(slab_pmats(pm_col), q_col,
                                   nx_slab, g.n_y, g.n_z, scales=sc_col)
                new, new_carry = accumulate(acc, carry, part)
            return new, new_carry, fin_slab(new) if with_volume else None

        return fold, rank_fold, accumulate

    def _state_specs(self, with_volume: bool):
        """(in-state specs, out_specs, pack) for a shard_mapped fold: the
        accumulator (plus carry when compensated, plus the volume when the
        epilogue is fused in) — shared wiring of update and staged-fold."""
        carry_spec = self._carry_spec if self._compensated else None
        state_in = ((self._acc_spec, carry_spec) if self._compensated
                    else (self._acc_spec,))
        outs = [self._acc_spec]
        if self._compensated:
            outs.append(carry_spec)
        if with_volume:
            outs.append(output_spec(self.plan.mesh, self.plan.reduce))

        def pack(new, new_carry, vol):
            out = (new,)
            if self._compensated:
                out += (new_carry,)
            if with_volume:
                out += (vol,)
            return out[0] if len(out) == 1 else out

        return state_in, (outs[0] if len(outs) == 1 else tuple(outs)), pack

    def _get_update_fn(self, n_d: int, with_volume: bool = False) -> Callable:
        """Jitted fold of one n_d-angle RAW delta: filter + encode + column
        AllGather + fold. with_volume=True additionally runs the reduce
        epilogue + FDK scale INSIDE the same dispatch and returns the
        finished volume alongside the new state — the time-from-last-delta
        path (one launch, XLA fuses the scale into the fold's epilogue
        instead of paying a second dispatch)."""
        fn = self._update_fns.get((n_d, with_volume))
        if fn is not None:
            return fn
        mesh = self.plan.mesh
        st = self._stages
        gather_batch = st.gather_batch
        scale = st.scale
        fold, rank_fold, _ = self._fold_closures(with_volume)

        if mesh is None:
            def update_fn(acc, pm_d, raw_d):
                new = fold(acc, *gather_batch(pm_d, raw_d))
                return (new, new * scale) if with_volume else new

            update_fn = jax.jit(update_fn)
        else:
            pspec = _proj_spec(mesh)
            state_in, out_specs, pack = self._state_specs(with_volume)
            if self._compensated:
                def rank(acc, carry, pm_d, raw_d):
                    return pack(*rank_fold(acc, carry,
                                           *gather_batch(pm_d, raw_d)))
            else:
                def rank(acc, pm_d, raw_d):  # carry unused: pass acc
                    return pack(*rank_fold(acc, acc,
                                           *gather_batch(pm_d, raw_d)))

            update_fn = jax.jit(shard_map(
                rank, mesh=mesh, in_specs=state_in + (pspec, pspec),
                out_specs=out_specs, check_vma=False))

        self._update_fns[(n_d, with_volume)] = update_fn
        return update_fn

    # -- staged folding (arrival-side work split off the fold) ---------------

    def _gathered_spec(self):
        """Spec of a staged column batch: the model-axis AllGather leaves
        projections sharded over the remaining (pod, data) axes and
        replicated over model."""
        return P(_lead_axes(self._stages.dp))

    def _get_stage_fn(self, n_d: int) -> Callable:
        fn = self._stage_fns.get(n_d)
        if fn is not None:
            return fn
        mesh = self.plan.mesh
        gather_batch = self._stages.gather_batch
        if mesh is None:
            fn = jax.jit(gather_batch)
        else:
            pspec = _proj_spec(mesh)
            gspec = self._gathered_spec()
            fn = jax.jit(shard_map(
                gather_batch, mesh=mesh, in_specs=(pspec, pspec),
                out_specs=(gspec, gspec, gspec), check_vma=False))
        self._stage_fns[n_d] = fn
        return fn

    def _get_fold_fn(self, n_d: int, with_volume: bool = False) -> Callable:
        """Jitted fold of a STAGED delta (post-filter, post-gather columns):
        only the back-projection + reduce (+ fused epilogue) — the work that
        cannot overlap acquisition."""
        fn = self._fold_fns.get((n_d, with_volume))
        if fn is not None:
            return fn
        mesh = self.plan.mesh
        scale = self._stages.scale
        fold, rank_fold, _ = self._fold_closures(with_volume)

        if mesh is None:
            def fold_fn(acc, pm_col, q_col, sc_col):
                new = fold(acc, pm_col, q_col, sc_col)
                return (new, new * scale) if with_volume else new

            fold_fn = jax.jit(fold_fn)
        else:
            gspec = self._gathered_spec()
            state_in, out_specs, pack = self._state_specs(with_volume)
            if self._compensated:
                def rank(acc, carry, pm_col, q_col, sc_col):
                    return pack(*rank_fold(acc, carry, pm_col, q_col,
                                           sc_col))
            else:
                def rank(acc, pm_col, q_col, sc_col):
                    return pack(*rank_fold(acc, acc, pm_col, q_col, sc_col))

            fold_fn = jax.jit(shard_map(
                rank, mesh=mesh,
                in_specs=state_in + (gspec, gspec, gspec),
                out_specs=out_specs, check_vma=False))

        self._fold_fns[(n_d, with_volume)] = fold_fn
        return fold_fn

    def stage(self, projection_delta: Array, angle_slice) -> "StagedDelta":
        """Run the ARRIVAL-side half of an update — filter + encode + column
        AllGather — without folding. Pure (no session state changes).

        Filtering is per-projection independent, so a streaming rank stages
        frames while the burst is still landing: by the time the burst's
        last frame commits, only the fold (back-projection + reduce) is
        left — `update(staged, finalize=True)` is then the entire
        time-from-last-projection tail (the instant-CT figure of merit,
        benchmarks/bench_streaming.py)."""
        lo, hi = self._check_slice(angle_slice)
        self._check_delta_shape(projection_delta, lo, hi)
        with get_tracer().span("session.stage", lo=lo, hi=hi) as sp:
            pm_d, raw_d = self._place_delta(projection_delta, lo, hi)
            pm_col, q_col, sc_col = sp.fence(
                self._get_stage_fn(hi - lo)(pm_d, raw_d))
        return StagedDelta(lo, hi, pm_col, q_col, sc_col)

    def _check_delta_shape(self, delta, lo: int, hi: int) -> None:
        g = self.plan.geometry
        if tuple(delta.shape) != (hi - lo, g.n_v, g.n_u):
            raise ValueError(
                f"projection_delta shape {tuple(delta.shape)} does not "
                f"match angles [{lo}, {hi}) x detector ({g.n_v}, {g.n_u})")

    def _place_delta(self, delta, lo: int, hi: int):
        """(pm_d, raw_d) for the angle range, device-placed for the mesh."""
        mesh = self.plan.mesh
        pm_d = jnp.asarray(self._pmats[lo:hi])
        raw_d = delta
        if mesh is not None:
            sharding = input_sharding(mesh)
            pm_d = jax.device_put(pm_d, sharding)
            raw_d = jax.device_put(raw_d, sharding)
        return pm_d, raw_d

    def update(self, projection_delta, angle_slice=None,
               finalize: bool = False):
        """Fold one contiguous angle subset: filter + encode + column
        AllGather + slab back-projection (+ per-delta scatter reduce).

        projection_delta : (n_d, N_v, N_u) raw projections for the global
                           angle range `angle_slice` = slice/(lo, hi),
                           n_d dividing over the rank grid — or a
                           `StagedDelta` from `stage()` (no angle_slice;
                           only the fold runs).
        finalize         : True fuses the reduce epilogue + FDK scale into
                           the SAME dispatch and returns the volume (the
                           time-from-last-delta path — one launch instead
                           of update-then-finalize). State is still folded,
                           and a full-coverage finalize streams to the
                           session's VolumeSink exactly like finalize().

        Returns the session (chaining) — or the volume when finalize=True.
        """
        if isinstance(projection_delta, StagedDelta):
            if angle_slice is not None:
                raise TypeError(
                    "a StagedDelta carries its own angle range; do not "
                    "pass angle_slice")
            s = projection_delta
            lo, hi = self._check_slice((s.lo, s.hi))
            fn = self._get_fold_fn(hi - lo, with_volume=finalize)
            args = (s.pm_col, s.q_col, s.sc_col)
        else:
            if angle_slice is None:
                raise TypeError("angle_slice is required for a raw delta")
            lo, hi = self._check_slice(angle_slice)
            self._check_delta_shape(projection_delta, lo, hi)
            fn = self._get_update_fn(hi - lo, with_volume=finalize)
            args = self._place_delta(projection_delta, lo, hi)
        volume = None
        staged = isinstance(projection_delta, StagedDelta)
        with get_tracer().span("session.fold", lo=lo, hi=hi, staged=staged,
                               final=finalize) as sp:
            if self._compensated:
                if finalize:
                    self._acc, self._carry, volume = fn(
                        self._acc, self._carry, *args)
                else:
                    self._acc, self._carry = fn(self._acc, self._carry,
                                                *args)
            elif finalize:
                self._acc, volume = fn(self._acc, *args)
            else:
                self._acc = fn(self._acc, *args)
            sp.fence(volume if finalize else self._acc)
        self._covered[lo:hi] = True
        if not finalize:
            return self
        if self._sink is not None and self.is_complete:
            jax.block_until_ready(volume)
            with get_tracer().span("stage.write"):
                self._sink.write(volume)
        return volume

    # -- source coupling ----------------------------------------------------

    def poll(self) -> int:
        """Discover newly landed deltas on the ProjectionSource and fold
        them. Returns the number of deltas folded (0 = nothing new)."""
        if self._source is None:
            raise TypeError(
                "session was built without a ProjectionSource; feed deltas "
                "via update(delta, angle_slice) instead")
        n = 0
        with get_tracer().span("session.poll") as sp:
            for lo, hi, delta in self._source.iter_deltas(self.plan.mesh):
                self.update(delta, (lo, hi))
                n += 1
            sp.set(n_deltas=n)
        return n

    # -- epilogue -----------------------------------------------------------

    def _get_finalize_fn(self) -> Callable:
        if self._finalize_fn is not None:
            return self._finalize_fn
        plan, st = self.plan, self._stages
        mesh = plan.mesh
        scale = st.scale
        if mesh is None:
            self._finalize_fn = jax.jit(lambda acc: acc * scale)
            return self._finalize_fn
        if self._scatter:
            pod_axis = st.pod_axis

            def rank(acc):
                slab = acc[0]
                if pod_axis is not None:  # cross-pod finish stays f32
                    slab = lax.psum(slab, pod_axis)
                return slab * scale
        else:
            dp = st.dp

            def rank(acc):
                slab = acc[0]
                for a in dp:
                    slab = lax.psum(slab, a)
                return slab * scale

        self._finalize_fn = jax.jit(shard_map(
            _scoped("fdk.reduce", rank), mesh=mesh,
            in_specs=(self._acc_spec,),
            out_specs=output_spec(mesh, plan.reduce), check_vma=False))
        return self._finalize_fn

    def finalize(self, partial: bool = False) -> Array:
        """Row-reduce epilogue + FDK scale — the ONLY work left after the
        last delta folds. Pure: the session keeps accepting updates.

        partial=True returns the reconstruction from the angles folded so
        far (a mid-scan peek; limited-angle artifacts are the caller's to
        interpret). The default demands full coverage. A full finalize
        streams the volume to the session's VolumeSink, if one was given.
        """
        if not partial and not self.is_complete:
            raise ValueError(
                f"only {self.n_folded}/{self.plan.geometry.n_proj} angles "
                f"folded; missing ranges {self.pending_ranges()} — fold "
                "them (update/poll) or pass partial=True for a mid-scan "
                "peek")
        tracer = get_tracer()
        with tracer.span("session.finalize", partial=partial) as sp:
            volume = sp.fence(self._get_finalize_fn()(self._acc))
        if self._sink is not None and not partial:
            jax.block_until_ready(volume)
            with tracer.span("stage.write"):
                self._sink.write(volume)
        return volume


class TracedIncrementalSession(IncrementalSession):
    """The streaming session cut at its stage seams — `build_traced` for
    schedule="incremental".

    Same state machine and exactness contract as `IncrementalSession`, but
    every `session.stage`/`session.fold` is decomposed into separately
    dispatched, fenced ``stage.*`` spans (the `STAGE_FIELDS` vocabulary):
    stage() emits ``stage.filter`` + ``stage.allgather``; a fold emits
    ``stage.backproject`` and — under the scatter reduces, where each delta
    psum_scatters its partial — ``stage.reduce`` (the accumulate half of
    `_fold_closures`, dispatched apart from the back-projection); the
    finalize epilogue is a ``stage.reduce`` span too (psum's one deferred
    reduce). Raw deltas are routed through stage() first so the raw-update
    path decomposes identically.

    Like `build_traced`, this is a MEASUREMENT configuration: the split
    dispatches trade away the fold fusion the production session buys, and
    the spans are `timed=True` so stage seconds accumulate even with the
    tracer disabled. On the first full-coverage volume (finalize, or a
    fused `update(..., finalize=True)`) the accumulated stage times are
    deposited into the calibration store (planner/calibrate.py) against
    the plan's incremental cost point — streaming sessions feed the same
    predicted->measured loop as the batch engines.
    """

    def __init__(self, plan: ReconstructionPlan, source=None, sink=None):
        super().__init__(plan, source=source, sink=sink)
        self._stage_seconds: Dict[str, float] = {}
        self._recorded = False
        self._traced_finalize = None

    def _bump(self, name: str, sp) -> None:
        self._stage_seconds[name] = (self._stage_seconds.get(name, 0.0)
                                     + sp.duration_s)

    def stage_seconds(self) -> Dict[str, float]:
        """Accumulated per-stage wall seconds so far (a copy)."""
        return dict(self._stage_seconds)

    # -- stage decomposition -------------------------------------------------

    def _get_stage_fn(self, n_d: int) -> Callable:
        fn = self._stage_fns.get(("traced", n_d))
        if fn is not None:
            return fn
        mesh = self.plan.mesh
        st = self._stages
        filter_encode = st.filter_encode
        gather_cols = st.gather_cols
        if mesh is None:
            _filter = jax.jit(filter_encode)
            _gather = jax.jit(gather_cols)

            def run_filter(raw):
                return _filter(raw)

            def run_gather(pm_d, data, scales):
                return _gather(pm_d, data, scales)
        else:
            pspec = _proj_spec(mesh)
            gspec = self._gathered_spec()
            if self.plan.resolved_precision().codec.has_scales:
                # plain tuple: shard_map's out_specs prefix does not match
                # the EncodedStream NamedTuple subtype (same trick as
                # build_traced's batch decomposition).
                _filter = jax.jit(shard_map(
                    lambda raw: tuple(filter_encode(raw)), mesh=mesh,
                    in_specs=(pspec,), out_specs=(pspec, pspec),
                    check_vma=False))
                _gather = jax.jit(shard_map(
                    gather_cols, mesh=mesh,
                    in_specs=(pspec, pspec, pspec),
                    out_specs=(gspec, gspec, gspec), check_vma=False))

                def run_filter(raw):
                    return _filter(raw)

                def run_gather(pm_d, data, scales):
                    return _gather(pm_d, data, scales)
            else:
                _filter = jax.jit(shard_map(
                    lambda raw: filter_encode(raw)[0], mesh=mesh,
                    in_specs=(pspec,), out_specs=pspec, check_vma=False))
                _gather = jax.jit(shard_map(
                    lambda pm, d: gather_cols(pm, d, None)[:2],
                    mesh=mesh, in_specs=(pspec, pspec),
                    out_specs=(gspec, gspec), check_vma=False))

                def run_filter(raw):
                    return _filter(raw), None

                def run_gather(pm_d, data, scales):
                    pm_col, q_col = _gather(pm_d, data)
                    return pm_col, q_col, None

        def staged_fn(pm_d, raw_d):
            tracer = get_tracer()
            with tracer.span("stage.filter", timed=True) as sp:
                data, scales = sp.fence(run_filter(raw_d))
            self._bump("stage.filter", sp)
            with tracer.span("stage.allgather", timed=True) as sp:
                cols = sp.fence(run_gather(pm_d, data, scales))
            self._bump("stage.allgather", sp)
            return cols

        self._stage_fns[("traced", n_d)] = staged_fn
        return staged_fn

    def _get_fold_fn(self, n_d: int, with_volume: bool = False) -> Callable:
        key = ("traced", n_d, with_volume)
        fn = self._fold_fns.get(key)
        if fn is not None:
            return fn
        fin = self._get_finalize_fn() if with_volume else None

        if not self._scatter:
            # psum: the fold IS the back-projection (accumulation is the
            # back-projector's own `init=` epilogue — nothing to cut); the
            # row reduce is deferred to finalize, dispatched via `fin`.
            inner = IncrementalSession._get_fold_fn(self, n_d,
                                                    with_volume=False)

            def traced_fold(*args):
                with get_tracer().span("stage.backproject",
                                       timed=True) as sp:
                    new = sp.fence(inner(*args))
                self._bump("stage.backproject", sp)
                return (new, fin(new)) if with_volume else new
        else:
            # scatter: cut the per-delta fold at the _fold_closures
            # `accumulate` seam — back-projection partial in one dispatch
            # (stage.backproject), carry + psum_scatter into the resident
            # state in another (stage.reduce).
            mesh = self.plan.mesh
            st = self._stages
            g = self.plan.geometry
            backproject, slab_pmats = st.backproject, st.slab_pmats
            nx_slab = st.nx_slab
            _, _, accumulate = self._fold_closures(with_volume=False)

            def bp_rank(pm_col, q_col, sc_col):
                return backproject(slab_pmats(pm_col), q_col, nx_slab,
                                   g.n_y, g.n_z, scales=sc_col)[None]

            gspec = self._gathered_spec()
            part_spec = P(_lead_axes(st.dp), AXIS_MODEL, None, None)
            bp_fn = jax.jit(shard_map(
                bp_rank, mesh=mesh, in_specs=(gspec, gspec, gspec),
                out_specs=part_spec, check_vma=False))
            state_in, out_specs, pack = self._state_specs(False)
            if self._compensated:
                def acc_rank(acc, carry, part):
                    new, new_carry = accumulate(acc, carry, part[0])
                    return pack(new, new_carry, None)
            else:
                def acc_rank(acc, part):  # carry unused: pass acc
                    new, _ = accumulate(acc, acc, part[0])
                    return pack(new, None, None)
            acc_fn = jax.jit(shard_map(
                acc_rank, mesh=mesh, in_specs=state_in + (part_spec,),
                out_specs=out_specs, check_vma=False))
            n_state = 2 if self._compensated else 1

            def traced_fold(*args):
                state, cols = args[:n_state], args[n_state:]
                tracer = get_tracer()
                with tracer.span("stage.backproject", timed=True) as sp:
                    part = sp.fence(bp_fn(*cols))
                self._bump("stage.backproject", sp)
                with tracer.span("stage.reduce", timed=True) as sp:
                    new_state = sp.fence(acc_fn(*state, part))
                self._bump("stage.reduce", sp)
                if not with_volume:
                    return new_state
                if n_state == 2:
                    new_acc, new_carry = new_state
                    return new_acc, new_carry, fin(new_acc)
                return new_state, fin(new_state)

        self._fold_fns[key] = traced_fold
        return traced_fold

    def _get_finalize_fn(self) -> Callable:
        if self._traced_finalize is None:
            inner = super()._get_finalize_fn()

            def fin(acc):
                with get_tracer().span("stage.reduce", timed=True) as sp:
                    out = sp.fence(inner(acc))
                self._bump("stage.reduce", sp)
                return out

            self._traced_finalize = fin
        return self._traced_finalize

    # -- calibration feedback ------------------------------------------------

    def update(self, projection_delta, angle_slice=None,
               finalize: bool = False):
        if not isinstance(projection_delta, StagedDelta):
            if angle_slice is None:
                raise TypeError("angle_slice is required for a raw delta")
            # route raw deltas through stage() so the raw-update path
            # decomposes into the same stage.filter/allgather/fold spans.
            projection_delta = self.stage(projection_delta, angle_slice)
            angle_slice = None
        out = super().update(projection_delta, angle_slice,
                             finalize=finalize)
        if finalize and self.is_complete:
            self._record_calibration()
        return out

    def finalize(self, partial: bool = False) -> Array:
        volume = super().finalize(partial=partial)
        if not partial:
            self._record_calibration()
        return volume

    def _record_calibration(self) -> None:
        if self._recorded:
            return
        self._recorded = True
        from repro.planner.calibrate import record_traced_run
        record_traced_run(self.plan, dict(self._stage_seconds))


_SPEC_INT_KEYS = ("n_steps", "y_chunks", "vmem_budget")
_SPEC_STR_KEYS = ("impl", "window", "precision", "schedule", "reduce")
_SPEC_KEYS = _SPEC_STR_KEYS + _SPEC_INT_KEYS + ("blocks",)

# Known *values*, mapped to the key they belong to — so a bare typo like
# "pipelned" can be answered with "did you mean 'schedule=pipelined'?".
_SPEC_VALUE_KEYS = {
    **{v: "schedule" for v in _SCHEDULES},
    **{v: "reduce" for v in _REDUCES},
    **{v: "impl" for v in _IMPLS},
    **{v: "precision" for v in _PRECISIONS},
    **{v: "window" for v in _WINDOWS},
}


def _spec_hint(token: str) -> str:
    """'; did you mean ...?' for the nearest valid spec token, or ''."""
    import difflib
    candidates = ["auto"] + list(_SPEC_KEYS) + list(_SPEC_VALUE_KEYS)
    close = difflib.get_close_matches(token, candidates, n=1, cutoff=0.6)
    if not close:
        return ""
    match = close[0]
    if match in _SPEC_VALUE_KEYS:
        match = f"{_SPEC_VALUE_KEYS[match]}={match}"
    elif match in _SPEC_KEYS:
        match = f"{match}=..."
    return f"; did you mean {match!r}?"


def plan_from_spec(geometry: CBCTGeometry, spec: str = "",
                   mesh: Mesh | None = None, **overrides) -> ReconstructionPlan:
    """Build a plan from a compact ``key=value,key=value`` spec string — the
    one-flag configuration surface shared by the benchmark/example harnesses
    (e.g. ``--plan "schedule=pipelined,n_steps=4,precision=bf16"``).

    Recognized keys: impl, window, precision, schedule, n_steps, y_chunks,
    reduce, vmem_budget, blocks (as ``bi:bj:bs``). ``overrides`` kwargs win
    over the spec string.

    The bare token ``auto`` hands the remaining (pinned) dimensions to the
    planner (repro/planner): ``"auto"`` searches the whole space for the
    best feasible plan on this (geometry, mesh); ``"auto,precision=bf16"``
    searches with the precision axis pinned.
    """
    kwargs: dict = {}
    auto = False
    for item in filter(None, (s.strip() for s in spec.split(","))):
        if "=" not in item:
            if item == "auto":
                auto = True
                continue
            raise ValueError(
                f"plan spec token {item!r} is not key=value and not 'auto'; "
                f"valid keys: {', '.join(_SPEC_KEYS)}{_spec_hint(item)}")
        key, val = (s.strip() for s in item.split("=", 1))
        if key in _SPEC_INT_KEYS:
            kwargs[key] = int(val)
        elif key == "blocks":
            kwargs[key] = tuple(int(v) for v in val.split(":"))
        elif key in _SPEC_STR_KEYS:
            kwargs[key] = val
        else:
            raise ValueError(
                f"unknown plan spec key {key!r}; valid keys: "
                f"{', '.join(_SPEC_KEYS)}{_spec_hint(key)}")
    kwargs.update(overrides)
    if auto:
        from repro.planner import auto_plan
        window = kwargs.pop("window", "ramlak")
        vmem_budget = kwargs.pop("vmem_budget", None)
        return auto_plan(geometry, mesh=mesh, window=window,
                         vmem_budget=vmem_budget, **kwargs)
    return ReconstructionPlan(geometry=geometry, mesh=mesh, **kwargs)

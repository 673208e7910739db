"""Auto-planner subsystem: plan-aware cost model, feasibility pruning,
ranked search, measured refinement, the `plan_from_spec(g, "auto")` wiring,
spec-error ergonomics, and the legacy-entry-point deprecation warnings."""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.core.distributed import IFDKGrid, grid_candidates, input_sharding
from repro.core.fdk import reconstruct
from repro.core.geometry import default_geometry, paper_geometry
from repro.core.perf_model import ABCI
from repro.core.phantom import forward_project
from repro.core.plan import ReconstructionPlan, plan_from_spec
from repro.core.precision import Precision
from repro.parallel.mesh import make_mesh, single_device_mesh
from repro.planner import (
    PlanPoint, auto_plan, check_feasible, enumerate_points, plan_footprint,
    point_from_plan, predict_plan, predict_point, search_grids, search_plans,
)
from repro.planner import measure as plan_measure
from repro.planner.cost import STEP_OVERHEAD_S

paper_problem = paper_geometry


GRID_256 = IFDKGrid(r=32, c=8)


# ---------------------------------------------------------------------------
# cost.py: plan-aware Eq. 8-19
# ---------------------------------------------------------------------------

class TestCost:
    def test_fused_serializes_stages(self):
        g = paper_problem()
        b = predict_point(g, PlanPoint(grid=GRID_256, schedule="fused"))
        assert not b.overlap
        assert b.t_compute == pytest.approx(
            b.t_load + b.t_flt + b.t_allgather + b.t_bp)

    def test_pipelined_overlaps_per_eq17(self):
        g = paper_problem()
        b = predict_point(g, PlanPoint(grid=GRID_256, schedule="pipelined",
                                       n_steps=4))
        assert b.overlap
        assert b.t_compute == pytest.approx(
            max(b.t_load, b.t_flt, b.t_allgather, b.t_bp))

    def test_pipelined_single_step_has_no_overlap(self):
        """n_steps=1 degenerates to fused semantics — the model must not
        award it Eq. 17's max."""
        g = paper_problem()
        b = predict_point(g, PlanPoint(grid=GRID_256, schedule="pipelined",
                                       n_steps=1))
        assert not b.overlap

    def test_storage_dtype_scales_comm(self):
        g = paper_problem()
        f32 = predict_point(g, PlanPoint(grid=GRID_256, precision="fp32"))
        b16 = predict_point(g, PlanPoint(grid=GRID_256, precision="bf16"))
        assert b16.t_allgather == pytest.approx(f32.t_allgather / 2)
        assert b16.t_load == pytest.approx(f32.t_load / 2)

    def test_chunked_restreams_projections(self):
        """More y-chunks -> more Q^T re-reads -> larger T_bp; the pipelined
        schedule at the same n_steps is the lower envelope."""
        g = paper_problem()
        pipe = predict_point(g, PlanPoint(grid=GRID_256,
                                          schedule="pipelined", n_steps=4))
        prev = pipe.t_bp
        for yc in (2, 8, 32):
            b = predict_point(g, PlanPoint(grid=GRID_256, schedule="chunked",
                                           n_steps=4, y_chunks=yc))
            assert b.t_bp > prev
            prev = b.t_bp

    def test_step_overhead_penalizes_deep_pipelines(self):
        g = paper_problem()
        t2 = predict_point(g, PlanPoint(grid=GRID_256, schedule="pipelined",
                                        n_steps=2)).t_bp
        t8 = predict_point(g, PlanPoint(grid=GRID_256, schedule="pipelined",
                                        n_steps=8)).t_bp
        assert t8 == pytest.approx(t2 + 6 * STEP_OVERHEAD_S)

    def test_psum_doubles_scatter_reduce_traffic(self):
        g = paper_problem()
        ps = predict_point(g, PlanPoint(grid=GRID_256, reduce="psum"))
        sc = predict_point(g, PlanPoint(grid=GRID_256, reduce="scatter"))
        assert ps.t_reduce == pytest.approx(2 * sc.t_reduce)
        c1 = predict_point(g, PlanPoint(grid=IFDKGrid(r=32, c=1)))
        assert c1.t_reduce == 0.0

    def test_impl_factors_order_t_bp(self):
        g = paper_problem()
        ts = {impl: predict_point(
                  g, PlanPoint(grid=GRID_256, impl=impl)).t_bp
              for impl in ("reference", "factorized", "kernel")}
        assert ts["reference"] > ts["factorized"] > ts["kernel"]
        with pytest.raises(ValueError, match="unknown impl"):
            predict_point(g, PlanPoint(grid=GRID_256, impl="cuda"))

    def test_predict_plan_matches_point(self):
        g = default_geometry(16, n_proj=8)
        plan = ReconstructionPlan(geometry=g, schedule="pipelined",
                                  n_steps=2, precision="bf16")
        assert predict_plan(plan) == predict_point(g, point_from_plan(plan))


class TestIOCost:
    """T_read/T_write in the plan-aware model: the slice-per-rank store's
    writer count and the PFS throttling the with-I/O ranking responds to."""

    def test_io_writers_counts_the_stores_concurrency(self):
        from repro.planner.cost import io_writers
        assert io_writers(PlanPoint(grid=GRID_256, reduce="psum")) == 32
        assert io_writers(PlanPoint(grid=GRID_256, reduce="scatter")) == 256
        assert io_writers(PlanPoint(grid=GRID_256, reduce="scatter",
                                    data_size=4)) == 128

    def test_scatter_store_outwrites_psum_under_rank_io_cap(self):
        """With per-rank PFS links the bottleneck, the parallel store
        (scatter: R x C writers) beats the replicated slab's R writers —
        the paper's reason for the slice-per-rank layout."""
        g = paper_problem()
        sys = ABCI.with_pfs(rank_io=50e6)
        ps = predict_point(g, PlanPoint(grid=GRID_256, reduce="psum"), sys)
        sc = predict_point(g, PlanPoint(grid=GRID_256, reduce="scatter"),
                           sys)
        assert sc.t_write < ps.t_write
        # uncapped (the paper's aggregate assumption): no difference
        ps0 = predict_point(g, PlanPoint(grid=GRID_256, reduce="psum"))
        sc0 = predict_point(g, PlanPoint(grid=GRID_256, reduce="scatter"))
        assert ps0.t_write == pytest.approx(sc0.t_write)

    def test_pfs_throttle_changes_auto_ranking(self):
        """The acceptance regression: throttling PFS read bandwidth flips
        the ranked search's winner — the planner ranks WITH I/O."""
        g = paper_problem()
        fast = search_grids(g, 256, system=ABCI, top_k=1)[0]
        slow = search_grids(g, 256,
                            system=ABCI.with_pfs(read=ABCI.bw_load / 200),
                            top_k=1)[0]
        assert (fast.point.grid, fast.spec()) != (slow.point.grid,
                                                  slow.spec())
        # under the throttle the winner is read-bound: Eq. 17's max is the
        # load term, so the ranking literally hinges on T_read
        assert slow.breakdown.t_compute == pytest.approx(
            slow.breakdown.t_read)
        assert slow.breakdown.t_read > fast.breakdown.t_read

    def test_rank_io_throttle_flips_reduce_mode_preference(self):
        """psum wins the tie-break when writes are free; capping per-rank
        links makes the parallel store's extra writers decisive."""
        g = paper_problem()
        points = [PlanPoint(grid=GRID_256, schedule="pipelined", n_steps=4,
                            precision="bf16", reduce=r)
                  for r in ("psum", "scatter")]
        sys = ABCI.with_pfs(rank_io=50e6)
        t = {p.reduce: predict_point(g, p, sys).t_runtime for p in points}
        assert t["scatter"] < t["psum"]


# ---------------------------------------------------------------------------
# feasibility.py: per-device memory model
# ---------------------------------------------------------------------------

class TestFeasibility:
    def test_chunked_scatter_divides_slab(self):
        g = paper_problem()
        fused = plan_footprint(g, PlanPoint(grid=GRID_256, schedule="fused"))
        chunk = plan_footprint(g, PlanPoint(grid=GRID_256,
                                            schedule="chunked", n_steps=8,
                                            y_chunks=16, reduce="scatter"))
        assert chunk.slab < fused.slab
        assert chunk.gathered < fused.gathered
        assert chunk.total < fused.total

    def test_scatter_divisor_is_data_axis_not_full_column(self):
        """The engine scatters the chunked accumulator over the DATA axis
        only (pod finishes replicated): on a multi-pod mesh the footprint
        must divide by data_size, not by all C columns."""
        g = paper_problem()
        single_pod = PlanPoint(grid=GRID_256, schedule="chunked", n_steps=8,
                               y_chunks=16, reduce="scatter")
        multi_pod = dataclasses.replace(single_pod, data_size=4)
        assert plan_footprint(g, multi_pod).slab > \
            plan_footprint(g, single_pod).slab
        mesh = single_device_mesh()
        plan = ReconstructionPlan(geometry=default_geometry(16, n_proj=8),
                                  mesh=mesh)
        assert point_from_plan(plan).data_size == 1

    def test_infeasible_reason_names_budget(self):
        g = paper_problem()
        point = PlanPoint(grid=IFDKGrid(r=1, c=1))
        ok, reason = check_feasible(g, point, hbm_bytes=16 * 2**30)
        assert not ok and "exceeds the HBM budget" in reason

    def test_kernel_vmem_floor(self):
        """A VMEM budget below the kernel's minimal working set prunes
        impl='kernel' with a kernel-specific reason; the XLA impls are
        untouched by it."""
        g = default_geometry(16, n_proj=8)
        point = PlanPoint(grid=IFDKGrid(r=1, c=1), impl="kernel")
        ok, _ = check_feasible(g, point)
        assert ok
        ok, reason = check_feasible(g, point, vmem_budget=1024)
        assert not ok and "fits VMEM" in reason
        ok, _ = check_feasible(
            g, PlanPoint(grid=IFDKGrid(r=1, c=1)), vmem_budget=1024)
        assert ok

    def test_kernel_needs_even_nz(self):
        g = dataclasses.replace(default_geometry(16, n_proj=8), n_z=15)
        ok, reason = check_feasible(
            g, PlanPoint(grid=IFDKGrid(r=1, c=1), impl="kernel"))
        assert not ok and "even N_z" in reason


# ---------------------------------------------------------------------------
# search.py: enumeration + ranking
# ---------------------------------------------------------------------------

class TestSearch:
    def test_grid_candidates_divisibility(self):
        g = paper_problem()
        grids = grid_candidates(g, 256)
        assert IFDKGrid(r=32, c=8) in grids
        for gr in grids:
            assert gr.n_ranks == 256 and g.n_x % gr.r == 0
        # 6 devices: only R in {1, 2} divide both 6 and n_x
        g6 = default_geometry(64, n_proj=96)
        assert [gr.r for gr in grid_candidates(g6, 6)] == [1, 2]
        # ranks must also tile the projections (validate()'s Eq. 5 rule)
        assert grid_candidates(default_geometry(64, n_proj=128), 6) == []

    def test_enumerate_points_respects_structure(self):
        g = default_geometry(16, n_proj=8)
        pts = list(enumerate_points(g, IFDKGrid(r=1, c=1)))
        assert all(p.n_steps == 1 for p in pts if p.schedule == "fused")
        assert all(p.reduce == "psum" for p in pts)  # c == 1: no scatter
        assert any(p.schedule == "chunked" and p.y_chunks == 4 for p in pts)

    def test_search_plans_returns_validated_ranked_plans(self):
        g = default_geometry(16, n_proj=8)
        props = search_plans(g, None, top_k=6)
        assert props and all(p.feasible for p in props)
        for p in props:
            assert p.plan is not None
            assert p.plan.validate() is p.plan
        ts = [p.predicted for p in props]
        assert ts == sorted(ts)

    def test_tight_budget_prunes_fused_for_chunked(self):
        """Acceptance: with a budget between the chunked and fused
        footprints, the fused plan is infeasible and the search returns a
        chunked winner instead."""
        g = default_geometry(16, n_proj=64)
        grid = IFDKGrid(r=1, c=1)
        fused_total = plan_footprint(
            g, PlanPoint(grid=grid, schedule="fused")).total
        chunk_total = plan_footprint(
            g, PlanPoint(grid=grid, schedule="chunked", n_steps=8,
                         y_chunks=4)).total
        assert chunk_total < fused_total
        budget = (fused_total + chunk_total) // 2
        props = search_plans(g, None, hbm_bytes=budget,
                             schedules=("fused", "chunked"), top_k=4)
        assert props and props[0].point.schedule == "chunked"
        assert all(p.point.schedule != "fused" for p in props)
        # and the fused plan really was pruned as infeasible, not absent
        # (top_k must cover the whole space — 170 points since the
        # precision axis grew to five codecs):
        with_inf = search_plans(g, None, hbm_bytes=budget,
                                schedules=("fused", "chunked"), top_k=1000,
                                include_infeasible=True)
        fused = [p for p in with_inf if p.point.schedule == "fused"]
        assert fused and not fused[0].feasible

    def test_bf16_outranks_f32_when_allgather_bound(self):
        """Acceptance: make AllGather the Eq. 17 bottleneck -> the halved
        collective bytes of bf16 storage win the ranking."""
        g = default_geometry(16, n_proj=8)
        ag_bound = dataclasses.replace(ABCI, th_allgather=1e-3)
        props = search_plans(g, None, system=ag_bound,
                             precisions=("fp32", "bf16"),
                             schedules=("pipelined",),
                             n_steps_candidates=(2,),
                             impls=("factorized",), top_k=8)
        assert [p.point.precision for p in props] == ["bf16", "fp32"]
        b = props[0].breakdown
        assert b.t_compute == pytest.approx(b.t_allgather)  # really AG-bound
        assert props[0].predicted == pytest.approx(props[1].predicted / 2,
                                                   rel=0.1)

    def test_search_grids_untileable_device_count_raises(self):
        # 4096 projections cannot spread over 100 ranks: a clear error,
        # not an empty table
        with pytest.raises(ValueError, match="no rectangular R x C"):
            search_grids(paper_problem(), 100)

    def test_search_grids_paper_scale(self):
        g = paper_problem()
        props = search_grids(g, 256, top_k=8)
        assert props and all(p.feasible for p in props)
        assert all(p.point.grid.n_ranks == 256 for p in props)
        assert all(p.plan is None for p in props)
        # every proposal's spec string round-trips through plan_from_spec
        # (construction parses the knobs; validation is geometry-specific)
        for p in props:
            plan = plan_from_spec(g, p.spec())
            assert plan.schedule == p.point.schedule
            assert plan.reduce == p.point.reduce
            assert plan.resolved_precision().storage == p.point.precision


class TestStreamCodecPlanner:
    """ISSUE 5: the planner prices the stream codecs — fp8_e4m3 wire bytes
    (+ scale sidecar) and the scatter_bf16 half-width reduce — with the
    same formulas the engine moves bytes by."""

    def test_search_space_includes_new_tokens(self):
        """`plan_from_spec(g, "auto")`'s search space (the default
        enumerate axes) contains fp8_e4m3 storage and scatter_bf16."""
        g = default_geometry(16, n_proj=8)
        pts = list(enumerate_points(g, IFDKGrid(r=2, c=4)))
        assert any(p.precision == "fp8_e4m3" for p in pts)
        assert any(p.reduce == "scatter_bf16" for p in pts)
        # and the planner's spec strings for them parse right back
        pt8 = next(p for p in pts if p.precision == "fp8_e4m3"
                   and p.reduce == "scatter_bf16")
        plan = plan_from_spec(g, pt8.spec())
        assert plan.resolved_precision().storage == "fp8_e4m3"
        assert plan.reduce == "scatter_bf16"

    def test_fp8_quarters_allgather_time(self):
        g = paper_problem()
        f32 = predict_point(g, PlanPoint(grid=GRID_256, precision="fp32"))
        fp8 = predict_point(g, PlanPoint(grid=GRID_256,
                                         precision="fp8_e4m3"))
        # 1/4 of the data bytes + the (tiny) scale sidecar
        assert fp8.t_allgather < f32.t_allgather / 4 * 1.01
        assert fp8.t_allgather > f32.t_allgather / 4  # sidecar is priced

    def test_fp8_outranks_bf16_when_allgather_bound(self):
        g = default_geometry(16, n_proj=8)
        ag_bound = dataclasses.replace(ABCI, th_allgather=1e-3)
        props = search_plans(g, None, system=ag_bound,
                             precisions=("bf16", "fp8_e4m3"),
                             schedules=("pipelined",),
                             n_steps_candidates=(2,),
                             impls=("factorized",), top_k=8)
        assert [p.point.precision for p in props] == ["fp8_e4m3", "bf16"]
        assert props[0].predicted == pytest.approx(props[1].predicted / 2,
                                                   rel=0.1)

    def test_scatter_bf16_halves_reduce_term(self):
        g = paper_problem()
        sc = predict_point(g, PlanPoint(grid=GRID_256, reduce="scatter"))
        hf = predict_point(g, PlanPoint(grid=GRID_256,
                                        reduce="scatter_bf16"))
        assert hf.t_reduce == pytest.approx(sc.t_reduce / 2)

    def test_wire_byte_accounting(self):
        from repro.planner.cost import (
            allgather_wire_bytes, reduce_wire_bytes,
        )
        g = paper_problem()
        ag = {p: allgather_wire_bytes(g, PlanPoint(grid=GRID_256,
                                                   precision=p))
              for p in ("fp32", "bf16", "fp8_e4m3")}
        assert ag["bf16"] * 2 == ag["fp32"]
        sidecar_moved = 256 * (4 * (g.n_proj // 8)) * 31 // 32
        assert ag["fp8_e4m3"] == ag["fp32"] // 4 + sidecar_moved
        rd = {r: reduce_wire_bytes(g, PlanPoint(grid=GRID_256, reduce=r))
              for r in ("psum", "scatter", "scatter_bf16")}
        assert rd["psum"] == 2 * rd["scatter"]
        assert rd["scatter_bf16"] * 2 == rd["scatter"]
        # nothing moves on a 1-rank axis
        assert allgather_wire_bytes(g, PlanPoint(grid=IFDKGrid(r=1,
                                                               c=8))) == 0
        assert reduce_wire_bytes(g, PlanPoint(grid=IFDKGrid(r=32,
                                                            c=1))) == 0

    def test_reduce_wire_bytes_multipod_scatters_data_axis_only(self):
        """The engine's scatter epilogue runs over the DATA axis and
        finishes across pods with an f32 psum of the 1/D-scattered slab —
        the accounting must NOT bill the whole C-column at bf16 width."""
        from repro.planner.cost import reduce_wire_bytes
        g = paper_problem()
        slab4 = (g.n_x // 32) * g.n_y * g.n_z * 4
        pt = PlanPoint(grid=GRID_256, reduce="scatter_bf16", data_size=2)
        # bf16 ring over the 2 data ranks + f32 allreduce over the 4 pods
        # of the half-slab
        per_rank = (slab4 // 2) * 1 // 2 + 2 * (slab4 // 2) * 3 // 4
        assert reduce_wire_bytes(g, pt) == 256 * per_rank
        # single-pod (data_size == c): pure half-width ring, no finish term
        single = PlanPoint(grid=GRID_256, reduce="scatter_bf16",
                           data_size=8)
        full = PlanPoint(grid=GRID_256, reduce="scatter", data_size=8)
        assert (reduce_wire_bytes(g, single) * 2
                == reduce_wire_bytes(g, full))

    def test_footprint_counts_sidecar_and_carry(self):
        g = default_geometry(16, n_proj=64)
        grid = IFDKGrid(r=1, c=1)
        f32 = plan_footprint(g, PlanPoint(grid=grid, precision="fp32"))
        fp8 = plan_footprint(g, PlanPoint(grid=grid, precision="fp8_e4m3"))
        # wire-format gathered batch: a quarter of f32 + 4 B/projection
        assert fp8.gathered == f32.gathered // 4 + 4 * g.n_proj
        # the compensated reduce's f32 error-feedback carry costs a full
        # slab of memory under the chunked schedule
        grid2 = IFDKGrid(r=1, c=2)
        plain = plan_footprint(g, PlanPoint(
            grid=grid2, schedule="chunked", n_steps=2, y_chunks=4,
            reduce="scatter"))
        comp = plan_footprint(g, PlanPoint(
            grid=grid2, schedule="chunked", n_steps=2, y_chunks=4,
            reduce="scatter_bf16"))
        assert comp.slab == plain.slab + g.n_x * g.n_y * g.n_z * 4

    def test_scatter_bf16_writer_count_matches_scatter(self):
        from repro.planner.cost import io_writers
        assert (io_writers(PlanPoint(grid=GRID_256, reduce="scatter_bf16"))
                == io_writers(PlanPoint(grid=GRID_256, reduce="scatter")))

    def test_search_grids_accepts_pinned_new_tokens(self):
        """The benchmark CLI path: restricting the axes to the new tokens
        yields a ranked table of only those plans."""
        g = paper_problem()
        props = search_grids(g, 256, precisions=("fp8_e4m3",),
                             reduces=("scatter_bf16",), top_k=4)
        assert props
        assert all(p.point.precision == "fp8_e4m3" for p in props)
        assert all(p.point.reduce == "scatter_bf16" for p in props)


class TestFp32KeepsAnF32Reduce:
    """Half-width wire bytes price the scatter_bf16 row reduce cheapest, so
    an unpinned search used to hand fp32 storage a lossy bf16 reduce. Under
    fp32 storage an unpinned search now offers only the reduces that move
    the partial slabs at f32; bf16 storage may still take scatter_bf16,
    and a pinned reduce is searched as given."""

    ALL_REDUCES = ("psum", "scatter", "scatter_bf16")

    def test_unpinned_fp32_never_yields_scatter_bf16(self):
        g = paper_problem()
        pts = list(enumerate_points(g, GRID_256))
        assert {p.reduce for p in pts if p.precision == "fp32"} == {
            "psum", "scatter"}
        ranked = search_grids(g, 256, precisions=("fp32",), top_k=None)
        assert ranked
        assert all(p.point.reduce != "scatter_bf16" for p in ranked)
        assert ranked[0].point.reduce == "scatter"

    def test_offered_every_reduce_fp32_would_rank_scatter_bf16_first(self):
        """Why the rule lives in the enumeration: the cost model alone
        ranks the lossy reduce above the exact one."""
        best = search_grids(paper_problem(), 256, precisions=("fp32",),
                            reduces=self.ALL_REDUCES, top_k=1)[0]
        assert best.point.reduce == "scatter_bf16"

    @pytest.mark.parametrize("precision", ["bf16", "fp8_e4m3"])
    def test_narrower_storage_may_take_scatter_bf16(self, precision):
        g = paper_problem()
        pts = list(enumerate_points(g, GRID_256, precisions=(precision,)))
        assert {p.reduce for p in pts} == set(self.ALL_REDUCES)
        best = search_grids(g, 256, precisions=(precision,), top_k=1)[0]
        assert best.point.reduce == "scatter_bf16"

    def test_a_pinned_reduce_wins_under_fp32(self):
        g = paper_problem()
        pinned = search_grids(g, 256, precisions=("fp32",),
                              reduces=("scatter_bf16",), top_k=None)
        assert pinned
        assert {p.point.reduce for p in pinned} == {"scatter_bf16"}
        assert {p.point.precision for p in pinned} == {"fp32"}

    def test_auto_plan_pin_reaches_the_search(self, monkeypatch):
        """auto_plan hands a `reduce` pin to the search as the only reduce,
        and leaves the reduces to the enumeration's rule otherwise."""
        from repro.planner import search as search_mod
        seen = []

        def spy(g, mesh=None, **kw):
            seen.append(kw.get("reduces"))
            raise ValueError("stop")

        monkeypatch.setattr(search_mod, "search_plans", spy)
        g = default_geometry(16, n_proj=8)
        for pins in ({"precision": "fp32"},
                     {"precision": "fp32", "reduce": "scatter_bf16"}):
            with pytest.raises(ValueError, match="stop"):
                auto_plan(g, calibration=None, **pins)
        assert seen == [None, ("scatter_bf16",)]


# ---------------------------------------------------------------------------
# auto_plan / plan_from_spec("auto") wiring
# ---------------------------------------------------------------------------

class TestAutoPlan:
    @pytest.fixture(scope="class")
    def case16(self):
        g = default_geometry(16, n_proj=8)
        proj = forward_project(g)
        oracle = np.array(reconstruct(g, proj, impl="factorized",
                                      precision="fp32"))
        return g, proj, oracle

    def _check_oracle(self, out, oracle, storage):
        p = Precision(storage)
        scale = float(np.max(np.abs(oracle))) + 1e-12
        rmse = float(np.sqrt(np.mean((out - oracle) ** 2))) / scale
        assert rmse < p.rmse_tol(), rmse

    def test_auto_engine_matches_oracle_on_1x1x1_mesh(self, case16):
        """Acceptance: plan_from_spec(g, "auto") on a 1x1x1 mesh returns a
        validate()-clean plan whose engine reproduces the f32 oracle."""
        g, proj, oracle = case16
        mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
        plan = plan_from_spec(g, "auto", mesh=mesh)
        assert plan.validate() is plan
        out = np.asarray(plan.build()(
            jax.device_put(proj, input_sharding(mesh))))
        out = out.reshape(g.n_x, g.n_y, g.n_z)
        self._check_oracle(out, oracle, plan.resolved_precision().storage)

    def test_auto_no_mesh_matches_oracle(self, case16):
        g, proj, oracle = case16
        plan = plan_from_spec(g, "auto,precision=fp32")
        out = np.asarray(plan.build()(proj))
        self._check_oracle(out, oracle, "fp32")

    def test_auto_pins_restrict_the_search(self, case16):
        g, _, _ = case16
        plan = plan_from_spec(g, "auto,schedule=chunked,precision=bf16")
        assert plan.schedule == "chunked" and plan.y_chunks is not None
        assert plan.resolved_precision().storage == "bf16"

    def test_auto_pinned_knobs_constrain_the_schedule(self, case16):
        """Pinning n_steps/y_chunks must not let a schedule that ignores
        the knob win with the pin silently dropped."""
        g, _, _ = case16
        p = plan_from_spec(g, "auto,y_chunks=4")
        assert p.schedule == "chunked" and p.y_chunks == 4
        p = plan_from_spec(g, "auto,n_steps=4")
        assert p.schedule != "fused" and p.n_steps == 4
        with pytest.raises(ValueError, match="pins conflict"):
            plan_from_spec(g, "auto,schedule=fused,n_steps=4")
        with pytest.raises(ValueError, match="pins conflict"):
            plan_from_spec(g, "auto,schedule=pipelined,y_chunks=4")

    def test_auto_unknown_pin_raises(self, case16):
        g, _, _ = case16
        with pytest.raises(ValueError, match="cannot pin"):
            auto_plan(g, bogus=3)

    def test_auto_infeasible_raises_with_cause(self, case16):
        """Budget failures and divisibility failures get DIFFERENT errors —
        the user must be steered at the knob that actually failed."""
        g, _, _ = case16
        with pytest.raises(ValueError, match="exceed the memory budget"):
            auto_plan(g, hbm_bytes=1024)
        # N_p local = 8: n_steps=3 never divides -> not a budget problem
        with pytest.raises(ValueError, match="no valid candidate"):
            auto_plan(g, n_steps=3)

    def test_cpu_auto_avoids_interpret_mode_kernel(self, case16):
        g, _, _ = case16
        if jax.default_backend() != "tpu":
            assert plan_from_spec(g, "auto").impl == "factorized"


# ---------------------------------------------------------------------------
# measure.py: timed refinement + file-backed cache
# ---------------------------------------------------------------------------

class TestMeasure:
    def test_refine_times_and_reranks(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_PLAN_CACHE",
                           str(tmp_path / "plan_cache.json"))
        plan_measure.clear_cache()
        g = default_geometry(16, n_proj=8)
        props = search_plans(g, None, impls=("factorized",), top_k=4)
        refined = plan_measure.refine(g, props, top_k=2, iters=1)
        assert len(refined) == len(props)
        head = refined[:2]
        assert all(p.measured is not None and p.measured > 0 for p in head)
        assert head[0].measured <= head[1].measured
        assert all(p.measured is None for p in refined[2:])

    def test_file_cache_serves_second_lookup(self, tmp_path, monkeypatch):
        cache = tmp_path / "plan_cache.json"
        monkeypatch.setenv("REPRO_PLAN_CACHE", str(cache))
        plan_measure.clear_cache()
        g = default_geometry(16, n_proj=8)
        props = search_plans(g, None, impls=("factorized",), top_k=1)
        t0 = plan_measure.measure_proposal(g, props[0], iters=1)
        assert cache.exists()
        plan_measure.clear_cache()  # simulate a fresh process
        hits = plan_measure.file_cache_hits()
        t1 = plan_measure.measure_proposal(g, props[0], iters=1)
        assert t1 == t0  # served verbatim from disk, not re-timed
        assert plan_measure.file_cache_hits() == hits + 1

    def test_cache_key_sees_engine_identity(self, tmp_path, monkeypatch):
        """Two plans differing only in a knob outside the spec string (the
        ramp window) must not share a timing entry."""
        monkeypatch.setenv("REPRO_PLAN_CACHE",
                           str(tmp_path / "plan_cache.json"))
        plan_measure.clear_cache()
        g = default_geometry(16, n_proj=8)
        a = search_plans(g, None, impls=("factorized",), top_k=1)[0]
        b = search_plans(g, None, impls=("factorized",), top_k=1,
                         window="hann")[0]
        assert a.spec() == b.spec()  # the spec alone cannot tell them apart
        ka = plan_measure._measure_key(g, a, 1)
        kb = plan_measure._measure_key(g, b, 1)
        assert ka != kb

    def test_grid_only_proposal_is_not_measurable(self):
        g = paper_problem()
        props = search_grids(g, 256, top_k=1)
        with pytest.raises(ValueError, match="grid-only"):
            plan_measure.measure_proposal(g, props[0])


# ---------------------------------------------------------------------------
# plan_from_spec error ergonomics (satellite)
# ---------------------------------------------------------------------------

class TestSpecErrors:
    def test_bare_typo_suggests_key_value(self):
        g = default_geometry(16, n_proj=8)
        with pytest.raises(ValueError) as ei:
            plan_from_spec(g, "pipelned")
        msg = str(ei.value)
        assert "valid keys: impl, window, precision, schedule" in msg
        assert "did you mean 'schedule=pipelined'?" in msg

    def test_unknown_key_lists_valid_and_nearest(self):
        g = default_geometry(16, n_proj=8)
        with pytest.raises(ValueError) as ei:
            plan_from_spec(g, "shedule=fused")
        msg = str(ei.value)
        assert "unknown plan spec key 'shedule'" in msg
        assert "did you mean 'schedule=...'" in msg

    def test_valid_value_of_wrong_kind_suggests_its_key(self):
        g = default_geometry(16, n_proj=8)
        with pytest.raises(ValueError, match="did you mean 'reduce=scatter'"):
            plan_from_spec(g, "scatter")

    def test_auto_token_still_parses_normally(self):
        g = default_geometry(16, n_proj=8)
        plan = plan_from_spec(g, "auto , precision=fp32")
        assert plan.resolved_precision().storage == "fp32"


# ---------------------------------------------------------------------------
# legacy entry-point deprecation (satellite)
# ---------------------------------------------------------------------------

class TestDeprecationWarnings:
    def _fired(self, fn):
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            fn()
        return [w for w in rec if issubclass(w.category, DeprecationWarning)
                and "ReconstructionPlan" in str(w.message)]

    def test_each_legacy_entry_point_warns_exactly_once_per_process(self):
        from repro.core import fdk
        from repro.core.distributed import make_distributed_fdk
        from repro.core.pipeline import make_chunked_fdk, make_pipelined_fdk

        g = default_geometry(16, n_proj=8)
        proj = forward_project(g)
        mesh = single_device_mesh()
        calls = {
            "fdk.reconstruct": lambda: reconstruct(g, proj),
            "make_distributed_fdk": lambda: make_distributed_fdk(mesh, g),
            "make_pipelined_fdk": lambda: make_pipelined_fdk(mesh, g,
                                                             n_steps=2),
            "make_chunked_fdk": lambda: make_chunked_fdk(mesh, g, n_steps=2,
                                                         y_chunks=4),
        }
        # the registry is process-wide; reset so this test is order-independent
        fdk._DEPRECATION_FIRED.clear()
        for name, call in calls.items():
            first = self._fired(call)
            assert len(first) == 1, name
            assert name in str(first[0].message)
            assert len(self._fired(call)) == 0, f"{name} warned twice"

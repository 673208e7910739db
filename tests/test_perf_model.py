"""iFDK performance model (paper Eqs. 8-19, Table 5, Fig. 5)."""
import pytest

from repro.core.distributed import IFDKGrid
from repro.core.geometry import paper_geometry as paper_problem
from repro.core.perf_model import (
    ABCI, TPU_V5E, MachineSpec, SystemConstants, gups_end_to_end,
    machine_for, predict,
)


class TestPerfModel:
    def test_compute_shrinks_with_devices(self):
        """Strong scaling: T_compute inversely proportional to C (paper
        §4.2.3 conclusion I)."""
        g = paper_problem()
        t = [predict(g, IFDKGrid(r=32, c=c), ABCI).t_compute
             for c in (1, 2, 4, 8)]
        assert t[0] > t[1] > t[2] > t[3]
        assert t[0] / t[3] == pytest.approx(8.0, rel=0.35)

    def test_post_time_constant_in_c(self):
        g = paper_problem()
        a = predict(g, IFDKGrid(r=32, c=2), ABCI)
        b = predict(g, IFDKGrid(r=32, c=8), ABCI)
        assert a.t_post == pytest.approx(b.t_post, rel=1e-6)

    def test_reduce_vanishes_when_c_is_1(self):
        g = paper_problem()
        assert predict(g, IFDKGrid(r=32, c=1), ABCI).t_reduce == 0.0

    def test_paper_magnitudes_4k_256gpus(self):
        """Paper Fig. 5a / §5.3.3: 4K problem, 256 GPUs (R=32, C=8):
        T_store ~ 9 s, T_D2H ~ 2.6 s, runtime tens of seconds."""
        g = paper_problem()
        b = predict(g, IFDKGrid(r=32, c=8), ABCI)
        assert b.t_store == pytest.approx(9.0, rel=0.1)
        # paper quotes ~2.6 s; Eq. 14 with their own constants gives ~1.4 s
        # (their text assumes switch contention) — accept the bracket.
        assert 1.2 < b.t_d2h < 3.0
        assert 10.0 < b.t_runtime < 60.0

    def test_paper_table5_compute_breakdown_256(self):
        """Table 5 row (4096^3, 256 GPUs): T_bp ~ 7.0s, T_compute ~ 10.2s.
        The model should land within ~50% (it is a peak projection)."""
        g = paper_problem()
        b = predict(g, IFDKGrid(r=32, c=8), ABCI)
        assert b.t_bp == pytest.approx(7.0, rel=0.5)
        assert b.t_compute == pytest.approx(10.2, rel=0.5)

    def test_delta_overlap_factor_exceeds_one(self):
        """Table 5: delta > 1 (pipelining wins) for all reported rows."""
        g = paper_problem()
        for c in (2, 4, 8):
            assert predict(g, IFDKGrid(r=32, c=c), ABCI).delta > 1.0

    def test_gups_increases_with_devices(self):
        g = paper_problem()
        g1 = gups_end_to_end(g, predict(g, IFDKGrid(r=32, c=2), ABCI))
        g2 = gups_end_to_end(g, predict(g, IFDKGrid(r=32, c=8), ABCI))
        assert g2 > g1

    def test_tpu_constants_give_finite_projection(self):
        g = paper_problem()
        b = predict(g, IFDKGrid(r=16, c=16), TPU_V5E)
        assert 0 < b.t_runtime < 120


class TestMonotonicity:
    """Structural properties any constant refresh must preserve."""

    def test_more_ranks_never_increases_t_compute(self):
        """Growing the grid in either direction (more columns OR more rows)
        never makes T_compute worse — Eq. 17 terms are each non-increasing
        in R and C (T_load is constant; the rest split further)."""
        g = paper_problem()
        for sys in (ABCI, TPU_V5E):
            for r in (8, 16, 32):
                seq = [predict(g, IFDKGrid(r=r, c=c), sys).t_compute
                       for c in (1, 2, 4, 8, 16)]
                assert all(a >= b for a, b in zip(seq, seq[1:])), (r, seq)
            for c in (2, 8):
                seq = [predict(g, IFDKGrid(r=r, c=c), sys).t_compute
                       for r in (4, 8, 16, 32, 64)]
                assert all(a >= b for a, b in zip(seq, seq[1:])), (c, seq)

    def test_halving_storage_never_increases_t_allgather(self):
        """The precision policy's promise: narrower storage can only shrink
        the projection-stream terms (AllGather, load, H2D)."""
        g = paper_problem()
        grid = IFDKGrid(r=32, c=8)
        for sys in (ABCI, TPU_V5E):
            wide = predict(g, grid, sys, storage_bytes=4.0)
            half = predict(g, grid, sys, storage_bytes=2.0)
            assert half.t_allgather <= wide.t_allgather
            assert half.t_load <= wide.t_load
            assert half.t_h2d <= wide.t_h2d
            assert half.t_allgather == pytest.approx(wide.t_allgather / 2)

    def test_storage_bytes_default_matches_f32(self):
        g = paper_problem()
        grid = IFDKGrid(r=32, c=8)
        assert predict(g, grid, ABCI) == predict(g, grid, ABCI,
                                                 storage_bytes=4.0)


class TestIOTerms:
    """T_read/T_write: the planner-visible I/O terms (Eq. 8/16) and the PFS
    bandwidth knobs (`MachineSpec.with_pfs`) they respond to."""

    def test_machinespec_is_the_old_systemconstants(self):
        assert SystemConstants is MachineSpec
        assert isinstance(ABCI, MachineSpec)

    def test_read_write_alias_the_eq8_eq16_terms(self):
        g = paper_problem()
        b = predict(g, IFDKGrid(r=32, c=8), ABCI)
        assert b.t_read == b.t_load
        assert b.t_write == b.t_store
        assert b.t_io == pytest.approx(b.t_read + b.t_write)

    def test_with_pfs_only_touches_io(self):
        """Monotonicity-suite anchor: throttling the PFS must move ONLY the
        I/O terms, and move them inversely to bandwidth."""
        g = paper_problem()
        grid = IFDKGrid(r=32, c=8)
        base = predict(g, grid, ABCI)
        prev_read, prev_write = base.t_read, base.t_write
        for f in (2.0, 8.0, 64.0):
            b = predict(g, grid, ABCI.with_pfs(read=ABCI.bw_load / f,
                                               write=ABCI.bw_store / f))
            assert b.t_read == pytest.approx(base.t_read * f)
            assert b.t_write == pytest.approx(base.t_write * f)
            assert b.t_read > prev_read and b.t_write > prev_write
            assert b.t_runtime > base.t_runtime
            # the non-I/O terms are untouched
            assert b.t_flt == base.t_flt
            assert b.t_allgather == base.t_allgather
            assert b.t_bp == base.t_bp
            assert b.t_reduce == base.t_reduce
            prev_read, prev_write = b.t_read, b.t_write

    def test_rank_io_cap_binds_few_ranks_not_many(self):
        """Per-rank PFS links: few concurrent ranks are link-bound, many
        saturate the filesystem aggregate (the slice-per-rank store's
        scaling argument)."""
        sys = ABCI.with_pfs(rank_io=1e9)
        # few readers: capped below aggregate
        assert sys.agg_read_bw(4) == pytest.approx(4e9)
        # many readers: the aggregate wins
        assert sys.agg_read_bw(256) == pytest.approx(ABCI.bw_load)
        assert sys.agg_write_bw(8) == pytest.approx(8e9)
        assert sys.agg_write_bw(256) == pytest.approx(ABCI.bw_store)

    def test_rank_io_cap_preserves_rank_monotonicity(self):
        """More ranks never increases T_compute, capped or not (the
        monotonicity property the planner's ranking rests on)."""
        g = paper_problem()
        sys = ABCI.with_pfs(rank_io=2e9)
        for r in (8, 32):
            seq = [predict(g, IFDKGrid(r=r, c=c), sys).t_compute
                   for c in (1, 2, 4, 8, 16)]
            assert all(x >= y for x, y in zip(seq, seq[1:])), (r, seq)

    def test_uncapped_rank_io_matches_paper_model(self):
        g = paper_problem()
        grid = IFDKGrid(r=32, c=8)
        assert predict(g, grid, ABCI.with_pfs(rank_io=1e30)) == \
            predict(g, grid, ABCI)


class TestPinnedPaperProjection:
    """Pinned ABCI-constants regression: the 4K / 2048-GPU deployment the
    paper headlines (§5.3: 4096^3 from 4096 projections "within 30 s").
    With R=32, C=64 the model is load-bound on T_compute and lands at
    ~15.3 s end-to-end — pinned here so constant drift is caught."""

    def test_4k_2048gpus_breakdown(self):
        g = paper_problem()
        b = predict(g, IFDKGrid(r=32, c=64), ABCI)
        assert b.t_compute == pytest.approx(b.t_load)  # load-bound at C=64
        assert b.t_load == pytest.approx(1.374, rel=0.01)
        assert b.t_bp == pytest.approx(0.820, rel=0.01)
        assert b.t_runtime == pytest.approx(15.33, rel=0.01)
        assert b.t_runtime < 30.0  # the paper's headline claim
        assert gups_end_to_end(g, b) == pytest.approx(17100, rel=0.01)


class TestMachineFor:
    """The planner prices a TPU from its device_kind, never a default."""

    @staticmethod
    def _dev(platform, kind):
        import types
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    def test_v5e_kind_maps_to_v5e(self):
        assert machine_for(self._dev("tpu", "TPU v5 lite")) is TPU_V5E

    def test_unknown_tpu_kind_raises(self):
        with pytest.raises(ValueError, match="no MachineSpec for TPU kind"):
            machine_for(self._dev("tpu", "TPU v99"))

    def test_cpu_keeps_paper_constants(self):
        import jax
        assert machine_for(jax.devices()[0]) is ABCI

"""Stream codecs (fp32/bf16/fp16/fp8 wire formats, f32 accumulate) and the
VMEM-budget kernel autotuner."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backprojection import (
    backproject_factorized, backproject_reference,
)
from repro.core.distributed import IFDKGrid, input_sharding, \
    make_distributed_fdk
from repro.core.fdk import reconstruct
from repro.core.filtering import filter_projections
from repro.core.geometry import default_geometry, projection_matrices
from repro.core.phantom import forward_project, shepp_logan_volume
from repro.core.plan import ReconstructionPlan
from repro.core.precision import (
    CODECS, Precision, codec_for, default_storage, psnr, resolve_precision,
)
from repro.kernels.backproject import tune
from repro.kernels.backproject.kernel import vmem_bytes
from repro.kernels.backproject.ops import backproject_mxu, backproject_pallas
from repro.parallel.mesh import make_mesh, single_device_mesh

STORAGES = ("fp32", "bf16", "fp16")


@pytest.fixture(scope="module")
def case16():
    """The 16^3 default geometry with its fp32 factorized oracle."""
    g = default_geometry(16, n_proj=8)
    proj = forward_project(g)
    pm = jnp.asarray(projection_matrices(g))
    q32 = filter_projections(g, proj, out_dtype=jnp.float32)
    oracle = backproject_factorized(pm, q32, g.n_x, g.n_y, g.n_z)
    return g, proj, pm, oracle


class TestPrecisionPolicy:
    def test_storage_dtypes(self):
        assert Precision("fp32").storage_dtype == jnp.float32
        assert Precision("bf16").storage_dtype == jnp.bfloat16
        assert Precision("fp16").storage_dtype == jnp.float16

    def test_canonical_aliases(self):
        assert Precision("float16").storage == "fp16"
        assert Precision("bfloat16").storage == "bf16"
        assert Precision("f32").storage == "fp32"

    def test_unknown_storage_rejected(self):
        with pytest.raises(ValueError):
            Precision("int8")

    def test_resolve(self):
        assert resolve_precision("fp16") == Precision("fp16")
        p = Precision("bf16")
        assert resolve_precision(p) is p
        # None -> backend default: bf16 on CPU/TPU, fp16 on GPU
        assert resolve_precision(None).storage == default_storage()
        assert default_storage("cpu") == "bf16"
        assert default_storage("tpu") == "bf16"
        assert default_storage("gpu") == "fp16"

    def test_accumulation_always_f32(self):
        for s in STORAGES:
            assert Precision(s).accum_dtype == jnp.float32

    def test_halved_allgather_bytes(self):
        g = default_geometry(16, n_proj=8)
        full = Precision("fp32").allgather_bytes(g.n_proj, g.n_v, g.n_u)
        half = Precision("bf16").allgather_bytes(g.n_proj, g.n_v, g.n_u)
        assert half * 2 == full

    def test_tolerances_scale_with_eps(self):
        assert Precision("fp32").rmse_tol() == pytest.approx(1e-5)
        assert Precision("fp16").rmse_tol() > Precision("fp32").rmse_tol()
        assert Precision("bf16").rmse_tol() > Precision("fp16").rmse_tol()
        assert Precision("fp8_e4m3").rmse_tol() > Precision("bf16").rmse_tol()

    def test_fp8_aliases(self):
        for alias in ("fp8", "e4m3", "float8_e4m3fn"):
            assert Precision(alias).storage == "fp8_e4m3"
        assert Precision("fp8_e4m3").storage_dtype == jnp.float8_e4m3fn
        assert Precision("fp8_e4m3").storage_bytes == 1
        for alias in ("e5m2", "float8_e5m2"):
            assert Precision(alias).storage == "fp8_e5m2"
        assert Precision("fp8_e5m2").storage_dtype == jnp.float8_e5m2
        assert Precision("fp8_e5m2").storage_bytes == 1
        # two mantissa bits vs three: e5m2 quantizes twice as coarsely
        assert Precision("fp8_e5m2").eps() == 2 * Precision("fp8_e4m3").eps()


class TestStreamCodecs:
    """The codec layer itself: wire formats, scale sidecars, and the
    engine/cost-model agreement on wire bytes (ISSUE 5 acceptance)."""

    @pytest.fixture(scope="class")
    def q32(self):
        g = default_geometry(16, n_proj=8)
        return g, filter_projections(g, forward_project(g),
                                     out_dtype=jnp.float32)

    def test_registry(self):
        assert set(CODECS) == {"fp32", "bf16", "fp16", "fp8_e4m3",
                               "fp8_e5m2"}
        for name, codec in CODECS.items():
            assert codec is codec_for(name)
            assert codec is Precision(name).codec
            assert (codec.wire_bytes_per_sample
                    == jnp.dtype(codec.wire_dtype).itemsize)
        assert not CODECS["fp32"].has_scales
        assert not CODECS["bf16"].has_scales
        assert CODECS["fp16"].has_scales      # scale-on-overflow
        assert CODECS["fp8_e4m3"].has_scales  # normalizing
        assert CODECS["fp8_e5m2"].has_scales  # normalizing

    def test_scale_free_encode_bitmatches_cast(self, q32):
        """bf16 (and f32) codecs are byte-identical to the historical
        plain-cast policy."""
        _, q = q32
        for name in ("fp32", "bf16"):
            data, scales = CODECS[name].encode(q)
            assert scales is None
            assert data.dtype == CODECS[name].wire_dtype
            assert bool(jnp.all(data == q.astype(CODECS[name].wire_dtype)))

    def test_fp16_in_range_bitmatches_cast(self, q32):
        """In-range streams: fp16 scales are exactly 1.0 and the data bits
        equal the naive cast (the historical behaviour)."""
        _, q = q32
        data, scales = CODECS["fp16"].encode(q)
        assert bool(jnp.all(scales == 1.0))
        assert bool(jnp.all(data == q.astype(jnp.float16)))

    def test_fp16_scales_on_overflow(self, q32):
        """Beyond-65504 projections encode finite and decode accurately
        (the overflow hazard the old docstring only warned about)."""
        _, q = q32
        big = q.astype(jnp.float32) * 3e5   # max |q| >> fp16 max
        assert not bool(jnp.all(jnp.isfinite(big.astype(jnp.float16))))
        data, scales = CODECS["fp16"].encode(big)
        assert bool(jnp.all(jnp.isfinite(data.astype(jnp.float32))))
        assert bool(jnp.any(scales > 1.0))
        dec = CODECS["fp16"].decode(data, scales)
        err = float(jnp.max(jnp.abs(dec - big))) / float(jnp.max(jnp.abs(big)))
        assert err < 2 * Precision("fp16").eps()

    def test_fp8_roundtrip_error_bound(self, q32):
        """encode/decode is a per-projection-relative quantization: each tap
        is recovered within eps/2 of the projection's max-abs."""
        _, q = q32
        codec = CODECS["fp8_e4m3"]
        data, scales = codec.encode(q)
        assert data.dtype == jnp.float8_e4m3fn
        assert scales.shape == (q.shape[0],) and scales.dtype == jnp.float32
        dec = codec.decode(data, scales)
        amax = jnp.max(jnp.abs(q.astype(jnp.float32)), axis=(-2, -1))
        per_proj = jnp.max(jnp.abs(dec - q), axis=(-2, -1)) / amax
        assert float(jnp.max(per_proj)) <= 0.5 * Precision("fp8_e4m3").eps()

    def test_fp8_e5m2_roundtrip_error_bound(self, q32):
        """Same normalizing contract as e4m3 at e5m2's coarser eps — and a
        wider exponent: the normalized stream never needs the sidecar to
        rescue range, only precision."""
        _, q = q32
        codec = CODECS["fp8_e5m2"]
        data, scales = codec.encode(q)
        assert data.dtype == jnp.float8_e5m2
        assert scales.shape == (q.shape[0],) and scales.dtype == jnp.float32
        dec = codec.decode(data, scales)
        amax = jnp.max(jnp.abs(q.astype(jnp.float32)), axis=(-2, -1))
        per_proj = jnp.max(jnp.abs(dec - q), axis=(-2, -1)) / amax
        assert float(jnp.max(per_proj)) <= 0.5 * Precision("fp8_e5m2").eps()

    def test_fp8_zero_projection_is_exact(self):
        codec = CODECS["fp8_e4m3"]
        data, scales = codec.encode(jnp.zeros((3, 4, 4), jnp.float32))
        assert bool(jnp.all(scales == 1.0))
        assert bool(jnp.all(codec.decode(data, scales) == 0.0))

    def test_decode_requires_sidecar(self):
        with pytest.raises(ValueError, match="scale"):
            CODECS["fp8_e4m3"].decode(
                jnp.zeros((2, 4, 4), jnp.float8_e4m3fn))

    def test_fp8_wire_bytes_quarter_of_f32(self, q32):
        """ISSUE 5 acceptance: the cost model and the engine agree that fp8
        AllGather wire bytes are 1/4 of f32 plus the scale sidecar — the
        encoded arrays, `Precision.wire_bytes`, and the planner's AllGather
        accounting are the same number."""
        from repro.planner.cost import allgather_wire_bytes, PlanPoint
        g, q = q32
        fp8 = Precision("fp8_e4m3")
        enc = fp8.codec.encode(q)
        n, v, u = g.n_proj, g.n_v, g.n_u
        # engine side: actual encoded bytes
        assert enc.nbytes == n * v * u + 4 * n
        # policy side: one formula
        assert fp8.wire_bytes(n, v, u) == enc.nbytes
        assert (fp8.wire_bytes(n, v, u)
                == Precision("fp32").wire_bytes(n, v, u) // 4 + 4 * n)
        assert fp8.allgather_bytes(n, v, u) == fp8.wire_bytes(n, v, u)
        # cost-model side: the AllGather accounting prices the same bytes
        grid = IFDKGrid(r=2, c=1)
        ag8 = allgather_wire_bytes(g, PlanPoint(grid=grid,
                                                precision="fp8_e4m3"))
        ag32 = allgather_wire_bytes(g, PlanPoint(grid=grid,
                                                 precision="fp32"))
        n_ranks, moved = grid.n_ranks, (grid.r - 1) / grid.r
        assert ag8 == int(n_ranks * moved * fp8.wire_bytes(n, v, u))
        assert ag8 == ag32 // 4 + int(n_ranks * moved * 4 * n)

    @pytest.mark.parametrize(
        "bp", [backproject_reference, backproject_factorized,
               backproject_pallas, backproject_mxu],
        ids=["reference", "factorized", "kernel", "mxu"],
    )
    def test_every_backprojector_dequantizes_fp8(self, case16, bp):
        """All four implementations decode the fp8 stream via the scale
        sidecar (taps dequantize before the f32 FMA) and agree with the f32
        oracle within the fp8 tolerance."""
        g, proj, pm, oracle = case16
        q = filter_projections(g, proj, out_dtype=jnp.float32)
        data, scales = CODECS["fp8_e4m3"].encode(q)
        out = bp(pm, data, g.n_x, g.n_y, g.n_z, scales=scales)
        assert out.dtype == jnp.float32
        p = Precision("fp8_e4m3")
        scale = float(jnp.max(jnp.abs(oracle))) + 1e-12
        rmse = float(jnp.sqrt(jnp.mean((out - oracle) ** 2))) / scale
        assert rmse < p.rmse_tol(), f"fp8 rmse {rmse:.3e}"


class TestFp16OverflowRegression:
    """ISSUE 5 satellite: ramp-filtered projections of a high-contrast scan
    exceed fp16's 65504 — the naive cast poisons the volume with inf/nan,
    the fp16 codec's scale-on-overflow keeps full fp16 accuracy."""

    def test_high_contrast_phantom(self, case16):
        g, proj, _, _ = case16
        big = proj * np.float32(1e6)        # filtered stream peaks ~ 1e6
        q = filter_projections(g, big, out_dtype=jnp.float32)
        assert float(jnp.max(jnp.abs(q))) > 65504.0  # genuinely overflows
        naive = q.astype(jnp.float16)
        assert not bool(jnp.all(jnp.isfinite(naive.astype(jnp.float32))))
        oracle = np.asarray(ReconstructionPlan(geometry=g).build()(big))
        out = np.asarray(
            ReconstructionPlan(geometry=g, precision="fp16").build()(big))
        assert np.all(np.isfinite(out))
        p = Precision("fp16")
        scale = float(np.max(np.abs(oracle))) + 1e-12
        rmse = float(np.sqrt(np.mean((out - oracle) ** 2))) / scale
        assert rmse < p.rmse_tol(), f"overflow rmse {rmse:.3e}"


class TestLowPrecisionBackprojection:
    """Oracle tests over {fp32, bf16, fp16} storage, tolerance from eps."""

    @pytest.mark.parametrize("storage", STORAGES)
    @pytest.mark.parametrize(
        "bp", [backproject_reference, backproject_factorized,
               backproject_pallas],
        ids=["reference", "factorized", "kernel"],
    )
    def test_matches_fp32_oracle(self, case16, bp, storage):
        g, proj, pm, oracle = case16
        p = Precision(storage)
        q = filter_projections(g, proj, out_dtype=p.storage_dtype)
        assert q.dtype == p.storage_dtype
        out = bp(pm, q, g.n_x, g.n_y, g.n_z)
        assert out.dtype == jnp.float32  # f32 accumulate, always
        scale = float(jnp.max(jnp.abs(oracle))) + 1e-12
        rmse = float(jnp.sqrt(jnp.mean((out - oracle) ** 2))) / scale
        mx = float(jnp.max(jnp.abs(out - oracle))) / scale
        assert rmse < p.rmse_tol(), f"{storage}: rmse {rmse:.3e}"
        assert mx < p.max_tol(), f"{storage}: max {mx:.3e}"

    @pytest.mark.parametrize("storage", ["bf16", "fp16"])
    def test_filtering_emits_storage_dtype(self, case16, storage):
        g, proj, _, _ = case16
        p = Precision(storage)
        q = filter_projections(g, proj, out_dtype=p.storage_dtype)
        assert q.dtype == p.storage_dtype
        assert q.nbytes * 2 == g.n_proj * g.n_v * g.n_u * 4

    @pytest.mark.parametrize("storage", STORAGES)
    def test_distributed_bitmatches_single_device(self, case16, storage):
        """The distributed path must be bit-identical to the single-device
        path at the same storage dtype (1x1 mesh: the collectives are
        identities, so any deviation is a precision-policy leak)."""
        g, proj, _, _ = case16
        mesh = single_device_mesh()
        fn = make_distributed_fdk(mesh, g, impl="factorized",
                                  precision=storage)
        dist = np.array(fn(jax.device_put(proj, input_sharding(mesh))))
        single = np.array(
            reconstruct(g, proj, impl="factorized", precision=storage)
        )
        np.testing.assert_array_equal(dist, single)


class TestGoldenPSNR:
    """Regression floor: future kernel/precision work must not silently
    degrade Shepp-Logan reconstruction quality. Measured 15.9 dB for every
    (impl, precision) pair at 16^3/24 views; floor set 2 dB under."""

    FLOOR_DB = 13.9

    @pytest.fixture(scope="class")
    def golden_case(self):
        g = default_geometry(16, n_proj=24)
        return g, forward_project(g), shepp_logan_volume(g)

    @pytest.mark.parametrize("impl", ["reference", "factorized", "kernel"])
    @pytest.mark.parametrize("storage", STORAGES)
    def test_psnr_floor(self, golden_case, impl, storage):
        g, proj, ph = golden_case
        vol = reconstruct(g, proj, impl=impl, precision=storage)
        m = g.n_x // 5
        interior = (slice(m, g.n_x - m),) * 3
        got = psnr(np.array(vol[interior]), np.array(ph[interior]))
        assert got > self.FLOOR_DB, f"{impl}/{storage}: {got:.2f} dB"


class TestQuantizationStudy:
    """ISSUE 5 satellite: PSNR sweep of the codec ladder against the f32
    Shepp-Logan oracle (the f32 reconstruction, 16^3 / 24 views).

    Measured on this geometry: bf16 ~76 dB, fp16 ~94 dB, fp8_e4m3 ~52 dB,
    fp8_e5m2 ~46 dB (the ~6 dB cost of trading a mantissa bit for
    exponent range). Each *_FLOOR_DB is the documented regression floor (a
    few dB under the measured value, the same convention as
    TestGoldenPSNR.FLOOR_DB); the ordering assertion pins the physics:
    narrower mantissa can only lose fidelity — fp32 >= bf16 >= e4m3 >=
    e5m2 on a normalized (in-range) stream.
    """

    FP8_FLOOR_DB = 48.0
    E5M2_FLOOR_DB = 42.0
    BF16_FLOOR_DB = 70.0

    @pytest.fixture(scope="class")
    def sweep(self):
        g = default_geometry(16, n_proj=24)
        proj = forward_project(g)
        mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
        oracle = np.asarray(ReconstructionPlan(geometry=g).build()(proj))
        vols = {}
        for storage in ("fp32", "bf16", "fp8_e4m3", "fp8_e5m2"):
            # the 1x1x1-mesh engine: the fp8 acceptance path of ISSUE 5
            plan = ReconstructionPlan(geometry=g, mesh=mesh,
                                      precision=storage)
            vols[storage] = np.asarray(plan.build()(
                jax.device_put(proj, input_sharding(mesh))))
        return oracle, vols

    def test_psnr_ordering(self, sweep):
        oracle, vols = sweep
        db = {s: psnr(v, oracle) for s, v in vols.items()}
        assert (db["fp32"] >= db["bf16"] >= db["fp8_e4m3"]
                >= db["fp8_e5m2"]), db

    def test_fp8_engine_clears_documented_floor(self, sweep):
        oracle, vols = sweep
        got = psnr(vols["fp8_e4m3"], oracle)
        assert got > self.FP8_FLOOR_DB, f"fp8: {got:.2f} dB"

    def test_fp8_e5m2_engine_clears_documented_floor(self, sweep):
        oracle, vols = sweep
        got = psnr(vols["fp8_e5m2"], oracle)
        assert got > self.E5M2_FLOOR_DB, f"e5m2: {got:.2f} dB"

    def test_bf16_engine_clears_documented_floor(self, sweep):
        oracle, vols = sweep
        got = psnr(vols["bf16"], oracle)
        assert got > self.BF16_FLOOR_DB, f"bf16: {got:.2f} dB"


class TestAutotuner:
    def test_candidates_tile_and_fit_budget(self):
        budget = 256 * 1024
        cands = tune.candidate_blocks(16, 16, 8, 24, 24, 8,
                                      jnp.float32, budget)
        assert cands
        for c in cands:
            assert 16 % c.bi == 0 and 16 % c.bj == 0
            assert c.vmem == vmem_bytes(c.bi, c.bj, c.bs, 24, 24, 8)
            assert c.vmem <= budget

    def test_low_precision_widens_feasible_set(self):
        """bf16 projections halve the qt VMEM term, so a tight budget
        admits strictly more (or larger-batch) candidates."""
        budget = vmem_bytes(8, 8, 8, 64, 64, 8, jnp.float32)
        n32 = len(tune.candidate_blocks(16, 16, 8, 64, 64, 8,
                                        jnp.float32, budget))
        n16 = len(tune.candidate_blocks(16, 16, 8, 64, 64, 8,
                                        jnp.float16, budget))
        assert n16 > n32

    def test_budget_too_small_raises(self):
        with pytest.raises(ValueError):
            tune.autotune(16, 16, 16, 8, 24, 24, budget=128, measure=False)

    def test_pick_is_cached(self):
        tune.clear_cache()
        a = tune.autotune(16, 16, 16, 8, 24, 24, measure=False)
        assert len(tune.cache_info()) == 1
        b = tune.autotune(16, 16, 16, 8, 24, 24, measure=False)
        assert a is b
        # a different storage dtype is a different cache entry
        tune.autotune(16, 16, 16, 8, 24, 24, qt_dtype=jnp.bfloat16,
                      measure=False)
        assert len(tune.cache_info()) == 2

    def test_measured_mode_times_survivors(self):
        tune.clear_cache()
        best = tune.autotune(16, 16, 16, 8, 24, 24, measure=True,
                             max_measure=2)
        assert best.elapsed > 0.0
        assert best.vmem <= tune.DEFAULT_VMEM_BUDGET

    def test_kernel_uses_tuned_blocks(self, case16):
        """backproject_pallas with a constrained budget still matches the
        oracle — the tuner only changes the tiling, never the math."""
        g, proj, pm, oracle = case16
        q = filter_projections(g, proj, out_dtype=jnp.float32)
        # the tightest budget any tiling fits: forces the minimal tile
        budget = tune.min_vmem_bytes(g.n_x, g.n_y, g.n_proj, g.n_u, g.n_v,
                                     g.n_z // 2)
        out = backproject_pallas(pm, q, g.n_x, g.n_y, g.n_z,
                                 vmem_budget=budget)
        scale = float(jnp.max(jnp.abs(oracle))) + 1e-12
        assert float(jnp.max(jnp.abs(out - oracle))) / scale < 1e-4

    def test_explicit_blocks_bypass_tuner(self, case16):
        g, proj, pm, oracle = case16
        q = filter_projections(g, proj, out_dtype=jnp.float32)
        out = backproject_pallas(pm, q, g.n_x, g.n_y, g.n_z,
                                 bi=4, bj=8, bs=4)
        scale = float(jnp.max(jnp.abs(oracle))) + 1e-12
        assert float(jnp.max(jnp.abs(out - oracle))) / scale < 1e-4


class TestFileBackedCache:
    """The tuner memo persists to a JSON file (REPRO_TUNE_CACHE) so tuning
    survives across processes."""

    def test_survives_in_process_memo_clear(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tc.json"))
        tune.clear_cache()
        hits0 = tune.file_cache_hits()
        a = tune.autotune(16, 16, 16, 8, 24, 24, measure=False)
        assert (tmp_path / "tc.json").exists()
        tune.clear_cache()  # drop the memo; the file must refill it
        b = tune.autotune(16, 16, 16, 8, 24, 24, measure=False)
        assert tune.file_cache_hits() == hits0 + 1
        assert b.as_tuple() == a.as_tuple()

    def test_disabled_by_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_CACHE", "off")
        assert tune.cache_path() is None
        tune.clear_cache()
        tune.autotune(16, 16, 16, 8, 24, 24, measure=False)
        assert not list(tmp_path.iterdir())

    def test_corrupt_cache_file_is_ignored(self, tmp_path, monkeypatch):
        path = tmp_path / "tc.json"
        path.write_text("{not json")
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
        tune.clear_cache()
        cfg = tune.autotune(16, 16, 16, 8, 24, 24, measure=False)
        assert cfg.vmem <= tune.DEFAULT_VMEM_BUDGET  # recomputed fine

    @pytest.mark.slow
    def test_second_process_hits_cache(self, tmp_path):
        """A second *process* serves the tuning key from the file cache."""
        import os
        import subprocess
        import sys
        script = (
            "from repro.kernels.backproject import tune\n"
            "cfg = tune.autotune(16, 16, 16, 8, 24, 24, measure=False)\n"
            "print('OUT', cfg.as_tuple(), tune.file_cache_hits())\n"
        )
        env = dict(os.environ)
        env["REPRO_TUNE_CACHE"] = str(tmp_path / "tc.json")
        env["PYTHONPATH"] = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "src"))
        outs = []
        for _ in range(2):
            r = subprocess.run([sys.executable, "-c", script], env=env,
                               capture_output=True, text=True, timeout=300)
            assert r.returncode == 0, r.stderr[-2000:]
            outs.append([l for l in r.stdout.splitlines()
                         if l.startswith("OUT")][0])
        blocks1, hits1 = outs[0][4:].rsplit(" ", 1)
        blocks2, hits2 = outs[1][4:].rsplit(" ", 1)
        assert (hits1, hits2) == ("0", "1")  # second process: served from disk
        assert blocks1 == blocks2

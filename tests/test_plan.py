"""ReconstructionPlan / staged-engine tests: the schedule x reduce x
precision cross-product against the single-device f32 oracle, centralized
validate() error messages, plan-time kernel block resolution, and the
choose_grid regression (non-power-of-two device counts)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.distributed import IFDKGrid, choose_grid, input_sharding
from repro.core.fdk import reconstruct
from repro.core.geometry import default_geometry
from repro.core.phantom import forward_project
from repro.core.plan import ReconstructionPlan, plan_from_spec
from repro.core.precision import Precision
from repro.parallel.mesh import make_mesh, single_device_mesh

SCHEDULES = ("fused", "pipelined", "chunked")
REDUCES = ("psum", "scatter")
STORAGES = ("fp32", "bf16", "fp16")


def _plan_kwargs(schedule):
    if schedule == "fused":
        return {}
    if schedule == "pipelined":
        return {"n_steps": 2}
    return {"n_steps": 2, "y_chunks": 4}


def _run_plan(plan, proj):
    if plan.mesh is None:
        out = plan.build()(proj)
    else:
        out = plan.build()(jax.device_put(proj, input_sharding(plan.mesh)))
    out = np.asarray(out)
    g = plan.geometry
    return out.reshape(g.n_x, g.n_y, g.n_z)  # chunked+scatter store layout


@pytest.fixture(scope="module")
def case16():
    g = default_geometry(16, n_proj=8)
    proj = forward_project(g)
    oracle = np.array(reconstruct(g, proj, impl="factorized",
                                  precision="fp32"))
    return g, proj, oracle


def _assert_matches_oracle(out, oracle, storage, label):
    p = Precision(storage)
    scale = float(np.max(np.abs(oracle))) + 1e-12
    rmse = float(np.sqrt(np.mean((out - oracle) ** 2))) / scale
    mx = float(np.max(np.abs(out - oracle))) / scale
    assert rmse < p.rmse_tol(), f"{label}: rmse {rmse:.3e}"
    assert mx < p.max_tol(), f"{label}: max {mx:.3e}"


class TestCrossProduct:
    """Every (schedule, reduce, precision) plan point on a 1x1x1 mesh must
    match the single-device f32 oracle within the precision policy's
    tolerance — including combinations the legacy builders never offered."""

    @pytest.mark.parametrize("storage", STORAGES)
    @pytest.mark.parametrize("reduce", REDUCES)
    @pytest.mark.parametrize("schedule", SCHEDULES)
    def test_mesh_1x1x1(self, case16, schedule, reduce, storage):
        g, proj, oracle = case16
        mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
        plan = ReconstructionPlan(geometry=g, mesh=mesh, schedule=schedule,
                                  reduce=reduce, precision=storage,
                                  **_plan_kwargs(schedule))
        out = _run_plan(plan, proj)
        _assert_matches_oracle(out, oracle, storage,
                               f"{schedule}/{reduce}/{storage}")

    @pytest.mark.parametrize("schedule", SCHEDULES)
    def test_single_device_no_mesh(self, case16, schedule):
        """mesh=None runs the same staged engine without shard_map —
        pipelined/chunked single-device did not exist before the plan
        layer."""
        g, proj, oracle = case16
        plan = ReconstructionPlan(geometry=g, schedule=schedule,
                                  reduce="psum", **_plan_kwargs(schedule))
        out = _run_plan(plan, proj)
        _assert_matches_oracle(out, oracle, "fp32", f"{schedule}/no-mesh")

    def test_chunked_psum_replicated_slab(self, case16):
        """Previously-impossible combination #1: the chunked schedule with a
        replicated (psum) output — legacy make_chunked_fdk hardwired
        psum_scatter. Output is the canonical 3-D volume."""
        g, proj, oracle = case16
        mesh = single_device_mesh()  # ("data", "model"), no pod axis
        plan = ReconstructionPlan(geometry=g, mesh=mesh, schedule="chunked",
                                  n_steps=2, y_chunks=4, reduce="psum")
        out = plan.build()(jax.device_put(proj, input_sharding(mesh)))
        assert out.shape == (g.n_x, g.n_y, g.n_z)
        _assert_matches_oracle(np.asarray(out), oracle, "fp32",
                               "chunked/psum")

    def test_pipelined_single_device(self, case16):
        """Previously-impossible combination #2: the pipelined (Fig. 4
        overlap) schedule without any mesh."""
        g, proj, oracle = case16
        plan = ReconstructionPlan(geometry=g, schedule="pipelined",
                                  n_steps=4)
        out = np.asarray(plan.build()(proj))
        _assert_matches_oracle(out, oracle, "fp32", "pipelined/no-mesh")


class TestStreamCodecPlans:
    """ISSUE 5: the fp8_e4m3 projection codec and the scatter_bf16
    compensated half-width reduce as plan points of the staged engine."""

    # Documented scatter_bf16 tolerance vs the f32 psum reduce: one bf16
    # rounding per rank on the reduced slab — relative error bounded by a
    # small multiple of bf16 eps (2^-8). See DESIGN.md (codec layer).
    BF16_REDUCE_RTOL = 4 * 2.0 ** -8

    def _mesh(self):
        return make_mesh((1, 1, 1), ("pod", "data", "model"))

    @pytest.mark.parametrize("schedule", SCHEDULES)
    def test_scatter_bf16_matches_f32_psum(self, case16, schedule):
        """ISSUE 5 acceptance: scatter_bf16 matches the f32 psum reduce
        within the documented tolerance (at half the reduce wire bytes —
        priced in planner/cost.py, accounted in tests/test_planner.py)."""
        g, proj, _ = case16
        mesh = self._mesh()
        kw = _plan_kwargs(schedule)
        f32 = _run_plan(ReconstructionPlan(geometry=g, mesh=mesh,
                                           schedule=schedule, reduce="psum",
                                           **kw), proj)
        out = _run_plan(ReconstructionPlan(geometry=g, mesh=mesh,
                                           schedule=schedule,
                                           reduce="scatter_bf16", **kw),
                        proj)
        scale = float(np.max(np.abs(f32))) + 1e-12
        mx = float(np.max(np.abs(out - f32))) / scale
        assert mx < self.BF16_REDUCE_RTOL, f"{schedule}: {mx:.3e}"

    def test_chunked_error_feedback_beats_naive_requantize(self, case16):
        """The f32 error-feedback carry keeps the chunked multi-round
        reduce at least as accurate as quantizing a single fused round —
        without it, n_steps independent roundings would accumulate."""
        g, proj, oracle = case16
        mesh = self._mesh()
        chunked = _run_plan(
            ReconstructionPlan(geometry=g, mesh=mesh, schedule="chunked",
                               n_steps=2, y_chunks=4,
                               reduce="scatter_bf16"), proj)
        scale = float(np.max(np.abs(oracle))) + 1e-12
        rmse = float(np.sqrt(np.mean((chunked - oracle) ** 2))) / scale
        # 4 quantized rounds with feedback must stay within the ONE-round
        # error bound (no accumulation across the n_steps micro-batches).
        assert rmse < self.BF16_REDUCE_RTOL, f"rmse {rmse:.3e}"

    @pytest.mark.parametrize("schedule", SCHEDULES)
    def test_fp8_plan_matches_oracle(self, case16, schedule):
        g, proj, oracle = case16
        out = _run_plan(
            ReconstructionPlan(geometry=g, mesh=self._mesh(),
                               schedule=schedule, precision="fp8_e4m3",
                               **_plan_kwargs(schedule)), proj)
        _assert_matches_oracle(out, oracle, "fp8_e4m3",
                               f"{schedule}/fp8_e4m3")

    def test_fp8_with_kernel_impl(self, case16):
        """The Pallas kernel consumes the fp8 wire stream + scale sidecar
        (dequantize at the tap) and agrees with the factorized engine."""
        g, proj, oracle = case16
        fac = _run_plan(ReconstructionPlan(geometry=g, mesh=self._mesh(),
                                           precision="fp8_e4m3"), proj)
        ker = _run_plan(ReconstructionPlan(geometry=g, mesh=self._mesh(),
                                           precision="fp8_e4m3",
                                           impl="kernel"), proj)
        np.testing.assert_allclose(ker, fac, rtol=1e-5, atol=1e-6)
        _assert_matches_oracle(ker, oracle, "fp8_e4m3", "kernel/fp8")

    def test_spec_tokens(self, case16):
        g, _, _ = case16
        p = plan_from_spec(g, "precision=fp8_e4m3,reduce=scatter_bf16")
        assert p.precision == "fp8_e4m3" and p.reduce == "scatter_bf16"
        assert p.resolved_precision().storage == "fp8_e4m3"

    def test_scatter_bf16_needs_data_axis(self, case16):
        g, _, _ = case16
        with pytest.raises(ValueError, match="scatter_bf16.*'data'"):
            ReconstructionPlan(geometry=g, reduce="scatter_bf16").validate()


class TestPlanResolution:
    def test_build_is_cached_per_plan(self, case16):
        g, _, _ = case16
        a = ReconstructionPlan(geometry=g).build()
        b = ReconstructionPlan(geometry=g).build()
        assert a is b
        c = ReconstructionPlan(geometry=g, precision="bf16").build()
        assert c is not a

    def test_kernel_blocks_resolved_at_plan_time(self, case16):
        """impl='kernel' plans resolve (bi, bj, bs) once via the autotuner;
        explicit blocks are honored verbatim and the math is unchanged."""
        g, proj, oracle = case16
        tuned = ReconstructionPlan(geometry=g, impl="kernel")
        bi, bj, bs = tuned.resolved_blocks()
        assert g.n_x % bi == 0 and g.n_y % bj == 0
        pinned = ReconstructionPlan(geometry=g, impl="kernel",
                                    blocks=(4, 8, 4))
        assert pinned.resolved_blocks() == (4, 8, 4)
        out = np.asarray(pinned.build()(proj))
        _assert_matches_oracle(out, oracle, "fp32", "kernel/pinned-blocks")

    def test_non_kernel_has_no_blocks(self, case16):
        g, _, _ = case16
        assert ReconstructionPlan(geometry=g).resolved_blocks() is None

    def test_describe(self, case16):
        g, _, _ = case16
        d = ReconstructionPlan(geometry=g, schedule="pipelined", n_steps=2,
                               precision=None).describe()
        assert d["schedule"] == "pipelined"
        assert d["grid"] == (1, 1)
        assert d["precision"] in ("bf16", "fp16")  # backend default

    def test_plan_from_spec(self, case16):
        g, _, _ = case16
        p = plan_from_spec(
            g, "schedule=chunked,n_steps=2,y_chunks=4,precision=bf16,"
               "impl=factorized,reduce=psum")
        assert (p.schedule, p.n_steps, p.y_chunks) == ("chunked", 2, 4)
        assert p.precision == "bf16" and p.reduce == "psum"
        with pytest.raises(ValueError, match="unknown plan spec key"):
            plan_from_spec(g, "bogus=1")
        with pytest.raises(ValueError, match="key=value"):
            plan_from_spec(g, "pipelined")


class TestValidate:
    """Every divisibility/compatibility failure raises a clear message from
    the one centralized validate()."""

    def _plan(self, g=None, **kw):
        return ReconstructionPlan(geometry=g or default_geometry(16,
                                                                 n_proj=8),
                                  **kw)

    def test_unknown_impl(self):
        with pytest.raises(ValueError, match="unknown back-projection impl"):
            self._plan(impl="cuda").validate()

    def test_unknown_window(self):
        with pytest.raises(ValueError, match="unknown window"):
            self._plan(window="kaiser").validate()

    def test_unknown_schedule(self):
        with pytest.raises(ValueError, match="unknown schedule"):
            self._plan(schedule="eager").validate()

    def test_unknown_reduce(self):
        with pytest.raises(ValueError, match="unknown reduce mode"):
            self._plan(reduce="allreduce").validate()

    def test_unknown_precision(self):
        with pytest.raises(ValueError, match="unknown storage precision"):
            self._plan(precision="int8").validate()

    def test_fused_rejects_micro_batching(self):
        with pytest.raises(ValueError, match="fused schedule has no"):
            self._plan(n_steps=2).validate()

    def test_n_steps_must_divide(self):
        with pytest.raises(ValueError, match="n_steps=3 micro-batches"):
            self._plan(schedule="pipelined", n_steps=3).validate()

    def test_n_steps_positive(self):
        with pytest.raises(ValueError, match="must be >= 1"):
            self._plan(schedule="pipelined", n_steps=0).validate()

    def test_chunked_requires_y_chunks(self):
        with pytest.raises(ValueError, match="requires y_chunks"):
            self._plan(schedule="chunked", n_steps=2).validate()

    def test_y_chunks_must_divide(self):
        with pytest.raises(ValueError, match="y_chunks=5"):
            self._plan(schedule="chunked", n_steps=2, y_chunks=5).validate()

    def test_y_chunks_only_for_chunked(self):
        with pytest.raises(ValueError, match="only applies to the chunked"):
            self._plan(schedule="pipelined", n_steps=2,
                       y_chunks=4).validate()

    def test_scatter_needs_data_axis(self):
        with pytest.raises(ValueError, match="needs a mesh with a 'data'"):
            self._plan(reduce="scatter").validate()

    def test_blocks_only_for_kernel(self):
        with pytest.raises(ValueError, match="only applies to impl='kernel'"):
            self._plan(blocks=(4, 4, 4)).validate()

    def test_blocks_must_tile_call_shape(self):
        with pytest.raises(ValueError, match="must tile the per-call"):
            self._plan(impl="kernel", blocks=(3, 4, 4)).validate()
        with pytest.raises(ValueError, match="must be positive"):
            self._plan(impl="kernel", blocks=(0, 4, 4)).validate()

    def test_kernel_needs_even_nz(self):
        import dataclasses
        g = dataclasses.replace(default_geometry(16, n_proj=8), n_z=15)
        with pytest.raises(ValueError, match="even N_z"):
            self._plan(g=g, impl="kernel").validate()

    @pytest.mark.parametrize("platform,refused", [("tpu", True),
                                                  ("cpu", False)])
    def test_kernel_fp16_refused_on_tpu(self, monkeypatch, platform,
                                        refused):
        """Mosaic cannot load f16 vectors on v5e: the kernel with fp16
        streams is refused on TPU, not silently compiled elsewhere."""
        import types
        from repro.planner import feasibility
        monkeypatch.setattr(feasibility, "plan_device",
                            lambda mesh=None: types.SimpleNamespace(
                                platform=platform))
        plan = self._plan(impl="kernel", precision="fp16")
        if refused:
            with pytest.raises(ValueError, match="cannot read fp16"):
                plan.validate()
        else:
            plan.validate()
        self._plan(impl="kernel", precision="bf16").validate()


class TestChooseGrid:
    """Regression: the old `while n_devices % r: r *= 2` never terminated
    for non-power-of-two device counts once the memory bound forced R
    beyond the device count's largest power-of-two factor."""

    def test_non_power_of_two_raises(self):
        g = default_geometry(64)
        # 4*64^3 B volume with 256 KiB sub-volumes -> R=4; 4 does not
        # divide 6 (and no larger power of two can) -> must raise, not hang
        with pytest.raises(ValueError, match="does not divide n_devices=6"):
            choose_grid(g, 6, sub_vol_bytes=256 * 1024)

    def test_non_power_of_two_ok_when_r_divides(self):
        g = default_geometry(64)
        assert choose_grid(g, 6, sub_vol_bytes=512 * 1024) == IFDKGrid(r=2,
                                                                       c=3)

    def test_paper_grid_rule_unchanged(self):
        # paper §5.3: R=32 for 4096^3 with 8 GB sub-volumes on 16 GB GPUs
        g = default_geometry(4096, n_proj=4096)
        assert choose_grid(g, 256) == IFDKGrid(r=32, c=8)

    def test_too_few_devices_still_raises(self):
        g = default_geometry(64)
        with pytest.raises(ValueError, match="only 2 devices"):
            choose_grid(g, 2, sub_vol_bytes=256 * 1024)


# ---------------------------------------------------------------------------
# 2x2x2 mesh cross-product (subprocess: needs 8 virtual devices)
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, numpy as np
from repro.core.fdk import reconstruct
from repro.core.distributed import input_sharding
from repro.core.geometry import default_geometry
from repro.core.phantom import forward_project
from repro.core.plan import ReconstructionPlan
from repro.parallel.mesh import make_mesh

results = {}
g = default_geometry(16, n_proj=32)
proj = forward_project(g)
ref = np.array(reconstruct(g, proj, impl="factorized"))
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

def kwargs(s):
    if s == "fused": return {}
    if s == "pipelined": return {"n_steps": 2}
    return {"n_steps": 2, "y_chunks": 4}

for sched in ("fused", "pipelined", "chunked"):
    for red in ("psum", "scatter"):
        plan = ReconstructionPlan(geometry=g, mesh=mesh, schedule=sched,
                                  reduce=red, **kwargs(sched))
        out = np.asarray(plan.build()(jax.device_put(proj,
                                                     input_sharding(mesh))))
        out = out.reshape(g.n_x, g.n_y, g.n_z)
        results[f"{sched}/{red}"] = float(np.max(np.abs(out - ref)))

# chunked+psum at bf16: previously-impossible combo under the precision
# policy, against the bf16 single-device reconstruction
ref16 = np.array(reconstruct(g, proj, impl="factorized", precision="bf16"))
plan = ReconstructionPlan(geometry=g, mesh=mesh, schedule="chunked",
                          n_steps=2, y_chunks=4, reduce="psum",
                          precision="bf16")
out = np.asarray(plan.build()(jax.device_put(proj, input_sharding(mesh))))
results["chunked/psum/bf16_vs_bf16single"] = float(
    np.max(np.abs(out.reshape(g.n_x, g.n_y, g.n_z) - ref16)))

# ISSUE 5: stream codecs on a real multi-rank grid (relative errors).
refmax = float(np.max(np.abs(ref)))
for sched, red, prec in [("fused", "scatter_bf16", "fp32"),
                         ("chunked", "scatter_bf16", "fp32"),
                         ("fused", "psum", "fp8_e4m3"),
                         ("pipelined", "scatter", "fp8_e4m3")]:
    plan = ReconstructionPlan(geometry=g, mesh=mesh, schedule=sched,
                              reduce=red, precision=prec, **kwargs(sched))
    out = np.asarray(plan.build()(jax.device_put(proj,
                                                 input_sharding(mesh))))
    out = out.reshape(g.n_x, g.n_y, g.n_z)
    results[f"codec/{sched}/{red}/{prec}"] = float(
        np.max(np.abs(out - ref))) / refmax

# fp8 on the mesh vs the fp8 single-device engine: the codec quantizes
# per projection (identical bytes either way), so the only deviation is
# f32 reassociation in the distributed reduce
ref8 = np.array(ReconstructionPlan(geometry=g,
                                   precision="fp8_e4m3").build()(proj))
plan = ReconstructionPlan(geometry=g, mesh=mesh, precision="fp8_e4m3")
out = np.asarray(plan.build()(jax.device_put(proj, input_sharding(mesh))))
results["codec/fused/fp8_vs_fp8single"] = float(
    np.max(np.abs(out - ref8))) / (float(np.max(np.abs(ref8))) + 1e-12)

# validate() failures that need a real multi-rank grid
try:
    ReconstructionPlan(geometry=default_geometry(16, n_proj=30),
                       mesh=mesh).validate()
    results["err/np_ranks"] = ""
except ValueError as e:
    results["err/np_ranks"] = str(e)
try:
    ReconstructionPlan(geometry=default_geometry(17, n_proj=32),
                       mesh=mesh).validate()
    results["err/nx_slabs"] = ""
except ValueError as e:
    results["err/nx_slabs"] = str(e)

print("RESULTS" + json.dumps(results))
"""


@pytest.fixture(scope="module")
def mesh222_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True,
        text=True, timeout=1200,
    )
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-4000:]}"
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULTS")][0]
    return json.loads(line[len("RESULTS"):])


@pytest.mark.slow
def test_cross_product_on_2x2x2_mesh(mesh222_results):
    for sched in ("fused", "pipelined", "chunked"):
        for red in ("psum", "scatter"):
            err = mesh222_results[f"{sched}/{red}"]
            assert err < 5e-6, f"{sched}/{red}: {err}"


@pytest.mark.slow
def test_chunked_psum_bf16_on_mesh(mesh222_results):
    assert mesh222_results["chunked/psum/bf16_vs_bf16single"] < 5e-6


@pytest.mark.slow
def test_validate_messages_on_mesh(mesh222_results):
    assert "must divide over the 8 ranks" in mesh222_results["err/np_ranks"]
    assert "R=2 volume slabs" in mesh222_results["err/nx_slabs"]


@pytest.mark.slow
def test_scatter_bf16_on_mesh(mesh222_results):
    """Half-width reduce on a real 2-rank data axis: within the documented
    bf16 tolerance of the f32 reference (see TestStreamCodecPlans)."""
    tol = TestStreamCodecPlans.BF16_REDUCE_RTOL
    assert mesh222_results["codec/fused/scatter_bf16/fp32"] < tol
    assert mesh222_results["codec/chunked/scatter_bf16/fp32"] < tol


@pytest.mark.slow
def test_fp8_on_mesh(mesh222_results):
    """fp8 stream + sidecar through real collectives: fp8-tolerance vs the
    f32 reference, and bit-identical to the single-device fp8 engine."""
    tol = Precision("fp8_e4m3").max_tol()
    assert mesh222_results["codec/fused/psum/fp8_e4m3"] < tol
    assert mesh222_results["codec/pipelined/scatter/fp8_e4m3"] < tol
    # per-projection quantization is identical on any grid — only f32
    # reassociation in the distributed reduce separates the two engines
    assert mesh222_results["codec/fused/fp8_vs_fp8single"] < 1e-5

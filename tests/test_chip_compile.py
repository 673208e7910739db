"""Ahead-of-time compiles for a described TPU v5e — no chip needed.

The TPU compiler ships with JAX and compiles for a topology that is
described rather than attached. These tests hold the device programs of the
reconstruction path to what the chip's compiler accepts, at the clinical
scan `chip_smoke.py` runs (720 projections of 768x768 -> 512^3 f32):

  * the Pallas back-projection kernel lowers through Mosaic
    (`tpu_custom_call` in the compiled module) at every wire dtype, and
    with its u-step window at the benchmark's RabbitCT scan (496 views of
    1248x960 -> 512^3);
  * the one-chip engine and the 2x2 (data, model) mesh engines compile with
    the kernel inside and fit a v5e's 16 GiB of HBM per device;
  * the benchmark's `rabbitct1024` deployment (496 views of 1248x960 ->
    1024^3 on the 2x2 mesh), with the plan the planner picks for it, does
    too, and its row reduce moves the partial slabs at f32.

Nothing runs, so nothing here says anything about results or time.
The topology is described inside a module fixture — never at import — so
that only the test worker given this file loads the TPU library.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.distributed import input_sharding
from repro.core.geometry import CBCTGeometry, default_geometry
from repro.core.plan import ReconstructionPlan
from repro.core.precision import resolve_precision
from repro.kernels.backproject import tune
from repro.kernels.backproject.kernel import (
    backproject_dual_pallas, vmem_bytes,
)
from repro.parallel.mesh import make_mesh

HBM_PER_DEVICE = 16 * 2**30  # TPU v5e
N, N_PROJ = 512, 720         # the chip smoke's scan


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernel(monkeypatch):
    """The suite runs Pallas in the interpreter; these compiles must not."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")


def _geometry():
    return default_geometry(N, n_proj=N_PROJ)


def _bench_config(name: str) -> dict:
    path = os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                        f"{name}.json")
    with open(path) as f:
        return json.load(f)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _compile_kernel(topo, storage):
    g = _geometry()
    dtype = resolve_precision(storage).storage_dtype
    bi, bj, bs = tune.pick_blocks(g.n_x, g.n_y, g.n_z, g.n_proj, g.n_u,
                                  g.n_v, qt_dtype=dtype)
    one_chip = SingleDeviceSharding(topo.devices[0])
    pm = jax.ShapeDtypeStruct((g.n_proj, 13), jnp.float32, sharding=one_chip)
    qt = jax.ShapeDtypeStruct((g.n_proj, g.n_u, g.n_v), dtype,
                              sharding=one_chip)

    def bp(pm, qt):
        return backproject_dual_pallas(
            pm, qt, g.n_x, g.n_y, g.n_z, bi=bi, bj=bj, bs=bs,
            interpret=False, vmem_limit=tune.DEFAULT_VMEM_BUDGET)

    return jax.jit(bp).lower(pm, qt).compile()


@pytest.mark.parametrize("storage", ["fp32", "bf16", "fp8_e4m3", "fp8_e5m2"])
def test_kernel_lowers_through_mosaic(topo, storage):
    compiled = _compile_kernel(topo, storage)
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) <= HBM_PER_DEVICE


@pytest.mark.parametrize("storage", ["fp32", "bf16", "fp8_e4m3"])
def test_windowed_kernel_lowers_at_rabbitct(topo, storage):
    """The plan's tile and u-window for the benchmark's RabbitCT scan, one
    call over all 496 views: K is below N_u, the working set fits the
    VMEM budget, and Mosaic compiles the kernel within that limit."""
    g = CBCTGeometry(**_bench_config("rabbitct512")["geometry"])
    plan = ReconstructionPlan(geometry=g, impl="kernel", precision=storage)
    bi, bj, bs = plan.resolved_blocks()
    kw = plan.resolved_u_window()
    assert kw < g.n_u
    dtype = resolve_precision(storage).storage_dtype
    budget = tune.DEFAULT_VMEM_BUDGET
    assert vmem_bytes(bi, bj, bs, g.n_u, g.n_v, g.n_z // 2, dtype,
                      kw=kw) <= budget
    one_chip = SingleDeviceSharding(topo.devices[0])
    pm = jax.ShapeDtypeStruct((g.n_proj, 13), jnp.float32, sharding=one_chip)
    qt = jax.ShapeDtypeStruct((g.n_proj, g.n_u, g.n_v), dtype,
                              sharding=one_chip)

    def bp(pm, qt):
        return backproject_dual_pallas(
            pm, qt, g.n_x, g.n_y, g.n_z, bi=bi, bj=bj, bs=bs,
            interpret=False, vmem_limit=budget, u_window=kw)

    compiled = jax.jit(bp).lower(pm, qt).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) <= HBM_PER_DEVICE


def test_kernel_refuses_fp16_on_v5e(topo):
    """Mosaic has no f16 vector load on v5e, so ReconstructionPlan.validate
    refuses impl="kernel" with fp16 streams on TPU. Should this start to
    compile, lift that refusal (core/plan.py _KERNEL_REFUSED_ON_TPU)."""
    with pytest.raises(Exception, match="Invalid vector type for load"):
        _compile_kernel(topo, "fp16")


def test_one_chip_engine_fits(topo, compiled_kernel):
    g = _geometry()
    engine = ReconstructionPlan(geometry=g, impl="kernel").build()
    proj = jax.ShapeDtypeStruct(
        g.proj_shape(), jnp.float32,
        sharding=SingleDeviceSharding(topo.devices[0]))
    compiled = engine.__wrapped__.lower(proj).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _device_bytes(compiled) <= HBM_PER_DEVICE
    # The kernel keeps its instruction name (the device trace finds it by
    # it) under the back-projection stage's scope.
    (scope,) = re.findall(
        r'%backproject_dual[.\d]* = [^\n]*op_name="([^"]*)"', text)
    assert "fdk.backproject" in scope.split("/")
    assert re.search(r'op_name="[^"]*/fdk\.filter/', text)


@pytest.mark.parametrize("reduce", ["psum", "scatter"])
def test_mesh_engine_fits(topo, compiled_kernel, reduce):
    g = _geometry()
    mesh = make_mesh((2, 2), ("data", "model"),
                     devices=np.asarray(topo.devices))
    engine = ReconstructionPlan(geometry=g, mesh=mesh, impl="kernel",
                                reduce=reduce).build()
    proj = jax.ShapeDtypeStruct(g.proj_shape(), jnp.float32,
                                sharding=input_sharding(mesh))
    compiled = engine.__wrapped__.lower(proj).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("reduce-scatter" in text) == (reduce == "scatter")
    assert _device_bytes(compiled) <= HBM_PER_DEVICE


def test_planner_pick_fits_with_its_service_bucket(topo, compiled_kernel):
    """What the planner picks for this scan on a v5e (both deployment impls
    admitted, as on a TPU backend) compiles, and so does the batched engine
    of the bucket size the service derives from the planner's footprint."""
    from repro.core.perf_model import TPU_V5E
    from repro.planner import plan_footprint, point_from_plan, search_plans

    g = _geometry()
    hbm = int(15.75 * 2**30)  # v5e HBM as its compiler counts it
    best = search_plans(g, system=TPU_V5E, hbm_bytes=hbm,
                        impls=("factorized", "kernel"), top_k=1)[0].plan
    bucket = 1
    while 2 * bucket * plan_footprint(g, point_from_plan(best)).total <= hbm:
        bucket *= 2
    one_chip = SingleDeviceSharding(topo.devices[0])
    engine = best.build_batched(bucket)
    proj = jax.ShapeDtypeStruct((bucket,) + g.proj_shape(), jnp.float32,
                                sharding=one_chip)
    compiled = engine.__wrapped__.lower(proj).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (best.impl == "kernel")
    assert _device_bytes(compiled) <= hbm


def test_rabbitct1024_mesh_engine_fits_with_an_f32_reduce(topo,
                                                          compiled_kernel):
    """The planner's pick for `rabbitct1024` under its pins (fp32, kernel,
    the reduce left to the planner) on a v5e:2x2: the kernel lowers, each
    device's program fits HBM, the column AllGather and the row reduce sit
    under their stage scopes, and the reduce stays f32 end to end: no bf16
    value anywhere under `fdk.reduce`."""
    from repro.core.perf_model import TPU_V5E
    from repro.planner import auto_plan

    config = _bench_config("rabbitct1024")
    g = CBCTGeometry(**config["geometry"])
    mesh = make_mesh(tuple(config["mesh"].values()), tuple(config["mesh"]),
                     devices=np.asarray(topo.devices))
    plan = auto_plan(g, mesh=mesh, system=TPU_V5E,
                     hbm_bytes=int(15.75 * 2**30), calibration=None,
                     **config["plan"])
    assert plan.reduce in ("psum", "scatter")
    assert plan.describe()["slab_shape"] == (g.n_x // 2, g.n_y, g.n_z)
    proj = jax.ShapeDtypeStruct(g.proj_shape(), jnp.float32,
                                sharding=input_sharding(mesh))
    compiled = plan.build().__wrapped__.lower(proj).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%backproject_dual[.\d]* = ", text)
    assert _device_bytes(compiled) <= HBM_PER_DEVICE
    lines = [line for line in text.splitlines() if " = " in line]
    gathers = [line for line in lines if " all-gather(" in line]
    reduces = [line for line in lines
               if re.search(r" (reduce-scatter|all-reduce)\(", line)]
    assert gathers and all("/fdk.gather/" in line for line in gathers)
    assert reduces and all("/fdk.reduce/" in line for line in reduces)
    assert all(line.split(" = ", 1)[1].startswith("f32[")
               for line in reduces)
    assert not [line for line in lines
                if "/fdk.reduce/" in line and "bf16[" in line]

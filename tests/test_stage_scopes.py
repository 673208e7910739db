"""The engine's stage scopes (core/plan.py STAGE_SCOPES) in the compiled
module: every schedule's device operations name their stage in `op_name`,
on one device and on the 2x2 (data, model) mesh (subprocess: four virtual
CPU devices), and the scopes leave the compiled code as it was."""
import json
import os
import re
import subprocess
import sys
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import pytest

from repro.core.geometry import default_geometry
from repro.core.plan import (
    STAGE_SCOPES, ReconstructionPlan, clear_engine_cache,
)

KWARGS = {"fused": {}, "pipelined": {"n_steps": 2},
          "chunked": {"n_steps": 2, "y_chunks": 4}}


@pytest.fixture(autouse=True)
def no_compile_cache():
    """Compile afresh: the persistent cache's key leaves out metadata, so a
    cached executable's text keeps the op_names of whoever compiled it."""
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def scope_counts(text: str) -> dict:
    """{stage scope: number of instructions under it} of a module's text."""
    names = re.findall(r'op_name="([^"]*)"', text)
    return {scope: sum(scope in name.split("/") for name in names)
            for scope in STAGE_SCOPES}


def compiled_text(schedule: str) -> str:
    g = default_geometry(16, n_proj=8)
    clear_engine_cache()
    engine = ReconstructionPlan(geometry=g, schedule=schedule,
                                impl="reference", precision="bf16",
                                **KWARGS[schedule]).build()
    spec = jax.ShapeDtypeStruct(g.proj_shape(), jnp.float32)
    return engine.__wrapped__.lower(spec).compile().as_text()


def instructions(text: str) -> list:
    """The module's instructions without their metadata."""
    return [re.sub(r",? metadata=\{[^}]*\}", "", line)
            for line in text.splitlines() if " = " in line]


@pytest.mark.parametrize("schedule", sorted(KWARGS))
def test_scopes_on_one_device(schedule):
    counts = scope_counts(compiled_text(schedule))
    # no collective on one device: the gather and the reduce hold nothing
    for scope in ("fdk.filter", "fdk.encode", "fdk.backproject"):
        assert counts[scope] > 0, (scope, counts)


@pytest.mark.parametrize("schedule", sorted(KWARGS))
def test_scopes_change_no_instruction(schedule, monkeypatch):
    scoped = compiled_text(schedule)
    monkeypatch.setattr(jax, "named_scope", lambda name: nullcontext())
    plain = compiled_text(schedule)
    assert scope_counts(plain) == dict.fromkeys(STAGE_SCOPES, 0)
    assert instructions(scoped) == instructions(plain)
    clear_engine_cache()


MESH_SCRIPT = r"""
import json, os, sys
import jax, jax.numpy as jnp, numpy as np
from repro import obs
from repro.core.distributed import input_sharding
from repro.core.geometry import default_geometry
from repro.core.phantom import forward_project
from repro.core.plan import ReconstructionPlan
from repro.io import ProjectionSource
from repro.parallel.mesh import make_mesh
sys.path.insert(0, sys.argv[2])
from test_stage_scopes import KWARGS, scope_counts

tmp = sys.argv[1]
g = default_geometry(16, n_proj=8)
mesh = make_mesh((2, 2), ("data", "model"))
spec = jax.ShapeDtypeStruct(g.proj_shape(), jnp.float32,
                            sharding=input_sharding(mesh))
out = {}
for schedule, kwargs in KWARGS.items():
    plan = ReconstructionPlan(geometry=g, mesh=mesh, schedule=schedule,
                              impl="reference", precision="bf16", **kwargs)
    text = plan.build().__wrapped__.lower(spec).compile().as_text()
    out[schedule] = scope_counts(text)

proj = np.asarray(forward_project(g))
src = ProjectionSource.write(os.path.join(tmp, "proj"), proj,
                             chunks=(4, 1, 1))
tracer = obs.Tracer(enabled=True)
obs.set_tracer(tracer)
loaded = src.load(mesh)
out["load_exact"] = bool(np.array_equal(np.asarray(loaded), proj))
out["load_sharding"] = loaded.sharding == input_sharding(mesh)
out["spans"] = sorted(e["name"] for e in tracer.spans("stage."))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_results(tmp_path_factory):
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(here, "..", "src"))
    proc = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT,
         str(tmp_path_factory.mktemp("mesh")), here],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("schedule", sorted(KWARGS))
def test_scopes_on_the_2x2_mesh(mesh_results, schedule):
    counts = mesh_results[schedule]
    assert all(counts[scope] > 0 for scope in STAGE_SCOPES), counts


def test_mesh_read_is_exact_and_split(mesh_results):
    assert mesh_results["load_exact"] and mesh_results["load_sharding"]
    assert mesh_results["spans"] == ["stage.read.copy", "stage.read.h2d"]

"""What one rank's collectives move per scan, as the plan reports it:
`gather_bytes` (the column AllGather), `reduce_bytes` (the row reduce) and
`slab_shape` (the rank's partial slab), in `describe()` and on the
`engine.reconstruct` span. Zero bytes on one device; on the 2x2 (data,
model) mesh (subprocess: four virtual CPU devices) the wire-width bytes of
each reduce mode and storage precision."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.geometry import default_geometry
from repro.core.plan import ReconstructionPlan, clear_engine_cache

N, N_PROJ = 16, 8
R = C = 2
CASES = [("psum", "fp32", "fused"), ("scatter", "fp32", "pipelined"),
         ("scatter_bf16", "fp32", "fused"), ("scatter", "bf16", "fused")]


def expected(reduce: str, precision: str) -> dict:
    """The counters from the geometry alone: each rank receives the other
    R - 1 ranks' N_p / (R C) filtered views of its column at the storage
    width; a ring psum moves 2 (C - 1) / C of the f32 slab, a
    psum_scatter (C - 1) / C of it at the reduce's wire width."""
    g = default_geometry(N, n_proj=N_PROJ)
    item = {"fp32": 4, "bf16": 2}[precision]
    gather = (R - 1) * (N_PROJ // (R * C)) * g.n_v * g.n_u * item
    slab = (N // R) * N * N * 4
    reduce_bytes = {"psum": 2 * slab * (C - 1) // C,
                    "scatter": slab * (C - 1) // C,
                    "scatter_bf16": slab // 2 * (C - 1) // C}[reduce]
    return {"gather_bytes": gather, "reduce_bytes": reduce_bytes,
            "slab_shape": [N // R, N, N]}


def _span_args(plan, proj) -> dict:
    clear_engine_cache()
    tracer = obs.Tracer(enabled=True)
    previous = obs.set_tracer(tracer)
    try:
        jax.block_until_ready(plan.build()(proj))
    finally:
        obs.set_tracer(previous)
    (span,) = tracer.spans("engine.reconstruct")
    return span["args"]


def test_one_device_moves_nothing():
    g = default_geometry(N, n_proj=N_PROJ)
    plan = ReconstructionPlan(geometry=g, impl="reference")
    d = plan.describe()
    assert (d["gather_bytes"], d["reduce_bytes"]) == (0, 0)
    assert d["slab_shape"] == (N, N, N)
    args = _span_args(plan, np.zeros(g.proj_shape(), np.float32))
    assert (args["gather_bytes"], args["reduce_bytes"]) == (0, 0)
    assert args["slab_shape"] == [N, N, N]


MESH_SCRIPT = r"""
import json, sys
import jax
from repro.core.distributed import input_sharding
from repro.core.geometry import default_geometry
from repro.core.phantom import forward_project
from repro.core.plan import ReconstructionPlan
from repro.parallel.mesh import make_mesh
sys.path.insert(0, sys.argv[1])
from test_exchange_counters import CASES, N, N_PROJ, _span_args

g = default_geometry(N, n_proj=N_PROJ)
mesh = make_mesh((2, 2), ("data", "model"))
proj = jax.device_put(forward_project(g), input_sharding(mesh))
out = {}
for reduce, precision, schedule in CASES:
    plan = ReconstructionPlan(
        geometry=g, mesh=mesh, impl="reference", reduce=reduce,
        precision=precision, schedule=schedule,
        n_steps=2 if schedule == "pipelined" else 1)
    d = plan.describe()
    out[f"{reduce}/{precision}"] = {
        "describe": {k: d[k] for k in ("gather_bytes", "reduce_bytes")}
        | {"slab_shape": list(d["slab_shape"])},
        "span": {k: v for k, v in _span_args(plan, proj).items()
                 if k in ("gather_bytes", "reduce_bytes", "slab_shape")},
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_counters():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(here, "..", "src"))
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT, here],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("reduce, precision, schedule", CASES)
def test_counters_on_the_2x2_mesh(mesh_counters, reduce, precision,
                                  schedule):
    got = mesh_counters[f"{reduce}/{precision}"]
    want = expected(reduce, precision)
    assert got["describe"] == want
    assert got["span"] == want

"""`correct` comes out false when the timed path is broken underneath: the
program's own bf16 storage in place of the configuration's fp32 (the
control), and each fault a reconstruction cell can have. The harness runs
as in a rehearsal on the CPU, at a tiny size, held to rabbitct512's limits."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import pytest

import benchtiny
from bench import harness
from repro.core import plan as core_plan
from repro.io.streams import ProjectionSource, VolumeSink

SEED = 2**31 + 4242


@pytest.fixture
def root(tmp_path, monkeypatch):
    benchtiny.hermetic(monkeypatch)
    return benchtiny.make_root(tmp_path)


def run(root, **kw):
    return harness.run_cell(root, "tiny.full", SEED, 0.0, False,
                            rehearsal=True, **kw)


def test_sound_run_is_correct(root):
    assert run(root)["correct"] is True


def test_control_bf16_storage_is_not_correct(root):
    result = run(root, precision="bf16")
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_half_the_views_left_out_is_not_correct(root, monkeypatch):
    load = ProjectionSource.load

    def half(self, mesh=None):
        proj = load(self, mesh)
        return proj.at[1::2].set(0.0) * 2.0    # the mean over the rest

    monkeypatch.setattr(ProjectionSource, "load", half)
    assert run(root)["correct"] is False


def test_volume_altered_where_produced_is_not_correct(root, monkeypatch):
    write = VolumeSink.write

    def altered(self, volume, layout=None):
        return write(self, volume.at[:2].multiply(1.01), layout)

    monkeypatch.setattr(VolumeSink, "write", altered)
    assert run(root)["correct"] is False


def test_window_that_stores_nothing_is_not_correct(root, monkeypatch):
    monkeypatch.setattr(VolumeSink, "write",
                        lambda self, volume, layout=None: self.path)
    result = run(root)
    assert result["correct"] is False


MESH_SCRIPT = textwrap.dedent("""
    import dataclasses, json, sys
    from bench import harness
    from repro.core import plan as core_plan

    root = sys.argv[1]
    out = {"sound": harness.run_cell(root, "tiny4.full", %d, 0.0, False,
                                     rehearsal=True)}
    make = core_plan.ReconstructionPlan._make_stages

    def no_reduce(self):
        return dataclasses.replace(make(self), reduce_slab=lambda s: s)

    core_plan.ReconstructionPlan._make_stages = no_reduce
    core_plan.clear_engine_cache()
    out["fault"] = harness.run_cell(root, "tiny4.full", %d, 0.0, False,
                                    rehearsal=True)
    print(json.dumps(out))
""") % (SEED, SEED)


def test_exchange_between_chips_left_out_is_not_correct(tmp_path):
    """On four virtual CPU devices as the 2x2 R x C mesh: the sound run is
    correct; with the row reduce left out each rank keeps its partial
    slab, and it is not."""
    root = benchtiny.make_root(tmp_path, [benchtiny.tiny_config(
        "tiny4", chips=4, mesh={"data": 2, "model": 2})])
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PALLAS_INTERPRET="1",
               REPRO_TUNE_CACHE="off", REPRO_PLAN_CACHE="off",
               REPRO_CALIB_CACHE="off",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [benchtiny.ROOT, os.path.join(benchtiny.ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT, root],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["device"]["count"] == 4
    assert out["sound"]["correct"] is True, out["sound"]["checks"]
    assert out["fault"]["correct"] is False, out["fault"]["checks"]

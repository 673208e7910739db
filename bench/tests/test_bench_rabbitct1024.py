"""The `rabbitct1024` configuration on the CPU: its R x C engine through
the harness at a tiny size on four virtual devices as the 2x2 (data, model)
mesh, and the two readers it adds, on a synthetic trace.

The rehearsal keeps everything of the configuration but its geometry: the
fp32 and kernel pins, the mesh, the limits, and the planner's own row
reduce. It is `correct`, and the planner picked a reduce that moves the
partial slabs at f32. With the reduce pinned to the half-width
`scatter_bf16`, the same run is not `correct`: the limits catch a lossy
reduce."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import benchtiny
from bench import harness

SEED = 2**31 + 1024
CONFIG = os.path.join(benchtiny.ROOT, "bench", "configs", "rabbitct1024.json")


def tiny_rabbitct1024(name: str, **plan) -> dict:
    with open(CONFIG) as f:
        config = json.load(f)
    config.update(name=name, geometry=dict(benchtiny.TINY_GEOMETRY),
                  sample_voxels=1024)
    config["plan"] = dict(config["plan"], **plan)
    return config


MESH_SCRIPT = textwrap.dedent("""
    import json, sys
    from bench import harness

    root, seed = sys.argv[1], int(sys.argv[2])
    out = {cell: harness.run_cell(root, cell, seed, 0.0, False,
                                  rehearsal=True)
           for cell in ("tiny1024.full", "tiny1024bf16.full")}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_runs(tmp_path_factory):
    """Both rehearsals in one process on four virtual CPU devices: the
    result lines, and the plan each run's set-up resolved."""
    root = benchtiny.make_root(tmp_path_factory.mktemp("rabbitct1024"), [
        tiny_rabbitct1024("tiny1024"),
        tiny_rabbitct1024("tiny1024bf16", reduce="scatter_bf16")])
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PALLAS_INTERPRET="1",
               REPRO_TUNE_CACHE="off", REPRO_PLAN_CACHE="off",
               REPRO_CALIB_CACHE="off",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [benchtiny.ROOT, os.path.join(benchtiny.ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT, root,
                           str(SEED)], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    plans = [json.loads(line.split("set-up: plan ", 1)[1].rsplit(
        " resolved in ", 1)[0]) for line in lines if "set-up: plan " in line]
    return json.loads(lines[-1]), plans


def test_the_planners_reduce_is_correct_on_the_mesh(mesh_runs):
    results, plans = mesh_runs
    sound = results["tiny1024.full"]
    assert sound["device"]["count"] == 4
    assert sound["correct"] is True, sound["checks"]
    assert plans[0]["grid"] == [2, 2]
    assert plans[0]["precision"] == "fp32" and plans[0]["impl"] == "kernel"
    assert plans[0]["reduce"] in ("psum", "scatter")


def test_a_bf16_row_reduce_is_not_correct(mesh_runs):
    results, plans = mesh_runs
    assert plans[1]["reduce"] == "scatter_bf16"
    lossy = results["tiny1024bf16.full"]
    assert lossy["correct"] is False, lossy["checks"]
    assert all(c["value"] > c["limit"] for c in lossy["checks"].values())


# -- the readers ---------------------------------------------------------------

DEVICES = [f"/device:TPU:{k}" for k in range(4)]
GATHER = "jit(reconstruct_fn)/shard_map/fdk.gather/all_gather"
REDUCE = "jit(reconstruct_fn)/shard_map/fdk.reduce/reduce_scatter"
FILTER = "jit(reconstruct_fn)/shard_map/fdk.filter/jit(fft)/fft"


def _trace() -> dict:
    """Two scans on four devices. Per device and scan: a 30 ms all-gather
    and a 10 ms copy under `fdk.gather`, a 50 ms reduce-scatter under
    `fdk.reduce` (70 ms on device 3), and a filter op that is neither.
    One more gather op lies outside the window."""
    ms = 1_000_000
    ops = {}
    for k, dev in enumerate(DEVICES):
        rows = []
        for s in (0, 1000 * ms):
            rows += [["all-gather.70", "all-gather", GATHER, s, 30 * ms],
                     ["copy.131", "copy", GATHER, s + 30 * ms, 10 * ms],
                     ["jit_fft_.3", "fusion", FILTER, s + 40 * ms, 100 * ms],
                     ["reduce_scatter.6", "reduce-scatter", REDUCE,
                      s + 500 * ms, (70 if k == 3 else 50) * ms]]
        rows.append(["all-gather.70", "all-gather", GATHER, 5000 * ms, ms])
        ops[dev] = rows
    return {"ops": ops, "scans": [[0, 900 * ms], [1000 * ms, 1900 * ms]]}


def _run(trace) -> harness.Run:
    with open(CONFIG) as f:
        geometry = json.load(f)["geometry"]
    return harness.Run(
        geometry=geometry, storage_bytes=4, n_chips=4, setup_s=1.0,
        scan_walls=[0.9, 0.9], window_s=1.9, spans={}, trace=trace,
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9})


@pytest.mark.parametrize("metric, seconds", [
    ("gather_device_s", 0.040),
    ("reduce_device_s", (3 * 0.050 + 0.070) / 4),
])
def test_reader_sums_its_stage_per_scan_over_the_chips(metric, seconds):
    read = harness.load_reader(os.path.join(benchtiny.ROOT, "bench"), metric)
    assert read(_run(_trace())) == pytest.approx(seconds)
    assert read(_run(None)) is None


@pytest.mark.parametrize("metric", ["gather_device_s", "reduce_device_s"])
def test_reader_reads_nothing_where_its_stage_ran_nothing(metric):
    """One chip: the stage scopes hold no device operation."""
    trace = _trace()
    scope = GATHER if metric == "gather_device_s" else REDUCE
    trace["ops"] = {dev: [r for r in rows if r[2] != scope]
                    for dev, rows in trace["ops"].items()}
    read = harness.load_reader(os.path.join(benchtiny.ROOT, "bench"), metric)
    assert read(_run(trace)) is None


def test_new_metrics_apply_to_the_four_chip_cell_only():
    bench = benchtiny.read_benchmark(benchtiny.ROOT)
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in ("gather_device_s", "reduce_device_s")}
    assert set(new) == {"gather_device_s", "reduce_device_s"}
    for metric in new.values():
        assert metric["workloads"] == ["rabbitct1024.full"]
        assert metric["moves"] == "scan_s"
    big = harness.load_cell(benchtiny.ROOT, "rabbitct1024.full")
    small = harness.load_cell(benchtiny.ROOT, "rabbitct512.full")
    assert big.chips == 4 and big.config["mesh"] == {"data": 2, "model": 2}
    assert ({m["name"] for m in big.per_layer}
            == {m["name"] for m in small.per_layer} | set(new))
    assert not set(new) & {m["name"] for m in small.per_layer}

"""The harness on the CPU: the result line's schema, the exit codes of
`bench/run.py`, and cells, mixes and metrics found by name from files."""
import filecmp
import json
import os
import subprocess
import sys

import pytest

import benchtiny
from bench import harness

SEED = 2**31 + 977


def test_result_line_schema(tmp_path, monkeypatch):
    benchtiny.hermetic(monkeypatch)
    root = benchtiny.make_root(tmp_path)
    result = json.loads(json.dumps(harness.run_cell(
        root, "tiny.full", SEED, 0.0, False, rehearsal=True)))
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True
    assert result["attempted"] == 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"scan_s", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


def test_traced_rehearsal_reports_no_device_number(tmp_path, monkeypatch):
    benchtiny.hermetic(monkeypatch)
    root = benchtiny.make_root(tmp_path)
    result = harness.run_cell(root, "tiny.full", SEED, 0.0, True,
                              rehearsal=True)
    assert set(result["metrics"]) == {"read_s", "write_s"}
    assert "busy_s" not in result["device"]
    assert "breakdown" not in result


def _run(root, *args, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "tiny.full", "--seed", str(SEED), "--seconds", "0",
         "--trace", "0", *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_prints_the_result_last(tmp_path):
    root = benchtiny.make_root(tmp_path)
    proc = _run(root, "--cpu-rehearsal")
    assert proc.returncode == 0, proc.stderr
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["device"]["platform"] == "cpu"
    assert proc.stderr.strip().splitlines()[-1] == "correct: True"
    assert os.path.isdir(os.path.join(root, harness.STATE_DIR, "jax"))


def test_run_py_refuses_without_a_chip(tmp_path):
    root = benchtiny.make_root(tmp_path)
    proc = _run(root)
    assert proc.returncode == 2
    assert "no accelerator" in proc.stderr
    assert "{" not in proc.stdout


def test_run_py_refuses_the_interpreter_on_a_chip(tmp_path, monkeypatch):
    """With the chip found (here: the CPU device, passed off as one), a run
    that would put the kernel in the Pallas interpreter is refused."""
    import jax

    benchtiny.hermetic(monkeypatch)
    root = benchtiny.make_root(tmp_path)
    monkeypatch.setattr(harness, "_chips",
                        lambda n, rehearsal: jax.devices()[:n])
    with pytest.raises(harness.NoChip, match="interpreter"):
        harness.run_cell(root, "tiny.full", SEED, 0.0, False)


def test_a_metric_that_reads_nothing_on_the_chip_fails_the_run(tmp_path):
    root = benchtiny.make_root(tmp_path)
    cell = harness.load_cell(root, "tiny.full")
    run = harness.Run(
        geometry=cell.config["geometry"], storage_bytes=4, n_chips=1,
        setup_s=1.0, scan_walls=[1.0], window_s=1.0,
        spans={"stage.read": [0.1], "stage.write": [0.1]},
        trace={"ops": {"/device:TPU:0": [["copy.1", "copy", "", 0, 10]]},
               "scans": [[0, 100]]},
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9})
    with pytest.raises(harness.MissingMetric, match="read nothing"):
        harness._metrics(cell, run, trace=True, rehearsal=False)
    rehearsed = harness._metrics(cell, run, trace=True, rehearsal=True)
    assert "bp_device_s" not in rehearsed and "read_s" in rehearsed


def test_run_py_refuses_without_the_program(tmp_path):
    root = benchtiny.make_root(tmp_path)
    os.unlink(os.path.join(root, "src"))
    proc = _run(root, "--cpu-rehearsal")
    assert proc.returncode == 2
    assert proc.stdout == ""


def test_new_config_mix_and_metric_are_only_files(tmp_path, monkeypatch):
    """A configuration, a traffic mix and a per-layer metric added as
    files, and entries in BENCHMARK.json: the harness runs them, and no
    file the benchmark had is edited."""
    benchtiny.hermetic(monkeypatch)
    root = benchtiny.make_root(tmp_path)
    bench_dir = os.path.join(root, "bench")
    config = benchtiny.tiny_config("small")
    config["geometry"].update(n_proj=16, n_u=32, n_v=32, d_u=0.15, d_v=0.15)
    with open(os.path.join(bench_dir, "configs", "small.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic", "again.json"), "w") as f:
        json.dump({"name": "again", "loop": "closed", "clients": 2,
                   "store": "dropped", "why": "test"}, f)
    with open(os.path.join(bench_dir, "metrics", "scans_seen.py"), "w") as f:
        f.write("def read(run):\n    return float(run.n_scans)\n")
    bench = benchtiny.read_benchmark(root)
    bench["configs"].append({"name": "small", "source": "test",
                             "file": "bench/configs/small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "small.again", "config": "small",
                               "traffic": "again", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "scans_seen", "unit": "scans", "better": "higher",
        "source": "program_counter", "layer": "entry", "moves": "scan_s",
        "workloads": ["small.again"]})
    benchtiny.write_benchmark(root, bench)

    result = harness.run_cell(root, "small.again", SEED, 0.0, True,
                              rehearsal=True)
    assert result["correct"] is True
    assert result["attempted"] == 2        # one scan by each of two clients
    assert result["metrics"]["scans_seen"] == {"value": 2.0,
                                               "unit": "scans"}
    old = harness.run_cell(root, "tiny.full", SEED, 0.0, True,
                           rehearsal=True)
    assert "scans_seen" not in old["metrics"]

    repo_bench = os.path.join(benchtiny.ROOT, "bench")
    for sub in ("", "configs", "traffic", "metrics", "reference"):
        names = [n for n in os.listdir(os.path.join(repo_bench, sub))
                 if n.endswith((".py", ".json"))]
        _, mismatch, errors = filecmp.cmpfiles(
            os.path.join(repo_bench, sub), os.path.join(bench_dir, sub),
            names, shallow=False)
        assert mismatch == [] and errors == [], (sub, mismatch, errors)


@pytest.mark.parametrize("traffic, clients, drop", [
    ({"loop": "closed", "clients": 1, "store": "page_cache"}, 1, False),
    ({"loop": "closed", "clients": 3, "store": "dropped"}, 3, True),
])
def test_closed_loop_reads_the_mix(traffic, clients, drop):
    assert harness.closed_loop(traffic) == (clients, drop)


@pytest.mark.parametrize("traffic, key", [
    ({"loop": "closed", "clients": 0, "store": "page_cache"}, "clients"),
    ({"loop": "closed", "clients": 1.5, "store": "page_cache"}, "clients"),
    ({"loop": "closed", "clients": 1, "store": "nvme"}, "store"),
])
def test_closed_loop_refuses_what_it_cannot_make(traffic, key):
    with pytest.raises(ValueError, match=key):
        harness.closed_loop(traffic)


def test_dropped_store_keeps_its_bytes(tmp_path):
    path = tmp_path / "store"
    path.mkdir()
    (path / "a.bin").write_bytes(b"x" * 8192)
    harness.sync_store(str(path))
    harness.drop_page_cache(str(path))
    assert (path / "a.bin").read_bytes() == b"x" * 8192


def test_unsupported_traffic_is_refused(tmp_path, monkeypatch):
    benchtiny.hermetic(monkeypatch)
    root = benchtiny.make_root(tmp_path)
    with open(os.path.join(root, "bench", "traffic", "full.json"), "w") as f:
        json.dump({"name": "full", "loop": "open", "clients": 1,
                   "store": "page_cache"}, f)
    with pytest.raises(ValueError, match="loop"):
        harness.run_cell(root, "tiny.full", SEED, 0.0, False, rehearsal=True)


def test_engine_scopes_name_the_fft(monkeypatch):
    """The compiled engine's instructions map to the JAX operations that
    made them; the filter's FFT is among them."""
    import jax

    from repro.core.geometry import CBCTGeometry
    from repro.core.plan import ReconstructionPlan

    benchtiny.hermetic(monkeypatch)
    g = CBCTGeometry(**benchtiny.TINY_GEOMETRY)
    plan = ReconstructionPlan(geometry=g, impl="factorized")
    scopes = harness._engine_scopes(plan, g, None, jax.devices()[0])
    assert any("jit(fft)" in s for s in scopes.values())

"""A tiny copy of the benchmark for tests on the CPU.

`make_root(tmp)` lays out a checkout under `tmp`: the benchmark's files as
they are in the repository, a link to the program, and a BENCHMARK.json
whose one cell, `tiny.full`, runs a 16^3 scan from 24 views of 24^2 under
the `full` traffic mix, held to the limits of `rabbitct512`.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_GEOMETRY = {
    "n_proj": 24, "n_u": 24, "n_v": 24, "d_u": 0.2, "d_v": 0.2,
    "d": 4.0, "dsd": 8.0, "n_x": 16, "n_y": 16, "n_z": 16,
    "d_x": 0.125, "d_y": 0.125, "d_z": 0.125,
}


def tiny_config(name: str = "tiny", chips: int = 1, mesh=None) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", "rabbitct512.json")) as f:
        config = json.load(f)
    config.update(name=name, geometry=dict(TINY_GEOMETRY), chips=chips,
                  mesh=mesh, sample_voxels=1024)
    return config


def make_root(tmp, configs=None) -> str:
    """A checkout under `tmp` whose BENCHMARK.json holds a cell
    `<name>.full` for each configuration in `configs` (default: one
    `tiny_config()`)."""
    root = str(tmp)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"], bench["workloads"] = [], []
    for config in configs or [tiny_config()]:
        name = config["name"]
        path = f"bench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(config, f)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": path, "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.full", "config": name,
                                   "traffic": "full",
                                   "chips": config["chips"], "why": "test"})
    for metric in bench["per_layer"] + bench["end_to_end"]:
        metric.pop("workloads", None)
    write_benchmark(root, bench)
    return root


def read_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def write_benchmark(root: str, bench: dict) -> None:
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


def hermetic(monkeypatch) -> None:
    """The program's file caches off and the Pallas interpreter on, as a
    CPU rehearsal needs them."""
    for var in ("REPRO_TUNE_CACHE", "REPRO_PLAN_CACHE", "REPRO_CALIB_CACHE"):
        monkeypatch.setenv(var, "off")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")

"""The benchmark harness: one run of one cell.

A cell (an entry of `workloads` in BENCHMARK.json) names a configuration,
whose file holds the scan geometry, the storage precision and the chips,
and a traffic mix, a file `traffic/<name>.json` under the benchmark's
directory. Every metric is read by a file of its own,
`metrics/<name>.py`, whose `read(run)` returns a number or None. Adding a
configuration, a mix or a metric is adding files: nothing here names one.

A run:
  set-up   projections of a phantom drawn from the seed, made on the
           device; written to a ProjectionSource store; the plan resolved
           by the program's planner under the configuration's pins;
           the engine built with the store and a VolumeSink; one warm-up
           scan. Its time is `setup_s`.
  window   the traffic's closed-loop clients, each running scans back to
           back: read from the store, filter, back-project, reduce, write
           to the client's own sink. A client starts scans until `seconds`
           have passed; its last one finishes.
  check    the volume each client's last scan wrote, read back from its
           sink at voxels drawn from the seed, against the plain float32
           FDK of `reference/fdk.py`.

A traffic mix is a file of parameters of that closed loop:
  loop     "closed" (an open loop, with arrivals, needs code not written
           yet, and is refused)
  clients  how many clients run at once, each with its own sink
  store    "page_cache": the store that set-up wrote is read as it lies
           in the page cache; "dropped": the store's pages are dropped
           from the page cache before each scan, so every read is cold
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import devtrace, roofline
from bench.reference import fdk as reference_fdk
from bench.reference import phantom

# Compile cache, planner caches and the stores of a run: fixed paths in the
# checkout, so that every run of a cell after the first finds its programs.
STATE_DIR = ".bench_state"
# What JAX records for each compilation that the persistent cache missed.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for, or
    the program is asked to run its kernel in the Pallas interpreter."""


class MissingMetric(RuntimeError):
    """A metric the cell reports read nothing in a run on the chip."""


# -- the benchmark's data files ----------------------------------------------

@dataclasses.dataclass
class Cell:
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: str


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`, with its configuration,
    traffic mix and the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, bench["paths"][0])
    with open(os.path.join(bench_dir, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if int(config["chips"]) != int(cell["chips"]):
        raise ValueError(f"cell {name} asks for {cell['chips']} chips, its "
                         f"configuration is for {config['chips']}")
    return Cell(
        chips=int(cell["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def load_reader(bench_dir: str, metric: str):
    """`read` of `<bench_dir>/metrics/<metric>.py`."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


STORES = ("page_cache", "dropped")


def closed_loop(traffic: dict) -> tuple:
    """(clients, drop) of a traffic mix: the number of closed-loop
    clients, and whether the store's pages are dropped before each scan.
    A mix this generator cannot make is refused, not approximated."""
    name = traffic.get("name")
    if traffic.get("loop") != "closed":
        raise ValueError(f"traffic {name!r}: loop={traffic.get('loop')!r} "
                         "is not supported (only 'closed')")
    clients = traffic.get("clients")
    if not isinstance(clients, int) or isinstance(clients, bool) \
            or clients < 1:
        raise ValueError(f"traffic {name!r}: clients={clients!r} is not a "
                         "whole number of at least 1")
    if traffic.get("store") not in STORES:
        raise ValueError(f"traffic {name!r}: store={traffic.get('store')!r}"
                         f" is not one of {STORES}")
    return clients, traffic["store"] == "dropped"


def _store_files(path: str) -> list:
    return [os.path.join(d, f) for d, _, files in os.walk(path)
            for f in files]


def sync_store(path: str) -> None:
    """Write the store's pages to disk, so that they can be dropped."""
    for name in _store_files(path):
        fd = os.open(name, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)


def drop_page_cache(path: str) -> None:
    """Ask the kernel to drop the store's (written-back) pages from the
    page cache, so that the next read comes from the disk."""
    for name in _store_files(path):
        fd = os.open(name, os.O_RDONLY)
        try:
            os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        finally:
            os.close(fd)


# -- what a metric reader sees ------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read, from one run."""
    geometry: dict            # the configuration's scan geometry
    storage_bytes: int        # bytes per stored filtered sample
    n_chips: int
    setup_s: float
    scan_walls: list          # seconds of each scan of the window
    window_s: float           # first scan's start to last scan's end
    spans: dict               # program span name -> [seconds per scan]
    trace: dict | None        # devtrace.load's dict; None untraced or CPU
    peaks: dict | None        # the chip's row of peaks.json; None on CPU

    @property
    def n_scans(self) -> int:
        return len(self.scan_walls)


# -- set-up, window, check ----------------------------------------------------

def configure_caches(root: str) -> None:
    """JAX's compile cache, the planner's file caches and the TPU runtime's
    logs in the checkout. Call before JAX touches a device."""
    state = os.path.join(root, STATE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(state, "jax")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(state, "tpu_logs"))
    for var, name in (("REPRO_TUNE_CACHE", "tune.json"),
                      ("REPRO_PLAN_CACHE", "plan.json"),
                      ("REPRO_CALIB_CACHE", "calibration.json")):
        os.environ[var] = os.path.join(state, name)
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    # Every program, however quick to compile, comes from the cache after
    # the first run, so that set-up does the same work in every run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _log(msg: str) -> None:
    print(msg, flush=True)


def _chips(n: int, rehearsal: bool):
    devices = jax.devices()
    platform = devices[0].platform
    if rehearsal and platform != "cpu":
        raise NoChip("a rehearsal runs on JAX's CPU devices only: set "
                     "JAX_PLATFORMS=cpu")
    if not rehearsal and platform == "cpu":
        raise NoChip("JAX found no accelerator (device 0 is a CPU)")
    if not rehearsal and platform != "tpu":
        raise NoChip(f"JAX found {platform!r} devices, not TPUs")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def _mesh(config: dict, devices):
    if not config.get("mesh"):
        return None
    from repro.parallel.mesh import make_mesh

    axes = config["mesh"]
    return make_mesh(tuple(axes.values()), tuple(axes), devices=devices)


def sample_voxels(geom: dict, seed: int, n: int) -> np.ndarray:
    """(n, 3) voxel indices drawn uniformly over the volume from `seed`."""
    r = phantom.rng(seed, 1)
    dims = [int(geom[k]) for k in ("n_x", "n_y", "n_z")]
    return np.stack([r.integers(0, d, n) for d in dims], axis=1)


def compare(got: np.ndarray, want: np.ndarray, limits: dict) -> dict:
    """The numbers `correct` is decided on, each beside its limit: the RMS
    and the largest gap to the reference, over the reference's largest
    magnitude in the sample."""
    scale = float(np.max(np.abs(want)))
    gap = np.abs(got.astype(np.float64) - want)
    values = {
        "rel_rmse": float(np.sqrt(np.mean(gap ** 2))) / scale,
        "rel_max": float(np.max(gap)) / scale,
    }
    if not np.all(np.isfinite(got)):
        values = {k: float("inf") for k in values}
    return {k: {"value": v, "limit": float(limits[k])}
            for k, v in values.items()}


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, rehearsal: bool = False,
             precision: str | None = None, started: float | None = None,
             ) -> dict:
    """One run of `workload`; returns the result line as a dict.

    The plan is the program's planner's pick (`auto`) under the pins of
    the configuration's `plan`. `rehearsal` runs on JAX's CPU devices with
    the Pallas interpreter and reports no number of the device.
    `precision` replaces the configuration's storage precision: the
    control, never a benchmark run.
    """
    started = time.perf_counter() if started is None else started
    cell = load_cell(root, workload)
    n_clients, drop = closed_loop(cell.traffic)
    if rehearsal:
        os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")
    from repro.core.geometry import CBCTGeometry
    from repro.core.plan import plan_from_spec
    from repro.io.shard_store import StoreError
    from repro.io.streams import ProjectionSource, VolumeSink
    from repro.kernels.backproject.kernel import resolve_interpret
    from repro import obs

    devices = _chips(cell.chips, rehearsal)
    if not rehearsal and resolve_interpret(None):
        raise NoChip("REPRO_PALLAS_INTERPRET asks for the Pallas "
                     "interpreter: a run on the chip times the compiled "
                     "kernel only")
    dev = devices[0]
    mesh = _mesh(cell.config, devices)
    geom = cell.config["geometry"]
    g = CBCTGeometry(**geom)
    pins = dict(cell.config["plan"])
    if precision is not None:
        pins["precision"] = precision
    spec = ",".join(["auto"] + [f"{k}={v}" for k, v in pins.items()])
    work = os.path.join(root, STATE_DIR, "work", workload)
    shutil.rmtree(work, ignore_errors=True)
    in_dir, out_dir = os.path.join(work, "in"), os.path.join(work, "out")
    try:
        # -- set-up --------------------------------------------------------
        t = time.perf_counter()
        table = phantom.fit(phantom.seeded_phantom(seed), geom)
        proj = np.asarray(phantom.project(table, geom, device=dev))
        _log(f"set-up: {g.n_proj} projections of {g.n_v}x{g.n_u} made on "
             f"the device in {time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        n_ranks = len(devices)
        source = ProjectionSource.write(
            in_dir, proj, chunks=(n_ranks, 1, 1) if n_ranks > 1 else None)
        if drop:
            sync_store(in_dir)
        _log(f"set-up: store of {proj.nbytes / 1e9:.3f} GB written in "
             f"{time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        plan = plan_from_spec(g, spec, mesh=mesh)
        _log(f"set-up: plan {json.dumps(plan.describe())} resolved in "
             f"{time.perf_counter() - t:.3f} s")
        sinks = [VolumeSink(os.path.join(out_dir, f"client{k}"))
                 for k in range(n_clients)]
        scans = [plan.build(source=source, sink=sink) for sink in sinks]
        t = time.perf_counter()
        jax.block_until_ready(scans[0]())
        _log(f"set-up: warm-up scan {time.perf_counter() - t:.3f} s")
        # Only the window's scans write the volume that is checked.
        shutil.rmtree(out_dir, ignore_errors=True)
        setup_s = time.perf_counter() - started
        _log(f"set-up: {setup_s:.3f} s in all")

        # -- window --------------------------------------------------------
        trace_dir = os.path.join(root, STATE_DIR, "trace", workload)
        scopes = (_engine_scopes(plan, g, mesh, dev)
                  if trace and not rehearsal else None)
        tracer = obs.Tracer(enabled=trace)
        previous = obs.set_tracer(tracer)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        walls, compiles = [], []

        def count_compiles(event, seconds, **_):
            if event == COMPILE_EVENT:
                compiles.append(seconds)

        jax.monitoring.register_event_duration_secs_listener(count_compiles)
        errors = []

        def client(scan):
            try:
                while True:
                    if drop:
                        drop_page_cache(in_dir)
                    s0 = time.perf_counter()
                    with jax.profiler.TraceAnnotation(devtrace.SCAN):
                        jax.block_until_ready(scan())
                    walls.append(time.perf_counter() - s0)
                    if time.perf_counter() - t0 >= seconds:
                        return
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        others = [threading.Thread(target=client, args=(scan,))
                  for scan in scans[1:]]
        t0 = time.perf_counter()
        for thread in others:
            thread.start()
        client(scans[0])
        for thread in others:
            thread.join()
        window_s = time.perf_counter() - t0
        if errors:
            raise errors[0]
        jax.monitoring.unregister_event_duration_listener(count_compiles)
        if trace:
            jax.profiler.stop_trace()
        obs.set_tracer(previous)
        _log(f"window: {len(walls)} scans by {n_clients} client(s) in "
             f"{window_s:.3f} s, each "
             f"{min(walls):.3f} to {max(walls):.3f} s; {len(compiles)} "
             "compiles")
        memory_peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                          for d in devices) if not rehearsal else 0
        del scans

        # -- check ---------------------------------------------------------
        t = time.perf_counter()
        voxels = sample_voxels(geom, seed, int(cell.config["sample_voxels"]))
        want = reference_fdk.fdk_voxels(geom, proj, voxels)
        got = []
        for k, sink in enumerate(sinks):
            try:
                volume = sink.read()
            except StoreError as e:
                _log(f"check: client {k} stored no volume: {e}")
                volume = np.full(g.volume_shape(), np.nan, np.float32)
            got.append(volume[voxels[:, 0], voxels[:, 1], voxels[:, 2]])
            del volume
        checks = compare(np.concatenate(got), np.tile(want, n_clients),
                         cell.config["limits"])
        _log(f"check: {len(voxels)} voxels of {n_clients} volume(s) against "
             f"the reference in {time.perf_counter() - t:.3f} s")

        spans: dict = {}
        for ev in tracer.spans("stage."):
            spans.setdefault(ev["name"], []).append(ev["dur"] / 1e6)
        trace_dict = None
        if trace and not rehearsal:
            trace_dict = devtrace.load(trace_dir, scopes)
            trace_dict["host"] = _host_spans(tracer, trace_dict)
            devtrace.save(trace_dict, os.path.join(trace_dir, "trace.json.gz"))
        run = Run(
            geometry=geom,
            storage_bytes=int(np.dtype(
                plan.resolved_precision().storage_dtype).itemsize),
            n_chips=len(devices), setup_s=setup_s,
            scan_walls=walls, window_s=window_s, spans=spans,
            trace=trace_dict,
            peaks=None if rehearsal else roofline.peaks(dev.device_kind))
        result = {
            "correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "attempted": len(walls),
            "failed": 0,
            "metrics": _metrics(cell, run, trace, rehearsal),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices()),
                       "memory_peak_bytes": int(memory_peak)},
        }
        if trace_dict is not None:
            busy = devtrace.busy_seconds(trace_dict)
            lo, hi = devtrace.window(trace_dict)
            result["device"]["busy_s"] = sum(busy.values()) / len(busy)
            result["device"]["window_s"] = (hi - lo) / 1e9
            result["breakdown"] = {
                "device_ops": devtrace.top_ops(trace_dict),
                "idle_gaps": devtrace.idle_gaps(
                    trace_dict, trace_dict["host"])[:10],
            }
        result["checks"] = checks
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _engine_scopes(plan, g, mesh, dev) -> dict:
    """Instruction name -> JAX operation of the engine the window runs,
    from its compiled module (the compile cache's copy)."""
    from jax.sharding import SingleDeviceSharding

    from repro.core.distributed import input_sharding

    sharding = (input_sharding(mesh) if mesh is not None
                else SingleDeviceSharding(dev))
    spec = jax.ShapeDtypeStruct(g.proj_shape(), jnp.float32,
                                sharding=sharding)
    compiled = plan.build().__wrapped__.lower(spec).compile()
    return devtrace.scopes_from_hlo(compiled.as_text())


def _host_spans(tracer, trace_dict: dict) -> dict:
    """The program's stage.read / stage.write spans on the trace's clock:
    the first scan's read starts where its bench.scan annotation does."""
    spans = tracer.spans("stage.")
    reads = [e for e in spans if e["name"] == "stage.read"]
    if not reads or not trace_dict["scans"]:
        return {}
    offset = trace_dict["scans"][0][0] - reads[0]["ts"] * 1e3
    out: dict = {}
    for e in spans:
        a = e["ts"] * 1e3 + offset
        out.setdefault(e["name"], []).append([a, a + e["dur"] * 1e3])
    return out


def _metrics(cell: Cell, run: Run, trace: bool, rehearsal: bool) -> dict:
    """The cell's end-to-end metrics (untraced run) or its per-layer ones
    (traced run), each from its reader. The cell reports each metric that
    applies to it: on the chip, one whose reader finds nothing to read
    fails the run. A rehearsal reports no device number, and leaves out a
    metric that reads nothing."""
    out = {}
    for metric in (cell.per_layer if trace else cell.end_to_end):
        if rehearsal and metric["source"] == "device_trace":
            continue
        value = load_reader(cell.bench_dir, metric["name"])(run)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
        elif not rehearsal:
            raise MissingMetric(f"{metric['name']} read nothing in this run")
    return out

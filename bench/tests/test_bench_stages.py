"""Per-stage reductions by the program's own names: stage scopes on the
device's operations, program spans on the profiler's host plane."""
import os

import pytest

import benchtiny
from bench import devtrace, harness, stages

MS = 1_000_000  # ns
SEED = 2**31 + 977


def synthetic():
    """One device, two scans of 100 ms. In each: the filter's `while` from
    10 to 30 ms with two operations of its body inside it, then the P shift
    and the kernel under the back-projection scope."""
    ops = []
    for scan0 in (0, 100 * MS):
        ops += [
            ["while.8", "while", "jit(r)/fdk.filter/while", scan0 + 10 * MS,
             20 * MS],
            ["fusion.1", "fusion",
             "jit(r)/fdk.filter/while/body/closed_call/jit(fft)",
             scan0 + 12 * MS, 6 * MS],
            ["fusion.2", "fusion", "jit(r)/fdk.filter/while/body/mul",
             scan0 + 20 * MS, 5 * MS],
            ["fusion.3", "fusion", "jit(r)/fdk.backproject/add",
             scan0 + 30 * MS, 2 * MS],
            ["backproject_dual.1", "custom-call",
             "jit(r)/fdk.backproject/jit(backproject_dual_pallas)/"
             "backproject_dual/pallas_call", scan0 + 32 * MS, 50 * MS],
            ["copy.1", "copy", "jit(r)/fdk.filtered", scan0 + 85 * MS,
             1 * MS],
        ]
    return {"ops": {"/device:TPU:0": ops},
            "scans": [[0, 100 * MS], [100 * MS, 200 * MS]]}


def test_stage_seconds_count_a_while_and_its_body_once():
    t = synthetic()
    assert stages.stage_seconds(t, "fdk.filter") == pytest.approx(0.020)
    assert stages.stage_seconds(t, "fdk.backproject") == pytest.approx(
        0.052)
    assert stages.stage_seconds(t, "fdk.gather") is None
    # the stage holds its FFT and its kernel, and more
    assert stages.stage_seconds(t, "fdk.filter") >= devtrace.per_scan(
        t, devtrace.is_fft)
    assert stages.stage_seconds(t, "fdk.backproject") >= devtrace.per_scan(
        t, devtrace.is_bp_kernel)


def test_stage_seconds_are_means_over_devices():
    t = synthetic()
    t["ops"]["/device:TPU:1"] = [
        row for row in t["ops"]["/device:TPU:0"] if row[0] != "fusion.3"]
    assert stages.stage_seconds(t, "fdk.backproject") == pytest.approx(
        (0.052 + 0.050) / 2)


def test_span_seconds_per_scan_inside_the_window():
    t = synthetic()
    t["host"] = {"stage.read": [[-5 * MS, 8 * MS], [100 * MS, 108 * MS]]}
    assert stages.span_seconds(t, "stage.read") == pytest.approx(0.008)
    assert stages.span_seconds(t, "stage.write") is None


def test_idle_gaps_pick_the_innermost_span():
    t = synthetic()
    # outer span first, as the profiler's host plane lists them
    host = {"stage.read": [[0, 10 * MS], [100 * MS, 110 * MS]],
            "stage.read.copy": [[0, 6 * MS], [100 * MS, 106 * MS]],
            "stage.read.h2d": [[6 * MS, 10 * MS], [106 * MS, 110 * MS]],
            "stage.write": [[86 * MS, 100 * MS], [186 * MS, 200 * MS]],
            "stage.write.d2h": [[86 * MS, 90 * MS], [186 * MS, 190 * MS]],
            "stage.write.file": [[90 * MS, 100 * MS],
                                 [190 * MS, 200 * MS]]}
    gaps = devtrace.idle_gaps(t, stages.innermost_first(host))
    idle: dict = {}
    for label, seconds in gaps:
        idle[label] = idle.get(label, 0.0) + seconds
    assert idle == {"stage.read.copy": pytest.approx(0.012),
                    "stage.read.h2d": pytest.approx(0.008),
                    "stage.write.d2h": pytest.approx(0.008),
                    "stage.write.file": pytest.approx(0.020),
                    "in scan": pytest.approx(0.006)}
    t["host"] = host
    assert stages.split(t)["idle_s"] == idle


def test_traced_rehearsal_puts_program_spans_on_the_profiler_clock(
        tmp_path, monkeypatch):
    """A traced run's program spans are on the profiler's host plane, each
    inside its scan's `bench.scan` and each I/O part inside its stage."""
    benchtiny.hermetic(monkeypatch)
    root = benchtiny.make_root(tmp_path)
    harness.run_cell(root, "tiny.full", SEED, 0.0, True,
                     rehearsal=True)
    trace_dir = os.path.join(root, harness.STATE_DIR, "trace", "tiny.full")
    host = stages.host_spans(trace_dir)
    scans = devtrace.load(trace_dir)["scans"]
    assert len(scans) == 1
    for name in stages.READ_SPANS + stages.WRITE_SPANS + (
            "engine.reconstruct",):
        (span,) = host[name]
        assert scans[0][0] <= span[0] <= span[1] <= scans[0][1], name
    for parts in (stages.READ_SPANS, stages.WRITE_SPANS):
        (outer,), (first,), (second,) = (host[name] for name in parts)
        assert outer[0] <= first[0] <= first[1] <= second[0] \
            <= second[1] <= outer[1]
    (read,), (engine,), (write,) = (
        host[name] for name in ("stage.read", "engine.reconstruct",
                                "stage.write"))
    assert read[1] <= engine[0] and engine[1] <= write[0]


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "rabbitct512_trace.json.gz")


def test_recorded_rabbitct512_window():
    """A window of rabbitct512.full recorded on one v5e (three scans), with
    the engine's stage scopes and the program's spans from the profiler's
    host plane, as `python3 -m bench.stages --save` left it."""
    t = devtrace.read(RECORDED)
    assert len(t["scans"]) == 3
    split = stages.split(t)
    for stage, first, second in (stages.READ_SPANS, stages.WRITE_SPANS):
        parts = split[first] + split[second]
        assert 0.95 * split[stage] <= parts <= split[stage]
    assert split["stage.read.copy"] == pytest.approx(3.6255025353)
    assert split["stage.write.file"] == pytest.approx(1.0440365573)
    assert split["fdk.filter"] == pytest.approx(0.8273511207)
    assert split["fdk.filter"] >= split["fft"] == pytest.approx(0.7743574543)
    assert split["fdk.backproject"] == pytest.approx(16.6628686163)
    assert split["fdk.backproject"] >= split["backproject_dual"]
    for scope in ("fdk.encode", "fdk.gather", "fdk.reduce"):
        assert split[scope] is None       # no such operation on one chip
    idle = split["idle_s"]
    named = sum(idle.get(name, 0.0) for name in (
        "stage.read.copy", "stage.read.h2d", "stage.write.d2h",
        "stage.write.file", "engine.reconstruct"))
    assert named >= 0.95 * sum(idle.values())
    lo, hi = devtrace.window(t)
    busy = devtrace.busy_seconds(t)["/device:TPU:0"]
    assert sum(idle.values()) == pytest.approx((hi - lo) / 1e9 - busy)

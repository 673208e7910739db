"""The reduction from a device trace to per-layer numbers."""
import os

import pytest

import benchtiny  # noqa: F401  (puts the checkout on sys.path)
from bench import devtrace

MS = 1_000_000  # ns


def synthetic():
    """Two devices, two scans of 100 ms each with a 10 ms gap between; the
    kernel takes 50 ms on device 0 and 40 ms on device 1."""
    ops = []
    for scan0 in (0, 110 * MS):
        ops += [
            ["fusion.7", "fusion", "jit(reconstruct_fn)/jit(fft)",
             scan0 + 20 * MS, 5 * MS],
            ["all-gather-start.1", "all-gather-start", "", scan0 + 25 * MS,
             2 * MS],
            ["all-gather-done.1", "all-gather-done", "", scan0 + 27 * MS,
             1 * MS],
            ["backproject_dual.1", "custom-call", "", scan0 + 30 * MS,
             50 * MS],
            ["all-reduce.4", "all-reduce", "", scan0 + 80 * MS, 4 * MS],
            ["copy.3", "copy", "", scan0 + 84 * MS, 1 * MS],
        ]
    return {
        "ops": {"/device:TPU:0": ops,
                "/device:TPU:1": [r[:4] + [40 * MS if r[0].startswith(
                    "backproject") else r[4]] for r in ops]},
        "scans": [[0, 100 * MS], [110 * MS, 210 * MS]],
    }


@pytest.mark.parametrize("text, name, opcode", [
    ('%backproject_dual.4 = f32[512,512,512]{2,1,0:T(8,128)} custom-call('
     'f32[4680]{0:T(1024)S(1)} %copy-done.13), custom_call_target='
     '"tpu_custom_call"', "backproject_dual.4", "custom-call"),
    ('%while.8 = (s32[]{:T(128)}, f32[24,15,768,768]{2,3,1,0:T(8,128)}) '
     'while((s32[]{:T(128)}, f32[24,15,768,768]{2,3,1,0:T(8,128)}) '
     '%tuple.115), condition=%wide.region_1.8', "while.8", "while"),
    ('%all-gather-start.1 = (f32[8]{0}, f32[16]{0}) all-gather-start('
     'f32[8]{0} %p), replica_groups={{0,1}}', "all-gather-start.1",
     "all-gather-start"),
    ("fft.0", "fft.0", "fft"),
    ("wrapped_pad", "wrapped_pad", "wrapped_pad"),
])
def test_parse_op(text, name, opcode):
    assert devtrace.parse_op(text) == (name, opcode)


def test_scopes_from_hlo():
    text = (
        '  %fusion.90 = f32[16,96,256]{2,1,0} fusion(f32[16,96,96]{2,1,0} '
        '%p), kind=kOutput, calls=%fused.1, metadata={op_name="jit(recon)/'
        'while/body/jit(_filter_batch)/jit(fft)" stack_frame_id=3}\n'
        '  ROOT %backproject_dual.1 = f32[64]{0} custom-call(), metadata={'
        'op_name="jit(recon)/backproject_dual/pallas_call"}\n'
        '  %copy.1 = f32[2]{0} copy(f32[2]{0} %x)\n')
    assert devtrace.scopes_from_hlo(text) == {
        "fusion.90": "jit(recon)/while/body/jit(_filter_batch)/jit(fft)",
        "backproject_dual.1": "jit(recon)/backproject_dual/pallas_call"}


@pytest.mark.parametrize("name, opcode, scope, fft, bp", [
    ("backproject_dual.1", "custom-call", "", False, True),
    ("backproject_dual", "custom-call", "", False, True),
    ("custom-call.4", "custom-call", "", False, False),
    ("fft.0", "fft", "", True, False),
    ("fusion.12", "fusion", "jit(reconstruct_fn)/while/body/jit(fft)",
     True, False),
    ("convolution.3", "convolution", "jit(reconstruct_fn)/jit(fft)/fft",
     True, False),
    ("fusion.13", "fusion", "jit(reconstruct_fn)/mul", False, False),
    ("all-gather-start.2", "all-gather-start", "", False, False),
    ("all-gather-done.2", "all-gather-done", "", False, False),
    ("all-reduce.1", "all-reduce", "", False, False),
    ("reduce-scatter.5", "reduce-scatter", "", False, False),
    ("collective-permute-done.1", "collective-permute-done", "", False,
     False),
    ("fusion.3", "fusion", "jit(f)/psum", False, False),
    ("copy-start.1", "copy-start", "", False, False),
])
def test_matchers(name, opcode, scope, fft, bp):
    assert devtrace.is_fft(name, opcode, scope) is fft
    assert devtrace.is_bp_kernel(name, opcode, scope) is bp


def test_per_scan_device_seconds_are_means_over_devices():
    t = synthetic()
    assert devtrace.per_scan(t, devtrace.is_bp_kernel) == pytest.approx(
        (50 + 40) / 2 / 1e3)
    assert devtrace.per_scan(t, devtrace.is_fft) == pytest.approx(5e-3)
    assert devtrace.per_scan(t, lambda *op: False) is None


def test_busy_is_the_union_inside_the_window():
    t = synthetic()
    t["ops"]["/device:TPU:0"].append(["fusion.99", "fusion", "", 30 * MS,
                                      10 * MS])
    t["ops"]["/device:TPU:0"].append(["fusion.98", "fusion", "", -50 * MS,
                                      60 * MS])
    busy = devtrace.busy_seconds(t)
    # device 0: [0, 10) from the clipped op, then 8 + 50 + 5 ms per scan
    assert busy["/device:TPU:0"] == pytest.approx((10 + 63 + 63) / 1e3)
    assert busy["/device:TPU:1"] == pytest.approx((53 + 53) / 1e3)
    assert devtrace.window(t) == (0, 210 * MS)


def test_idle_gaps_are_labelled_by_host_span():
    t = synthetic()
    host = {"stage.read": [[0, 19 * MS], [110 * MS, 129 * MS]],
            "stage.write": [[86 * MS, 100 * MS], [196 * MS, 210 * MS]]}
    gaps = devtrace.idle_gaps(t, host)
    labels = {}
    for label, seconds in gaps:
        labels.setdefault(label, []).append(seconds)
    assert sorted(labels) == ["between scans", "in scan", "stage.read",
                              "stage.write"]
    # device 0 idles from 85 to 130 ms: cut where the write, the scan and
    # the read start and end
    assert max(labels["stage.read"]) == pytest.approx(0.019)
    assert max(labels["between scans"]) == pytest.approx(0.010)
    assert max(labels["stage.write"]) == pytest.approx(0.014)
    assert max(labels["in scan"]) == pytest.approx(0.010)   # device 1
    assert sum(s for _, s in gaps) == pytest.approx(
        2 * 0.210 - (0.063 + 0.063 + 0.053 + 0.053))
    assert gaps[0][1] >= gaps[-1][1]


def test_top_ops_rank_device_time():
    top = devtrace.top_ops(synthetic(), 2)
    assert top[0][0] == "backproject_dual.1"
    assert top[0][1] == pytest.approx(0.090)
    assert len(top) == 2


def test_a_trace_without_scans_is_refused():
    t = synthetic()
    t["scans"] = []
    with pytest.raises(ValueError, match="bench.scan"):
        devtrace.window(t)


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "cbct512_trace.json.gz")


def test_recorded_cbct512_window():
    """A window of cbct512.full recorded on one v5e (two scans), as
    `devtrace.load` left it: each number the per-layer metrics read."""
    t = devtrace.read(RECORDED)
    assert len(t["scans"]) == 2
    assert devtrace.per_scan(t, devtrace.is_bp_kernel) == pytest.approx(
        14.2995957035)
    assert devtrace.per_scan(t, devtrace.is_fft) == pytest.approx(
        0.327296088)
    busy = devtrace.busy_seconds(t)
    lo, hi = devtrace.window(t)
    assert busy == {"/device:TPU:0": pytest.approx(29.372175791)}
    assert (hi - lo) / 1e9 == pytest.approx(37.873322568)
    top = devtrace.top_ops(t, 3)
    assert [name for name, _ in top[:2]] == ["backproject_dual.4",
                                             "backproject_dual.3"]
    gaps = devtrace.idle_gaps(t, t["host"])
    assert [label for label, _ in gaps[:4]] == [
        "stage.read", "stage.read", "stage.write", "stage.write"]
    assert sum(s for _, s in gaps) == pytest.approx(
        (hi - lo) / 1e9 - busy["/device:TPU:0"])

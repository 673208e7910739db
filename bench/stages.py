"""Per-stage numbers of a traced run, read by the program's own names.

The engine runs each stage under a `jax.named_scope` (core/plan.py
`STAGE_SCOPES`), which every operation of the stage keeps in its `op_name`
(`devtrace.load` puts it in the operation's `scope`). Each span the program
records (repro/obs/trace.py) is also a `jax.profiler.TraceAnnotation` of its
name, so it lands on the profiler's host plane, on the device trace's clock.
The reductions here read both, from the dict of `devtrace.load` with the
program's spans under `host`.

After a `--trace 1` run of a cell,

    python3 -m bench.stages .bench_state/trace/<cell> [--save PATH]

prints the per-scan split of the window as one JSON line. `--save` writes
the window's dict, with the program's spans as `host`, in the format of
`devtrace.save`.
"""
from __future__ import annotations

import argparse
import json
import os

from bench import devtrace

# Name prefixes of the program's spans (repro/obs/trace.py).
PREFIXES = ("stage.", "engine.")

# The I/O spans of the production path (io/streams.py), each inside its
# stage's span.
READ_SPANS = ("stage.read", "stage.read.copy", "stage.read.h2d")
WRITE_SPANS = ("stage.write", "stage.write.d2h", "stage.write.file")
SCOPES = ("fdk.filter", "fdk.encode", "fdk.gather", "fdk.backproject",
          "fdk.reduce")


def host_spans(trace_dir: str) -> dict:
    """{span name: [[start_ns, end_ns], ...]} of the program's spans on the
    profiler's host plane of the trace in `trace_dir`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(devtrace.find_xplane(trace_dir))
    out: dict = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.setdefault(ev.name, []).append(
                        [int(ev.start_ns), int(ev.start_ns + ev.duration_ns)])
    for spans in out.values():
        spans.sort()
    return out


def span_seconds(trace: dict, name: str) -> float | None:
    """Seconds per scan of the program's span `name` inside the window;
    None where the trace holds no such span."""
    spans = trace.get("host", {}).get(name)
    if not spans:
        return None
    lo, hi = devtrace.window(trace)
    total = sum(max(0, min(b, hi) - max(a, lo)) for a, b in spans)
    return total / 1e9 / len(trace["scans"])


def stage_seconds(trace: dict, scope: str) -> float | None:
    """Device seconds per scan of the stage `scope`, the mean over the
    devices of the trace: on each device the union of the intervals of the
    operations that ran under it, so that a `while` and the operations of
    its body count once. None where no operation ran under it."""
    lo, hi = devtrace.window(trace)
    total, matched = 0, False
    for rows in trace["ops"].values():
        mine = [row for row in rows if scope in row[2].split("/")]
        matched = matched or bool(mine)
        total += sum(b - a for a, b in devtrace.busy_intervals(mine, lo, hi))
    if not matched:
        return None
    return total / 1e9 / len(trace["ops"]) / len(trace["scans"])


def innermost_first(host: dict) -> dict:
    """`host` in the order that makes `devtrace.idle_gaps`, which labels a
    piece with the first span that covers it, pick the innermost one: the
    program's spans nest by name (`stage.read.copy` in `stage.read`)."""
    return dict(sorted(host.items(), key=lambda kv: -kv[0].count(".")))


def split(trace: dict) -> dict:
    """The window's per-scan split: each I/O span's seconds, each stage
    scope's device seconds, the FFT's and the kernel's device seconds as
    `devtrace` matches them, and the device's idle seconds by the innermost
    program span over them (summed over the window's scans and devices)."""
    out = {name: span_seconds(trace, name)
           for name in READ_SPANS + WRITE_SPANS}
    out.update({scope: stage_seconds(trace, scope) for scope in SCOPES})
    out["fft"] = devtrace.per_scan(trace, devtrace.is_fft)
    out["backproject_dual"] = devtrace.per_scan(trace, devtrace.is_bp_kernel)
    idle: dict = {}
    for label, seconds in devtrace.idle_gaps(
            trace, innermost_first(trace.get("host", {}))):
        idle[label] = idle.get(label, 0.0) + seconds
    out["idle_s"] = idle
    return out


def window_only(trace: dict) -> dict:
    """`trace` with only the operations and spans that overlap its window."""
    lo, hi = devtrace.window(trace)
    return {
        "ops": {dev: [row for row in rows
                      if row[3] < hi and row[3] + row[4] > lo]
                for dev, rows in trace["ops"].items()},
        "scans": trace["scans"],
        "host": {name: [s for s in spans if s[0] < hi and s[1] > lo]
                 for name, spans in trace.get("host", {}).items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--save")
    args = ap.parse_args(argv)
    trace = devtrace.read(os.path.join(args.trace_dir, "trace.json.gz"))
    trace["host"] = host_spans(args.trace_dir)
    if args.save:
        devtrace.save(window_only(trace), args.save)
    print(json.dumps(split(trace)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

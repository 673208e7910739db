#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as the last line.

    python bench/run.py --workload rabbitct512.full --seed 7 --seconds 45 --trace 0

Run from the root of a checkout: the program is `src/repro` there. The cells,
configurations, traffic mixes and metrics are those of BENCHMARK.json.
`--trace 0` reports the cell's end-to-end metrics; `--trace 1` traces the
window with the JAX profiler and reports its per-layer metrics.

Exit status 0 with a result line; 2, and no result line, when JAX finds no
accelerator, fewer chips than the cell asks for, or no program beside the
benchmark, or when REPRO_PALLAS_INTERPRET asks for the Pallas interpreter
on the chip; 3, and no result line, when a metric the cell reports reads
nothing on the chip. `--cpu-rehearsal` runs on JAX's CPU devices with the Pallas
interpreter at whatever size the cell has, prints `"platform": "cpu"` and no
number read from a device trace: for trying the harness without a chip.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no program at {src}/repro", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    from bench import harness

    harness.configure_caches(ROOT)
    try:
        result = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            rehearsal=args.cpu_rehearsal, started=STARTED)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except harness.MissingMetric as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

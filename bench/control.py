#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, many seeds in one
process (set-up compiles once).

    python bench/control.py --workload rabbitct512.full --seeds 11,12,13
    python bench/control.py --workload rabbitct512.full --seeds 11,12,13 \
        --precision bf16

Each seed is one run of the cell as `bench/run.py` makes it, with a window
of one scan. Without `--precision` the program runs as the configuration
states (the lower readings); with it, the program's own path at that
storage precision stands in (the control, whose readings are the upper
ones). One JSON line per seed: the seed, the precision and the numbers
compared. The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--precision", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    harness.configure_caches(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(ROOT, args.workload, seed, 0.0, False,
                                  precision=args.precision)
        print(json.dumps({"seed": seed, "precision": args.precision,
                          "correct": result["correct"],
                          "scan_s": result["metrics"]["scan_s"]["value"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

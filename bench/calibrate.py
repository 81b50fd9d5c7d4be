#!/usr/bin/env python3
"""Readings that a cell's limits are set from, many seeds in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 4 [--controls 3]

For each seed it runs the cell's driver once, as ``run.py`` does (set-up,
a closed-loop window of ``--seconds``, the comparison with the plain
reference), and prints one JSON line with the numbers compared and
``correct``.  For the first ``--controls`` seeds it also reads the
driver's ``control``: the same comparison with the control in the
program's place, which has to fail.  The benchmark's own runs never do
this; it is how the limits in the traffic files were chosen (PERF.md
gives the readings).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")]

import harness  # noqa: E402
from run import Context  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    try:
        cell = harness.find_cell(harness.load_spec(), args.workload)
        devices = harness.require_chips(cell.entry["chips"])
        harness.enable_compile_cache()
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = Context(cell, seed, args.seconds, False, time.perf_counter(),
                      devices)
        run = cell.driver.run(ctx)
        line = {"seed": seed, "correct": run["correct"],
                "attempted": run["attempted"], "setup_s": run["setup_s"],
                "checks": {c["name"]: c["value"] for c in run["checks"]}}
        if i < args.controls:
            line["control"] = {c["name"]: c["value"]
                               for c in cell.driver.control(ctx, run)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

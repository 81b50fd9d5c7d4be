#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, driver and metric readers are files under
``bench/`` found by name (see ``harness.py``).  The run sets up, warms
up every shape its traffic uses, measures a closed-loop window of
``--seconds``, checks the outputs against the plain reference, and
prints one JSON line last on standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``, each compared number
beside its limit (also printed last on standard error).

It exits non-zero, printing no result, when JAX finds no accelerator or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Any  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")]

import harness  # noqa: E402


@dataclass
class Context:
    cell: harness.Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list[Any]


def main(argv: list[str] | None = None, *,
         require_chips=harness.require_chips,
         bench_dir: str = BENCH_DIR) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = harness.load_spec(os.path.dirname(bench_dir))
        cell = harness.find_cell(spec, args.workload, bench_dir)
        devices = require_chips(cell.entry["chips"])
        harness.enable_compile_cache()
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), T_START,
                  devices)
    run = cell.driver.run(ctx)
    run["cell"] = cell.name
    result: dict[str, Any] = {
        "correct": run["correct"], "attempted": run["attempted"],
        "failed": run["failed"],
        "metrics": harness.read_metrics(
            cell, cell.per_layer if args.trace else cell.end_to_end, run),
        "device": dict(run["device"]),
        "compiles_in_window": run["compiles_in_window"],
    }
    if args.trace:
        tr = run["trace"] or {}
        result["device"]["busy_s"] = tr.get("busy_s", 0.0)
        result["device"]["window_s"] = tr.get("window_s", 0.0)
        if tr.get("breakdown"):
            result["breakdown"] = tr["breakdown"]
    harness.emit(result, run["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())

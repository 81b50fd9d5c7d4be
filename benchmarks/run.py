"""Benchmark driver: one section per paper table/figure.

  fig5    — Fig. 5 reproduction, fully simulated at Table-I sizes
            (conventional vs dataflow vs ARM baseline; writes
            experiments/paper_fig5.json + BENCH_sim.json)
  sweep   — Fig. 5 design-space sweep (kernels × memory models × FIFO
            depths × SCC modes × port knobs; ``--smoke`` after the
            section name for the reduced CI grid, e.g.
            ``run.py sweep --smoke``)

Both fig5 and sweep memoize resolved traces under
``experiments/.rescache`` (chunk-granular records in an in-process LRU
+ on-disk store, shared across grid cells, chunk sizes, iteration
counts — an N-iteration artifact prefix-serves any shorter run, so
``fig5 --quick`` after a full run resolves nothing — and worker
processes; interrupted runs resume from their last completed chunk).
Pass ``--no-rescache`` after the section name to force cold
resolution — e.g. ``run.py fig5 --no-rescache`` — for timing runs or
when a trace generator changed without changing its fingerprinted
sample; ``--workers N`` shards each dataflow task's resolution over
the chunk-graph process pool (bit-identical; pays off from ~4 cores
up), and ``--server auto`` (or an address) delegates resolution to
the persistent resolution daemon — shared worker pool, cross-client
in-flight dedup, bit-identical results (see ``docs/serving.md``).
  serving — serving smoke: one daemon, two racing ``sweep --smoke``
            clients; asserts bit-identity with library mode and
            exactly-once resolution (``benchmarks.serving_smoke``)
  chaos   — fault-injection drills (worker SIGKILL, corrupt record,
            daemon SIGKILL + journal restart); asserts every scenario
            ends bit-identical to a clean library run with exactly one
            committed record per chunk (``benchmarks.chaos_smoke``)
  engine  — resolution-engine A/B smoke: the same full-scale
            resolution once per backend (numpy / jax), asserts
            bit-identical cycle counts, times the ported kernels head
            to head, and writes an ``engine`` section to
            ``BENCH_sim.json`` (``benchmarks.engine_smoke``; backend
            contract in ``docs/engine.md``)
  lint    — IR lint: compile every shipped kernel (paper kernels +
            example kernels) with the static dataflow verifier and
            report every diagnostic; exits nonzero on error-severity
            findings (``benchmarks.lint``, rule catalog in
            ``docs/verify.md``)
  gc      — garbage-collect the rescache store (``run.py gc
            [--max-bytes N]``: drop pre-v3 orphans, then enforce the
            byte cap — the flag overrides ``$REPRO_RESCACHE_MAX_BYTES``)
  table2  — Table II analogue (stage/channel/duplication accounting)
"""

from __future__ import annotations

import sys

from repro.compile_cache import enable_compile_cache


def main() -> None:
    # sections are the leading non-flag arguments; everything from the
    # first "-" on belongs to the section's own argparse (run.py fig5
    # --quick, run.py sweep --smoke)
    sections = []
    for a in sys.argv[1:]:
        if a.startswith("-"):
            break
        sections.append(a)
    sections = sections or ["fig2", "fig5", "table2"]
    enable_compile_cache()

    if "fig2" in sections:
        print("=" * 72)
        print("Fig. 2 reproduction — execution schedule (Gantt)")
        print("=" * 72)
        from . import fig2_schedule
        fig2_schedule.main()
        print()

    if "fig5" in sections:
        print("=" * 72)
        print("Fig. 5 reproduction — conventional vs dataflow vs baseline")
        print("=" * 72)
        from . import paper_fig5
        paper_fig5.cli()  # parse_known_args: run.py fig5 --quick works

    if "sweep" in sections:
        print("\n" + "=" * 72)
        print("Fig. 5 design-space sweep — mems × FIFO depths × SCC modes")
        print("=" * 72)
        from . import sweep
        sweep.main()

    if "serving" in sections:
        print("\n" + "=" * 72)
        print("Serving smoke — daemon + two racing sweep clients")
        print("=" * 72)
        from . import serving_smoke
        serving_smoke.main()

    if "chaos" in sections:
        print("\n" + "=" * 72)
        print("Chaos smoke — fault-injection drills against the "
              "serving stack")
        print("=" * 72)
        from . import chaos_smoke
        chaos_smoke.main()

    if "engine" in sections:
        print("\n" + "=" * 72)
        print("Resolution-engine A/B smoke — numpy vs jax, bit-identity "
              "+ kernel walls")
        print("=" * 72)
        from . import engine_smoke
        engine_smoke.main()

    if "gc" in sections:
        import argparse
        import json
        from repro.core import rescache
        ap = argparse.ArgumentParser(prog="run.py gc")
        ap.add_argument("--max-bytes", type=int, default=None,
                        help="store byte cap for this collection "
                             "(overrides $REPRO_RESCACHE_MAX_BYTES)")
        a, _ = ap.parse_known_args()
        print("=" * 72)
        print("rescache gc — drop orphans, enforce the byte cap")
        print("=" * 72)
        print(json.dumps(rescache.gc(a.max_bytes), indent=1))

    if "lint" in sections:
        print("\n" + "=" * 72)
        print("IR lint — static dataflow verifier over every shipped "
              "kernel")
        print("=" * 72)
        from . import lint
        lint.main([])  # section names are run.py's, not lint targets

    if "table2" in sections:
        print("\n" + "=" * 72)
        print("Table II analogue — stages / channels / duplication")
        print("=" * 72)
        from . import paper_table2
        paper_table2.main()


if __name__ == "__main__":
    main()

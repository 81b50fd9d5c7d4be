"""Fig. 5 reproduction: conventional vs dataflow accelerators vs ARM core.

Pipeline per kernel:
  1. trace the loop body → cyclic CDFG (carry back-edges),
  2. Algorithm 1 partition (the *real* partitioner, not a hand decomposition),
  3. derive SimStages: II/latency from the partition, memory-SCC stages
     detected automatically (the DFS pathology), traces attached to memory
     stages in pipeline order,
  4. simulate the three machines over four memory configs (ACP, ACP+64KB,
     HP, HP+64KB) at the **full Table-I iteration counts** — the vectorized
     simulator streams even Floyd–Warshall's 1024^3 iterations chunk by
     chunk, so no steady-state extrapolation is involved (``--quick``
     restores the old extrapolated small-window mode for development).

Checked claims (§V-A):
  * conventional accelerators run below the ARM baseline;
  * dataflow ≫ conventional (paper: 3.3–9.1×, avg 5.6× best-config);
  * caches help conventional more than dataflow (−45.4 % vs −18.7 %);
  * HP (uncached) degrades conventional vs ACP (~40 %);
  * DFS shows no meaningful dataflow gain (memory SCC).

The grid is planned so cells sharing work run together: per kernel, ONE
task simulates the dataflow machine on all four memory configs at once
(windows, burst masks, and each cache geometry resolved a single time —
see ``simulate_dataflow_many``), one task covers the conventional engine
on all four, and one the processor baseline.  Tasks are farmed longest-
first to a small process pool (``--jobs``), and resolved traces are
memoized on disk (``experiments/.rescache``) so repeated runs and the
sweep harness share work; ``--no-rescache`` forces cold resolution.
The PR 2 layout re-resolved every (kernel × machine × memory) cell from
scratch — ~1.5 h on 2 cores for this grid; the shared-resolution planner
plus the vectorized N-way LRU and the fast-path wavefront bring full
regeneration down to minutes (recorded in ``BENCH_sim.json``).
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import time

import numpy as np

from repro.core.engine import cpu_children
from repro.core.simulator import (simulate_conventional_many,
                                  simulate_dataflow_many,
                                  simulate_processor,
                                  standard_memory_models)
from repro.dataflow import compile as dataflow_compile, fused_stage
from .paper_kernels import ALL_KERNELS, PaperKernel

MEM_NAMES = ("ACP", "ACP+64KB", "HP", "HP+64KB")
SPMV_SCALE = 0.125  # correctness-data scale; traces are full-size anyway
#: The template's FIFO sizing rule: depth must cover the latency a channel
#: has to hide — worst access latency plus stage latency, with margin
#: (§III-B2).  Shallower FIFOs (< ~80 here) make occasional DRAM-latency
#: spikes eat the producer's lead permanently: every spike then stalls
#: the pipeline and the whole run degrades to lockstep backpressure.
FIFO_DEPTH = 256
MAX_OUTSTANDING = 16  # the paper's "multiple outstanding requests"

#: Measured PR 2 baseline for full regeneration of this grid (per-cell
#: timings extrapolated to Table-I iteration counts on the CI container;
#: ROADMAP recorded "~1.5 h on 2 cores" for the same run).
PR2_BASELINE_CPU_S = 10594.0


def _dataflow_mems() -> dict:
    mems = {}
    for mn, mk in standard_memory_models().items():
        m = mk()
        m.max_outstanding = MAX_OUTSTANDING
        mems[mn] = m
    return mems


def build_stages(k: PaperKernel, *, full: bool = True):
    """(dataflow stages, conventional stage) from the compiler driver.

    The driver traces the loop body in loop mode (carry back-edges),
    partitions with Algorithm 1, and classifies memory-in-SCC stages (the
    DFS pathology); traces are attached positionally to memory ops in
    pipeline-stage order."""
    compiled = dataflow_compile(
        k.loop_body, k.carry_example, *k.body_args,
        loop=True,
        nonaliasing_carries=getattr(k, "nonaliasing_carries", ()))
    del full  # --quick truncates the iteration count, not the traces:
    # both modes attach the full-scale windowed traces, so a --quick run
    # is an exact *prefix* of the full run and the v3 rescache serves it
    # from any full-scale artifact with zero cold resolution
    traces = k.full_traces
    df_stages = compiled.sim_stages(traces=list(traces.values()))
    return df_stages, [fused_stage(df_stages)]


def _make_kernel(kname: str) -> PaperKernel:
    mk = ALL_KERNELS[kname]
    return mk(SPMV_SCALE) if kname == "spmv" else mk()


def run_kernel(k: PaperKernel, *, full: bool = False) -> dict:
    """Single-kernel, in-process version of the grid (tests / notebooks).

    ``full=False`` simulates the small window and extrapolates (the
    pre-sweep behaviour); ``full=True`` simulates all Table-I iterations.
    """
    n = k.n_iters_full if full else k.n_iters_sim
    traces = k.full_traces
    df_stages, conv_stages = build_stages(k, full=full)
    base = simulate_processor(k.instrs_per_iter, list(traces.values()), n)
    t_base = base.runtime_s if full else base.scaled_runtime(k.n_iters_full)
    out: dict = {"kernel": k.name,
                 "stages": len(df_stages),
                 "n_iters_simulated": n,
                 "n_iters_full": k.n_iters_full,
                 "fully_simulated": bool(full),
                 "baseline_s": t_base}
    dfs = simulate_dataflow_many(df_stages, _dataflow_mems(), n,
                                 fifo_depths=(FIFO_DEPTH,),
                                 collect_stalls=False)
    cvs = simulate_conventional_many(
        conv_stages, {mn: mk() for mn, mk in
                      standard_memory_models().items()}, n)
    for name in MEM_NAMES:
        df = dfs[(name, FIFO_DEPTH)]
        cv = cvs[name]
        t_df = df.runtime_s if full else df.scaled_runtime(k.n_iters_full)
        t_cv = cv.runtime_s if full else cv.scaled_runtime(k.n_iters_full)
        out[name] = {
            "dataflow_s": t_df,
            "conventional_s": t_cv,
            "dataflow_vs_baseline": t_base / t_df,
            "conventional_vs_baseline": t_base / t_cv,
            "dataflow_vs_conventional": t_cv / t_df,
        }
    return out


def _sim_task(task: tuple) -> tuple:
    """One (kernel, machine) group: all four memory configs resolved in a
    single shared pass — a top-level function so a spawn-based process
    pool can run the grid.  ``workers > 1`` additionally shards the
    dataflow group's resolution over the chunk-graph executor."""
    kname, what, full, workers, server = task
    t0 = time.perf_counter()
    k = _make_kernel(kname)
    n = k.n_iters_full if full else k.n_iters_sim
    traces = k.full_traces
    if what == "processor":
        r = {"": simulate_processor(k.instrs_per_iter,
                                    list(traces.values()), n)}
    elif what == "dataflow":
        df_stages, _ = build_stages(k, full=full)
        grid = simulate_dataflow_many(df_stages, _dataflow_mems(), n,
                                      fifo_depths=(FIFO_DEPTH,),
                                      collect_stalls=False,
                                      workers=workers, server=server)
        r = {mn: grid[(mn, FIFO_DEPTH)] for mn in MEM_NAMES}
    else:
        _, conv_stages = build_stages(k, full=full)
        r = simulate_conventional_many(
            conv_stages, {mn: mk() for mn, mk in
                          standard_memory_models().items()}, n)
    return kname, what, r, time.perf_counter() - t0


#: Rough relative cost of a machine group, for longest-first scheduling.
_MACHINE_WEIGHT = {"dataflow": 3.0, "conventional": 1.2, "processor": 1.0}


def run_all(*, full: bool = True, jobs: int | None = None,
            kernels: tuple[str, ...] | None = None,
            workers: int | None = None,
            server: str | None = None,
            ) -> tuple[dict, dict, int, int]:
    """The full grid; returns (per-kernel results, per-task seconds,
    resolved job count, resolved per-task resolution workers).

    ``workers`` shards each dataflow task's trace resolution over the
    chunk-graph executor (default: leftover cores after the task pool,
    so ≥8-core machines shard the Floyd–Warshall tail instead of
    idling behind one bandwidth-bound worker; resolves to 1 — the
    streaming engine, no extra processes — on the 2-core CI
    container)."""
    kernels = tuple(kernels or ALL_KERNELS)
    if jobs is None:
        # one extra worker over the core count: the three Floyd–Warshall
        # machine groups are near-equal, so exact 2-way packing wastes a
        # core for the whole tail — oversubscription lets the scheduler
        # interleave them and the wall approaches total-CPU / cores
        jobs = min(multiprocessing.cpu_count() + 1, 4) if full \
            else min(2, multiprocessing.cpu_count())
    # the grid's wall clock IS the Floyd–Warshall dataflow task
    # (everything else overlaps under it — see task_s in
    # BENCH_sim.json), so on ≥4 cores always shard it: early in the
    # run the extra worker processes time-share with the other
    # tasks, and once only the tail task remains its workers own
    # the freed cores.  Below 4 cores the streaming engine wins
    # (sharding pays a second cache replay per chunk) — the shared
    # heuristic in repro.core.chunkgraph.default_workers.
    from repro.core.chunkgraph import default_workers
    workers = default_workers(jobs=jobs, explicit=workers, full=full)
    if server == "auto":
        from repro.serve import ensure_daemon
        server = ensure_daemon()
    tasks = [(kn, what, full, workers, server) for kn in kernels
             for what in ("dataflow", "conventional", "processor")]
    tasks.sort(key=lambda t: -(_make_kernel(t[0]).n_iters_full if full
                               else 1) * _MACHINE_WEIGHT[t[1]])
    sims: dict[tuple, object] = {}
    task_s: dict[str, float] = {}
    pool = None
    if jobs > 1:
        with cpu_children():
            pool = multiprocessing.get_context("spawn").Pool(jobs)
    try:
        results = (pool.imap_unordered(_sim_task, tasks) if pool
                   else map(_sim_task, tasks))
        for kn, what, group, dt in results:
            for mn, r in group.items():
                sims[(kn, what, mn)] = r
            task_s[f"{kn}/{what}"] = dt
            print(f"  [{kn}] {what:<12} all-mems "
                  f"({dt:.1f}s)", flush=True)
    finally:
        if pool is not None:
            pool.close()
            pool.join()

    results_out: dict[str, dict] = {}
    for kn in kernels:
        k = _make_kernel(kn)
        n = k.n_iters_full if full else k.n_iters_sim
        base = sims[(kn, "processor", "")]
        t_base = (base.runtime_s if full
                  else base.scaled_runtime(k.n_iters_full))
        out: dict = {"kernel": kn,
                     "n_iters_simulated": n,
                     "n_iters_full": k.n_iters_full,
                     "fully_simulated": bool(full),
                     "baseline_s": t_base}
        for mn in MEM_NAMES:
            df = sims[(kn, "dataflow", mn)]
            cv = sims[(kn, "conventional", mn)]
            t_df = (df.runtime_s if full
                    else df.scaled_runtime(k.n_iters_full))
            t_cv = (cv.runtime_s if full
                    else cv.scaled_runtime(k.n_iters_full))
            out[mn] = {
                "dataflow_s": t_df,
                "conventional_s": t_cv,
                "dataflow_cycles": df.cycles,
                "conventional_cycles": cv.cycles,
                "dataflow_vs_baseline": t_base / t_df,
                "conventional_vs_baseline": t_base / t_cv,
                "dataflow_vs_conventional": t_cv / t_df,
            }
        results_out[kn] = out
    return results_out, task_s, jobs, workers


def summarize(results: dict) -> dict:
    """Aggregate the paper's headline numbers from the per-kernel table."""
    pipelineable = [r for n, r in results.items() if n != "dfs"]

    def best_vs_best(r):
        """Paper §V-A: best dataflow config vs best conventional config."""
        best_df = min(r[m]["dataflow_s"] for m in MEM_NAMES)
        best_cv = min(r[m]["conventional_s"] for m in MEM_NAMES)
        return best_cv / best_df
    conv_cache_cut = np.mean(
        [1 - r["ACP+64KB"]["conventional_s"] / r["ACP"]["conventional_s"]
         for r in pipelineable])
    df_cache_cut = np.mean(
        [1 - r["ACP+64KB"]["dataflow_s"] / r["ACP"]["dataflow_s"]
         for r in pipelineable])
    summary = {
        "dataflow_vs_conventional_best": {
            n: best_vs_best(r) for n, r in results.items()},
        "avg_best_gain_pipelineable": float(np.mean(
            [best_vs_best(r) for r in pipelineable])),
        "avg_dataflow_vs_baseline_acp_pipelineable": float(np.mean(
            [r["ACP"]["dataflow_vs_baseline"] for r in pipelineable])),
        "conv_runtime_cut_by_cache": float(conv_cache_cut),
        "df_runtime_cut_by_cache": float(df_cache_cut),
        "conv_hp_vs_acp_slowdown": float(np.mean(
            [r["HP"]["conventional_s"] / r["ACP"]["conventional_s"]
             for r in pipelineable])),
    }
    if "dfs" in results:
        summary["dfs_best_gain"] = float(best_vs_best(results["dfs"]))
    return summary


def _rescache_disk_stats() -> dict:
    """Artifact count/bytes in the on-disk store (the workers of a spawn
    pool write there; the parent's in-process stats stay empty)."""
    from repro.core import rescache as _rc
    d = _rc._dir()
    try:
        files = os.listdir(d) if d and os.path.isdir(d) else []
        return {"dir": d, "artifacts": len(files),
                "bytes": sum(os.path.getsize(os.path.join(d, f))
                             for f in files)}
    except OSError:
        return {"dir": d, "artifacts": 0, "bytes": 0}


def main(out_path: str | None = "experiments/paper_fig5.json",
         *, quick: bool = False, jobs: int | None = None,
         kernels: tuple[str, ...] | None = None,
         rescache: bool = True, workers: int | None = None,
         server: str | None = None) -> dict:
    if not rescache:
        # spawn-pool workers inherit the environment, not configure()
        os.environ["REPRO_RESCACHE"] = "0"
        from repro.core import rescache as _rc
        _rc.configure(enabled=False)
    full = not quick
    mode = ("fully simulated (Table-I iteration counts)" if full
            else "extrapolated from a small window (--quick)")
    print(f"Fig. 5 grid — {mode}")
    t0 = time.perf_counter()
    results, task_s, jobs_used, workers_used = run_all(
        full=full, jobs=jobs, kernels=kernels, workers=workers,
        server=server)
    wall_s = time.perf_counter() - t0
    summary = summarize(results)
    print(f"\n{'kernel':<16}{'mem':<10}{'conv/base':>10}{'df/base':>10}"
          f"{'df/conv':>10}")
    for name, r in results.items():
        for m in MEM_NAMES:
            print(f"{name:<16}{m:<10}"
                  f"{r[m]['conventional_vs_baseline']:>10.2f}"
                  f"{r[m]['dataflow_vs_baseline']:>10.2f}"
                  f"{r[m]['dataflow_vs_conventional']:>10.2f}")
    print(f"\nwall-clock: {wall_s:.1f}s")
    print("summary:", json.dumps(summary, indent=1))
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump({"results": results, "summary": summary}, f,
                      indent=1, default=float)
    if full and (kernels is None or set(kernels) == set(ALL_KERNELS)):
        # perf trajectory: fig5 grid + vectorized-vs-reference timings
        # (--quick is a dev loop; only real full runs update BENCH)
        from repro.core import rescache as _rc
        from .sweep import measure_perf, update_bench
        update_bench("fig5", {"fully_simulated": True, "results": results,
                              "summary": summary})
        update_bench("fig5_wallclock", {
            "wall_s": wall_s,
            "jobs": jobs_used,
            "resolution_workers": workers_used,
            "resolution_mode": ("served" if server else
                                "streaming" if workers_used < 2 else
                                f"sharded:{workers_used}"),
            "server": server,
            "task_s": task_s,
            "rescache": rescache,
            "rescache_stats": _rc.stats(),  # parent process; workers own
            "rescache_disk": _rescache_disk_stats(),
            "pr2_baseline_cpu_s": PR2_BASELINE_CPU_S,
            "pr2_baseline_wall_2core_s": PR2_BASELINE_CPU_S / 2,
            "speedup_vs_pr2_wall": (PR2_BASELINE_CPU_S / 2) / wall_s,
        })
        update_bench("perf", measure_perf())
    return {"results": results, "summary": summary, "wall_s": wall_s}


def cli() -> dict:
    """Entry point parsing flags from sys.argv (shared with run.py, so
    ``run.py fig5 --quick`` behaves like ``python -m benchmarks.paper_fig5
    --quick``)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small-window extrapolated mode (development)")
    ap.add_argument("--full", action="store_true",
                    help="full Table-I simulation (the default; kept as "
                         "an explicit flag for scripts)")
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--kernels", nargs="*", default=None)
    ap.add_argument("--out", default="experiments/paper_fig5.json")
    ap.add_argument("--no-rescache", action="store_true",
                    help="bypass the resolved-trace cache (cold timings)")
    ap.add_argument("--workers", type=int, default=None,
                    help="shard each dataflow task's resolution over N "
                         "processes (chunk-graph executor; default: "
                         "leftover cores after the task pool)")
    ap.add_argument("--server", default=None, metavar="auto|ADDR",
                    help="delegate trace resolution to the resolution "
                         "daemon ('auto' spawns one for this store) — "
                         "bit-identical results, shared across clients")
    a, _ = ap.parse_known_args()
    return main(a.out, quick=a.quick, jobs=a.jobs,
                kernels=tuple(a.kernels) if a.kernels else None,
                rescache=not a.no_rescache, workers=a.workers,
                server=a.server)


if __name__ == "__main__":
    cli()

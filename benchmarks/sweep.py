"""Design-space sweep over the paper's kernels (Fig. 5, §V).

Grid: kernels × memory models (ACP / HP, ±64 KB System Cache) × FIFO
depths × ``mem_in_scc`` modes, each point **fully simulated** at the
Table-I iteration counts (no steady-state extrapolation — the vectorized
simulator streams even Floyd–Warshall's 1024^3 iterations).  This is the
sweep-style evaluation of de Fine Licht et al. / HIDA applied to the
dataflow template: how much FIFO depth the latency tolerance needs, what
the DFS pathology costs, and which memory port wins per kernel.

Also measures the simulator's own perf trajectory (vectorized vs the
scalar reference at 65536 iterations — the PR's ≥20× acceptance bar) and
writes everything to ``BENCH_sim.json`` (CI uploads it as an artifact).

``--smoke`` runs a reduced grid at small iteration counts (seconds) for
CI; the full sweep is a multi-hour batch job — ``--jobs``/-``--kernels``
split it.  ``--dse`` additionally explores the *partition space* per
kernel (``Compiled.explore``: merge/split/duplicate re-partitionings
under resource constraints, fully simulated, resolution shared through
the per-op rescache) and records each kernel's cycles-vs-FIFO-bits
Pareto front in the ``dse`` section of ``BENCH_sim.json``;
``--dse-only`` skips the grid.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import time

import numpy as np

from repro.core.engine import cpu_children
from repro.core.simulator import (MemAccess, SimStage, acp,
                                  simulate_conventional, simulate_dataflow,
                                  standard_memory_models)
from repro.dataflow import compile as dataflow_compile

from .paper_fig5 import MAX_OUTSTANDING, _make_kernel

BENCH_PATH = "BENCH_sim.json"
SMOKE_ITERS = 20_000
#: Full-scale sweep depths: both sized past the DRAM-spike threshold
#: (see benchmarks.paper_fig5.FIFO_DEPTH) so billion-iteration runs stay
#: on the solver's fast path; the smoke grid exercises a shallow FIFO.
FIFO_DEPTHS = (128, 256)
SCC_MODES = ("auto",)


def update_bench(section: str, payload: dict,
                 path: str = BENCH_PATH) -> None:
    """Merge one section into the BENCH_sim.json perf-trajectory file."""
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            data = {}
    data[section] = payload
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float)


def _perf_pipeline(n: int) -> list[SimStage]:
    rng = np.random.default_rng(0)
    return [
        SimStage("addr", ii=1, latency=2,
                 accesses=[MemAccess("idx", np.arange(n) * 4)]),
        SimStage("fetch", ii=1, latency=2,
                 accesses=[MemAccess("x", rng.integers(0, 4 << 20, n) * 4),
                           MemAccess("w", rng.integers(0, 4 << 20, n) * 4)]),
        SimStage("fma", ii=6, latency=8),
        SimStage("store", ii=1, latency=2,
                 accesses=[MemAccess("y", np.arange(n) * 4,
                                     is_store=True)]),
    ]


def measure_perf(n: int = 65536) -> dict:
    """Vectorized-vs-reference timing at ``n`` iterations (identical
    cycle counts asserted) — the perf trajectory tracked across PRs."""
    stages = _perf_pipeline(n)
    out: dict = {"n_iters": n}
    for label, mk in (("ACP", acp),):
        t0 = time.perf_counter()
        ref = simulate_dataflow(stages, mk(), n, fifo_depth=32,
                                reference=True)
        t1 = time.perf_counter()
        vec = simulate_dataflow(stages, mk(), n, fifo_depth=32)
        t2 = time.perf_counter()
        assert ref.cycles == vec.cycles, (ref.cycles, vec.cycles)
        cr0 = time.perf_counter()
        cref = simulate_conventional(stages, mk(), n, reference=True)
        cr1 = time.perf_counter()
        cvec = simulate_conventional(stages, mk(), n)
        cr2 = time.perf_counter()
        assert cref.cycles == cvec.cycles, (cref.cycles, cvec.cycles)
        out[label] = {
            "dataflow_reference_s": t1 - t0,
            "dataflow_vectorized_s": t2 - t1,
            "dataflow_speedup": (t1 - t0) / max(1e-9, t2 - t1),
            "conventional_reference_s": cr1 - cr0,
            "conventional_vectorized_s": cr2 - cr1,
            "conventional_speedup": (cr1 - cr0) / max(1e-9, cr2 - cr1),
            "vectorized_iters_per_s": n / max(1e-9, t2 - t1),
        }
    return out


def _sweep_task(task: tuple) -> list[dict]:
    """Sweep one kernel over one memory model (top-level for spawn).

    Within the task the planner in ``sweep_schedule`` shares all trace
    resolution across FIFO depths / SCC modes / port-knob variants (one
    streaming pass per SCC mode), and the resolved traces are memoized
    on disk so tasks in sibling processes — and later ``paper_fig5``
    runs — share with this one.  Reduced (``--smoke``) runs use the
    *full-scale* traces at a truncated iteration count, so the v3
    rescache prefix-serves them from any full-scale run's artifacts —
    and every row records ``n_iters_requested`` (the Table-I count) vs
    ``n_iters_simulated`` so trend comparisons never silently mix
    scales."""
    (kname, mem_name, fifo_depths, scc_modes, n_iters,
     wpcs, mos, workers, server, transform) = task
    k = _make_kernel(kname)
    n = n_iters or k.n_iters_full
    traces = k.full_traces
    compiled = dataflow_compile(
        k.loop_body, k.carry_example, *k.body_args, loop=True,
        nonaliasing_carries=getattr(k, "nonaliasing_carries", ()),
        transforms=transform)
    mems = {mem_name: standard_memory_models()[mem_name]}
    res = compiled.sweep(n_iters=n, mems=mems,
                         fifo_depths=fifo_depths, scc_modes=scc_modes,
                         traces=list(traces.values()),
                         max_outstanding=MAX_OUTSTANDING,
                         words_per_cycle=wpcs, max_outstandings=mos,
                         workers=workers, server=server)
    for row in res.rows:
        row["kernel"] = kname
        row["n_iters"] = n
        row["n_iters_requested"] = k.n_iters_full
        row["n_iters_simulated"] = n
        row["fully_simulated"] = n == k.n_iters_full
        row["trace_set"] = "full"
    return res.rows


def measure_worker_scaling(n: int | None = None) -> dict:
    """The chunk-graph worker-scaling probe: one fixed cached-model
    pipeline resolved cold by the streaming engine (``--workers 1``)
    and by the sharded executor at all cores, identical cycles
    asserted.  Recorded in ``BENCH_sim.json`` (``worker_scaling``) and
    trend-gated: the workers=1 wall must never regress, and the two
    modes must agree bit-for-bit — the speedup column documents what
    the fused effect+replay executor buys on this machine.  Each arm
    records its per-phase walls (effect / replay / fold / solve, see
    ``repro.core.engine.walls``) and the resolution-engine backend, so
    a trend regression is attributable to a phase instead of one
    opaque wall number."""
    from repro.core import engine as _eng
    from repro.core import rescache as _rc
    from repro.core.simulator import simulate_dataflow_many
    if n is None:
        # enough chunks for the pool to engage, and enough work that
        # the two spawn-context worker startups (~seconds) don't
        # dominate what the probe is actually measuring
        n = 8 * _rc.CHUNK_ITERS
    stages = _perf_pipeline(n)
    cpus = multiprocessing.cpu_count()
    out = {"n_iters": n, "cpus": cpus, "engine": _eng.current()}
    mems = standard_memory_models()
    _eng.reset_walls()
    t0 = time.perf_counter()
    r1 = simulate_dataflow_many(
        stages, {"ACP+64KB": mems["ACP+64KB"]()}, n, fifo_depths=(64,),
        collect_stalls=False, use_rescache=False)
    out["workers1_s"] = time.perf_counter() - t0
    out["phases_workers1"] = _eng.walls()
    w = max(2, cpus)
    _eng.reset_walls()
    t0 = time.perf_counter()
    rw = simulate_dataflow_many(
        stages, {"ACP+64KB": mems["ACP+64KB"]()}, n, fifo_depths=(64,),
        collect_stalls=False, use_rescache=False, workers=w)
    out["workers_all_s"] = time.perf_counter() - t0
    out["phases_workers_all"] = _eng.walls()
    _eng.reset_walls()
    out["workers_all"] = w
    out["identical"] = all(rw[key].cycles == r1[key].cycles
                           for key in r1)
    out["speedup"] = out["workers1_s"] / max(1e-9, out["workers_all_s"])
    return out


def run_dse(*, smoke: bool = False,
            kernels: tuple[str, ...] | None = None,
            out_path: str = BENCH_PATH,
            max_candidates: int = 16,
            rescache: bool = True,
            server: str | None = None) -> dict:
    """Partition-space DSE over the paper kernels (``--dse``).

    Per kernel: explore merge/split/duplicate re-partitionings of the
    Algorithm 1 plan with ``Compiled.explore`` (every candidate fully
    simulated; the per-op rescache shares trace resolution across
    candidates, so the whole exploration costs little more than one cold
    simulation) and record the cycles-vs-FIFO-bits Pareto front, the
    baseline, and whether some candidate strictly dominates Algorithm 1.
    The exploration is *widened* with the transformation catalog
    (unroll=2 ± coalescing as per-candidate lanes, joint with a halved
    FIFO depth so transformed points can win at equal bits) and spans
    two memory models (``ACP`` / ``ACP+64KB``) in one call; the entry
    records ``transformed_dominates`` — whether some transformed
    candidate strictly dominates the best untransformed point — which
    bench_trend hard-gates.
    ``--smoke`` explores the first two kernels at SMOKE_ITERS for CI;
    the full mode explores at the Table-I iteration counts (defaults to
    spmv — Floyd–Warshall's 10⁹-iteration traces exceed the artifact
    cap, so its candidates would each resolve cold).
    """
    from .paper_fig5 import FIFO_DEPTH
    if not rescache:
        os.environ["REPRO_RESCACHE"] = "0"
        from repro.core import rescache as _rc
        _rc.configure(enabled=False)
    if smoke:
        from .paper_kernels import ALL_KERNELS
        kernels = tuple(kernels or ALL_KERNELS)[:2]
        n_iters, fifo_depth = SMOKE_ITERS, 8
    else:
        kernels = tuple(kernels or ("spmv",))
        n_iters, fifo_depth = None, FIFO_DEPTH
    payload: dict = {"smoke": smoke, "fifo_depth": fifo_depth,
                     "max_candidates": max_candidates,
                     "trace_set": "full", "kernels": {}}
    t0 = time.perf_counter()
    for kn in kernels:
        k = _make_kernel(kn)
        n = n_iters or k.n_iters_full
        traces = k.full_traces
        compiled = dataflow_compile(
            k.loop_body, k.carry_example, *k.body_args, loop=True,
            nonaliasing_carries=getattr(k, "nonaliasing_carries", ()))
        mem = acp()
        mem.max_outstanding = MAX_OUTSTANDING
        # acceptance meter: one cold simulation of the Algorithm 1
        # partition under the repo's default regime (rescache enabled —
        # a cold run resolves *and stores*, exactly what the first fig5
        # or sweep cell pays).  Run at seed+1 so it neither serves from
        # nor pre-warms the DSE's own artifacts.
        from repro.core.simulator import simulate_dataflow
        from repro.dataflow.dse import (sim_stages_for_partition,
                                        traces_by_node)
        from repro.dataflow.schedule import _cyclic_nodes
        nt = traces_by_node(compiled.cdfg, compiled.partition,
                            list(traces.values()), n_iters=n)
        cyc = {x for x in _cyclic_nodes(compiled.cdfg)
               if compiled.cdfg.node(x).is_memory}
        base_stages = sim_stages_for_partition(compiled.partition, nt,
                                               cyc)
        from repro.core import rescache as _rc
        colds = []
        for probe_seed in (1, 2, 3):  # median of three: the artifact
            tc = time.perf_counter()  # store makes single timings noisy
            simulate_dataflow(base_stages, mem, n, fifo_depth=fifo_depth,
                              seed=probe_seed)
            colds.append(time.perf_counter() - tc)
            # evict the probe's artifact so re-runs stay cold (a warm
            # serve would fake the meter) and the store keeps only
            # artifacts real sweeps reuse
            _rc.evict(_rc.resolution_key("dataflow", base_stages, mem,
                                         probe_seed))
        cold_s = sorted(colds)[1]
        from repro.dataflow import TransformConfig
        mem64 = standard_memory_models()["ACP+64KB"]()
        mem64.max_outstanding = MAX_OUTSTANDING
        te = time.perf_counter()
        res = compiled.explore(
            n_iters=n, traces=list(traces.values()), mem=mem,
            mems=[mem, mem64],
            fifo_depth=fifo_depth,
            fifo_depths=[fifo_depth, max(1, fifo_depth // 2)],
            transforms=[TransformConfig(unroll=2),
                        TransformConfig(unroll=2, coalesce=True)],
            max_candidates=max_candidates,
            server=server)
        explore_s = time.perf_counter() - te  # incl. front Compiled
        entry = res.to_json()                 # artifact materialization
        entry["single_cold_s"] = cold_s
        entry["explore_wall_s"] = explore_s
        entry["cost_ratio_vs_cold"] = explore_s / max(1e-9, cold_s)
        payload["kernels"][kn] = entry
        print(f"  [{kn}] {res.summary()}", flush=True)
        print(f"  [{kn}] single cold sim {cold_s:.2f}s, DSE wall "
              f"{explore_s:.2f}s over {len(res.evaluated())} simulated "
              f"candidates ({entry['cost_ratio_vs_cold']:.2f}x; "
              f"{res.eval_stats.get('cold_groups', 0)} cold resolution "
              f"group(s))", flush=True)
    payload["wall_s"] = time.perf_counter() - t0
    update_bench("dse", payload, out_path)
    print(f"\nwrote dse section to {out_path} "
          f"({payload['wall_s']:.1f}s)")
    return payload


def run_sweep(*, smoke: bool = False, jobs: int | None = None,
              kernels: tuple[str, ...] | None = None,
              out_path: str = BENCH_PATH,
              words_per_cycle: tuple[float, ...] | None = None,
              max_outstandings: tuple[int, ...] | None = None,
              rescache: bool = True,
              workers: int | None = None,
              server: str | None = None) -> dict:
    from .paper_kernels import ALL_KERNELS
    if not rescache:
        os.environ["REPRO_RESCACHE"] = "0"  # spawn workers inherit env
        from repro.core import rescache as _rc
        _rc.configure(enabled=False)
    if server == "auto":
        # spawn (or find) the daemon for this store up front, then hand
        # every task the concrete address — job subprocesses must not
        # race to spawn their own
        from repro.serve import ensure_daemon
        server = ensure_daemon()
    kernels = tuple(kernels or ALL_KERNELS)
    if smoke:
        kernels = kernels[:2]
        mems = ("ACP", "ACP+64KB")
        fifo_depths, scc_modes, n_iters = (8,), ("auto",), SMOKE_ITERS
        if words_per_cycle is None:
            # exercise the port-knob axes + Pareto view in CI
            words_per_cycle = (0.5, 1.0)
    else:
        mems = tuple(standard_memory_models())
        fifo_depths, scc_modes, n_iters = FIFO_DEPTHS, SCC_MODES, None
    tasks = [(kn, mn, fifo_depths, scc_modes, n_iters,
              words_per_cycle, max_outstandings, workers, server, None)
             for kn in kernels for mn in mems]
    # the transformation-catalog axis: spmv re-swept under
    # unroll=2 (+coalescing) — the rows land with a distinct
    # ``transform`` signature so bench_trend keys them separately
    if "spmv" in kernels:
        from repro.dataflow import TransformConfig
        tf_mems = mems if smoke else ("ACP",)
        tasks += [("spmv", mn, fifo_depths, scc_modes, n_iters,
                   words_per_cycle, max_outstandings, workers, server,
                   TransformConfig(unroll=2, coalesce=True))
                  for mn in tf_mems]
    if jobs is None:
        jobs = 1 if smoke else min(2, multiprocessing.cpu_count())
    rows: list[dict] = []
    t0 = time.perf_counter()
    pool = None
    if jobs > 1:
        with cpu_children():
            pool = multiprocessing.get_context("spawn").Pool(jobs)
    try:
        parts = (pool.imap_unordered(_sweep_task, tasks) if pool
                 else map(_sweep_task, tasks))
        for part in parts:
            rows.extend(part)
            r = part[0]
            print(f"  [{r['kernel']}] {r['mem']:<9} done "
                  f"({len(part)} points)", flush=True)
    finally:
        if pool is not None:
            pool.close()
            pool.join()
    rows.sort(key=lambda r: (r["kernel"], r["mem"],
                             r.get("transform") or "none",
                             r["fifo_depth"], r["mem_in_scc"],
                             r["words_per_cycle"], r["max_outstanding"]))
    # per-kernel cycles-vs-FIFO-bits Pareto fronts (HIDA-style DSE view,
    # the same dominance rule as Compiled.sweep via SweepResult.pareto)
    from repro.dataflow.schedule import SweepResult
    fronts: dict[str, list] = {}
    for kn in kernels:
        krows = [r for r in rows if r["kernel"] == kn]
        front = SweepResult(krows, krows[0]["n_iters"]).pareto()
        fronts[kn] = [
            {"mem": r["mem"], "fifo_depth": r["fifo_depth"],
             "fifo_bits": r["fifo_bits"],
             "words_per_cycle": r["words_per_cycle"],
             "max_outstanding": r["max_outstanding"],
             "transform": r.get("transform") or "none",
             "dataflow_cycles": r["dataflow_cycles"]}
            for r in front]
    perf = measure_perf()
    scaling = measure_worker_scaling()
    payload = {"smoke": smoke, "wall_s": time.perf_counter() - t0,
               "workers": workers, "server": server, "rows": rows,
               "pareto": fronts}
    update_bench("sweep", payload, out_path)
    update_bench("perf", perf, out_path)
    update_bench("worker_scaling", scaling, out_path)
    if server:
        # the daemon's own telemetry (dedup rates, utilization, queue
        # wall) rides along so bench_trend can gate the serving path
        from repro.serve import ServeUnavailable, get_stats
        try:
            update_bench("serving_stats", get_stats(server), out_path)
        except ServeUnavailable:
            pass
    print(f"worker scaling: workers=1 {scaling['workers1_s']:.1f}s, "
          f"workers={scaling['workers_all']} "
          f"{scaling['workers_all_s']:.1f}s "
          f"({scaling['speedup']:.2f}x, identical="
          f"{scaling['identical']}) on {scaling['cpus']} cpus")
    print(f"\n{'kernel':<16}{'mem':<10}{'fifo':>5}{'wpc':>5}{'mo':>4}"
          f"{'df cyc/it':>11}{'conv cyc/it':>13}{'speedup':>9}")
    for r in rows:
        print(f"{r['kernel']:<16}{r['mem']:<10}{r['fifo_depth']:>5}"
              f"{r['words_per_cycle']:>5.2g}{r['max_outstanding']:>4}"
              f"{r['dataflow_cpi']:>11.2f}{r['conventional_cpi']:>13.2f}"
              f"{r['speedup']:>9.2f}")
    print(f"\nsimulator perf: dataflow {perf['ACP']['dataflow_speedup']:.0f}x"
          f" / conventional {perf['ACP']['conventional_speedup']:.0f}x"
          f" vectorized-vs-reference at {perf['n_iters']} iters; "
          f"wrote {out_path}")
    return payload


def main() -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grid at small iteration counts (CI)")
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--kernels", nargs="*", default=None)
    ap.add_argument("--out", default=BENCH_PATH)
    ap.add_argument("--words-per-cycle", nargs="*", type=float,
                    default=None, help="port bandwidth axis values")
    ap.add_argument("--max-outstandings", nargs="*", type=int,
                    default=None, help="in-flight request cap axis values")
    ap.add_argument("--no-rescache", action="store_true",
                    help="bypass the resolved-trace cache (cold timings)")
    ap.add_argument("--workers", type=int, default=None,
                    help="shard trace resolution over N processes per "
                         "sweep task (the chunk-graph executor; "
                         "bit-identical results)")
    ap.add_argument("--server", default=None, metavar="auto|ADDR",
                    help="delegate trace resolution to the resolution "
                         "daemon ('auto' spawns one for this store; "
                         "else an AF_UNIX path or host:port) — shared "
                         "pool, cross-client in-flight dedup, "
                         "bit-identical results")
    ap.add_argument("--dse", action="store_true",
                    help="also run the partition-space DSE and record "
                         "the Pareto fronts in BENCH_sim.json")
    ap.add_argument("--dse-only", action="store_true",
                    help="run only the DSE section (skip the sweep grid)")
    ap.add_argument("--dse-candidates", type=int, default=16)
    a, _ = ap.parse_known_args()
    kernels = tuple(a.kernels) if a.kernels else None
    out: dict = {}
    server = a.server
    if server == "auto":
        from repro.serve import ensure_daemon
        server = ensure_daemon()
    if not a.dse_only:
        out = run_sweep(smoke=a.smoke, jobs=a.jobs,
                        kernels=kernels,
                        out_path=a.out,
                        words_per_cycle=(tuple(a.words_per_cycle)
                                         if a.words_per_cycle else None),
                        max_outstandings=(tuple(a.max_outstandings)
                                          if a.max_outstandings else None),
                        rescache=not a.no_rescache,
                        workers=a.workers, server=server)
    if a.dse or a.dse_only:
        out["dse"] = run_dse(smoke=a.smoke, kernels=kernels,
                             out_path=a.out,
                             max_candidates=a.dse_candidates,
                             rescache=not a.no_rescache,
                             server=server)
    return out


if __name__ == "__main__":
    main()

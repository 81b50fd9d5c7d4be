"""Driver for cycle simulations of a compiled loop kernel.

Set-up compiles the configuration's loop body in loop mode, checks that
the compiler's pipeline is the configured one, attaches the seed's
traces and runs one whole simulation to warm every shape.  The window
runs whole simulations back to back (closed loop), each resolving cold
(the resolution cache is off), on the traffic's machine model, memory
model and engine.  After the window the plain reference simulates the
same pipeline on the same traces once, and every simulation of the run
must equal it field for field.
"""

from __future__ import annotations

import time

from harness import CompileCounter, closed_loop, device_info


def _memory_model(name: str, m: dict):
    from repro.core.simulator import CacheConfig, MemoryModel
    c = m.get("cache")
    return MemoryModel(
        name=name, port_latency=m["port_latency"],
        dram_latency=m["dram_latency"],
        backing_hit_rate=m["backing_hit_rate"],
        words_per_cycle=m["words_per_cycle"],
        max_outstanding=m["max_outstanding"],
        cache=CacheConfig(size_bytes=c["size_bytes"],
                          line_bytes=c["line_bytes"], ways=c["ways"],
                          hit_cycles=c["hit_cycles"]) if c else None)


def _flatten(r) -> dict:
    out = {"cycles": int(r.cycles), "cache_hits": int(r.cache_hits),
           "cache_misses": int(r.cache_misses)}
    for stage, buckets in r.stage_stall_cycles.items():
        for bucket, v in buckets.items():
            out[f"stall.{stage}.{bucket}"] = int(v)
    return out


def _differing(got: dict, want: dict) -> list[str]:
    return sorted(k for k in set(got) | set(want)
                  if got.get(k, 0) != want.get(k, 0))


def pipeline_of(stages) -> list[dict]:
    return [{"name": s.name, "ii": int(s.ii), "latency": int(s.latency),
             "regions": [a.region for a in s.accesses],
             "mem_in_scc": bool(s.mem_in_scc)} for s in stages]


def run(ctx) -> dict:
    from repro import dataflow
    from repro.core import engine as eng
    from repro.core.simulator import (MemAccess, ProcessorModel,
                                      simulate_dataflow, simulate_processor)

    cfg, traffic, ref = ctx.cell.config, ctx.cell.traffic, \
        ctx.cell.config_module
    n = cfg["iterations"]
    body, carry, args = ref.loop_kernel(cfg, ctx.seed)
    compiled = dataflow.compile(body, carry, *args, loop=True)
    gens = ref.traces(cfg, ctx.seed)
    accesses = {r: MemAccess(r, gen=g, length=n) for r, g in gens.items()}
    order = [r for st in cfg["pipeline"] for r in st["regions"]]
    stages = compiled.sim_stages(traces=[accesses[r] for r in order])
    pipeline_diff = sum(a != b for a, b in zip(pipeline_of(stages),
                                               cfg["pipeline"])) \
        + abs(len(stages) - len(cfg["pipeline"]))

    model = traffic["model"]
    if model == "dataflow":
        memory = traffic["memory"]
        mem = _memory_model(memory, cfg["memory_models"][memory])

        def simulate():
            return simulate_dataflow(
                stages, mem, n, fifo_depth=cfg["fifo_depth"],
                freq_mhz=cfg["freq_mhz"], seed=ctx.seed,
                use_rescache=traffic["rescache"], engine=traffic["engine"])
    else:
        p = cfg["processor"]
        proc = ProcessorModel(freq_mhz=p["freq_mhz"], ipc=p["ipc"],
                              l1_kb=p["l1_kb"], l2_kb=p["l2_kb"],
                              l1_hit=p["l1_hit"], l2_hit=p["l2_hit"],
                              dram=p["dram"])
        proc_traces = [accesses[r] for r in p["access_order"]]

        def simulate():
            with eng.use(traffic["engine"]):
                return simulate_processor(
                    p["instrs_per_iter"], proc_traces, n, model=proc,
                    use_rescache=traffic["rescache"])

    results = [_flatten(simulate())]          # warm-up: every shape
    counter = CompileCounter()
    eng.reset_walls()
    eng.reset_dispatches()
    t_window = time.perf_counter()

    def unit(k: int) -> dict:
        results.append(_flatten(simulate()))
        return {"iterations": n}

    w = closed_loop(unit, ctx.seconds, trace=ctx.trace, counter=counter)
    walls, dispatches = eng.walls(), eng.dispatches()
    device = device_info(ctx.devices)

    if model == "dataflow":
        want = ref.simulate_dataflow(cfg, traffic["memory"], ctx.seed)
    else:
        want = ref.simulate_processor(cfg, ctx.seed)
    bad = [_differing(r, want) for r in results]
    checks = [
        {"name": "pipeline_fields_differing", "value": pipeline_diff,
         "limit": 0},
        {"name": "result_fields_differing", "value": max(map(len, bad)),
         "limit": 0},
        {"name": "simulations_differing", "value": sum(map(bool, bad)),
         "limit": 0},
    ]
    return {
        "correct": all(c["value"] <= c["limit"] for c in checks),
        "attempted": len(w.units), "failed": 0, "checks": checks,
        "setup_s": t_window - ctx.t_start, "window_s": w.window_s,
        "units": w.units, "device": device, "trace": w.trace,
        "compiles_in_window": w.compiles, "walls": walls,
        "dispatches": dispatches, "iterations": n, "reference": want,
        "differing_fields": sorted({k for b in bad for k in b}),
    }


def control(ctx, run: dict) -> list[dict]:
    """The controls' readings: the reference with one stated guarantee
    broken (``CONTROLS`` of the configuration module) in the program's
    place, as fields differing from the plain reference."""
    cfg, traffic, ref = ctx.cell.config, ctx.cell.traffic, \
        ctx.cell.config_module
    out = []
    for name in ref.CONTROLS[traffic["model"]]:
        if traffic["model"] == "dataflow":
            got = ref.simulate_dataflow(cfg, traffic["memory"], ctx.seed,
                                        control=name)
        else:
            got = ref.simulate_processor(cfg, ctx.seed, control=name)
        out.append({"name": f"result_fields_differing.{name}",
                    "value": len(_differing(got, run["reference"])),
                    "limit": 0})
    return out

"""Driver for a language model with expert layers, served by static
batches as one chip's share of an expert-parallel deployment.

The run is that of ``serve_lm.py``, whose batch plan (``batch_plan``,
``prompts``, ``sample_batches``, ``requested_lengths``) it loads and
reuses: set-up builds the program's model configuration, draws the
weights on the device from the seed, starts ``BatchedServer`` and serves
one short batch at every prompt length of the traffic; afterwards whole
finished batches drawn from the seed are run teacher-forced through the
plain reference.  The window is a closed loop of whole decks: a unit is
one batch at each prompt length of the traffic, in the seed's order, so
every window holds the same mix of prompt lengths whatever the seed; the
run record keeps one record a batch, each with its own latency.

The comparison is the mean, over the compared tokens, of how far the
reference's logit of each served token lies below its best: a near-tie in
a token's top-k routing that bf16 breaks otherwise than float32 moves
that token's held experts and can move its logits far, but only for the
few tokens it touches, so the widest gap reads near the top of the
logits' spread in a sound run and the mean does not (PERF.md).

What belongs to the configuration comes from its module: the check of the
program's configuration (``config_differences``), the forward FLOPs of a
request (``request_flops``) and the bytes a decode step must read
(``decode_step_bytes``).  The program's spans and counters are cleared
after the warm-up, and the run record holds what the window left in them
(``walls``, ``counts``), among them the server's ``moe.*`` pair counters.
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from harness import CompileCounter, closed_loop, device_info, load_module

lm = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "serve_lm.py"), "bench_driver_serve_lm")


def program_config(cfg: dict):
    """The program's configuration of ``cfg["program"]["arch"]`` with the
    file's overrides; an override of ``mla`` or ``moe`` is a dict of that
    group's fields."""
    from repro.configs.base import load_config
    prog = cfg["program"]
    base = load_config(prog["arch"])
    over = dict(prog.get("overrides", {}))
    for group in ("mla", "moe"):
        if group in over:
            over[group] = dataclasses.replace(getattr(base, group),
                                              **over[group])
    return dataclasses.replace(base, **over)


def run(ctx) -> dict:
    import jax

    from repro import dataflow, trace
    from repro.launch.serve import BatchedServer, Request

    cfg, traffic, ref = ctx.cell.config, ctx.cell.traffic, \
        ctx.cell.config_module
    pcfg = program_config(cfg)
    config_diff = ref.config_differences(pcfg, cfg)
    params = jax.block_until_ready(ref.make_params(cfg, ctx.seed))
    server = BatchedServer(pcfg, params, max_len=traffic["max_len"])

    def batch(k: int, length: int, gens: list[int], base: int):
        toks = lm.prompts(traffic, ctx.seed, base + k, length)
        return [Request(base + k * traffic["batch"] + i, toks[i], g)
                for i, g in enumerate(gens)]

    for j, length in enumerate(sorted(traffic["prompt_lengths"])):
        server.serve(batch(j, length, [2] * traffic["batch"], 1 << 20))

    counter = CompileCounter()
    trace.reset()
    t_window = time.perf_counter()
    served: list[list[dict]] = []       # per batch, per slot
    deck = len(traffic["prompt_lengths"])

    def one_batch(b: int) -> dict:
        length, gens = lm.batch_plan(traffic, ctx.seed, b)
        reqs = batch(b, length, gens, 0)
        t = time.perf_counter()
        res = server.serve(reqs)
        latency = time.perf_counter() - t
        served.append([{"prompt": q.prompt.tolist(), "tokens": r.tokens,
                        "requested": q.max_new_tokens}
                       for r, q in zip(res, reqs)])
        got = [min(len(r.tokens), q.max_new_tokens)
               for r, q in zip(res, reqs)]
        steps = max(gens)
        return {
            "requests": len(reqs), "prompt_len": length,
            "t_start": t - t_window, "latency_s": latency,
            "request_tokens": got, "useful_tokens": sum(got),
            "flops": sum(ref.request_flops(cfg, length, q.max_new_tokens)
                         for q in reqs),
            "prefill_s": res[0].prefill_s,
            "decode_s_per_step": res[0].decode_s,
            "decode_steps": steps,
            "decode_bytes": sum(ref.decode_step_bytes(
                cfg, len(reqs), length + j + 1) for j in range(steps)),
        }

    def unit(k: int) -> dict:
        return {"batches": [one_batch(k * deck + i) for i in range(deck)]}

    w = closed_loop(unit, ctx.seconds, trace=ctx.trace, counter=counter)
    # one record a batch; closed_loop's clock starts a hair after t_window
    shift = w.t0 - t_window
    units = [dict(b, t_start=b["t_start"] - shift,
                  t_done=b["t_start"] - shift + b["latency_s"])
             for u in w.units for b in u["batches"]]
    walls, counts = trace.walls(), trace.counts()
    device = device_info(ctx.devices)
    # the program's state: the server, and the dataflow compile cache,
    # which keeps the example arguments of every step it compiled
    del server, params
    dataflow.clear_cache()

    sample, total = lm.sample_batches(served, ctx.seed,
                                      traffic["sample_tokens"])
    short = sum(len(s["tokens"]) < s["requested"]
                for b in served for s in b)
    gaps = ref.reference_gaps(cfg, ctx.seed, sample, traffic["max_len"],
                              traffic["reference_rows"])
    stats = gap_stats([g["gaps"] for g in gaps])
    checks = [
        {"name": "config_fields_differing", "value": len(config_diff),
         "limit": 0},
        {"name": "requests_short", "value": short, "limit": 0},
        {"name": "mean_logit_gap", "value": stats["mean"],
         "limit": traffic["mean_logit_gap_limit"]},
        {"name": "compared_tokens_missing",
         "value": max(0, traffic["sample_tokens"] - total), "limit": 0},
    ]
    correct = all(c["value"] <= c["limit"] for c in checks)
    return {
        "correct": bool(correct),
        "attempted": sum(u["requests"] for u in units), "failed": short,
        "checks": checks, "setup_s": t_window - ctx.t_start,
        "window_s": w.window_s, "units": units, "device": device,
        "trace": w.trace, "compiles_in_window": w.compiles,
        "config_differences": config_diff, "sample": sample,
        "gap_stats": stats, "walls": walls, "counts": counts,
        "experts_held": pcfg.moe.held,
    }


def gap_stats(gaps: list[list[float]]) -> dict:
    """Readings of the compared tokens' gaps (one list a request): their
    mean, the widest, the 99th percentile, the share (%) above 0.25 and
    above 0.5, and the largest mean of one request."""
    flat = np.concatenate([np.asarray(g, np.float64) for g in gaps])
    return {"mean": float(flat.mean()), "widest": float(flat.max()),
            "p99": float(np.quantile(flat, 0.99)),
            "share_above_0.25": float(100 * (flat > 0.25).mean()),
            "share_above_0.5": float(100 * (flat > 0.5).mean()),
            "worst_request_mean": float(max(np.mean(g) for g in gaps))}


def control(ctx, run: dict) -> list[dict]:
    """Readings of the same sample with the reference in the program's
    place, each position read at the token that it puts first: in fp8
    (has to fail), with held expert 0 zeroed in every expert layer in
    bf16 (a planted fault, has to fail), and in bf16 (the program's
    precision, a witness of what rounding alone gives); the served run's
    own readings; and the share of the sample's token-layers whose top-k
    set moves when the router's operands are rounded to bf16."""
    cfg, traffic, ref = ctx.cell.config, ctx.cell.traffic, \
        ctx.cell.config_module
    params = ref.make_params(cfg, ctx.seed)
    controls = {"fp8": ("fp8", None),
                "expert0_zeroed": ("bf16", ref.zero_held_expert(params, 0)),
                "bf16": ("bf16", None)}
    gaps = ref.reference_gaps(cfg, ctx.seed, run["sample"],
                              traffic["max_len"], traffic["reference_rows"],
                              controls=controls, params=params)
    limit = traffic["mean_logit_gap_limit"]
    out = []
    for name in controls:
        stats = gap_stats([g["control_gaps"][name] for g in gaps])
        out += [{"name": f"{k}.{name}", "value": v,
                 "limit": limit if k == "mean" else None}
                for k, v in stats.items()]
    out += [{"name": f"{k}.served", "value": v, "limit": None}
            for k, v in run["gap_stats"].items()]
    blocks = gaps[::traffic["reference_rows"]]
    return out + [{"name": "routing_moved.bf16",
                   "value": sum(g["route_moved"] for g in blocks)
                   / sum(g["route_tokens"] for g in blocks),
                   "limit": None}]

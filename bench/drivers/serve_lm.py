"""Driver for a language model served by static batches.

Set-up builds the program's model configuration (checked against the
configuration file), draws the weights on the device from the seed,
starts ``BatchedServer`` and serves one short batch at every prompt
length of the traffic, so every program the window uses is compiled.
The window is a closed loop of whole batches: a batch is admitted when
the last has returned.  Each batch has one prompt length from the
traffic's deck (one order per seed) and the traffic's fixed set of
requested lengths (one order per batch); prompt tokens are drawn from
the seed.  After the window the program's state is freed, whole finished
batches are drawn from the seed (every slot, the longest request among
them), and the plain reference is run teacher-forced over each prompt
and its served tokens: the widest gap by which a served token's
reference logit lies below the reference's best is compared with the
traffic's limit.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from harness import CompileCounter, closed_loop, device_info
from flops import dense_request_flops, mlp_hidden


def program_config(cfg: dict):
    from repro.configs.base import load_config
    prog = cfg["program"]
    return dataclasses.replace(load_config(prog["arch"]),
                               **prog.get("overrides", {}))


def config_differences(pcfg, cfg: dict) -> list[str]:
    """Fields in which the program's configuration departs from the
    configuration file."""
    want = {
        "d_model": cfg["d_model"], "num_heads": cfg["n_heads"],
        "num_kv_heads": 1 if cfg["multi_query_attention"] else cfg["n_heads"],
        "num_layers": cfg["n_layers"], "d_ff": mlp_hidden(cfg) // 2,
        "vocab_size": cfg["embedding_size"],
        "tie_embeddings": cfg["weight_tying"],
        "norm": "nonparametric_ln"
        if not cfg["layer_norm_with_affine"] else "layernorm",
        "act": "silu" if cfg["activation_type"] == "swiglu" else "gelu",
        "qkv_bias": cfg["include_bias"],
        "rope_theta": float(cfg["rope_theta"]),
        "dtype": cfg["served_dtype"], "parallel_block": False,
    }
    return [k for k, v in want.items() if getattr(pcfg, k) != v]


def requested_lengths(traffic: dict) -> list[int]:
    """The fixed set of new-token counts of one batch: the midpoints of
    ``batch`` equal slices of the traffic's range."""
    lo, hi, b = traffic["new_tokens"][0], traffic["new_tokens"][1], \
        traffic["batch"]
    return [int(lo + (hi - lo) * (j + 0.5) / b) for j in range(b)]


def batch_plan(traffic: dict, seed: int, k: int) -> tuple[int, list[int]]:
    """Prompt length and requested lengths of the run's ``k``-th batch."""
    deck = np.random.default_rng([seed, 0]).permutation(
        traffic["prompt_lengths"])
    gens = np.random.default_rng([seed, 1, k]).permutation(
        requested_lengths(traffic))
    return int(deck[k % len(deck)]), [int(g) for g in gens]


def prompts(traffic: dict, seed: int, k: int, length: int) -> np.ndarray:
    return np.random.default_rng([seed, 2, k]).integers(
        0, traffic["token_ids"], (traffic["batch"], length), dtype=np.int32)


def sample_batches(batches: list[list[dict]], seed: int,
                   tokens: int) -> tuple[list[dict], int]:
    """The finished requests that the reference checks, and how many
    tokens they were served: whole batches, so that every slot of the
    batch is compared.  The batch that holds the longest request comes
    first, then the others in an order drawn from the seed, until at
    least ``tokens`` served tokens are in."""
    order = [int(k) for k in
             np.random.default_rng([seed, 3]).permutation(len(batches))]
    longest = max(order, key=lambda k: max(len(r["tokens"])
                                           for r in batches[k]))
    sample, total = [], 0
    for k in [longest] + [k for k in order if k != longest]:
        if total >= tokens:
            break
        sample += batches[k]
        total += sum(len(r["tokens"]) for r in batches[k])
    return sample, total


def run(ctx) -> dict:
    import jax

    from repro import dataflow
    from repro.launch.serve import BatchedServer, Request

    cfg, traffic, ref = ctx.cell.config, ctx.cell.traffic, \
        ctx.cell.config_module
    pcfg = program_config(cfg)
    config_diff = config_differences(pcfg, cfg)
    params = jax.block_until_ready(ref.make_params(cfg, ctx.seed))
    server = BatchedServer(pcfg, params, max_len=traffic["max_len"])

    def batch(k: int, length: int, gens: list[int], base: int):
        toks = prompts(traffic, ctx.seed, base + k, length)
        return [Request(base + k * traffic["batch"] + i, toks[i], g)
                for i, g in enumerate(gens)]

    for j, length in enumerate(sorted(traffic["prompt_lengths"])):
        server.serve(batch(j, length, [2] * traffic["batch"], 1 << 20))

    counter = CompileCounter()
    t_window = time.perf_counter()
    served: list[list[dict]] = []       # per batch, per slot

    def unit(k: int) -> dict:
        length, gens = batch_plan(traffic, ctx.seed, k)
        reqs = batch(k, length, gens, 0)
        t = time.perf_counter()
        res = server.serve(reqs)
        latency = time.perf_counter() - t
        served.append([{"prompt": q.prompt.tolist(), "tokens": r.tokens,
                        "requested": q.max_new_tokens}
                       for r, q in zip(res, reqs)])
        got = [min(len(r.tokens), q.max_new_tokens)
               for r, q in zip(res, reqs)]
        return {
            "requests": len(reqs), "latency_s": latency,
            "request_tokens": got, "useful_tokens": sum(got),
            "flops": sum(dense_request_flops(cfg, length, q.max_new_tokens)
                         for q in reqs),
            "prefill_s": res[0].prefill_s,
            "decode_s_per_step": res[0].decode_s,
        }

    w = closed_loop(unit, ctx.seconds, trace=ctx.trace, counter=counter)
    device = device_info(ctx.devices)
    # the program's state: the server, and the dataflow compile cache,
    # which keeps the example arguments (weights, KV cache) of every step
    # it compiled
    del server, params
    dataflow.clear_cache()

    sample, total = sample_batches(served, ctx.seed,
                                   traffic["sample_tokens"])
    short = sum(len(s["tokens"]) < s["requested"]
                for b in served for s in b)
    gaps = ref.reference_gaps(cfg, ctx.seed, sample, traffic["max_len"],
                              traffic["reference_rows"])
    widest = max(max(g["gaps"]) for g in gaps)
    checks = [
        {"name": "config_fields_differing", "value": len(config_diff),
         "limit": 0},
        {"name": "requests_short", "value": short, "limit": 0},
        {"name": "widest_logit_gap", "value": float(widest),
         "limit": traffic["logit_gap_limit"]},
        {"name": "compared_tokens_missing",
         "value": max(0, traffic["sample_tokens"] - total), "limit": 0},
    ]
    correct = all(c["value"] <= c["limit"] for c in checks)
    return {
        "correct": bool(correct),
        "attempted": sum(u["requests"] for u in w.units), "failed": short,
        "checks": checks, "setup_s": t_window - ctx.t_start,
        "window_s": w.window_s, "units": w.units, "device": device,
        "trace": w.trace, "compiles_in_window": w.compiles,
        "config_differences": config_diff, "sample": sample,
    }


def control(ctx, run: dict) -> list[dict]:
    """The control's reading of the same sample: the reference in fp8 in
    the program's place, each position read at the token that it puts
    first (see the configuration module's ``reference_gaps``)."""
    cfg, traffic, ref = ctx.cell.config, ctx.cell.traffic, \
        ctx.cell.config_module
    gaps = ref.reference_gaps(cfg, ctx.seed, run["sample"],
                              traffic["max_len"], traffic["reference_rows"],
                              precision="fp8")
    return [{"name": "widest_logit_gap.fp8",
             "value": float(max(max(g["control_gaps"]) for g in gaps)),
             "limit": traffic["logit_gap_limit"]}]

#!/usr/bin/env python3
"""Chip smoke: the system's main path once on a TPU, every phase checked
against a reference computed independently in this process.

    python chip_smoke.py             # one chip: phases 1-3 below
    python chip_smoke.py --chips 4   # four chips: systolic + pipeline_apply

One chip:

1. Resolution engine.  Table-I spmv (4,194,304 iterations, 4096^2 matrix
   at density 0.25) compiled in loop mode and simulated in streaming mode
   on the dataflow model under ACP+64KB and on the processor model, once
   with the jax engine (rescache off) and once with numpy.  Cycles, stall
   buckets and cache hits/misses must be bit-identical, and every jax
   kernel dispatch must have come back from the TPU.
2. Compiled programs.  The quickstart kernel through the sequential,
   emulated and xla backends against the direct call; ``spmv_bsr`` at
   Table-I size against a float32 dense product on the host; and
   ``decoupled_gather`` against ``decoupled_gather_ref``.  Both kernels
   must compile to ``tpu_custom_call`` (no interpret mode).
3. LM serving at published width.  SmolLM-135M (30 layers, d_model 576,
   9/3 heads, d_ff 1536, vocab 49,152) with weights from ``--seed``,
   served by ``BatchedServer``: 4 requests, 128-token prompts, 32 new
   tokens each.  The prefill logits are compared with the same forward in
   float32 on this process's CPU device.

Four chips: the ``systolic`` backend (one pipeline stage per chip) against
``sequential``, and ``pipeline_apply`` forward and ``jax.grad`` over a
4-chip ``stage`` mesh against ``pipeline_apply_emulated``.

Each phase prints one JSON line (wall seconds, counts, mismatches).  The
last line is ``{"ok": true, "device": {...}}``; without a TPU, or if any
check fails, the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402

#: relative L2 error allowed between the bf16 prefill logits on the chip
#: and the float32 CPU forward (bf16 keeps 8 mantissa bits; 30 residual
#: layers compound a few per mille per layer into percents)
LOGITS_REL_L2 = 5e-2

#: spmv_bsr: |y - y_ref| <= SPMV_RTOL * sum_j |a_ij x_j| per row — float32
#: summation order differs between the kernel and the host product
SPMV_RTOL = 1e-5

#: the kernel-applied tanh against XLA's (two lowerings of one function)
GATHER_TANH_ATOL = 1e-5

#: Table I: spmv over a 4096 x 4096 matrix at density 0.25
SPMV_DIM, SPMV_DENSITY = 4096, 0.25


class SmokeError(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def emit(phase: str, t0: float, **info) -> None:
    print(json.dumps({"phase": phase, "seconds": time.perf_counter() - t0,
                      **info}), flush=True)


# ---------------------------------------------------------------------------
# Phase 1: resolution engine on Table-I spmv
# ---------------------------------------------------------------------------

def phase_engine(seed: int) -> None:
    from benchmarks.paper_fig5 import FIFO_DEPTH, _dataflow_mems
    from benchmarks.paper_kernels import make_spmv
    from repro import dataflow
    from repro.core import engine as eng
    from repro.core.simulator import simulate_dataflow, simulate_processor

    t0 = time.perf_counter()
    k = make_spmv(1.0, seed=seed)
    compiled = dataflow.compile(k.loop_body, k.carry_example, *k.body_args,
                                loop=True)
    traces = list(k.full_traces.values())
    stages = compiled.sim_stages(traces=traces)
    n = k.n_iters_full
    check(n == 4_194_304, f"spmv iterations {n}")
    emit("engine.setup", t0, iterations=n, stages=len(stages))

    runs = {}
    for name in ("jax", "numpy"):
        eng.reset_dispatches()
        eng.reset_walls()
        t0 = time.perf_counter()
        df = simulate_dataflow(stages, _dataflow_mems()["ACP+64KB"], n,
                               fifo_depth=FIFO_DEPTH, use_rescache=False,
                               engine=name)
        with eng.use(name):
            proc = simulate_processor(k.instrs_per_iter, traces, n,
                                      use_rescache=False)
        runs[name] = (vars(df), vars(proc), eng.dispatches())
        emit(f"engine.{name}", t0, dataflow_cycles=df.cycles,
             dataflow_stalls=df.total_stalls(),
             dataflow_cache=[df.cache_hits, df.cache_misses],
             processor_cycles=proc.cycles,
             processor_cache=[proc.cache_hits, proc.cache_misses],
             dispatches=eng.dispatches(), phase_walls=eng.walls())

    (df_j, pr_j, disp_j), (df_n, pr_n, disp_n) = runs["jax"], runs["numpy"]
    mism = [key for key in df_n if df_j[key] != df_n[key]] \
        + [f"processor.{key}" for key in pr_n if pr_j[key] != pr_n[key]]
    emit("engine.compare", time.perf_counter(), mismatched_fields=mism)
    check(not mism, f"jax and numpy engines differ in {mism}")
    check(not disp_n, f"numpy run dispatched to a device: {disp_n}")
    check(disp_j.get("pallas_running_max@tpu", 0) > 0
          and disp_j.get("nway@tpu", 0) > 0
          and all(key.endswith("@tpu") for key in disp_j),
          f"jax run did not run its kernels on the TPU: {disp_j}")


# ---------------------------------------------------------------------------
# Phase 2: compiled programs and the template's Pallas kernels
# ---------------------------------------------------------------------------

def phase_programs(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.dataflow import dataflow_jit
    from repro.kernels import csr_to_bsr, spmv
    from repro.kernels.decoupled_gather import (decoupled_gather,
                                                decoupled_gather_ref)

    # the quickstart kernel (examples/quickstart.py) on every backend that
    # runs on one chip
    @dataflow_jit(stream_argnums=(1,))
    def kernel(table, idx, w):
        g = table[idx]
        h = g * w
        return jnp.tanh(h) + 1.0

    t0 = time.perf_counter()
    table = jnp.arange(1024, dtype=jnp.float32)
    idx = jnp.asarray([3, 997, 41, 512, 7, 800, 64, 2])
    w = jnp.float32(1.5)
    ref = np.asarray(kernel.__wrapped__(table, idx, w))
    for name in ("sequential", "emulated", "xla"):
        got = np.asarray(kernel(table, idx, w, backend=name))
        np.testing.assert_allclose(got, ref, rtol=1e-6)
    emit("programs.quickstart", t0, backends=["sequential", "emulated",
                                               "xla"],
         stages=kernel.lower(table, idx, w).num_stages)

    # spmv_bsr at Table-I size against a float32 dense product
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    dim = SPMV_DIM
    dense = np.where(rng.random((dim, dim)) < SPMV_DENSITY,
                     rng.standard_normal((dim, dim)), 0).astype(np.float32)
    rows, cols = np.nonzero(dense)
    indptr = np.concatenate([[0], np.cumsum(np.bincount(rows,
                                                        minlength=dim))])
    values, col_ids = csr_to_bsr(indptr, cols, dense[rows, cols],
                                 (dim, dim))
    x = rng.standard_normal(dim).astype(np.float32)
    args = (jnp.asarray(values), jnp.asarray(col_ids), jnp.asarray(x))
    hlo = jax.jit(spmv).lower(*args).compile().as_text()
    check("tpu_custom_call" in hlo, "spmv_bsr did not compile to a kernel")
    y = np.asarray(jax.block_until_ready(spmv(*args)))
    want = dense @ x
    bound = SPMV_RTOL * (np.abs(dense) @ np.abs(x))
    bad = int((np.abs(y - want) > bound).sum())
    emit("programs.spmv_bsr", t0, nnz=int(len(rows)),
         blocks=list(values.shape[:2]), mismatches=bad,
         max_abs_err=float(np.abs(y - want).max()))
    check(bad == 0, f"spmv_bsr: {bad} rows outside the bound")

    # decoupled_gather: exact on an exact row function, and the default
    # tanh row function within GATHER_TANH_ATOL
    t0 = time.perf_counter()
    tab = jnp.asarray(rng.standard_normal((8192, 256)).astype(np.float32))
    gidx = jnp.asarray(rng.integers(0, 8192, 4096).astype(np.int32))
    hlo = decoupled_gather.lower(gidx, tab).compile().as_text()
    check("tpu_custom_call" in hlo,
          "decoupled_gather did not compile to a kernel")
    got = np.asarray(decoupled_gather(gidx, tab, fn=_double))
    exact_bad = int((got != np.asarray(
        decoupled_gather_ref(gidx, tab, fn=_double))).any(axis=1).sum())
    got = np.asarray(decoupled_gather(gidx, tab))
    tanh_err = float(np.abs(got - np.asarray(
        decoupled_gather_ref(gidx, tab))).max())
    emit("programs.decoupled_gather", t0, rows=int(gidx.shape[0]),
         exact_mismatched_rows=exact_bad, tanh_max_abs_err=tanh_err)
    check(exact_bad == 0, f"decoupled_gather: {exact_bad} rows differ")
    check(tanh_err <= GATHER_TANH_ATOL, f"decoupled_gather tanh {tanh_err}")


def _double(row):
    return row * 2.0


# ---------------------------------------------------------------------------
# Phase 3: LM serving at SmolLM-135M's published width
# ---------------------------------------------------------------------------

def phase_serving(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import load_config
    from repro.launch.serve import BatchedServer, Request
    from repro.models import init_params, prefill

    t0 = time.perf_counter()
    cfg = load_config("smollm-135m")
    check((cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size) == (30, 576, 9, 3, 1536, 49152),
          "smollm-135m is not at its published width")
    params = init_params(jax.random.PRNGKey(seed), cfg)
    prompt_len, gen, batch = 128, 32, 4
    max_len = prompt_len + gen + 8
    rng = np.random.default_rng(seed)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, prompt_len)
                    .astype(np.int32), gen) for i in range(batch)]
    server = BatchedServer(cfg, params, max_len=max_len)
    logits = np.asarray(jax.block_until_ready(server.prefill(reqs)[0]),
                        np.float32)
    emit("serving.setup", t0, params=int(sum(
        a.size for a in jax.tree_util.tree_leaves(params))))

    t0 = time.perf_counter()
    warm = server.serve(reqs)        # compiles the decode step
    res = server.serve(reqs)
    check(all(len(r.tokens) == gen for r in res), "short generation")
    check([r.tokens for r in res] == [r.tokens for r in warm],
          "greedy decode is not deterministic")
    emit("serving.serve", t0, requests=batch, prompt_tokens=prompt_len,
         new_tokens=sum(len(r.tokens) for r in res),
         prefill_s=res[0].prefill_s, decode_ms_per_token=res[0].decode_s
         * 1e3, note="warm timings of one run, not a benchmark")

    # the same forward in float32 on this process's CPU device
    t0 = time.perf_counter()
    cpu = jax.devices("cpu")[0]
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.device_put(jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), params), cpu)
    prompts = jax.device_put(np.stack([r.prompt for r in reqs]), cpu)
    ref = np.asarray(jax.jit(lambda p, t: prefill(p, t, cfg32, max_len)[0])(
        params32, prompts))
    rel = np.linalg.norm(logits - ref, axis=1) / np.linalg.norm(ref, axis=1)
    emit("serving.reference", t0, logits_rel_l2=rel.tolist(),
         tolerance=LOGITS_REL_L2, argmax_agree=int(
             (logits.argmax(1) == ref.argmax(1)).sum()),
         finite=bool(np.isfinite(logits).all()))
    check(np.isfinite(logits).all(), "non-finite prefill logits")
    check(bool((rel <= LOGITS_REL_L2).all()),
          f"prefill logits off the float32 reference: {rel}")


# ---------------------------------------------------------------------------
# Four chips: the executors that exist only across devices
# ---------------------------------------------------------------------------

def phase_systolic(seed: int) -> None:
    import jax.numpy as jnp

    from repro.dataflow import dataflow_jit

    @dataflow_jit(stream_argnums=(1,))
    def kernel(table, idx, w):
        g = table[idx]
        h = g * w
        return jnp.tanh(h) + 1.0

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal(1024).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 1024, 8).astype(np.int32))
    w = jnp.float32(1.5)
    stages = kernel.lower(table, idx, w).num_stages
    check(stages <= 4, f"{stages} stages do not fit 4 chips")
    want = np.asarray(kernel(table, idx, w, backend="sequential"))
    got = np.asarray(kernel(table, idx, w, backend="systolic"))
    emit("systolic", t0, stages=stages,
         bitwise_mismatches=int((got != want).sum()),
         max_abs_err=float(np.abs(got - want).max()))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def phase_pipeline(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import pipeline_apply, pipeline_apply_emulated

    t0 = time.perf_counter()
    S, M, D = 4, 16, 128
    rng = np.random.default_rng(seed)
    params = jnp.asarray(rng.normal(size=(S, D, D)).astype(np.float32)
                         * 0.1)
    mbs = jnp.asarray(rng.normal(size=(M, 8, D)).astype(np.float32))
    mesh = jax.make_mesh((S,), ("stage",))

    def stage_fn(wt, x):
        return jnp.tanh(x @ wt)

    def loss(p, run):
        return jnp.mean(run(p) ** 2)

    def on_mesh(p):
        return pipeline_apply(stage_fn, p, mbs, mesh=mesh)

    def emulated(p):
        return pipeline_apply_emulated(stage_fn, p, mbs, num_stages=S)

    got, want = np.asarray(on_mesh(params)), np.asarray(emulated(params))
    g = np.asarray(jax.grad(loss)(params, on_mesh))
    g_ref = np.asarray(jax.grad(loss)(params, emulated))
    fwd_err = float(np.abs(got - want).max())
    grad_err = float(np.abs(g - g_ref).max())
    emit("pipeline_apply", t0, stages=S, microbatches=M,
         fwd_max_abs_err=fwd_err, grad_max_abs_err=grad_err)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-6)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    phases = ((phase_engine, phase_programs, phase_serving)
              if args.chips == 1 else (phase_systolic, phase_pipeline))
    t0 = time.perf_counter()
    for phase in phases:
        phase(args.seed)
    emit("total", t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

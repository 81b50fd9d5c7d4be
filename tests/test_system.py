"""End-to-end behaviour tests for the paper's system.

These exercise whole flows: the paper-claim reproduction (Fig. 5 trends),
the training loop (loss decreases, recovery), serving (prefill+decode),
and one dry-run cell (lower+compile on the 256-chip placeholder mesh, in a
subprocess so this process keeps one device).
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Paper claims (Fig. 5 trends on the SpMV + DFS kernels)
# ---------------------------------------------------------------------------

def test_paper_fig5_spmv_band():
    """SpMV dataflow-vs-conventional gain must land in the paper's band
    (3.3–9.1× best-config, wide tolerance for the simulator)."""
    sys.path.insert(0, _ROOT)
    from benchmarks.paper_fig5 import build_stages, run_kernel
    from benchmarks.paper_kernels import make_spmv

    k = make_spmv(scale=0.0625)
    r = run_kernel(k)
    cfgs = ("ACP", "ACP+64KB", "HP", "HP+64KB")
    best_df = min(r[m]["dataflow_s"] for m in cfgs)
    best_cv = min(r[m]["conventional_s"] for m in cfgs)
    gain = best_cv / best_df
    assert 2.0 < gain < 20.0, gain
    # conventional below the ARM baseline (paper §V-A)
    assert r["ACP"]["conventional_vs_baseline"] < 1.0


def test_paper_fig5_dfs_negative():
    """DFS must NOT benefit (memory SCC) — the paper's negative result."""
    sys.path.insert(0, _ROOT)
    from benchmarks.paper_fig5 import run_kernel
    from benchmarks.paper_kernels import make_dfs

    r = run_kernel(make_dfs())
    for m in ("ACP", "ACP+64KB"):
        assert r[m]["dataflow_vs_conventional"] < 1.5


def test_partitioner_collapses_dfs_to_one_stage():
    sys.path.insert(0, _ROOT)
    from benchmarks.paper_fig5 import build_stages
    from benchmarks.paper_kernels import make_dfs

    df_stages, _ = build_stages(make_dfs())
    mem_stages = [s for s in df_stages if s.accesses]
    assert all(s.mem_in_scc for s in mem_stages), \
        "DFS memory ops must sit inside the dependence cycle"


# ---------------------------------------------------------------------------
# Training end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_loss_decreases(tmp_path):
    from repro.configs import load_config, reduced
    from repro.launch.train import train_loop

    cfg = reduced(load_config("smollm-135m"), d_model=128, max_repeats=2)
    out = train_loop(cfg, steps=40, batch_size=8, seq_len=64,
                     ckpt_dir=str(tmp_path), ckpt_every=50, lr=1e-3)
    first = float(np.mean(out["losses"][:5]))
    last = float(np.mean(out["losses"][-5:]))
    assert last < first, (first, last)


# ---------------------------------------------------------------------------
# Serving end-to-end
# ---------------------------------------------------------------------------

def test_serve_batched_deterministic():
    from repro.configs import load_config, reduced
    from repro.launch.serve import BatchedServer, Request
    from repro.models import init_params

    cfg = reduced(load_config("olmo-1b"), max_repeats=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = BatchedServer(cfg, params, max_len=48)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=(8,))
                    .astype(np.int32), 8) for i in range(3)]
    a = server.serve(reqs)
    b = server.serve(reqs)
    for ra, rb in zip(a, b):
        assert ra.tokens == rb.tokens


def test_serve_decode_donates_cache_and_matches_reference():
    """``serve`` decodes with the cache donated: the prefill's cache is
    consumed, every step counts in ``serve.cache_donated``, and the greedy
    tokens are those of a plain loop over ``models.decode_step`` that
    donates nothing, and the argmax of the full forward over them."""
    import jax.numpy as jnp
    from repro import trace
    from repro.configs import load_config, reduced
    from repro.launch.serve import BatchedServer, Request
    from repro.models import decode_step, forward, init_params, prefill

    cfg = reduced(load_config("olmo-1b"), max_repeats=2)
    params = init_params(jax.random.PRNGKey(2), cfg)
    server = BatchedServer(cfg, params, max_len=32)
    rng = np.random.default_rng(2)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=(8,))
                    .astype(np.int32), 6) for i in range(3)]
    prefilled = []
    served_prefill = server.prefill

    def keep_cache(requests):
        logits, cache = served_prefill(requests)
        prefilled.append(cache)
        return logits, cache

    server.prefill = keep_cache
    trace.reset()
    got = [r.tokens for r in server.serve(reqs)]

    assert all(a.is_deleted()
               for a in jax.tree_util.tree_leaves(prefilled[0]))
    c = trace.counts()
    assert c["serve.cache_donated"] == c["serve.decode_steps"] == 6

    prompts = jnp.asarray(np.stack([r.prompt for r in reqs]))
    logits, cache = prefill(params, prompts, cfg, 32)
    tok, want = jnp.argmax(logits, -1), []
    for step in range(6):
        want.append(np.asarray(tok))
        logits, cache = decode_step(params, tok, cache,
                                    jnp.asarray(8 + step, jnp.int32), cfg)
        tok = jnp.argmax(logits, -1)
    assert got == np.stack(want, 1).tolist()
    full, _ = forward(params, jnp.concatenate(
        [prompts, jnp.asarray(got)[:, :-1]], 1), cfg)
    assert got == np.asarray(jnp.argmax(full[:, 7:], -1)).tolist()


def test_serve_counts_expert_pairs():
    """A model with expert layers: the server counts the pairs that every
    prefill and decode step routed (tokens × top-k × expert layers), those
    that went to a held expert, and the busiest held expert's per layer,
    from the loads the steps yield; its tokens are those of a plain loop
    over the model's steps.  A dense model counts none."""
    import jax.numpy as jnp
    from repro import trace
    from repro.configs import load_config, reduced
    from repro.launch.serve import BatchedServer, Request
    from repro.models import decode_step, init_params, prefill

    cfg = reduced(load_config("moonlight-16b-a3b"))   # 2 of 4 held, top-2
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=(8,))
                    .astype(np.int32), g) for i, g in enumerate([3, 5])]
    trace.reset()
    got = [r.tokens for r in BatchedServer(cfg, params,
                                           max_len=32).serve(reqs)]
    c = trace.counts()

    prompts = jnp.asarray(np.stack([r.prompt for r in reqs]))
    logits, cache, load = prefill(params, prompts, cfg, 32,
                                  expert_load=True)
    loads, tok, want = [np.asarray(load)], jnp.argmax(logits, -1), []
    for step in range(5):
        want.append(np.asarray(tok))
        logits, cache, load = decode_step(
            params, tok, cache, jnp.asarray(8 + step, jnp.int32), cfg,
            expert_load=True)
        loads.append(np.asarray(load))
        tok = jnp.argmax(logits, -1)
    assert got == [w[:r.max_new_tokens] for w, r in
                   zip(np.stack(want, 1).tolist(), reqs)]
    assert loads[0].shape == (2, 2)                   # expert layers, held
    assert c["moe.pairs_routed"] == (2 * 8 + 2 * 5) * 2 * 2
    assert c["moe.pairs_held"] == sum(int(a.sum()) for a in loads)
    assert c["moe.pairs_max_expert"] == sum(int(a.max(1).sum())
                                            for a in loads)
    assert 0 < c["moe.pairs_held"] < c["moe.pairs_routed"]

    dense = reduced(load_config("olmo-1b"), max_repeats=2)
    trace.reset()
    BatchedServer(dense, init_params(jax.random.PRNGKey(0), dense),
                  max_len=32).serve(reqs)
    assert not [k for k in trace.counts() if k.startswith("moe.")]


#: the grouped dispatch on the tiny Moonlight: (tokens, repeat, dtype,
#: every token routed to held expert 0)
_GROUPED_CASES = {
    "decode-first-repeat": (8, 0, "float32", False),
    "two-blocks-last-repeat": (4100, 1, "float32", False),
    "skew-bf16": (40, 1, "bfloat16", True),
}


@pytest.mark.parametrize("case", list(_GROUPED_CASES))
def test_grouped_kernel_path_matches_ragged_dot(case, monkeypatch):
    """``moe._grouped`` with the Pallas grouped matmul forced on (in
    interpret mode here) against the ``ragged_dot`` path a CPU takes, on
    the tiny Moonlight (2 of 4 experts held, top-2) with its experts
    stacked over the segment's repeats: the same loads, and the same
    output up to the rounding of gate and up (bf16 rounds them before the
    SwiGLU on the ragged path only).  A prompt past ``GROUP_TOKENS`` runs
    in two blocks; under skew one group holds every token."""
    import dataclasses

    import jax.numpy as jnp
    from repro.configs import load_config, reduced
    from repro.models import init_params, moe

    T, rep, dtype, skew = _GROUPED_CASES[case]
    cfg = dataclasses.replace(reduced(load_config("moonlight-16b-a3b")),
                              dtype=dtype)
    stacked = init_params(jax.random.PRNGKey(4), cfg)["segment_1"][0]["mlp"]
    params = {n: (a if n in ("w_gate", "w_up", "w_down") else
                  jax.tree_util.tree_map(lambda v: v[rep], a))
              for n, a in stacked.items()}
    if skew:
        params["router_bias"] = params["router_bias"].at[0].set(100.0)
    x = jax.random.normal(jax.random.key(5), (1, T, cfg.d_model))

    def apply(path):
        monkeypatch.setattr(moe, "grouped_path", lambda: path)
        y, aux = jax.jit(lambda p, x, r: moe.moe_apply(p, x, cfg, layer=r))(
            params, x, jnp.int32(rep))
        return np.asarray(y, np.float32), np.asarray(aux["load"])

    (want, want_load), (got, load) = apply("ragged_dot"), apply("pallas")
    np.testing.assert_array_equal(load, want_load)
    assert load.sum() > 0
    if skew:
        assert load[0] == T
    tol = 1e-5 if dtype == "float32" else 2e-2 * np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("path", ["platform", "pallas"])
def test_serve_counts_grouped_dispatches(path, monkeypatch):
    """The server counts each expert-layer dispatch on the grouped matmul's
    path: expert layers x (prefill blocks + decode steps), here a prefill
    of 2 x 8 tokens in blocks of 8, and 0 on the other path.  The path is
    the platform's (``ragged_dot`` on a CPU) unless the test forces the
    kernel, which then serves the same tokens."""
    from repro import trace
    from repro.configs import load_config, reduced
    from repro.launch.serve import BatchedServer, Request
    from repro.models import init_params, moe

    cfg = reduced(load_config("moonlight-16b-a3b"))   # 2 expert layers
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=(8,))
                    .astype(np.int32), g) for i, g in enumerate([3, 5])]
    monkeypatch.setattr(moe, "GROUP_TOKENS", 8)

    def serve():
        trace.reset()
        tokens = [r.tokens for r in BatchedServer(cfg, params,
                                                  max_len=32).serve(reqs)]
        return tokens, trace.counts()

    want, _ = serve()
    if path == "pallas":
        monkeypatch.setattr(moe, "grouped_path", lambda: "pallas")
    got, c = serve()
    used = moe.grouped_path()
    other, = {"pallas", "ragged_dot"} - {used}
    assert used == ("ragged_dot" if path == "platform" else "pallas")
    assert c[f"moe.grouped.{used}"] == 2 * (2 + 5)
    assert c[f"moe.grouped.{other}"] == 0
    assert got == want


def test_serve_prefill_matches_forward():
    """``BatchedServer.prefill`` returns the model's last-position logits
    for the batch, as the plain prefill forward gives them."""
    import jax.numpy as jnp
    from repro.configs import load_config, reduced
    from repro.launch.serve import BatchedServer, Request
    from repro.models import init_params, prefill

    cfg = reduced(load_config("smollm-135m"), max_repeats=2)
    params = init_params(jax.random.PRNGKey(1), cfg)
    server = BatchedServer(cfg, params, max_len=32)
    rng = np.random.default_rng(1)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=(8,))
                    .astype(np.int32), 4) for i in range(2)]
    logits, cache = server.prefill(reqs)
    want, _ = prefill(params, jnp.asarray(np.stack([r.prompt
                                                    for r in reqs])),
                      cfg, 32)
    assert logits.shape == (2, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_compile_cache_dir(monkeypatch):
    """The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR``
    wins untouched; otherwise a fixed ``.jax_cache`` in the checkout."""
    from repro import compile_cache as cc
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cc.enable_compile_cache() == "/elsewhere"
    assert seen == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    d = cc.enable_compile_cache()
    assert d == os.path.join(_ROOT, ".jax_cache")
    assert seen == [("jax_compilation_cache_dir", d)]


# ---------------------------------------------------------------------------
# Dry-run: one full cell in a 512-device subprocess
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_dryrun_cell_compiles():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    code = textwrap.dedent("""
        from repro.launch.dryrun import run_cell
        rec = run_cell("smollm-135m", "train_4k", multi_pod=False,
                       save=False)
        assert rec["status"] == "ok", rec
        assert rec["coll"]["total"] > 0
        print("cell ok", rec["hlo_flops"])
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=_ROOT)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"


def test_roofline_cost_model_consistency():
    """Analytic cost model sanity: train flops/chip ≈ 6·N·D/chips for a
    dense arch (±2× for attention quadratic + logits)."""
    from repro.configs import SHAPES, load_config
    from repro.runtime.cost_model import cost_for_cell

    cfg = load_config("qwen2.5-14b")
    c = cost_for_cell(cfg, SHAPES["train_4k"])
    model = 6 * cfg.param_count() * (256 * 4096) / 256
    assert 0.5 < c.flops / model < 2.5, c.flops / model


def test_experiment_artifacts_exist():
    """The committed dry-run artifacts cover the full matrix."""
    import glob
    import json
    recs = []
    for p in glob.glob(os.path.join(_ROOT, "experiments/dryrun/*.json")):
        with open(p) as f:
            recs.append(json.load(f))
    base = [r for r in recs if not r.get("variant")]
    ok = [r for r in base if r["status"] == "ok"]
    skip = [r for r in base if r["status"] == "skip"]
    err = [r for r in base if r["status"] == "error"]
    assert len(ok) == 64, len(ok)
    assert len(skip) == 16, len(skip)
    assert not err
    # every ok cell compiled with nonzero flops and a collective census
    for r in ok:
        assert r["hlo_flops"] > 0
        assert "coll" in r

"""Moonlight-16B-A3B as one chip's share of 8-way expert parallelism, at a
tiny width on the CPU and seeded random weights: the program against the
plain float32 reference (prefill, and prefill then decode through the
latent cache), the expert shares against the uncut layer, a forced skew
that the capacity dispatch would drop, the driver end to end on a tiny copy
of the benchmark, and the readers of the cell's new metrics."""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import pytest

from benchtest import BENCH, drive, make_tiny_bench

import harness

CONFIG = "moonlight-16b-a3b-ep8"

#: the configuration at a tiny width: 1 dense + 2 expert layers, 8 routed
#: experts of which 2 are held here (experts 2 and 3), top-3, 2 shared
TINY_MOONLIGHT = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "intermediate_size": 96, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "moe_intermediate_size": 24, "n_routed_experts": 2,
    "num_experts_per_tok": 3, "vocab_size": 256, "init_std": 0.5,
}
TINY_DEPLOYMENT = {"n_routed_experts_published": 8, "experts_held": [2, 3],
                   "router_outputs": 8}
TINY_PROGRAM = {
    "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 16,
    "d_ff": 96, "vocab_size": 256,
    "mla": {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16},
    "moe": {"num_experts": 8, "held_experts": 2, "first_held": 2,
            "top_k": 3, "d_ff": 24},
}
TINY_SEGMENTS = (1, 2)
#: the tiny decode traffic: sound runs (served in float32) read a mean gap
#: of about 1e-7, the fp8 control well above the limit
TINY_TRAFFIC = {"batch": 4, "prompt_lengths": [8, 16], "new_tokens": [4, 12],
                "max_len": 32, "token_ids": 256, "sample_tokens": 20,
                "reference_rows": 2, "mean_logit_gap_limit": 0.01}

#: both sides in float32 on the CPU; they differ in the order of their
#: sums (the program's scan, the absorbed decode's reassociated latent
#: attention, the grouped expert matmuls), which moves logits of size ~1
#: by ~1e-6
ATOL = 1e-4


def tiny_config() -> dict:
    cfg = harness.load_json(BENCH, "configs", CONFIG + ".json")
    cfg.update(TINY_MOONLIGHT)
    cfg["deployment"].update(TINY_DEPLOYMENT)
    cfg["program"]["overrides"] = dict(TINY_PROGRAM)
    return cfg


def program_config(cfg: dict, dtype: str = "float32"):
    """The driver's program configuration of ``cfg``, with the tiny depth's
    segments."""
    from repro.configs.base import Segment
    drv = harness.load_module(os.path.join(BENCH, "drivers",
                                           "serve_lm_ep.py"))
    pcfg = drv.program_config(cfg)
    dense, moe = pcfg.segments
    return dataclasses.replace(
        pcfg, dtype=dtype, num_layers=sum(TINY_SEGMENTS),
        segments=(Segment(dense.unit, TINY_SEGMENTS[0]),
                  Segment(moe.unit, TINY_SEGMENTS[1])))


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    ref = harness.load_module(os.path.join(BENCH, "configs", CONFIG + ".py"),
                              "bench_moonlight_ref_test")
    return cfg, ref, program_config(cfg)


def _f32(params):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def test_config_matches_the_program(tiny):
    """The tiny program configuration departs from the tiny file in no
    field; the full-size program and file neither."""
    cfg, ref, _ = tiny
    pcfg = program_config(cfg, dtype="bfloat16")
    assert ref.config_differences(pcfg, cfg) == []
    full = harness.load_json(BENCH, "configs", CONFIG + ".json")
    drv = harness.load_module(os.path.join(BENCH, "drivers",
                                           "serve_lm_ep.py"))
    assert ref.config_differences(drv.program_config(full), full) == []
    # a moved field is caught
    bad = dataclasses.replace(pcfg, moe=dataclasses.replace(
        pcfg.moe, routed_scale=1.0))
    assert ref.config_differences(bad, cfg) == ["moe.routed_scale"]


def test_prefill_logits_match(tiny):
    from repro.models import prefill
    cfg, ref, pcfg = tiny
    seed = 2_500_000_003
    params = ref.make_params(cfg, seed)
    toks = np.random.default_rng(seed).integers(0, cfg["vocab_size"],
                                                (3, 12), dtype=np.int32)
    got, _ = prefill(_f32(params), toks, pcfg, 16)
    want = ref.logits_at(cfg, params, toks, np.full((3, 1), 11, np.int32))
    np.testing.assert_allclose(np.asarray(got), want[:, 0], atol=ATOL)


@pytest.mark.parametrize("absorbed", [True, False])
def test_prefill_then_decode_matches(tiny, absorbed):
    """Decoding through the latent cache, absorbed (as served) or naive,
    gives the logits that the reference's full forward gives at each
    later position."""
    import jax.numpy as jnp

    from repro.models import decode_step, prefill
    cfg, ref, pcfg = tiny
    pcfg = dataclasses.replace(pcfg, mla_absorbed=absorbed)
    seed = 9
    params = ref.make_params(cfg, seed)
    p32 = _f32(params)
    toks = np.random.default_rng(seed).integers(0, cfg["vocab_size"],
                                                (2, 14), dtype=np.int32)
    P, max_len = 8, 20
    logits, cache = prefill(p32, toks[:, :P], pcfg, max_len)
    got = [np.asarray(logits)]
    for t in range(P, toks.shape[1]):
        logits, cache = decode_step(p32, jnp.asarray(toks[:, t]), cache,
                                    jnp.asarray(t, jnp.int32), pcfg)
        got.append(np.asarray(logits))
    pos = np.tile(np.arange(P - 1, toks.shape[1]), (2, 1)).astype(np.int32)
    want = ref.logits_at(cfg, params, toks, pos)
    np.testing.assert_allclose(np.stack(got, 1), want, atol=ATOL)


def _moe_weights(cfg, seed):
    """One expert layer of the tiny model's weights (float32) holding all
    8 experts, and the reference's dims for the uncut layer."""
    import jax
    import jax.numpy as jnp
    uncut = dict(cfg, n_routed_experts=8,
                 deployment=dict(cfg["deployment"], experts_held=[0, 7]))
    ref = harness.load_module(os.path.join(BENCH, "configs", CONFIG + ".py"),
                              "bench_moonlight_ref_test")
    params = ref.make_params(uncut, seed)
    m = jax.tree_util.tree_map(lambda a: a[0].astype(jnp.float32),
                               params["segment_1"][0]["mlp"])
    m["router_bias"] = m["router_bias"] * 20.0    # a bias that moves choices
    return ref, m, ref.dims(uncut)


def _program_moe(pcfg, m, x, first, held, dropless=True):
    """The program's expert layer holding experts ``first .. first+held-1``
    of ``m`` (weights sliced as that chip would hold them): the serving
    path's grouped dispatch, which takes the experts stacked over layers
    (here a stack of one), or the training path's capacity dispatch."""
    from repro.models import moe
    share = dict(m, **{n: m[n][first:first + held]
                       for n in ("w_gate", "w_up", "w_down")})
    cfg = dataclasses.replace(pcfg, moe=dataclasses.replace(
        pcfg.moe, held_experts=held, first_held=first))
    if not dropless:
        y, aux = moe.moe_apply(share, x[None], cfg)
        return np.asarray(y[0]), aux
    stack = dict(share, **{n: share[n][None]
                           for n in ("w_gate", "w_up", "w_down")})
    y, aux = moe.moe_apply(stack, x[None], cfg, layer=np.int32(0))
    return np.asarray(y[0]), aux


def test_expert_shares_add_up_to_the_uncut_layer(tiny):
    """Eight chips of one expert each: their partial outputs, with the
    shared experts (which every chip computes alike) counted once, add up
    to the plain reference's uncut layer; the pairs they count add up to
    every pair the router chose."""
    import jax
    cfg, _, pcfg = tiny
    ref, m, d = _moe_weights(cfg, 2_600_000_011)
    x = np.asarray(jax.random.normal(jax.random.key(1), (40, 64)))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(x, m, d)
        shared = np.asarray(ref._swiglu(x, m["shared"], "fp32"))
        parts = [_program_moe(pcfg, m, x, e, 1) for e in range(8)]
    total = sum(y - shared for y, _ in parts) + shared
    np.testing.assert_allclose(total, np.asarray(want), atol=ATOL)
    assert sum(int(a["load"].sum()) for _, a in parts) == 40 * d["k"]


def test_forced_skew_drops_no_pair(tiny):
    """Every token routed to held expert 0 among its top-k: the dropless
    dispatch gives the reference's layer, every pair counted; the capacity
    dispatch, whose expert keeps ceil(k T / E * 1.25) of T pairs, does
    not."""
    import jax
    cfg, _, pcfg = tiny
    ref, m, d = _moe_weights(cfg, 2_600_000_013)
    m["router_bias"] = m["router_bias"].at[0].set(100.0)
    x = np.asarray(jax.random.normal(jax.random.key(2), (40, 64)))
    held = dict(d, held=2, first=0)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(x, dict(m, **{
            n: m[n][:2] for n in ("w_gate", "w_up", "w_down")}), held)
        got, aux = _program_moe(pcfg, m, x, 0, 2)
        dropped, cap = _program_moe(pcfg, m, x, 0, 2, dropless=False)
    np.testing.assert_allclose(got, np.asarray(want), atol=ATOL)
    assert int(aux["load"][0]) == 40
    assert float(cap["dropped_frac"]) > 0
    assert np.abs(dropped - np.asarray(want)).max() > 100 * ATOL


# ---------------------------------------------------------------------------
# The driver on a tiny copy of the benchmark
# ---------------------------------------------------------------------------

@pytest.fixture
def tiny_ep_bench(tmp_path):
    """A tiny copy of the benchmark whose Moonlight cell runs the tiny
    configuration and traffic (and a bandwidth for the CPU, so that the
    HBM share's reader runs; no CPU number is ever reported)."""
    bench = make_tiny_bench(str(tmp_path))
    cfg = tiny_config()
    cfg["name"] = "moonlight-tiny"
    # served in float32, as the reference computes: the widest gap is then
    # rounding alone, whatever sample the window's length draws (in bf16 a
    # near-tie in the top-k moves a token's experts, see PERF.md)
    cfg["served_dtype"] = "float32"
    cfg["program"]["overrides"]["dtype"] = "float32"
    with open(os.path.join(bench, "configs", "moonlight-tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(BENCH, "configs", CONFIG + ".py")) as src, \
            open(os.path.join(bench, "configs", "moonlight-tiny.py"),
                 "w") as dst:
        dst.write(src.read())
    with open(os.path.join(bench, "traffic", "decode-tiny-b4.json"),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    hbm = harness.load_json(bench, "hbm.json")
    hbm["devices"]["cpu"] = {"hbm_bytes_per_s": 1e12}
    with open(os.path.join(bench, "hbm.json"), "w") as f:
        json.dump(hbm, f)
    spec_path = os.path.join(str(tmp_path), "BENCHMARK.json")
    spec = harness.load_json(spec_path)
    for w in spec["workloads"]:
        if w["config"] == CONFIG:
            w["config"], w["traffic"] = "moonlight-tiny", "decode-tiny-b4"
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return bench


@pytest.fixture
def tiny_segments(monkeypatch):
    """The program's Moonlight configuration at the tiny depth: one dense
    and two expert layers (segments are not a field the file's overrides
    can give)."""
    import repro.configs.base as base
    real = base.load_config

    def load(arch):
        cfg = real(arch)
        if arch != "moonlight-16b-a3b":
            return cfg
        return dataclasses.replace(
            cfg, num_layers=sum(TINY_SEGMENTS),
            segments=tuple(base.Segment(s.unit, n) for s, n in
                           zip(cfg.segments, TINY_SEGMENTS)))
    monkeypatch.setattr(base, "load_config", load)


def test_traced_run_is_correct_and_reports_every_metric(
        tiny_ep_bench, monkeypatch, tiny_segments):
    spec = harness.load_spec(os.path.dirname(BENCH))
    # the CPU's trace holds no device plane, so no idle share
    want = {m["name"] for m in spec["per_layer"]
            if "moonlight-ep8-decode" in m.get("workloads", [])
            and m["source"] != "device_trace"}
    r = drive(monkeypatch, tiny_ep_bench, "moonlight-ep8-decode", trace=1)
    assert r["rc"] == 0 and r["correct"] is True, r
    assert r["checks"]["mean_logit_gap"]["value"] < 1e-4
    assert r["checks"]["config_fields_differing"]["value"] == 0
    assert want <= set(r["metrics"])
    share = r["metrics"]["moe.held_pair_share"]["value"]
    assert 0 < share < 100
    assert r["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
    assert 0 < r["metrics"]["hbm_share.decode"]["value"]
    e2e = drive(monkeypatch, tiny_ep_bench, "moonlight-ep8-decode", trace=0)
    assert {"tok_per_s", "tpot_p95_ms", "setup_s"} <= set(e2e["metrics"])


def test_served_token_altered_is_not_correct(tiny_ep_bench, monkeypatch,
                                             tiny_segments):
    """Every fifth decode step puts token 7 first in every row: the mean
    logit gap passes the limit."""
    import jax.numpy as jnp

    import repro.models as models
    real = models.decode_step

    def decode(params, token, cache, length, cfg, **kw):
        logits, *rest = real(params, token, cache, length, cfg, **kw)
        logits = logits.at[:, 7].add(jnp.where(length % 5 == 3, 1e4, 0.0))
        return (logits, *rest)
    monkeypatch.setattr(models, "decode_step", decode)
    r = drive(monkeypatch, tiny_ep_bench, "moonlight-ep8-decode")
    assert r["rc"] == 0 and r["correct"] is False
    gap = r["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_controls_fail_the_limit(tiny):
    """The reference in fp8, and in bf16 with held expert 2 (the tiny
    chip's first) zeroed, in the program's place: the tokens they put first
    read a mean gap above the tiny limit; in float32 with the seed's
    weights, the reference reads its own gaps, all zero."""
    cfg, ref, _ = tiny
    drv = harness.load_module(os.path.join(BENCH, "drivers",
                                           "serve_lm_ep.py"))
    seed = 2_900_000_011
    rng = np.random.default_rng(seed)
    samples = [{"prompt": rng.integers(0, 256, 16).tolist(),
                "tokens": rng.integers(0, 256, 12).tolist()}
               for _ in range(4)]
    params = ref.make_params(cfg, seed)
    controls = {"fp8": ("fp8", None),
                "zeroed": ("bf16", ref.zero_held_expert(params, 0)),
                "same": ("fp32", None)}
    gaps = ref.reference_gaps(cfg, seed, samples, 32, 2, controls=controls,
                              params=params)
    limit = TINY_TRAFFIC["mean_logit_gap_limit"]
    for name in ("fp8", "zeroed"):
        got = drv.gap_stats([g["control_gaps"][name] for g in gaps])
        assert got["mean"] > limit, name
    assert drv.gap_stats([g["control_gaps"]["same"] for g in gaps]) \
        ["widest"] == 0
    assert all(0 <= g["route_moved"] <= g["route_tokens"] for g in gaps)


def test_gap_stats():
    drv = harness.load_module(os.path.join(BENCH, "drivers",
                                           "serve_lm_ep.py"))
    got = drv.gap_stats([[0.0, 0.0, 1.0], [0.3, 0.0]])
    assert got["mean"] == pytest.approx(0.26)
    assert got["widest"] == 1.0
    assert got["share_above_0.25"] == pytest.approx(40.0)
    assert got["share_above_0.5"] == pytest.approx(20.0)
    assert got["worst_request_mean"] == pytest.approx(1 / 3)


def test_window_holds_whole_decks(tiny_ep_bench, tiny_segments):
    """Each unit of the window is one batch at every prompt length of the
    traffic, and the run records one unit a batch, in time order, the
    last ending with the window."""
    import time

    import jax

    import run as bench_run
    spec = harness.load_spec(os.path.dirname(tiny_ep_bench))
    cell = harness.find_cell(spec, "moonlight-ep8-decode", tiny_ep_bench)
    ctx = bench_run.Context(cell, 3_000_000_023, 1.0, False,
                            time.perf_counter(), jax.devices()[:1])
    run = cell.driver.run(ctx)
    assert run["correct"] is True, run["checks"]
    units, deck = run["units"], sorted(TINY_TRAFFIC["prompt_lengths"])
    assert units and len(units) % len(deck) == 0
    for i in range(0, len(units), len(deck)):
        assert sorted(u["prompt_len"] for u in units[i:i + len(deck)]) \
            == deck
    starts = [u["t_start"] for u in units]
    assert starts == sorted(starts) and starts[0] >= 0
    assert units[-1]["t_done"] == pytest.approx(run["window_s"], abs=1e-3)


# ---------------------------------------------------------------------------
# Readers, operations and bytes
# ---------------------------------------------------------------------------

def _read(name, run):
    return harness.load_module(os.path.join(BENCH, "metrics",
                                            name + ".py")).read(run)


def test_pair_readers():
    counts = {"moe.pairs_routed": 6000, "moe.pairs_held": 750,
              "moe.pairs_max_expert": 150}
    run = {"counts": counts, "experts_held": 8}
    assert _read("moe.held_pair_share", run) == pytest.approx(12.5)
    # held mean 750 / 8 = 93.75 a layer-step summed; busiest 150
    assert _read("moe.load_max_over_mean", run) == pytest.approx(1.6)
    # a program without the counters reports nothing
    for name in ("moe.held_pair_share", "moe.load_max_over_mean"):
        assert _read(name, {"counts": {}, "experts_held": 8}) is None


def test_hbm_share_reader():
    units = [{"decode_bytes": 8.19e9, "decode_s_per_step": 0.01,
              "decode_steps": 2}]
    run = {"units": units, "device": {"kind": "TPU v5 lite", "count": 1}}
    assert _read("hbm_share.decode", run) == pytest.approx(50.0)
    with pytest.raises(KeyError):
        _read("hbm_share.decode", dict(run, device={"kind": "cpu",
                                                     "count": 1}))


def test_step_bytes_are_the_programs_weights_and_latent_cache():
    """At full size: a decode step's bytes are the program's parameter tree
    but the embedding table, plus the batch's embedding rows, plus the
    latent cache of every layer up to the given length."""
    import jax

    from repro.models import init_params
    cfg = harness.load_json(BENCH, "configs", CONFIG + ".json")
    ref = harness.load_module(os.path.join(BENCH, "configs", CONFIG + ".py"),
                              "bench_moonlight_ref_test")
    drv = harness.load_module(os.path.join(BENCH, "drivers",
                                           "serve_lm_ep.py"))
    pcfg = drv.program_config(cfg)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                dataclasses.replace(
                                                    pcfg, dtype="bfloat16")))
    leaves = jax.tree_util.tree_leaves(shapes)
    # the program draws its router in f32; the benchmark serves it in bf16
    router = 26 * 2048 * 64 * 2
    weights = sum(a.size * a.dtype.itemsize for a in leaves) - router
    embed = 163840 * 2048 * 2
    assert ref.decode_step_bytes(cfg, 128, 0) == \
        weights - embed + 128 * 2048 * 2
    assert ref.decode_step_bytes(cfg, 128, 840) - \
        ref.decode_step_bytes(cfg, 128, 0) == 128 * 840 * 27 * 576 * 2
    # 3.36 B parameters held here
    assert abs(sum(a.size for a in leaves) / 1e9 - 3.36) < 0.01


def test_request_flops_count_each_part():
    """A one-token prompt and one new token: every layer's projections and
    MLP once, attention over one key, the head once."""
    cfg = harness.load_json(BENCH, "configs", CONFIG + ".json")
    ref = harness.load_module(os.path.join(BENCH, "configs", CONFIG + ".py"),
                              "bench_moonlight_ref_test")
    D, H = 2048, 16
    mla = 2 * (D * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D)
    dense = 2 * 3 * D * 11264
    moe = 2 * (D * 64 + 3 * D * 2816 + 6 * 8 / 64 * 3 * D * 1408)
    attn = 2 * H * (192 + 128)
    want = mla + dense + 26 * (mla + moe) + 27 * attn + 2 * D * 163840
    assert ref.request_flops(cfg, 1, 1) == pytest.approx(want)

"""The plain float32 OLMo reference against the program's model at a tiny
width on the CPU: the prefill logits, and prefill followed by decoding
through the KV cache, on the same seeded weights."""

from __future__ import annotations

import dataclasses
import os

import numpy as np
import pytest

from benchtest import BENCH, TINY_OLMO, TINY_OLMO_PROGRAM

import harness

#: both sides in float32 on the CPU; they differ only in the order of
#: their sums (the program's scan, its online-softmax decode), which
#: moves logits of size ~1 by ~1e-6
ATOL = 1e-4


@pytest.fixture(scope="module")
def olmo():
    cfg = harness.load_json(BENCH, "configs", "olmo-1b.json")
    cfg.update(TINY_OLMO)
    ref = harness.load_module(os.path.join(BENCH, "configs", "olmo-1b.py"),
                              "bench_olmo_ref_test")
    from repro.configs.base import load_config
    pcfg = dataclasses.replace(
        load_config(cfg["program"]["arch"]), dtype="float32",
        **cfg["program"]["overrides"], **TINY_OLMO_PROGRAM)
    return cfg, ref, pcfg


def _f32(params):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def test_prefill_logits_match(olmo):
    from repro.models import prefill
    cfg, ref, pcfg = olmo
    seed = 2_500_000_001
    params = ref.make_params(cfg, seed)
    toks = np.random.default_rng(seed).integers(0, cfg["vocab_size"],
                                                (2, 12), dtype=np.int32)
    got, _ = prefill(_f32(params), toks, pcfg, 16)
    want = ref.logits_at(cfg, params, toks, np.full((2, 1), 11, np.int32))
    np.testing.assert_allclose(np.asarray(got), want[:, 0], atol=ATOL)


def test_prefill_then_decode_matches(olmo):
    """Decoding through the cache gives the logits that the reference's
    full forward gives at each later position."""
    import jax.numpy as jnp

    from repro.models import decode_step, prefill
    cfg, ref, pcfg = olmo
    seed = 7
    params = ref.make_params(cfg, seed)
    p32 = _f32(params)
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], (2, 14), dtype=np.int32)
    P, max_len = 8, 16
    logits, cache = prefill(p32, toks[:, :P], pcfg, max_len)
    got = [np.asarray(logits)]
    for t in range(P, toks.shape[1]):
        logits, cache = decode_step(p32, jnp.asarray(toks[:, t]), cache,
                                    jnp.asarray(t, jnp.int32), pcfg)
        got.append(np.asarray(logits))
    pos = np.tile(np.arange(P - 1, toks.shape[1]), (2, 1)).astype(np.int32)
    want = ref.logits_at(cfg, params, toks, pos)
    np.testing.assert_allclose(np.stack(got, 1), want, atol=ATOL)


def test_reference_gaps_are_zero_on_its_own_tokens(olmo):
    """Tokens that the reference itself puts first read a gap of 0; the
    fp8 control's first tokens read the gaps the control is judged by."""
    cfg, ref, _ = olmo
    seed = 11
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg["vocab_size"], 6).tolist()
    params = ref.make_params(cfg, seed)
    toks = np.array([prompt + [0] * 4], np.int32)
    first = []
    for t in range(4):
        lg = ref.logits_at(cfg, params, toks,
                           np.array([[5 + t]], np.int32))[0, 0]
        first.append(int(lg.argmax()))
        toks[0, 6 + t] = first[-1]
    out = ref.reference_gaps(cfg, seed, [{"prompt": prompt,
                                          "tokens": first}], 16, 1,
                             precision="fp8")
    assert out[0]["gaps"] == [0.0] * 4
    assert all(g >= 0.0 for g in out[0]["control_gaps"])

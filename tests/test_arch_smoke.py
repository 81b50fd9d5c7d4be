"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, output shapes + no NaNs; plus decode-step and prefill↔decode
consistency.  The FULL configs are exercised only via the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, load_config, reduced

_HEAVY_ARCHS = {"deepseek-v3-671b", "jamba-1.5-large-398b"}
ARCH_PARAMS = [pytest.param(a, marks=pytest.mark.slow)
               if a in _HEAVY_ARCHS else a for a in ARCH_IDS]
from repro.models import (decode_step, forward, init_cache, init_params,
                          input_specs, loss_fn, prefill)

_B, _S = 2, 16


def _batch(cfg, rng):
    if cfg.frontend_stub:
        return {
            "embeds": jax.random.normal(rng, (_B, _S, cfg.d_model),
                                        jnp.float32),
            "labels": jax.random.randint(rng, (_B, _S), 0, cfg.vocab_size),
        }
    return {"tokens": jax.random.randint(rng, (_B, _S + 1), 0,
                                         cfg.vocab_size)}


@pytest.fixture(scope="module")
def arch_setup():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = reduced(load_config(arch))
            rng = jax.random.PRNGKey(hash(arch) % 2**31)
            params = init_params(rng, cfg)
            cache[arch] = (cfg, params, rng)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_forward_shapes_and_finite(arch, arch_setup):
    cfg, params, rng = arch_setup(arch)
    batch = _batch(cfg, rng)
    inputs = batch.get("tokens", batch.get("embeds"))
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
    logits, aux = forward(params, inputs, cfg)
    S = inputs.shape[1]
    assert logits.shape == (_B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all()), arch


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_train_step_loss_finite_and_grads(arch, arch_setup):
    cfg, params, rng = arch_setup(arch)
    batch = _batch(cfg, rng)
    (loss, metrics), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, batch, cfg)
    assert bool(jnp.isfinite(loss)), arch
    # at least one grad leaf is nonzero and all are finite
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
               for g in leaves), arch
    assert any(float(jnp.abs(g.astype(jnp.float32)).max()) > 0
               for g in leaves), arch


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_decode_step_runs(arch, arch_setup):
    cfg, params, rng = arch_setup(arch)
    cache = init_cache(cfg, _B, max_len=_S + 8)
    token = jnp.zeros((_B,), jnp.int32)
    logits, new_cache = decode_step(params, token, cache,
                                    jnp.asarray(0, jnp.int32), cfg)
    assert logits.shape == (_B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all()), arch
    # cache structure preserved
    assert (jax.tree_util.tree_structure(cache)
            == jax.tree_util.tree_structure(new_cache))


# cache variants beyond each architecture's own: config overrides, and the
# tolerance that the variant's arithmetic allows (int8 K/V with per-vector
# scales of max|x| / 127 moves these logits by about 4e-3)
_DECODE_VARIANTS = {
    "int8kv": ({"kv_cache_dtype": "int8"}, 1e-2),
    "absorbed": ({"mla_absorbed": True}, 2e-3),
}


@pytest.mark.parametrize("arch", ["smollm-135m", "rwkv6-1.6b",
                                  "deepseek-v3-671b",
                                  "jamba-1.5-large-398b",
                                  "smollm-135m+int8kv",
                                  "deepseek-v3-671b+absorbed"])
def test_prefill_then_decode_matches_forward(arch, arch_setup):
    """prefill(t_0..t_{n-1}) + decode(t_n) must equal forward on the full
    prefix — the serving path is consistent with training semantics."""
    import dataclasses
    arch, _, variant = arch.partition("+")
    overrides, tol = _DECODE_VARIANTS.get(variant, ({}, 2e-3))
    cfg, params, rng = arch_setup(arch)
    cfg = dataclasses.replace(cfg, **overrides)
    if cfg.moe is not None:
        # token-dropping MoE is batch-order dependent; relax via high cap
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    n = 8
    tokens = jax.random.randint(rng, (_B, n + 1), 0, cfg.vocab_size)
    # ground truth: forward over n+1 tokens, logits at position n
    logits_full, _ = forward(params, tokens, cfg)
    want = logits_full[:, -1]
    # serving: prefill n tokens, then decode token n
    _, cache = prefill(params, tokens[:, :n], cfg, max_len=n + 4)
    got, _ = decode_step(params, tokens[:, n], cache,
                         jnp.asarray(n, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_input_specs_all_shapes(arch):
    from repro.configs import SHAPES, cell_is_applicable
    cfg = load_config(arch)
    for shape in SHAPES.values():
        if not cell_is_applicable(cfg, shape):
            continue
        specs = input_specs(cfg, shape)
        leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        assert leaves, (arch, shape.name)
        for l in leaves:
            assert isinstance(l, jax.ShapeDtypeStruct)


def test_param_counts_match_published():
    expected = {
        "jamba-1.5-large-398b": (398, 30),
        "qwen2.5-14b": (14.8, 1),
        "olmo-1b": (1.3, 0.2),
        "smollm-135m": (0.135, 0.03),
        "command-r-plus-104b": (104, 5),
        "rwkv6-1.6b": (1.6, 0.3),
        "deepseek-v3-671b": (671, 10),
        "llama4-scout-17b-a16e": (109, 10),
        "chameleon-34b": (34, 2),
    }
    for arch, (want_b, tol_b) in expected.items():
        got = load_config(arch).param_count() / 1e9
        assert abs(got - want_b) < tol_b, (arch, got, want_b)
    # active params for the MoE flagships
    assert abs(load_config("deepseek-v3-671b").active_param_count() / 1e9
               - 37) < 3
    assert abs(load_config("llama4-scout-17b-a16e").active_param_count()
               / 1e9 - 17) < 2

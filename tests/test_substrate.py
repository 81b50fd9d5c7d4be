"""Tests for the training substrate: optimizer, schedule, compression,
data pipeline, checkpointing, fault tolerance."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # noqa: E402 — skips when hypothesis is missing

from repro.checkpoint import Checkpointer
from repro.data import DataConfig, prefetched, synthetic_stream
from repro.optim import (AdamWConfig, apply_updates, compress,
                         init_opt_state, warmup_cosine)


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def _toy_params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "dense": {"w": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
                  "bias": jnp.zeros((4,), jnp.float32)},
        "norm": {"scale": jnp.ones((8,), jnp.float32)},
    }


@pytest.mark.slow
def test_adamw_converges_on_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip_norm=1e9)
    params = _toy_params()
    target = jax.tree_util.tree_map(lambda p: jnp.ones_like(p), params)
    state = init_opt_state(params, cfg)

    def loss(p):
        return sum(jnp.sum((a - b) ** 2) for a, b in zip(
            jax.tree_util.tree_leaves(p),
            jax.tree_util.tree_leaves(target)))

    l0 = float(loss(params))
    for _ in range(200):
        grads = jax.grad(loss)(params)
        params, state, _ = apply_updates(params, grads, state, cfg)
    assert float(loss(params)) < 1e-3 * l0


def test_adamw_grad_clip():
    cfg = AdamWConfig(lr=1e-3, grad_clip_norm=1.0)
    params = _toy_params()
    state = init_opt_state(params, cfg)
    huge = jax.tree_util.tree_map(lambda p: 1e6 * jnp.ones_like(p), params)
    new_params, _, info = apply_updates(params, huge, state, cfg)
    # update magnitude bounded: params can't move more than ~lr per element
    delta = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(new_params),
        jax.tree_util.tree_leaves(params)))
    assert delta < 10 * cfg.lr
    assert float(info["grad_norm"]) > 1e5


def test_adamw_no_decay_on_norm_and_bias():
    cfg = AdamWConfig(lr=0.0, weight_decay=1.0)  # lr 0: only decay matters
    params = _toy_params()
    state = init_opt_state(params, cfg)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    new_params, _, _ = apply_updates(params, zeros, state, cfg)
    # with lr=0 nothing changes at all — decay also scales by lr
    for a, b in zip(jax.tree_util.tree_leaves(new_params),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_warmup_cosine_shape():
    s = [float(warmup_cosine(i, warmup_steps=10, total_steps=100))
         for i in range(100)]
    assert s[0] == 0.0
    assert abs(s[10] - 1.0) < 0.11
    assert s[99] < 0.2
    assert max(s) <= 1.0 + 1e-6


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

@given(st.integers(min_value=1, max_value=2000),
       st.integers(min_value=0, max_value=100))
@settings(max_examples=20, deadline=None)
def test_int8_quant_roundtrip_error_bound(n, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    q, scale = compress.quantize_int8(x, chunk=256)
    back = compress.dequantize_int8(q, scale, (n,))
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.repeat(np.asarray(scale), 256)[:n] * 0.5 + 1e-9
    assert (err <= bound + 1e-6).all()


@pytest.mark.slow
def test_compressed_psum_multidevice():
    import subprocess, sys, textwrap
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(root, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import compressed_psum

        mesh = jax.make_mesh((8,), ("pod",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))

        def f(xs):
            return compressed_psum(xs, "pod")

        got = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                    out_specs=P("pod"),
                                    check_vma=False))(x)
        want = x.sum(0, keepdims=True).repeat(8, 0)
        # theoretical bound: per-contributor error <= shared_scale/2,
        # 8 contributors; shared scale = max|x| over shards / 127
        scale = np.abs(np.asarray(x)).max(axis=0) / 127.0
        bound = 8 * 0.5 * scale.max() + 1e-6
        err = np.abs(np.asarray(got) - np.asarray(want)).max()
        assert err <= bound, (err, bound)
        print("compressed psum OK", err, "<=", bound)
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_stream_deterministic_resume():
    cfg = DataConfig(batch_size=2, seq_len=16, vocab_size=64, seed=3)
    a = synthetic_stream(cfg)
    batches = [next(a) for _ in range(6)]
    # resume from step 3 must reproduce batch 3 exactly
    b = synthetic_stream(cfg, start_step=3)
    resumed = next(b)
    np.testing.assert_array_equal(batches[3]["tokens"], resumed["tokens"])


def test_prefetched_pipeline_preserves_order():
    cfg = DataConfig(batch_size=1, seq_len=8, vocab_size=32)
    direct = synthetic_stream(cfg)
    want = [next(direct)["tokens"] for _ in range(5)]
    fifo = prefetched(synthetic_stream(cfg), depth=3)
    got = [np.asarray(next(fifo)["tokens"]) for _ in range(5)]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_stream_is_learnable_structure():
    """The synthetic process must be predictable (loss can decrease)."""
    cfg = DataConfig(batch_size=4, seq_len=32, vocab_size=64)
    batch = next(synthetic_stream(cfg))["tokens"]
    # >50% of adjacent-token transitions repeat the previous token's block
    same = (np.diff(batch, axis=1) == 0).mean()
    assert same > 0.3


# ---------------------------------------------------------------------------
# Checkpointer
# ---------------------------------------------------------------------------

def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": jnp.asarray(rng.normal(size=(4, 4)),
                                        jnp.float32)},
            "step": jnp.asarray(seed, jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    s = _state(7)
    ck.save(7, s, blocking=True)
    restored, step = ck.restore(_state(0))
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(s["params"]["w"]))


def test_checkpoint_keep_n_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for i in range(5):
        ck.save(i, _state(i), blocking=True)
    assert ck.all_steps() == [3, 4]


def test_checkpoint_atomic_no_partial(tmp_path):
    """A .tmp file lying around must never be visible as a checkpoint."""
    ck = Checkpointer(str(tmp_path), keep=3)
    ck.save(1, _state(1), blocking=True)
    # simulate a crashed write
    with open(os.path.join(str(tmp_path), "step_00000002.tmp"), "wb") as f:
        f.write(b"garbage")
    assert ck.all_steps() == [1]
    restored, step = ck.restore(_state(0))
    assert step == 1


def test_checkpoint_shape_mismatch_raises(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state(1), blocking=True)
    bad = {"params": {"w": jnp.zeros((2, 2))}, "step": jnp.zeros((),
                                                                 jnp.int32)}
    with pytest.raises(ValueError, match="shape"):
        ck.restore(bad)


# ---------------------------------------------------------------------------
# Fault tolerance end-to-end (train loop with injected failure)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_recovers_from_injected_failure(tmp_path):
    from repro.configs import load_config, reduced
    from repro.launch.train import train_loop

    cfg = reduced(load_config("smollm-135m"), max_repeats=1)
    # run A: uninterrupted
    out_a = train_loop(cfg, steps=12, batch_size=2, seq_len=16,
                       ckpt_dir=str(tmp_path / "a"), ckpt_every=4)
    # run B: failure injected at step 9 → restore from ckpt 8 → same result
    out_b = train_loop(cfg, steps=12, batch_size=2, seq_len=16,
                       ckpt_dir=str(tmp_path / "b"), ckpt_every=4,
                       fail_at=9)
    assert out_b["failures"] == 1 and out_b["restores"] == 1
    np.testing.assert_allclose(out_a["final_loss"], out_b["final_loss"],
                               rtol=1e-5)


@pytest.mark.slow
def test_train_resume_matches_uninterrupted(tmp_path):
    """Kill after 8 steps, restart to 12 — identical final loss to a
    single 12-step run (deterministic data + bitwise state restore)."""
    from repro.configs import load_config, reduced
    from repro.launch.train import train_loop

    cfg = reduced(load_config("smollm-135m"), max_repeats=1)
    full = train_loop(cfg, steps=12, batch_size=2, seq_len=16,
                      ckpt_dir=str(tmp_path / "full"), ckpt_every=100)
    part1 = train_loop(cfg, steps=8, batch_size=2, seq_len=16,
                       ckpt_dir=str(tmp_path / "r"), ckpt_every=100,
                       schedule_steps=12)
    part2 = train_loop(cfg, steps=12, batch_size=2, seq_len=16,
                       ckpt_dir=str(tmp_path / "r"), ckpt_every=100,
                       schedule_steps=12)
    np.testing.assert_allclose(full["final_loss"], part2["final_loss"],
                               rtol=1e-5)

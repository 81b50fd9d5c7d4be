"""The comparison that decides ``correct`` fails when it should.

Each fault breaks the timed path underneath a whole run of a tiny cell
(the look for a chip skipped) and ``correct`` must come out false; the
controls (the reference with a guarantee broken, or in a lower
precision, put in the program's place) must fail the same comparison.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchtest import BENCH, drive

import harness


# ---------------------------------------------------------------------------
# Faults in the simulator
# ---------------------------------------------------------------------------

def _bump_first_backing_draw(monkeypatch):
    """An answer altered where it is produced: the first backing-store
    trip of every simulation takes one cycle more."""
    from repro.core import simulator
    real = simulator._backing_latencies

    def bumped(mem, rng, count):
        lat = real(mem, rng, count)
        if len(lat):
            lat = lat.copy()
            lat[0] += 1
        return lat
    monkeypatch.setattr(simulator, "_backing_latencies", bumped)


def _flip_first_l1_verdict(monkeypatch):
    """An answer altered where it is produced: the first lookup of every
    cache call reports the opposite verdict."""
    from repro.core import simulator
    real = simulator.BatchedCacheSim.lookup

    def flipped(self, addrs):
        hit = real(self, addrs)
        if len(hit):
            hit = hit.copy()
            hit[0] = ~hit[0]
        return hit
    monkeypatch.setattr(simulator.BatchedCacheSim, "lookup", flipped)


def _cache_keeps_nothing(monkeypatch):
    """A step that returns its state unchanged: the cache replay never
    keeps a line, so every access misses."""
    from repro.core import simulator

    def none(self, s_s, t_s):
        return np.zeros(len(s_s), dtype=bool)
    monkeypatch.setattr(simulator.BatchedCacheSim, "_lookup2", none)
    monkeypatch.setattr(simulator.BatchedCacheSim, "_lookup_nway", none)


def _half_batch_left_out_of_replay(monkeypatch):
    """Half of the batch left out: each cache replay resolves the first
    half of its accesses and hands their verdicts on to the second."""
    from repro.core import simulator
    for name in ("_lookup2", "_lookup_nway"):
        real = getattr(simulator.BatchedCacheSim, name)

        def half(self, s_s, t_s, real=real):
            h = (len(s_s) + 1) // 2
            hit = real(self, s_s[:h], t_s[:h])
            return np.concatenate([hit, hit[:len(s_s) - h]])
        monkeypatch.setattr(simulator.BatchedCacheSim, name, half)


@pytest.mark.parametrize("cell,fault", [
    ("spmv-acp64k-stream", _bump_first_backing_draw),
    ("spmv-acp64k-stream", _cache_keeps_nothing),
    ("spmv-acp64k-stream", _half_batch_left_out_of_replay),
    ("spmv-processor-stream", _flip_first_l1_verdict),
    ("spmv-processor-stream", _cache_keeps_nothing),
    ("spmv-processor-stream", _half_batch_left_out_of_replay),
])
def test_simulator_fault_is_not_correct(tiny_bench, monkeypatch, cell,
                                        fault):
    fault(monkeypatch)
    r = drive(monkeypatch, tiny_bench, cell)
    assert r["rc"] == 0 and r["attempted"] > 0
    assert r["correct"] is False
    assert r["checks"]["result_fields_differing"]["value"] > 0


@pytest.mark.parametrize("model,control", [
    ("dataflow", "no_backpressure"),
    ("dataflow", "fifo_replacement"),
    ("processor", "fifo_replacement"),
])
def test_simulator_control_differs(tiny_bench, model, control):
    """The reference with one stated guarantee broken, in the program's
    place, differs from the plain reference in some field."""
    cfg = harness.load_json(tiny_bench, "configs", "spmv-tiny.json")
    ref = harness.load_module(os.path.join(tiny_bench, "configs",
                                           "spmv-tiny.py"))
    seed = 2_900_000_003
    if model == "dataflow":
        want = ref.simulate_dataflow(cfg, "ACP+64KB", seed)
        got = ref.simulate_dataflow(cfg, "ACP+64KB", seed, control=control)
    else:
        want = ref.simulate_processor(cfg, seed)
        got = ref.simulate_processor(cfg, seed, control=control)
    assert any(got[k] != want[k] for k in want)


# ---------------------------------------------------------------------------
# Faults in the served model
# ---------------------------------------------------------------------------

def _wrap_decode(monkeypatch, change):
    """Put ``change(logits, new_cache, old_cache, length)`` behind the
    model's decode step, as the server binds it."""
    import repro.models as models
    real = models.decode_step

    def decode(params, token, cache, length, cfg):
        logits, new = real(params, token, cache, length, cfg)
        return change(logits, new, cache, length)
    monkeypatch.setattr(models, "decode_step", decode)


def _token_altered(monkeypatch):
    """Every fifth decode step puts token 7 first in every row."""
    import jax.numpy as jnp
    _wrap_decode(monkeypatch, lambda lg, new, old, n: (
        lg.at[:, 7].add(jnp.where(n % 5 == 3, 1e4, 0.0)), new))


def _half_batch_left_out(monkeypatch):
    """The second half of the batch decodes from zero logits."""
    import jax.numpy as jnp

    def change(lg, new, old, n):
        keep = jnp.arange(lg.shape[0]) < lg.shape[0] // 2
        return jnp.where(keep[:, None], lg, 0.0), new
    _wrap_decode(monkeypatch, change)


def _cache_unchanged(monkeypatch):
    """The decode step returns the KV cache it was given."""
    _wrap_decode(monkeypatch, lambda lg, new, old, n: (lg, old))


@pytest.mark.parametrize("batch", [4, 32])
@pytest.mark.parametrize("fault", [_token_altered, _half_batch_left_out,
                                   _cache_unchanged])
def test_served_model_fault_is_not_correct(tiny_bench, monkeypatch, fault,
                                           batch):
    """Also at the decode cell's batch of 32, where the sample of tokens
    that the reference checks is a small part of one batch's."""
    path = os.path.join(tiny_bench, "traffic", "decode-tiny.json")
    traffic = harness.load_json(path)
    traffic["batch"] = batch
    with open(path, "w") as f:
        json.dump(traffic, f)
    fault(monkeypatch)
    r = drive(monkeypatch, tiny_bench, "olmo1b-decode")
    assert r["rc"] == 0 and r["attempted"] > 0
    assert r["correct"] is False
    gap = r["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_served_model_control_fails_the_limit(tiny_bench):
    """The reference in fp8 in the program's place: the tokens it puts
    first read a widest gap above the cell's limit."""
    cfg = harness.load_json(tiny_bench, "configs", "olmo-tiny.json")
    traffic = harness.load_json(tiny_bench, "traffic", "decode-tiny.json")
    ref = harness.load_module(os.path.join(tiny_bench, "configs",
                                           "olmo-tiny.py"))
    seed = 2_900_000_007
    rng = np.random.default_rng(seed)
    samples = [{"prompt": rng.integers(0, traffic["token_ids"], 16).tolist(),
                "tokens": rng.integers(0, traffic["token_ids"],
                                       12).tolist()} for _ in range(4)]
    gaps = ref.reference_gaps(cfg, seed, samples, traffic["max_len"], 2,
                              precision="fp8")
    widest = max(max(g["control_gaps"]) for g in gaps)
    assert widest > traffic["logit_gap_limit"], json.dumps(widest)

"""The harness: discovery by name, the result line, the FLOP counter and
the window arithmetic of the end-to-end metrics."""

from __future__ import annotations

import hashlib
import json
import os
import shutil

import pytest

from benchtest import BENCH, drive, load_driver

import harness
from flops import dense_request_flops, peak

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compiles_in_window", "checks"]
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _digest(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_spec_names_files_that_exist():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        cell = harness.find_cell(spec, w["name"])
        assert cell.config["name"] == w["config"]
        for m in cell.end_to_end + cell.per_layer:
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(os.path.dirname(BENCH),
                                           c["file"]))


def test_added_config_cell_and_metric_are_found_by_name(tiny_bench,
                                                        monkeypatch):
    """A configuration, a traffic mix, a cell and a metric dropped into a
    copy are found by name, with no existing file under bench/ edited."""
    before = _digest(tiny_bench)
    cdir = os.path.join(tiny_bench, "configs")
    shutil.copy(os.path.join(cdir, "spmv-tiny.json"),
                os.path.join(cdir, "spmv-extra.json"))
    shutil.copy(os.path.join(cdir, "spmv-tiny.py"),
                os.path.join(cdir, "spmv-extra.py"))
    with open(os.path.join(tiny_bench, "traffic", "extra-stream.json"),
              "w") as f:
        json.dump({"model": "processor", "engine": "numpy",
                   "rescache": False}, f)
    with open(os.path.join(tiny_bench, "metrics", "extra.count.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run['units']) + 0.5\n")
    spec_path = os.path.join(os.path.dirname(tiny_bench), "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "extra-cell", "config": "spmv-extra",
                              "traffic": "extra-stream", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "extra.count", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "sim_iter_per_s",
                              "workloads": ["extra-cell"]})
    spec["end_to_end"][0]["workloads"].append("extra-cell")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    r = drive(monkeypatch, tiny_bench, "extra-cell", trace=1)
    assert r["rc"] == 0 and r["correct"] is True
    assert r["metrics"]["extra.count"]["value"] == r["attempted"] + 0.5
    after = _digest(tiny_bench)
    assert all(after[k] == v for k, v in before.items())


def test_result_line_schema(tiny_bench, monkeypatch):
    r = drive(monkeypatch, tiny_bench, "spmv-acp64k-stream", trace=0)
    assert r.pop("rc") == 0
    assert list(r) == RESULT_KEYS             # "checks" comes last
    assert set(r["device"]) == DEVICE_KEYS
    assert set(r["metrics"]) == {"sim_iter_per_s", "setup_s"}
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert all(set(c) == {"value", "limit"} for c in r["checks"].values())
    t = drive(monkeypatch, tiny_bench, "spmv-processor-stream", trace=1)
    assert set(t["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}


def test_no_accelerator_exits_nonzero_without_result(capsys):
    import run
    rc = run.main(["--workload", "spmv-acp64k-stream", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_unknown_workload_exits_nonzero(capsys):
    import run
    assert run.main(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("mlp", [{"mlp_hidden_size": 32},
                                 {"mlp_hidden_size": None, "mlp_ratio": 4}])
def test_dense_request_flops(mlp):
    """The MLP width is stated, or mlp_ratio times the model width."""
    cfg = {"d_model": 8, "n_layers": 2, "embedding_size": 10, **mlp}
    # per position per layer: 2 * (4*64 + 3*8*16) = 1280; attention over
    # positions 0..3 of a 3-token prompt and 2 new tokens: 4*8*(1+2+3+4)
    want = 2 * (1280 * 4 + 4 * 8 * 10) + 2 * 8 * 10 * 2
    assert dense_request_flops(cfg, 3, 2) == want
    assert dense_request_flops(cfg, 3, 1) == \
        2 * (1280 * 3 + 4 * 8 * 6) + 2 * 8 * 10


def test_peak_table_refuses_unknown_device():
    assert peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError):
        peak("cpu", "bf16_flops_per_s")


def _read(name, run):
    return harness.load_module(os.path.join(BENCH, "metrics",
                                            name + ".py")).read(run)


def test_window_arithmetic():
    units = [{"iterations": 100, "t_done": 1.0},
             {"iterations": 100, "t_done": 2.5}]
    assert _read("sim_iter_per_s", {"units": units, "window_s": 2.5}) == 80
    served = [{"useful_tokens": 30, "requests": 3, "latency_s": 1.0},
              {"useful_tokens": 10, "requests": 1, "latency_s": 4.0}]
    assert _read("tok_per_s", {"units": served, "window_s": 5.0}) == 8


def test_p95_is_a_tail_over_all_requests():
    """19 requests at 1 s in one batch and one at 9 s alone: the p95 over
    all 20 requests is 1 s (nearest rank 19); a p95 over per-batch values
    would read 9 s."""
    units = [{"requests": 19, "latency_s": 1.0},
             {"requests": 1, "latency_s": 9.0}]
    assert _read("ttft_p95_ms", {"units": units}) == 1000.0
    units.append({"requests": 2, "latency_s": 9.0})
    assert _read("ttft_p95_ms", {"units": units}) == 9000.0
    assert harness.nearest_rank(list(range(1, 101)), 0.95) == 95


def test_tpot_is_each_requests_latency_over_its_own_tokens():
    """A batch of 20 requests that returns after 2 s: the 19 that asked
    for 100 tokens read 20 ms a token, the one that asked for 10 reads
    200 ms; nearest rank 19 of 20 is 20 ms, and one more short request
    makes the tail 200 ms."""
    units = [{"latency_s": 2.0, "request_tokens": [100] * 19 + [10]}]
    assert _read("tpot_p95_ms", {"units": units}) == 20.0
    units[0]["request_tokens"] += [10]
    assert _read("tpot_p95_ms", {"units": units}) == 200.0


def test_idle_share_and_mfu():
    run = {"trace": {"busy_s": 0.25, "window_s": 1.0}}
    assert _read("idle_share.sim", run) == 75.0
    assert _read("idle_share.decode", {"trace": None}) is None
    run = {"units": [{"flops": 197e12}], "window_s": 2.0,
           "device": {"kind": "TPU v5 lite", "count": 1}}
    assert _read("mfu.decode", run) == pytest.approx(50.0)


@pytest.mark.parametrize("traffic", ["decode-heavy", "prefill-heavy"])
def test_sample_covers_every_slot_of_a_batch(traffic):
    """At the cells' own sizes the reference checks whole batches: every
    slot of the batch (so half of a batch left out cannot hide), the
    longest request, and at least the traffic's sample of tokens."""
    lm = load_driver("serve_lm")
    t = harness.load_json(BENCH, "traffic", traffic + ".json")
    seed = 2_147_485_123
    batches = []
    for k in range(4):
        _, gens = lm.batch_plan(t, seed, k)
        batches.append([{"slot": i, "tokens": [0] * max(gens)}
                        for i in range(t["batch"])])
        batches[-1][gens.index(max(gens))]["tokens"].append(0)
    for s in range(seed, seed + 8):
        sample, total = lm.sample_batches(batches, s, t["sample_tokens"])
        assert total >= t["sample_tokens"]
        assert len(sample) % t["batch"] == 0
        assert {r["slot"] for r in sample} == set(range(t["batch"]))
        assert max(len(r["tokens"]) for r in sample) == max(
            len(r["tokens"]) for b in batches for r in b)

"""The readers of the program's simulator and engine spans and counters:
their arithmetic on hand-built runs, and one tiny traced run per
simulation cell in which every one of them reports."""

from __future__ import annotations

import json
import os

import pytest

from benchtest import BENCH, drive

import harness

SPAN_METRICS = ["sim.windows_s_per_miter", "engine.roundtrip_s_per_miter",
                "engine.dispatches_per_miter", "engine.elems_per_dispatch"]


def _read(name, run):
    return harness.load_module(os.path.join(BENCH, "metrics",
                                            name + ".py")).read(run)


@pytest.fixture
def registry():
    from repro import trace
    trace.reset()
    yield trace
    trace.reset()


def test_span_readers_per_million_iterations():
    units = [{"iterations": 1_500_000}, {"iterations": 2_500_000}]
    run = {"units": units,
           "walls": {"windows": 0.2, "roundtrip": 0.06, "solve": 0.3},
           "dispatches": {"pallas_running_max@tpu": 36, "nway@tpu": 4}}
    assert _read("sim.windows_s_per_miter", run) == pytest.approx(0.05)
    assert _read("engine.roundtrip_s_per_miter", run) == pytest.approx(0.015)
    assert _read("engine.dispatches_per_miter", run) == pytest.approx(10.0)
    # a program that opens no such span, or dispatches nothing, reports
    # nothing
    bare = {"units": units, "walls": {"solve": 0.3}}
    for name in SPAN_METRICS[:3]:
        assert _read(name, bare) is None
    assert _read("engine.dispatches_per_miter",
                 {"units": units, "dispatches": {}}) is None


def test_elements_per_dispatch_reads_the_registry(registry):
    assert _read("engine.elems_per_dispatch", {}) is None
    registry.count("dispatch.pallas_running_max@tpu", 3)
    registry.count("elements.pallas_running_max@tpu", 3 << 20)
    registry.count("dispatch.nway@tpu", 1)
    registry.count("elements.nway@tpu", 1 << 18)
    registry.count("serve.batches", 7)          # not an engine counter
    assert _read("engine.elems_per_dispatch", {}) == \
        ((3 << 20) + (1 << 18)) / 4


@pytest.mark.parametrize("cell", ["spmv-acp64k-stream",
                                  "spmv-processor-stream"])
def test_traced_run_reports_the_span_metrics(tiny_bench, monkeypatch,
                                             registry, cell):
    """A tiny traced run of each simulation cell on the jax engine (the
    chip's engine; on the CPU it must be asked for by name), with enough
    iterations that the solver's running max is sent to the device:
    every span metric reports."""
    tdir = os.path.join(tiny_bench, "traffic")
    for name in ("acp64k-stream", "processor-stream"):
        path = os.path.join(tdir, name + ".json")
        with open(path) as f:
            t = json.load(f)
        t["engine"] = "jax"
        with open(path, "w") as f:
            json.dump(t, f)
    cpath = os.path.join(tiny_bench, "configs", "spmv-tiny.json")
    with open(cpath) as f:
        cfg = json.load(f)
    cfg["iterations"] = 40_000
    with open(cpath, "w") as f:
        json.dump(cfg, f)
    r = drive(monkeypatch, tiny_bench, cell, trace=1)
    assert r["rc"] == 0 and r["correct"] is True
    for name in SPAN_METRICS:
        assert r["metrics"][name]["value"] > 0, name
    assert r["metrics"]["engine.elems_per_dispatch"]["unit"] == "elements"

"""The reduction from a profiler trace to busy time, idle gaps and the
traced window."""

from __future__ import annotations

import os

import pytest

from benchtest import BENCH

import xplane

MS = 1e6      # nanoseconds


def _ev(a_ms, b_ms, name):
    return (a_ms * MS, b_ms * MS, name)


def test_busy_is_the_union_of_device_ops_inside_the_window():
    host = [_ev(10, 110, xplane.WINDOW_SPAN), _ev(10, 60, "bench.unit"),
            _ev(60, 110, "bench.unit"), _ev(40, 55, "host.solve")]
    dev = {"/device:TPU:0": [_ev(0, 20, "before"),       # clipped to 10..20
                             _ev(15, 30, "op.a"),        # overlaps: 20..30
                             _ev(70, 80, "op.b"),
                             _ev(105, 130, "op.a")]}     # clipped to ..110
    s = xplane.summarize(host, dev)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.035)           # 10..30, 70..80, 105..110
    assert s["devices"] == 1
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["op.a"] == pytest.approx(0.020)
    assert ops["before"] == pytest.approx(0.010)
    gaps = dict(s["breakdown"]["idle_gaps"])
    # 30..70 (middle 50, inside host.solve) and 80..105 (bench.unit)
    assert gaps == pytest.approx({"host.solve": 0.040, "bench.unit": 0.025})


def test_busy_is_averaged_over_the_devices_that_ran():
    host = [_ev(0, 100, xplane.WINDOW_SPAN)]
    dev = {"/device:TPU:0": [_ev(0, 50, "x")],
           "/device:TPU:1": [_ev(0, 10, "x")],
           "/device:TPU:2": []}
    s = xplane.summarize(host, dev)
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx(0.030)


def test_recorded_chip_trace():
    """A traced run of ``spmv-acp64k-stream`` on one TPU v5e: the window
    span, the one device op (the solver's Pallas running max) and the
    idle gaps, pinned to the values that run printed."""
    path = os.path.join(BENCH, "tests", "data",
                        "spmv-acp64k-stream.xplane.pb")
    host, dev = xplane.read_events(path)
    assert list(dev) == ["/device:TPU:0"]
    assert [(a, b) for a, b, n in host if n == xplane.WINDOW_SPAN] == \
        [(51328739.0, 2713907394.0)]
    s = xplane.reduce(path)
    assert s["window_s"] == pytest.approx(2.662578655, abs=1e-9)
    assert s["busy_s"] == pytest.approx(0.003723716, abs=1e-9)
    assert s["devices"] == 1
    ops = s["breakdown"]["device_ops"]
    assert [n for n, _ in ops] == ["%tpu_custom_call.1"]
    assert ops[0][1] == pytest.approx(s["busy_s"])
    assert s["breakdown"]["idle_gaps"] == [["bench.unit", pytest.approx(
        1.44423997)]]


def test_nothing_to_read():
    assert xplane.summarize([], {"/device:TPU:0": [_ev(0, 1, "x")]}) is None
    assert xplane.summarize([_ev(0, 10, xplane.WINDOW_SPAN)],
                            {"/device:TPU:0": [_ev(20, 30, "x")]}) is None

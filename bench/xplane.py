"""Reduction of a profiler trace (``.xplane.pb``) to device busy time.

The traced window is the host span named :data:`WINDOW_SPAN`, which the
harness opens around the traced part of the measured window.  A device's
busy time is the union of the intervals of its ``XLA Ops`` events inside
that span; ``busy_s`` is its mean over the devices that ran anything.
``breakdown`` lists the device operations that took most time and the
longest idle gaps, each named by the innermost host span open over its
middle.
"""

from __future__ import annotations

import glob
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
TOP = 10

#: (start_ns, end_ns, name)
Event = tuple[float, float, str]


def find_xplane(trace_dir: str) -> str | None:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def op_name(hlo: str) -> str:
    """An XLA op's event name is its HLO text (``%fusion.3 = bf16[...]
    fusion(...)``); the breakdown keeps the instruction's name."""
    return hlo.split(" = ", 1)[0]


def read_events(path: str) -> tuple[list[Event], dict[str, list[Event]]]:
    """The host spans, and each accelerator's device operations, of one
    trace."""
    from jax.profiler import ProfileData

    host: list[Event] = []
    devices: dict[str, list[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((float(ev.start_ns), float(ev.end_ns), ev.name)
                            for ev in line.events)
        elif plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (float(ev.start_ns), float(ev.end_ns),
                         op_name(ev.name))
                        for ev in line.events)
    return host, devices


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def summarize(host: list[Event],
              devices: dict[str, list[Event]]) -> dict | None:
    """``{"busy_s", "window_s", "devices", "breakdown"}``, or None when
    there is no window span or no device operation inside it."""
    windows = [(a, b) for a, b, n in host if n == WINDOW_SPAN]
    if not windows:
        return None
    w0, w1 = windows[0]
    op_time: dict[str, float] = {}
    busy = []
    gaps: list[tuple[float, float]] = []
    for evs in devices.values():
        inside = [(max(a, w0), min(b, w1), n) for a, b, n in evs
                  if b > w0 and a < w1]
        if not inside:
            continue
        for a, b, n in inside:
            op_time[n] = op_time.get(n, 0.0) + (b - a) * 1e-9
        merged = _union([(a, b) for a, b, _ in inside])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    if not busy:
        return None
    named: dict[str, float] = {}
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (a + b) / 2
        cover = [s for s in host
                 if s[0] <= mid <= s[1] and s[2] != WINDOW_SPAN]
        name = min(cover, key=lambda s: s[1] - s[0])[2] if cover \
            else "host:none"
        named[name] = named.get(name, 0.0) + (b - a) * 1e-9
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (w1 - w0) * 1e-9,
        "devices": len(busy),
        "breakdown": {
            "device_ops": sorted(([n, s] for n, s in op_time.items()),
                                 key=lambda x: -x[1])[:TOP],
            "idle_gaps": sorted(([n, s] for n, s in named.items()),
                                key=lambda x: -x[1])[:TOP],
        },
    }


def reduce(path: str) -> dict | None:
    """:func:`summarize` of the trace at ``path``."""
    return summarize(*read_events(path))


def idle_share(run: dict) -> float | None:
    """Share (%) of the traced window in which no operation ran on the
    device: 1 - busy / window."""
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

"""The benchmark's general machinery.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json``   the configuration as it is run, and
  ``configs/<config>.py``     its workload generator and plain reference;
* ``traffic/<traffic>.json``  the parameters of one traffic mix;
* ``drivers/<driver>.py``     the general driver that a configuration's
  ``"driver"`` names (one per kind of system under test);
* ``metrics/<metric>.py``     a reader ``read(run) -> float | None``.

A driver's ``run(ctx)`` sets up, warms up, measures one closed-loop
window through :func:`closed_loop`, checks the outputs against the plain
reference, and returns the run record that the metric readers read.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

#: a traced run stops the profiler after the first unit of work that
#: ends this long into the window (the rest of the window runs untraced)
TRACE_MIN_S = 2.0


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown name)."""


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str | None = None):
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + os.path.basename(path).replace(".", "_")
        .replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    entry: dict
    config: dict
    config_module: Any
    traffic: dict
    driver: Any
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: str = BENCH_DIR


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(spec: dict, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    cdir = os.path.join(bench_dir, "configs")
    config = load_json(cdir, entry["config"] + ".json")
    return Cell(
        name=name, entry=entry, config=config,
        config_module=load_module(os.path.join(cdir, entry["config"]
                                               + ".py")),
        traffic=load_json(bench_dir, "traffic", entry["traffic"] + ".json"),
        driver=load_module(os.path.join(bench_dir, "drivers",
                                        config["driver"] + ".py")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def read_metrics(cell: Cell, metrics: list[dict], run: dict) -> dict:
    """``{name: {"value", "unit"}}`` for each metric whose reader finds
    something in ``run``."""
    out = {}
    for m in metrics:
        reader = load_module(os.path.join(cell.bench_dir, "metrics",
                                          m["name"] + ".py"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# Device and compile cache
# ---------------------------------------------------------------------------

def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache/``; every program is kept, however fast it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int) -> list:
    """The accelerator devices, or BenchError when JAX finds none or
    fewer than ``n``."""
    # libtpu writes its logs to /tmp/tpu_logs unless told where
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise BenchError("no accelerator: jax found only the CPU")
    if len(devices) < n:
        raise BenchError(f"the cell needs {n} chips, jax found "
                         f"{len(devices)}")
    return devices[:n]


def device_info(devices: list) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts XLA backend compilations while it is active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.active = False
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------

@dataclass
class Window:
    units: list[dict] = field(default_factory=list)
    t0: float = 0.0
    window_s: float = 0.0
    compiles: int = 0
    trace: dict | None = None


def closed_loop(unit: Callable[[int], dict], seconds: float, *,
                trace: bool = False, counter: CompileCounter | None = None
                ) -> Window:
    """Run ``unit(k)`` back to back for ``seconds``: each unit starts when
    the last has returned, and the one running at the deadline completes.
    Each record gains ``t_start`` and ``t_done`` (seconds from the window's
    start).  ``window_s`` runs to the last completion.  With ``trace``, the
    profiler records the units up to the first that ends
    :data:`TRACE_MIN_S` into the window."""
    import jax
    w = Window()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if counter:
        counter.active = True
    tracing = False
    span = None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no event per Python call
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        tracing = True
    w.t0 = time.perf_counter()
    k = 0
    try:
        while True:
            t = time.perf_counter() - w.t0
            if t >= seconds:
                break
            with (jax.profiler.TraceAnnotation("bench.unit") if tracing
                  else contextlib.nullcontext()):
                rec = unit(k)
            rec["t_start"] = t
            rec["t_done"] = time.perf_counter() - w.t0
            w.units.append(rec)
            k += 1
            if tracing and rec["t_done"] >= min(TRACE_MIN_S, seconds):
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
    finally:
        if tracing:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if counter:
            counter.active = False
            w.compiles = counter.count
    w.window_s = w.units[-1]["t_done"] if w.units else 0.0
    if trace:
        from xplane import find_xplane, reduce
        path = find_xplane(tdir)
        w.trace = reduce(path) if path else None
        shutil.rmtree(tdir, ignore_errors=True)
    return w


def nearest_rank(values: list[float], q: float) -> float:
    """The ``q`` quantile (0..1) of all ``values`` by nearest rank."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


# ---------------------------------------------------------------------------
# Result
# ---------------------------------------------------------------------------

def emit(result: dict, checks: list[dict]) -> None:
    """The compared numbers beside their limits, last on standard error,
    then the result line, last on standard output."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    print(json.dumps(line), flush=True)

"""Tests for the span-and-counter registry (repro.trace): accumulation,
nesting, merge and reset; the engine's names for it; the spans of the
simulator, the resolution engine and the LM server on a profiler trace's
clock; and the stable names of the jitted kernels and serving steps."""

import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.core import engine as eng
from repro.core.simulator import (CacheConfig, MemAccess, SimStage,
                                  acp_cache, simulate_dataflow)


@pytest.fixture(autouse=True)
def _clean_registry():
    trace.reset()
    yield
    trace.reset()


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------

def test_spans_accumulate_and_nest():
    with trace.span("outer") as outer:
        time.sleep(0.01)
        with trace.span("inner", batch=3) as inner:
            time.sleep(0.01)
    with trace.span("inner"):
        pass
    w = trace.walls()
    assert set(w) == {"outer", "inner"}
    # inclusive: the outer span holds the inner one
    assert outer.seconds >= inner.seconds + 0.01
    assert w["outer"] == outer.seconds
    assert w["inner"] >= inner.seconds


def test_span_records_when_the_block_raises():
    with pytest.raises(ValueError):
        with trace.span("failing"):
            raise ValueError("x")
    assert "failing" in trace.walls()


def test_counters_merge_and_reset():
    trace.count("serve.batches")
    trace.count("serve.batches")
    trace.count("serve.tokens_decoded", 32 * 316)
    assert trace.counts() == {"serve.batches": 2,
                              "serve.tokens_decoded": 10112}
    with trace.span("replay"):
        pass
    trace.merge({"replay": 1.5, "fold": 2.0}, {"serve.batches": 3})
    trace.merge(None, None)  # a child may report nothing
    w, c = trace.walls(), trace.counts()
    assert w["replay"] >= 1.5 and w["fold"] == 2.0
    assert c["serve.batches"] == 5
    trace.reset()
    assert trace.walls() == {} and trace.counts() == {}


def test_counts_from_many_threads_are_not_lost():
    """Updates are read-modify-write: a lost one would show here."""
    import sys
    import threading
    n_threads, n = 16, 2000

    def work():
        for _ in range(n):
            trace.count("c")
            with trace.span("s"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert trace.counts() == {"c": n_threads * n}


def test_engine_names_are_the_same_registry():
    """``engine.phase`` / ``walls`` / ``merge_walls`` / ``dispatches`` and
    the two resets read and write ``repro.trace``; dispatches are the
    ``dispatch.`` counters without their prefix."""
    with eng.phase("solve"):
        pass
    eng.merge_walls({"fold": 1.0})
    assert trace.walls() == eng.walls()
    assert set(eng.walls()) == {"solve", "fold"}
    trace.count("dispatch.cummax@cpu", 2)
    trace.count("elements.cummax@cpu", 70000)
    assert eng.dispatches() == {"cummax@cpu": 2}
    eng.reset_dispatches()
    assert trace.counts() == {} and trace.walls() == {}
    trace.count("dispatch.nway@cpu")
    with eng.phase("replay"):
        pass
    eng.reset_walls()
    assert eng.dispatches() == {} and eng.walls() == {}


def test_running_max_counts_elements_and_round_trips():
    a = np.random.default_rng(0).integers(0, 1 << 40, eng.JIT_MIN_ELEMS + 5)
    with eng.use("jax"):
        eng.running_max(a)
    c = trace.counts()
    assert c["dispatch.cummax@cpu"] == 1
    assert c["elements.cummax@cpu"] == eng.JIT_MIN_ELEMS + 5
    assert trace.walls()["roundtrip"] > 0


# ---------------------------------------------------------------------------
# Spans on the profiler's clock
# ---------------------------------------------------------------------------

def _host_events(trace_dir: str) -> list[tuple[float, float, str]]:
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((float(ev.start_ns), float(ev.end_ns), ev.name)
                           for ev in line.events)
    return out


def _tiny_server():
    from repro.configs import load_config, reduced
    from repro.launch.serve import BatchedServer, Request
    from repro.models import init_params
    cfg = reduced(load_config("olmo-1b"), max_repeats=2)
    server = BatchedServer(cfg, init_params(jax.random.PRNGKey(0), cfg),
                           max_len=32)
    rng = np.random.default_rng(0)
    reqs = [Request(100 + i, rng.integers(0, cfg.vocab_size, size=(8,))
                    .astype(np.int32), g) for i, g in enumerate((3, 5))]
    return server, reqs


def test_spans_land_on_the_profiler_trace(tmp_path):
    """A tiny simulation on the jax engine and a tiny served batch, under
    the profiler: the program's spans are host events of the trace, the
    engine's round trips lie inside the phases that make them, and the
    server opens one dispatch and one sync span per decode step."""
    n = 40_000          # the solve's running max reaches the jit size
    rng = np.random.default_rng(0)
    stages = [SimStage("ld", ii=1, latency=2, accesses=[
        MemAccess("x", rng.integers(0, 1 << 16, n) * 4)]),
        SimStage("fma", ii=2, latency=4)]
    mem = acp_cache()
    mem.cache = CacheConfig(size_bytes=8 * 1024, ways=4)   # N-way core
    server, reqs = _tiny_server()
    server.serve(reqs)                                      # compile
    simulate_dataflow(stages, mem, n, use_rescache=False, engine="jax")
    trace.reset()
    with jax.profiler.trace(str(tmp_path)):
        simulate_dataflow(stages, mem, n, use_rescache=False, engine="jax")
        res = server.serve(reqs)
    ev = _host_events(str(tmp_path))
    names = [e[2] for e in ev]
    for name in ("replay", "solve", "windows", "fold", "roundtrip",
                 "serve.prefill", "serve.decode", "dataflow.lower"):
        assert name in names, name
    # one dispatch and one sync span per decode step, as the counter says
    steps = trace.counts()["serve.decode_steps"]
    assert steps == 5
    assert names.count("serve.dispatch") == names.count("serve.sync") \
        == steps
    phases = [e for e in ev if e[2] in ("replay", "solve")]
    trips = [e for e in ev if e[2] == "roundtrip"]
    assert {p[2] for p in phases
            for t in trips if p[0] <= t[0] and t[1] <= p[1]} \
        == {"replay", "solve"}
    assert all(any(p[0] <= t[0] and t[1] <= p[1] for p in phases)
               for t in trips)
    # the registry's walls and the results' times are the same spans
    dec = [e for e in ev if e[2] == "serve.decode"]
    assert len(dec) == 1
    assert (dec[0][1] - dec[0][0]) * 1e-9 == pytest.approx(
        res[0].decode_s * 5, rel=0.05)
    w, c = trace.walls(), trace.counts()
    assert res[0].prefill_s == pytest.approx(w["serve.prefill"])
    assert res[0].decode_s * 5 == pytest.approx(w["serve.decode"])
    assert w["serve.decode"] >= w["serve.dispatch"] + w["serve.sync"]
    assert c["serve.batches"] == 1
    assert c["serve.tokens_decoded"] == 2 * 5
    assert c["serve.tokens_returned"] == 3 + 5


# ---------------------------------------------------------------------------
# Stable module names
# ---------------------------------------------------------------------------

def _module_name(jitted, *args) -> str:
    text = jitted.lower(*args).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


def test_kernels_and_steps_lower_to_stable_module_names():
    """The trace's ``XLA Modules`` line names each kernel and serving step
    by its function, never ``jit_<lambda>``."""
    eng._nway_jit = eng._nway_jit or eng._build_nway_jit()
    T = np.full((2, 16), -2, np.int64)
    sg = np.arange(16, dtype=np.int64)
    with eng._x64():
        assert _module_name(eng._nway_jit, T, sg, np.ones(16, bool),
                            np.full((16, 4), -1, np.int64), 1) \
            == "jit_nway_core"
        a = np.arange(eng.JIT_MIN_ELEMS, dtype=np.int64)
        with eng.use("jax"):
            eng.running_max(a.copy())
        assert _module_name(eng._cummax_jit, a) == "jit_cummax"
    rmax = eng._build_pallas_rmax(8, 1, True)
    assert _module_name(rmax, np.zeros((8, 128), np.int32)) \
        == "jit_running_max"
    server, reqs = _tiny_server()
    tok = jnp.zeros((2,), jnp.int32)
    prompts = jnp.zeros((2, 8), jnp.int32)
    pre = server._prefill.lower(server.params, prompts)
    assert _module_name(jax.jit(pre.fn), server.params, prompts) \
        == "jit_prefill_step"
    _, cache = server._prefill(server.params, prompts)
    length = jnp.asarray(8, jnp.int32)
    dec = server._decode.lower(server.params, tok, cache, length)
    assert _module_name(jax.jit(dec.fn), server.params, tok, cache,
                        length) == "jit_decode_step"

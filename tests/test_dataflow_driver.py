"""Tests for the repro.dataflow compiler driver: backend parity, the
compilation cache, the pass pipeline surface, and the schedule reports."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dataflow import (Backend, CompileOptions, Pass,
                            clear_cache, cache_stats, compile as dcompile,
                            dataflow_jit, default_pipeline, execute_backends,
                            get_backend, register_backend,
                            unregister_backend)


def _quickstart_kernel(table, idx, w):
    g = table[idx]
    h = g * w
    return jnp.tanh(h) + 1.0


def _example():
    table = jnp.arange(1024, dtype=jnp.float32)
    idx = jnp.asarray([3, 997, 41, 512, 7, 800, 64, 2])
    w = jnp.float32(1.5)
    return table, idx, w


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_cache()
    yield
    clear_cache()


# ---------------------------------------------------------------------------
# Backend parity
# ---------------------------------------------------------------------------

def test_backend_parity_on_quickstart_kernel():
    """sequential == emulated == xla == direct call."""
    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w, stream_argnums=(1,))
    ref = np.asarray(_quickstart_kernel(table, idx, w))
    for name in execute_backends():
        if name not in c.backends():  # systolic needs one device per stage
            continue
        got = np.asarray(c(table, idx, w, backend=name))
        np.testing.assert_allclose(got, ref, rtol=1e-6, err_msg=name)


@pytest.mark.slow
def test_all_backends_including_systolic_subprocess():
    """With forced host devices every registered execute backend runs and
    matches the direct call (the quickstart acceptance check)."""
    import os
    import subprocess
    import sys
    import textwrap

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(root, "src")
    code = textwrap.dedent("""
        import numpy as np, jax.numpy as jnp
        from repro.dataflow import compile as dcompile, execute_backends

        def kernel(table, idx, w):
            return jnp.tanh(table[idx] * w) + 1.0

        table = jnp.arange(1024, dtype=jnp.float32)
        idx = jnp.asarray([3, 997, 41, 512, 7, 800, 64, 2])
        w = jnp.float32(1.5)
        c = dcompile(kernel, table, idx, w, stream_argnums=(1,))
        assert set(execute_backends()) <= set(c.backends()), c.backends()
        ref = np.asarray(kernel(table, idx, w))
        for name in execute_backends():
            got = np.asarray(c(table, idx, w, backend=name))
            np.testing.assert_allclose(got, ref, rtol=1e-6, err_msg=name)
        print("parity across", execute_backends())
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"


def test_simulate_backend_returns_report():
    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w)
    rep = c(table, idx, w, backend="simulate")
    assert rep.dataflow.cycles > 0
    assert rep.conventional.cycles >= rep.dataflow.cycles
    assert "Fig. 2" in rep.summary()


def test_compiled_sweep_grid():
    """Compiled.sweep: the design-space grid over memory models × FIFO
    depths × SCC modes, dispatched through the simulate backend."""
    import json

    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w)
    res = c.sweep(n_iters=1500, fifo_depths=(4, 16),
                  scc_modes=("auto", "forced"))
    # 4 memory models x 2 depths x 2 modes
    assert len(res.rows) == 16
    assert {r["mem"] for r in res.rows} == {"ACP", "ACP+64KB", "HP",
                                            "HP+64KB"}
    for r in res.rows:
        assert r["dataflow_cycles"] > 0
        assert r["speedup"] == r["conventional_cycles"] / r["dataflow_cycles"]
    # the grid is JSON-ready (the BENCH_sim.json contract)
    json.dumps(res.to_json())
    best = res.best()
    assert best["dataflow_cycles"] == min(r["dataflow_cycles"]
                                          for r in res.rows)
    assert "best dataflow config" in res.summary()
    # forcing the DFS pathology can never make the pipeline faster
    for mem in ("ACP", "HP"):
        auto = [r for r in res.rows if r["mem"] == mem
                and r["mem_in_scc"] == "auto" and r["fifo_depth"] == 16]
        forced = [r for r in res.rows if r["mem"] == mem
                  and r["mem_in_scc"] == "forced" and r["fifo_depth"] == 16]
        assert forced[0]["dataflow_cycles"] >= auto[0]["dataflow_cycles"]


def test_sweep_conventional_shared_across_depths():
    """The conventional engine has no FIFOs: one simulation per
    (memory, SCC mode) is reused across the depth axis."""
    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w)
    res = c.sweep(n_iters=800, fifo_depths=(2, 8, 32))
    by_mem: dict = {}
    for r in res.rows:
        by_mem.setdefault(r["mem"], set()).add(r["conventional_cycles"])
    for mem, cycles in by_mem.items():
        assert len(cycles) == 1, (mem, cycles)


def test_stream_matches_per_microbatch_calls():
    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w, stream_argnums=(1,))
    T = 5
    idxs = jnp.stack([(idx + t) % 1024 for t in range(T)])
    outs = c.stream(table, idxs, w)
    ref = np.stack([np.asarray(_quickstart_kernel(table, idxs[t], w))
                    for t in range(T)])
    np.testing.assert_allclose(np.asarray(outs), ref, rtol=1e-6)


def test_zero_rank_channel_var_roundtrips():
    """A scalar crossing a stage boundary (satellite: _example_for_var must
    handle zero-rank avals consistently with the channel specs)."""

    def kernel(x, idx):
        s = jnp.exp(jnp.float32(0.5)) * x.sum()   # zero-rank, long op
        return x[idx] * s

    x = jnp.arange(16, dtype=jnp.float32)
    idx = jnp.asarray([3, 1, 7, 2])
    c = dcompile(kernel, x, idx, stream_argnums=(1,))
    ref = np.asarray(kernel(x, idx))
    np.testing.assert_allclose(
        np.asarray(c(x, idx, backend="emulated")), ref, rtol=1e-6)


def test_pytree_outputs_roundtrip():
    def kernel(x):
        return {"a": x * 2.0, "b": (jnp.tanh(x), x.sum())}

    x = jnp.arange(8, dtype=jnp.float32)
    c = dcompile(kernel, x)
    ref = kernel(x)
    for backend in ("sequential", "xla"):
        got = c(x, backend=backend)
        assert set(got) == {"a", "b"}
        np.testing.assert_allclose(np.asarray(got["a"]),
                                   np.asarray(ref["a"]))
        np.testing.assert_allclose(np.asarray(got["b"][1]),
                                   np.asarray(ref["b"][1]))


# ---------------------------------------------------------------------------
# dataflow_jit decorator
# ---------------------------------------------------------------------------

def test_dataflow_jit_decorator_and_lower():
    table, idx, w = _example()

    @dataflow_jit(stream_argnums=(1,), backend="emulated")
    def kernel(table, idx, w):
        return jnp.tanh(table[idx] * w) + 1.0

    ref = np.asarray(kernel.__wrapped__(table, idx, w))
    np.testing.assert_allclose(np.asarray(kernel(table, idx, w)), ref,
                               rtol=1e-6)
    compiled = kernel.lower(table, idx, w)
    assert compiled.num_stages >= 3
    assert "stage 0" in compiled.report()
    # second lower with the same shapes returns the same artifact
    assert kernel.lower(table, idx, w) is compiled


def test_dataflow_jit_loop_mode():
    """Loop mode keeps the carried SCC in one stage (paper §III)."""

    @dataflow_jit(loop=True, backend="sequential")
    def body(carry, x):
        y = jnp.exp(x)
        return carry * 0.9 + y

    c = body.lower(jnp.float32(0.0), jnp.float32(1.0))
    part = c.partition
    carried = [n.id for n in c.cdfg.nodes if n.prim in ("mul", "add")]
    stages = {part.stage_of_node[n] for n in carried}
    assert len(stages) == 1, "loop-carried SCC split across stages"


# ---------------------------------------------------------------------------
# Compilation cache
# ---------------------------------------------------------------------------

def test_cache_hit_on_identical_options():
    table, idx, w = _example()
    opts = CompileOptions(stream_argnums=(1,))
    c1 = dcompile(_quickstart_kernel, table, idx, w, options=opts)
    c2 = dcompile(_quickstart_kernel, table, idx, w, options=opts)
    assert c1 is c2
    stats = cache_stats()
    assert stats["hits"] == 1 and stats["misses"] == 1


def test_cache_miss_on_changed_options():
    table, idx, w = _example()
    c1 = dcompile(_quickstart_kernel, table, idx, w, policy="paper")
    c2 = dcompile(_quickstart_kernel, table, idx, w, policy="fused")
    assert c1 is not c2
    assert c2.num_stages == 1
    assert cache_stats()["misses"] == 2


def test_cache_distinguishes_output_trees():
    """Identical flat computations with different return containers must
    not alias in the cache (regression: out_tree is part of the key)."""

    def as_tuple(x):
        return (x * 2, x + 1)

    def as_dict(x):
        return {"a": x * 2, "b": x + 1}

    x = jnp.arange(4.)
    c1 = dcompile(as_tuple, x)
    c2 = dcompile(as_dict, x)
    assert c1 is not c2
    assert isinstance(c1(x), tuple)
    assert isinstance(c2(x), dict)


def test_cache_miss_on_changed_shapes():
    table, idx, w = _example()
    c1 = dcompile(_quickstart_kernel, table, idx, w)
    c2 = dcompile(_quickstart_kernel, table, idx[:4], w)
    assert c1 is not c2


# ---------------------------------------------------------------------------
# Pass pipeline surface
# ---------------------------------------------------------------------------

def test_default_pipeline_names():
    assert default_pipeline().names() == [
        "trace", "memdep", "transform", "partition", "rewrite", "dse",
        "decouple",
        "schedule"]


def test_pipeline_pass_swap():
    """A custom partition pass slots into the pipeline by name."""
    from repro.core.partition import materialize, stage_groups

    class MaximalPartitionPass(Pass):
        name = "partition"

        def run(self, ctx):
            ctx.plan = stage_groups(ctx.cdfg, policy="maximal")
            ctx.partition = materialize(ctx.cdfg, ctx.plan)

    pipeline = default_pipeline().replace("partition",
                                          MaximalPartitionPass())
    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w, pipeline=pipeline,
                 duplicate_cheap=False)
    assert c.num_stages == len(c.cdfg.nodes)
    ref = np.asarray(_quickstart_kernel(table, idx, w))
    np.testing.assert_allclose(np.asarray(c(table, idx, w)), ref)


def test_pipeline_without_and_insert_after():
    ran = []

    class ProbePass(Pass):
        name = "probe"

        def run(self, ctx):
            ran.append(ctx.partition.num_stages)

    p = default_pipeline().without("rewrite").insert_after("partition",
                                                           ProbePass())
    assert "rewrite" not in p.names()
    assert p.names().index("probe") == p.names().index("partition") + 1
    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w, pipeline=p)
    assert ran == [c.num_stages]
    assert not c.partition.duplicated  # rewrite pass removed


def test_pass_timings_recorded():
    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w)
    assert set(c.context.timings) == set(default_pipeline().names())


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

def test_register_custom_backend_dispatch():
    class DoublingBackend(Backend):
        name = "test-doubling"

        def execute(self, compiled, args):
            seq = get_backend("sequential")
            return jax.tree_util.tree_map(lambda x: x * 2,
                                          seq.execute(compiled, args))

    register_backend(DoublingBackend)
    try:
        table, idx, w = _example()
        c = dcompile(_quickstart_kernel, table, idx, w)
        ref = np.asarray(_quickstart_kernel(table, idx, w))
        got = np.asarray(c(table, idx, w, backend="test-doubling"))
        np.testing.assert_allclose(got, 2 * ref, rtol=1e-6)
    finally:
        unregister_backend("test-doubling")


def test_unknown_backend_raises():
    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w)
    with pytest.raises(KeyError, match="unknown backend"):
        c(table, idx, w, backend="nope")


def test_duplicate_backend_name_rejected():
    class Clash(Backend):
        name = "sequential"

    with pytest.raises(ValueError, match="already registered"):
        register_backend(Clash)


# ---------------------------------------------------------------------------
# Options
# ---------------------------------------------------------------------------

def test_options_freeze_mappings_and_hash():
    o1 = CompileOptions(latency_table={"mul": 1, "add": 2},
                        regions={0: "table"})
    o2 = CompileOptions(latency_table={"add": 2, "mul": 1},
                        regions={0: "table"})
    assert o1 == o2 and hash(o1) == hash(o2)
    assert o1.latency_model().latency("mul") == 1
    assert o1.regions_map() == {0: "table"}


def test_options_regions_flow_into_report():
    table, idx, w = _example()
    c = dcompile(_quickstart_kernel, table, idx, w,
                 regions={0: "embedding_table"})
    assert any("embedding_table" in s.regions for s in c.schedule.stages)

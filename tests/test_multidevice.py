"""Multi-device tests for the shard_map pipeline executors.

These run in a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
because the main test process must keep seeing exactly one device (the
dry-run is the only other place allowed to fake a mesh).
"""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess + 8 forced host devices

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_systolic_pipeline_on_devices():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import CDFG, partition_cdfg, decouple, SystolicPipeline

        def kernel(x, idx, w):
            a = x[idx]
            b = a * w
            return jnp.tanh(b) + 1.0

        x = jnp.arange(64, dtype=jnp.float32)
        T = 9
        idxs = jnp.stack([(jnp.arange(8) * (t + 1)) % 64 for t in range(T)])
        w = jnp.float32(0.5)
        cdfg = CDFG.from_function(kernel, x, idxs[0], w)
        part = partition_cdfg(cdfg)
        prog = decouple(part)
        pipe = SystolicPipeline(prog, stream_argnums=(1,))
        S = pipe.num_stages
        mesh = jax.make_mesh((S,), ("stage",))
        run = pipe.build_sharded(mesh)
        outs = run(x, idxs, w)
        ref = jnp.stack([kernel(x, idxs[t], w) for t in range(T)])
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(ref),
                                   rtol=1e-6)
        print("systolic sharded OK, stages =", S)
    """)


def test_pipeline_apply_on_devices_fwd_and_grad():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import pipeline_apply, pipeline_apply_emulated

        S, M, D = 8, 16, 4
        rng = np.random.default_rng(0)
        params = jnp.asarray(rng.normal(size=(S, D, D)).astype(np.float32) * .2)
        mbs = jnp.asarray(rng.normal(size=(M, D)).astype(np.float32))
        mesh = jax.make_mesh((S,), ("stage",))

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        got = pipeline_apply(stage_fn, params, mbs, mesh=mesh)
        ref = pipeline_apply_emulated(stage_fn, params, mbs, num_stages=S)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

        # gradient flows through the ppermute channels (GPipe training)
        def loss(params):
            y = pipeline_apply(stage_fn, params, mbs, mesh=mesh)
            return jnp.mean(y ** 2)

        def loss_ref(params):
            y = pipeline_apply_emulated(stage_fn, params, mbs, num_stages=S)
            return jnp.mean(y ** 2)

        g = jax.grad(loss)(params)
        g_ref = jax.grad(loss_ref)(params)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-6)
        print("pipeline_apply fwd+grad OK")
    """)


def test_collectives_in_dp_tp_mesh():
    """Sanity: the production sharding pattern (DP×TP) compiles and runs
    a small matmul+psum on an 8-device (2,4) mesh."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P

        mesh = jax.make_mesh((2, 4), ("data", "model"))

        def f(x, w):
            y = jnp.einsum('bd,df->bf', x, w)
            return jax.lax.psum(y, 'model')

        x = jnp.ones((8, 16), jnp.float32)
        w = jnp.ones((16, 32), jnp.float32)
        out = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(P('data', 'model'), P('model', None)),
            out_specs=P('data', None), check_vma=False))(x, w)
        np.testing.assert_allclose(np.asarray(out), 16.0)
        print("dp-tp shard_map OK")
    """)


def test_transformer_pipeline_parallel():
    """The paper's template as pipeline parallelism for a real LM: layers
    split into 4 stages over a 'stage' mesh axis, microbatches streaming
    through ppermute channels; must match the sequential forward."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import pipeline_apply

        S, M, B, L, D = 4, 8, 2, 16, 32
        rng = np.random.default_rng(0)
        # per-stage params: one mini transformer block per stage
        def init_stage(k):
            k1, k2 = jax.random.split(k)
            return {
                "w_qkv": jax.random.normal(k1, (D, D), jnp.float32) * 0.05,
                "w_ff": jax.random.normal(k2, (D, D), jnp.float32) * 0.05,
            }
        keys = jax.random.split(jax.random.PRNGKey(0), S)
        params = jax.vmap(init_stage)(keys)

        def stage_fn(p, x):  # x: (B, L, D)
            h = jnp.tanh(x @ p["w_qkv"])
            return x + jnp.tanh(h @ p["w_ff"])

        mbs = jnp.asarray(rng.normal(size=(M, B, L, D)).astype(np.float32))
        mesh = jax.make_mesh((4,), ("stage",))

        def flat_stage(p, x):
            return stage_fn(p, x)

        got = pipeline_apply(flat_stage, params, mbs, mesh=mesh)

        def seq(x):
            for s in range(S):
                x = stage_fn(jax.tree_util.tree_map(lambda q: q[s], params), x)
            return x

        want = jnp.stack([seq(mbs[m]) for m in range(M)])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        from repro.core import gpipe_bubble_fraction
        print("transformer PP OK, bubble =",
              gpipe_bubble_fraction(S, M))
    """)


def test_elastic_resharded_restore(tmp_path):
    """Checkpoint saved unsharded restores onto a live (2,4) mesh with
    NamedShardings — the elastic-scaling path (different mesh than the
    writer's)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.checkpoint import Checkpointer

    state = {"w": jnp.asarray(np.arange(64, dtype=np.float32)
                              .reshape(8, 8))}
    ck = Checkpointer(str(tmp_path))
    ck.save(3, state, blocking=True)

    _run(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import Checkpointer

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        sh = {{"w": NamedSharding(mesh, P("data", "model"))}}
        ck = Checkpointer({str(tmp_path)!r})
        example = {{"w": jnp.zeros((8, 8), jnp.float32)}}
        restored, step = ck.restore(example, shardings=sh)
        assert step == 3
        assert restored["w"].sharding.spec == P("data", "model")
        np.testing.assert_array_equal(
            np.asarray(restored["w"]),
            np.arange(64, dtype=np.float32).reshape(8, 8))
        print("elastic restore OK on", len(restored["w"].devices()),
              "devices")
    """)

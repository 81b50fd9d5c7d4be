"""Compile the main path's kernels for a TPU v5e that is described, not
attached.

The TPU compiler is installed with jax, so a kernel that the chip would
refuse (a block that breaks the (8, 128) tiling, too much VMEM, a dtype
Mosaic cannot lower) fails here at no chip time.  Nothing runs: these
tests say nothing about results or speed.  Each kernel compiles at the
size the system uses it: the resolution engine's kernels at full-scale
Table-I spmv shapes, the paper's spmv at Table-I size, and the LM kernels
at SmolLM-135M widths (d_model 576, 9/3 heads of 64, d_ff 1536).  The
served decode step compiles whole, at 128-wide heads, to check that it
updates its KV cache in place.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The kernel modules are compiled directly with ``interpret=False``
because the ``ops`` wrappers pick interpret mode on a CPU backend.
"""

import importlib
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import engine as eng

# the kernel modules themselves: ``repro.kernels`` re-exports the ops
# wrappers under the same names
_mm, _dg, _fa, _rn, _spmv = (
    importlib.import_module(f"repro.kernels.{m}")
    for m in ("dataflow_matmul", "decoupled_gather", "flash_attention",
              "rmsnorm", "spmv"))


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host.  The persistent compile
    cache is off meanwhile: an entry written without a chip cannot be
    read back, and the next read would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _hlo(fn, *args) -> str:
    """Compiled HLO text of ``fn`` at ``args`` (ShapeDtypeStructs)."""
    lowered = fn.lower(*args) if hasattr(fn, "lower") else \
        jax.jit(fn).lower(*args)
    return lowered.compile().as_text()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# ---------------------------------------------------------------------------
# Resolution engine (full-scale spmv: 1M-iteration chunks)
# ---------------------------------------------------------------------------

def test_pallas_running_max_compiles(one_chip):
    """The solver's int32 running max over one 2**20-iteration chunk."""
    n = 1 << 20
    rows = eng._RMAX_ROWS
    nb = n // (rows * eng._LANES)
    fn = eng._build_pallas_rmax(rows, nb, False)
    hlo = _hlo(fn, _sds(one_chip, (nb * rows, eng._LANES), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", ["int32", "int64"])
def test_nway_core_compiles(one_chip, dtype):
    """The jitted N-way replay core at the padded shape a full-scale spmv
    chunk gives the processor model's 8-way L2.  (Its 4-way L1 pads to
    32 x 2**17 segments, which takes about a minute to compile: the chip
    smoke compiles that one.)"""
    W, G, sets, ways = 32, 1 << 14, 2048, 8
    core = eng._build_nway_jit()
    with jax.enable_x64(True):
        hlo = _hlo(core,
                   _sds(one_chip, (W, G), dtype),
                   _sds(one_chip, (G,), jnp.int32),
                   _sds(one_chip, (G,), jnp.bool_),
                   _sds(one_chip, (sets, ways), dtype),
                   _sds(one_chip, (), jnp.int64))
    assert "while" in hlo


# ---------------------------------------------------------------------------
# Compiled-program kernels (Table-I sizes)
# ---------------------------------------------------------------------------

def test_spmv_bsr_compiles(one_chip):
    """4096 x 4096 at density 0.25 in 8 x 128 blocks: every block of the
    matrix is stored, 32 per block row."""
    nbr, nnz, bm, bk = 4096 // 8, 4096 // 128, 8, 128
    hlo = _hlo(_spmv.spmv_bsr,
               _sds(one_chip, (nbr, nnz, bm, bk), jnp.float32),
               _sds(one_chip, (nbr, nnz), jnp.int32),
               _sds(one_chip, (4096,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("N,R,D", [(1024, 4096, 256), (5, 7, 128)])
def test_decoupled_gather_compiles(one_chip, N, R, D):
    hlo = _hlo(_dg.decoupled_gather,
               _sds(one_chip, (N,), jnp.int32),
               _sds(one_chip, (R, D), jnp.float32))
    assert "tpu_custom_call" in hlo


# ---------------------------------------------------------------------------
# LM kernels at SmolLM-135M widths
# ---------------------------------------------------------------------------

B, S, HQ, HKV, HD, D_MODEL, D_FF = 4, 128, 9, 3, 64, 576, 1536


def test_flash_attention_compiles(one_chip):
    q = _sds(one_chip, (B, HQ, S, HD), jnp.bfloat16)
    kv = _sds(one_chip, (B, HKV, S, HD), jnp.bfloat16)
    hlo = _hlo(_fa.flash_attention, q, kv, kv)
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles(one_chip):
    hlo = _hlo(_fa.decode_attention,
               _sds(one_chip, (B, HQ, HD), jnp.bfloat16),
               _sds(one_chip, (B, HKV, 256, HD), jnp.bfloat16),
               _sds(one_chip, (B, HKV, 256, HD), jnp.bfloat16),
               _sds(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_rmsnorm_compiles(one_chip):
    hlo = _hlo(_rn.rmsnorm,
               _sds(one_chip, (B * S, D_MODEL), jnp.bfloat16),
               _sds(one_chip, (D_MODEL,), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_dataflow_matmul_compiles(one_chip):
    """The MLP up-projection as ``ops.matmul`` pads it: K 576 -> 1024
    for 512-wide K blocks."""
    hlo = _hlo(_mm.dataflow_matmul,
               _sds(one_chip, (B * S, 1024), jnp.bfloat16),
               _sds(one_chip, (1024, D_FF), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


# ---------------------------------------------------------------------------
# The served decode step: the KV cache updated in place
# ---------------------------------------------------------------------------

def _decode_config(kind: str):
    """Two repeats of a decoder at TPU head widths (128), bf16: OLMo's
    layer with a bf16 or an int8 cache, or DeepSeek-V3's MLA layers with
    the absorbed decode (the naive one decompresses every layer's cache by
    design); or Moonlight-16B-A3B whole, at its published widths, as the
    benchmark serves it (absorbed MLA with no query LoRA, 8 of 64 experts
    held)."""
    import dataclasses
    from repro.configs.base import MLAConfig, load_config, reduced
    if kind == "moonlight":
        return load_config("moonlight-16b-a3b")
    arch = "deepseek-v3-671b" if kind == "mla" else "olmo-1b"
    cfg = reduced(load_config(arch), d_model=512)
    cfg = dataclasses.replace(cfg, dtype="bfloat16", attn_impl="auto")
    if kind == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if kind == "mla":
        cfg = dataclasses.replace(cfg, mla=MLAConfig(
            q_lora_rank=128, kv_lora_rank=128, qk_nope_head_dim=64,
            qk_rope_head_dim=64, v_head_dim=64), mla_absorbed=True)
    return cfg


def _hbm_buffers(hlo: str) -> list[tuple[str, str]]:
    """(opcode, dims) of every instruction outside fused computations whose
    result lives in HBM (no memory-space mark such as VMEM's ``S(1)``):
    the buffers the step materialises there."""
    fused = set(re.findall(r"fusion\(.*?calls=(%[\w.\-]+)", hlo))
    out, skip = [], False
    for line in hlo.splitlines():
        if line and not line.startswith(" "):
            skip = line.split(" ", 1)[0] in fused
            continue
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\{([^}]*)\} "
                     r"([\w\-]+)\(", line)
        if m and not skip and "S(" not in m.group(2):
            out.append((m.group(3), m.group(1)))
    return out


def _served_decode_step(one_chip, cfg, batch, max_len):
    """``(compiled jit_decode_step as the server builds it, cache shapes)``
    of ``cfg`` at ``batch`` rows and ``max_len`` positions."""
    from repro.launch.serve import BatchedServer
    from repro.models import init_cache, init_params

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = sds(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = sds(jax.eval_shape(lambda: init_cache(cfg, batch, max_len)))
    args = (params, _sds(one_chip, (batch,), jnp.int32), cache,
            _sds(one_chip, (), jnp.int32))
    step = BatchedServer(cfg, params, max_len=max_len)._decode.lower(*args)
    assert step.options.donate_argnums == (2,)
    compiled = jax.jit(step.fn, donate_argnums=step.options.donate_argnums
                       ).lower(*args).compile()
    assert compiled.as_text().startswith("HloModule jit_decode_step")
    return compiled, cache


@pytest.mark.parametrize("kind", ["bf16", "int8", "mla", "moonlight"])
def test_decode_step_updates_cache_in_place(one_chip, kind):
    """``jit_decode_step`` as the server builds it (cache donated) aliases
    every cache leaf to its output, materialises no HBM buffer with the
    shape of one layer's cache (in any dtype: no float32 copy of the
    latent cache either), and needs less scratch than one layer's K: the
    layer scan writes this token's entries into the carried cache and
    never copies a layer or the stack.  One layer's K is 64 MiB here (the
    latent ``c_kv`` 105 MiB at the Moonlight cell's batch of 128 and 840
    positions), too large for the compiler to stage a copy of it in
    VMEM."""
    batch, max_len = (128, 840) if kind == "moonlight" else (32, 2048)
    compiled, cache = _served_decode_step(one_chip, _decode_config(kind),
                                          batch, max_len)
    hlo = compiled.as_text()

    leaves = jax.tree_util.tree_leaves(cache)
    nbytes = [a.size * a.dtype.itemsize for a in leaves]
    aliased = re.findall(r"\{\d+\}: \(\d+, \{\}",
                         hlo.split("\n", 1)[0])
    memory = compiled.memory_analysis()
    assert len(aliased) == len(leaves)
    assert memory.alias_size_in_bytes == sum(nbytes)
    layer = {",".join(map(str, a.shape[1:])) for a in leaves}
    assert [(op, d) for op, d in _hbm_buffers(hlo)
            if d in layer and op != "bitcast"] == []
    assert memory.temp_size_in_bytes < max(
        n // a.shape[0] for n, a in zip(nbytes, leaves))


def test_moonlight_decode_step_runs_grouped_kernel(one_chip, monkeypatch):
    """Moonlight's decode step on the path a TPU takes (the test steers the
    path and the kernel's native lowering, which a CPU backend would not
    pick): every expert layer's grouped matmuls are the Pallas kernel (two
    calls a layer, gate and up fused), no ``ragged-dot`` is left, and no
    HBM buffer has the shape of one layer's held experts, so nothing is
    copied out of the stacked weights; the cache is still updated in
    place."""
    from repro.kernels import ops
    from repro.models import moe
    monkeypatch.setattr(moe, "grouped_path", lambda: "pallas")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = _decode_config("moonlight")
    compiled, cache = _served_decode_step(one_chip, cfg, 128, 840)
    hlo = compiled.as_text()
    assert "ragged-dot" not in hlo, re.findall(r".{80}ragged.{80}", hlo)[:3]
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*"
                          r"grouped_matmul", hlo)) == 2
    m, d = cfg.moe, cfg.d_model
    experts = {f"{m.held},{d},{m.d_ff}", f"{m.held},{m.d_ff},{d}"}
    assert [(op, s) for op, s in _hbm_buffers(hlo) if s in experts] == []
    leaves = jax.tree_util.tree_leaves(cache)
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in leaves)

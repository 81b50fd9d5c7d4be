"""Compile the main path's kernels for a TPU v5e that is described, not
attached.

The TPU compiler is installed with jax, so a kernel that the chip would
refuse (a block that breaks the (8, 128) tiling, too much VMEM, a dtype
Mosaic cannot lower) fails here at no chip time.  Nothing runs: these
tests say nothing about results or speed.  Each kernel compiles at the
size the system uses it: the resolution engine's kernels at full-scale
Table-I spmv shapes, the paper's spmv at Table-I size, and the LM kernels
at SmolLM-135M widths (d_model 576, 9/3 heads of 64, d_ff 1536).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The kernel modules are compiled directly with ``interpret=False``
because the ``ops`` wrappers pick interpret mode on a CPU backend.
"""

import importlib
import os

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

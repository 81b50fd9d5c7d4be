"""Decoupled access/execute matmul — the template inside one TPU kernel.

The paper's pipeline template maps 1:1 onto a Pallas grid pipeline:

* **access stage**: the ``BlockSpec`` index maps describe the HBM→VMEM tile
  streams; Pallas's grid pipeliner issues the DMA for tile *(i, j, k+1)*
  while tile *(i, j, k)* is being consumed — the double-buffered VMEM slots
  are the FIFO channel between the access and execute stages.
* **execute stage**: the MXU contraction over the resident tiles, with an
  fp32 VMEM accumulator (the long-latency stage whose steady consumption
  rate shadows HBM latency — Fig. 2's schedule).

Block shapes are chosen so the working set fits VMEM and the contraction
dims are MXU-aligned (multiples of 128 on the minor axes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref):
    # k == 0: reset the accumulator (new output tile begins)
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # execute stage: MXU contraction of the resident VMEM tiles
    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    # last k: write back the fp32 accumulator in the output dtype
    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype",
                     "interpret"))
def dataflow_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype: jnp.dtype | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``x @ w`` with fp32 accumulation.  x: (M, K), w: (K, N).

    Shapes must be divisible by the block sizes (the ops.py wrapper pads).
    VMEM working set: bm*bk + bk*bn (inputs, double-buffered by the
    pipeliner) + bm*bn fp32 (accumulator); defaults keep this ≈ 1.2 MB for
    bf16 inputs — well inside the ~16 MB v5e VMEM even with multi-slot
    buffering.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        (M, K, N), (block_m, block_k, block_n))
    out_dtype = out_dtype or x.dtype
    grid = (M // block_m, N // block_n, K // block_k)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w)

"""SpMV — the paper's first benchmark kernel, TPU-native.

The paper's CSR SpMV is the canonical irregular-access workload: loads of
the floating-point values depend on data in an index array (§V).  The HLS
flow decouples it into: (1) index fetch → (2) value/x gather → (3) FMA.

The GPU/CPU CSR layout is hostile to the MXU, so per the hardware-adaptation
mandate we *re-block* the matrix into BSR (block-sparse rows) and realize
the same three decoupled stages with TPU mechanisms:

1. **index fetch** — the block-column ids are *scalar-prefetched*
   (``PrefetchScalarGridSpec``): they land in SMEM before the grid step
   runs, exactly the paper's "stage issuing the memory request" running
   ahead.
2. **gather** — the ``x`` tile's ``BlockSpec`` index map reads the
   prefetched ids, so the DMA engine performs the data-dependent gather of
   ``x[col]`` while the previous block is still being multiplied (the FIFO
   between stages is the double-buffered VMEM slot).
3. **FMA** — the ``(bm, bk)`` block times the ``(1, bk)`` x tile, summed
   across the lanes into an fp32 ``(bm, 1)`` column in VMEM scratch (a
   matrix-vector product is bandwidth-bound: the VPU keeps up with HBM).

Every block keeps TPU tiling: ``x`` is viewed as ``(K // bk, 1, bk)`` and
``y`` as ``(n_block_rows, bm, 1)``, so each tile's last two dims are
either full dims or multiples of (8, 128).  Padding blocks (col_id == −1)
are mapped to block 0 and masked in-kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spmv_kernel(col_ref, val_ref, x_ref, y_ref, acc_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = col_ref[i, j] >= 0
    xblk = jnp.where(valid, x_ref[0].astype(jnp.float32), 0.0)   # (1, bk)
    acc_ref[...] += jnp.sum(val_ref[0, 0].astype(jnp.float32) * xblk,
                            axis=1, keepdims=True)               # (bm, 1)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmv_bsr(
    values: jax.Array,
    col_ids: jax.Array,
    x: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Block-sparse-row SpMV.

    values : (n_block_rows, nnz_blocks, bm, bk), bm % 8 == 0, bk % 128 == 0
    col_ids: (n_block_rows, nnz_blocks) int32, −1 = padding
    x      : (K,) with K divisible by bk
    returns (n_block_rows * bm,)
    """
    nbr, nnz, bm, bk = values.shape
    K = x.shape[0]
    assert K % bk == 0
    xb = x.reshape(K // bk, 1, bk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbr, nnz),
        in_specs=[
            pl.BlockSpec((1, 1, bm, bk), lambda i, j, cols: (i, j, 0, 0)),
            # the data-dependent gather: x's tile address comes from the
            # prefetched index array (stage 1 feeding stage 2); padding
            # blocks (−1) clamp to 0 and are masked in-kernel.
            pl.BlockSpec((1, 1, bk),
                         lambda i, j, cols: (jnp.maximum(cols[i, j], 0),
                                             0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, 1), lambda i, j, cols: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32)],
    )
    y = pl.pallas_call(
        _spmv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nbr, bm, 1), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(col_ids.astype(jnp.int32), values, xb)
    return y.reshape(-1)


def csr_to_bsr(indptr: np.ndarray, indices: np.ndarray, data: np.ndarray,
               shape: tuple[int, int], bm: int = 8, bk: int = 128
               ) -> tuple[np.ndarray, np.ndarray]:
    """Host-side re-blocking of CSR into the kernel's BSR layout.

    Returns (values, col_ids) with values (nbr, nnz_max, bm, bk) and
    col_ids (nbr, nnz_max) int32 (−1 padding); each block row lists its
    touched block columns in ascending order.  This is the analogue of
    the paper's memory-space partitioning step: restructure the
    irregular structure once, off the critical path, so the steady-state
    pipeline sees only block-granular traffic.
    """
    M, K = shape
    nbr = (M + bm - 1) // bm
    nbc = (K + bk - 1) // bk
    indptr = np.asarray(indptr)
    rows = np.repeat(np.arange(M), np.diff(indptr))
    br, rr = np.divmod(rows, bm)
    bc, cc = np.divmod(np.asarray(indices, np.int64), bk)
    key = br * nbc + bc
    blocks = np.unique(key)                      # sorted (row, col) pairs
    ub, uc = np.divmod(blocks, nbc)
    per_row = np.bincount(ub, minlength=nbr)
    nnz_max = max(1, int(per_row.max(initial=0)))
    first = np.concatenate([[0], np.cumsum(per_row)[:-1]])
    slot_of = np.arange(len(blocks)) - first[ub]
    col_ids = np.full((nbr, nnz_max), -1, dtype=np.int32)
    col_ids[ub, slot_of] = uc
    values = np.zeros((nbr, nnz_max, bm, bk), dtype=np.asarray(data).dtype)
    values[br, slot_of[np.searchsorted(blocks, key)], rr, cc] = data
    return values, col_ids

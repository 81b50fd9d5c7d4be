"""Grouped matmul over the held experts of a stacked expert layer.

An expert layer's dispatch is the paper's data-dependent scatter: the
(token, expert) pairs are sorted by expert, and each expert's FFN runs
over its own rows.  At decode each held expert sees a dozen rows, so the
layer is a weight stream: its time is reading every held expert's
weights once.  The kernel keeps that stream whole:

* **weights stay in their stack** ``(repeats · groups, K, N)``; the
  groups of this call are ``layer · groups + e``, picked by the weight
  ``BlockSpec``'s index map from a scalar-prefetched table, so no layer's
  slice is ever copied out of the stack;
* **rows come in tiles of one group each**: the caller lays each group
  out from a multiple of ``block_rows`` (:func:`group_tiles`), so a tile
  never holds two groups and consecutive tiles of one group keep the
  same weight block, which the grid pipeline then fetches once;
* **the grid visits only the tiles that hold rows**: its row axis has a
  static bound (every group's padding counted), the tiles past the used
  ones repeat the last used tile's block indices (no DMA) and skip the
  body, and no group without rows is ever fetched.

Operands in their dtype, products accumulated in float32.  With
``w_up`` the call is the SwiGLU's first half, ``silu(x·w) · (x·w_up)``
rounded once to the operands' dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def num_tiles(rows: int, groups: int, block_rows: int) -> int:
    """Static bound on the row tiles of ``groups`` groups holding at most
    ``rows`` rows in all, each group padded to whole tiles."""
    return pl.cdiv(rows, block_rows) + groups - 1


def group_tiles(sizes: jax.Array, block_rows: int) -> jax.Array:
    """``(groups + 1,)`` int32: the first tile of each group and, last,
    the tiles used.  Group ``e``'s rows start at row
    ``tiles[e] · block_rows``."""
    tiles = (sizes + block_rows - 1) // block_rows
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(tiles).astype(jnp.int32)])


def _kernel(group_ref, used_ref, x_ref, *refs):
    *w_refs, o_ref = refs

    @pl.when(pl.program_id(1) < used_ref[0])
    def _body():
        x = x_ref[...]
        acc = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
               for w in w_refs]
        y = acc[0] if len(acc) == 1 else jax.nn.silu(acc[0]) * acc[1]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("groups", "block_rows", "block_cols",
                              "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, tiles: jax.Array,
                   layer: jax.Array, w_up: jax.Array | None = None, *,
                   groups: int, block_rows: int, block_cols: int,
                   interpret: bool = False) -> jax.Array:
    """``x`` (M, K) rows laid out by :func:`group_tiles` (``tiles``), M a
    multiple of ``block_rows``; ``w`` (and ``w_up``) (L · groups, K, N),
    this call's groups ``layer · groups`` onwards; N a multiple of
    ``block_cols``.  Returns (M, N) in ``x``'s dtype; rows outside every
    group hold anything."""
    M, K = x.shape
    N = w.shape[-1]
    n_tiles = M // block_rows
    assert M % block_rows == 0 and N % block_cols == 0, (M, N)
    used = tiles[groups:]

    def tile(i, u):             # past the used tiles, the last used one
        return jnp.minimum(i, jnp.maximum(u[0] - 1, 0))

    # each tile's group and its place in the stack
    group = jnp.searchsorted(
        tiles[1:groups], tile(jnp.arange(n_tiles, dtype=jnp.int32), used),
        side="right", method="compare_all")
    stack_group = (layer * groups + group).astype(jnp.int32)

    def row_block(j, i, g, u):
        return tile(i, u), 0

    def w_block(j, i, g, u):
        return g[i], 0, j

    def out_block(j, i, g, u):
        return tile(i, u), j

    ws = (w,) if w_up is None else (w, w_up)
    w_spec = pl.BlockSpec((pl.Squeezed(), K, block_cols), w_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // block_cols, n_tiles),
        in_specs=[pl.BlockSpec((block_rows, K), row_block)]
        + [w_spec] * len(ws),
        out_specs=pl.BlockSpec((block_rows, block_cols), out_block),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="grouped_matmul",
    )(stack_group, used, x, *ws)

"""Absorbed-MLA decode attention over the live blocks of a stacked latent
cache.

Absorbed MLA (DeepSeek-V2 §Inference) attends in the compressed latent
space: a row's H query heads score each cached position by
``q_lat · c_kv + q_pe · k_pe``, and its output is the softmax-weighted sum
of the latent rows ``c_kv``.  At decode the step is a stream of the cache,
so the kernel keeps that stream as short as the step allows:

* **one pass**: each 128-position block of a tile of batch rows is read
  once, and its scores, a running max and sum (online softmax) and the
  latent accumulator are all taken from the block in VMEM;
* **only the live blocks**: positions past ``length`` hold nothing this
  step reads, so the steps past a tile's last live block skip the body
  and fetch nothing but the next tile's first blocks, once, while the
  last live block computes; inside the last live block the positions
  past ``length`` are masked out of both products, their latent rows
  zeroed (a dead row may hold anything, NaN included);
* **the cache stays in its stack** ``(repeats, B, S, r)`` and
  ``(repeats, B, rope, S')``: the layer is ``rep``, a scalar-prefetched
  index of the stack's leading axis, so no layer's slice is ever copied
  out of the cache the layer scan carries.

The products take their operands in the cache's dtype with float32
accumulation, and the softmax runs in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions a block: whole 128-lane tiles of the RoPE key's minor axis
BLOCK = 128

_MASK = -1e30


def _kernel(rep_ref, len_ref, q_ref, qp_ref, c_ref, p_ref, o_ref, m_ref,
            l_ref, acc_ref, *, scale: float):
    del rep_ref
    s = pl.program_id(1)
    length = len_ref[0]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(last: bool):
        c = c_ref[...]                                  # (bt, BLOCK, r)
        logits = (jnp.einsum("bhr,bsr->bhs", q_ref[...], c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bhp,bps->bhs", qp_ref[...], p_ref[...],
                               preferred_element_type=jnp.float32)) * scale
        if last:
            pos = s * BLOCK + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, BLOCK), 2)
            logits = jnp.where(pos <= length, logits, _MASK)
            rows = s * BLOCK + jax.lax.broadcasted_iota(
                jnp.int32, (1, BLOCK, 1), 1)
            c = jnp.where(rows <= length, c, jnp.zeros_like(c))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "bhs,bsr->bhr", p.astype(c.dtype), c,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    last = length // BLOCK
    pl.when(s < last)(lambda: attend(False))
    pl.when(s == last)(lambda: attend(True))

    @pl.when(s == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...] / l_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_rows", "interpret"))
def mla_decode(q_lat: jax.Array, q_pe: jax.Array, c_kv: jax.Array,
               k_pe: jax.Array, rep: jax.Array, length: jax.Array, *,
               scale: float, block_rows: int,
               interpret: bool = False) -> jax.Array:
    """``o_lat`` (B, H, r) float32: the softmax over positions ``0 ..
    length`` of layer ``rep``'s cache of ``(q_lat · c_kv + q_pe · k_pe) ·
    scale``, applied to ``c_kv``.

    ``q_lat`` (B, H, r) and ``q_pe`` (B, H, rope) in the cache's dtype;
    ``c_kv`` (repeats, B, S, r) and ``k_pe`` (repeats, B, rope, S'), S'
    a multiple of :data:`BLOCK` covering S; ``rep`` and ``length`` int32
    scalars, ``length`` < S.  ``block_rows`` divides B."""
    B, H, r = q_lat.shape
    rope = q_pe.shape[-1]
    S = c_kv.shape[2]
    assert B % block_rows == 0 and k_pe.shape[-1] % BLOCK == 0
    assert k_pe.shape[-1] >= S, (k_pe.shape, S)

    tiles = B // block_rows

    def step(i, s, n):
        """The batch tile and position block that step ``(i, s)`` reads:
        past the last live block, the next tile's first (fetched once,
        while this tile's last live block computes) or, in the last tile,
        the last live block again (no DMA)."""
        last = n[0] // BLOCK
        dead, more = s > last, i + 1 < tiles
        return (jnp.where(dead & more, i + 1, i),
                jnp.where(dead, jnp.where(more, 0, last), s))

    def row_block(i, s, rep, n):
        return step(i, s, n)[0], 0, 0

    def c_block(i, s, rep, n):
        t, b = step(i, s, n)
        return rep[0], t, b, 0

    def p_block(i, s, rep, n):
        t, b = step(i, s, n)
        return rep[0], t, 0, b

    def out_block(i, s, rep, n):
        return i, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, pl.cdiv(S, BLOCK)),
        in_specs=[
            pl.BlockSpec((block_rows, H, r), row_block),
            pl.BlockSpec((block_rows, H, rope), row_block),
            pl.BlockSpec((pl.Squeezed(), block_rows, BLOCK, r), c_block),
            pl.BlockSpec((pl.Squeezed(), block_rows, rope, BLOCK), p_block),
        ],
        out_specs=pl.BlockSpec((block_rows, H, r), out_block),
        scratch_shapes=[
            pltpu.VMEM((block_rows, H, 1), jnp.float32),
            pltpu.VMEM((block_rows, H, 1), jnp.float32),
            pltpu.VMEM((block_rows, H, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="mla_decode",
    )(jnp.reshape(rep, (1,)).astype(jnp.int32),
      jnp.reshape(length, (1,)).astype(jnp.int32), q_lat, q_pe, c_kv, k_pe)

"""Streaming attention kernels (prefill + decode) with online softmax.

Attention is the framework's dominant "memory operation" in the paper's
sense: at decode time the KV cache read is a huge, latency-bound HBM stream
feeding a tiny amount of compute.  The template's decoupling maps onto the
Pallas grid pipeline: KV tiles stream HBM→VMEM (access stage, double
buffered) while the VPU/MXU consume the previous tile (execute stage), with
the online-softmax running state (m, l, acc) living in VMEM scratch — the
template's in-stage registers.

GQA is handled in the index maps (kv head = q head // group) so KV tiles
are fetched once per group, not repeated — the paper's "burst" optimization
(§III-B2) applied to head-sharing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASK = -0.7 * float(np.finfo(np.float32).max)


# ---------------------------------------------------------------------------
# Prefill (causal, GQA)
# ---------------------------------------------------------------------------

def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                    *, scale: float, causal: bool,
                    block_q: int, block_k: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qi = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            ki = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(ki <= qi, s, _MASK)
        m_prev = m_ref[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip fully-masked KV blocks: kv block start beyond q block end
        @pl.when(ik * block_k <= iq * block_q + block_q - 1)
        def _():
            _body()
    else:
        _body()

    @pl.when(ik == pl.num_programs(3) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,     # (B, Hq, Sq, d)
    k: jax.Array,     # (B, Hkv, Sk, d)
    v: jax.Array,     # (B, Hkv, Sk, d)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk)
    scale_v = scale if scale is not None else 1.0 / float(np.sqrt(d))

    grid = (B, Hq, Sq // block_q, Sk // block_k)
    kernel = functools.partial(
        _prefill_kernel, scale=scale_v, causal=causal,
        block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Decode (one new token against a long KV cache, GQA, ragged lengths)
# ---------------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, block_s: int):
    b, s = pl.program_id(0), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    # skip cache blocks entirely beyond the valid length (ragged batch):
    @pl.when(s * block_s < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bs, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bs, d)
        logits = jnp.dot(q, k.T,
                         preferred_element_type=jnp.float32) * scale
        pos = s * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        logits = jnp.where(pos < length, logits, _MASK)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_s", "interpret"))
def decode_attention(
    q: jax.Array,        # (B, Hq, d)
    k_cache: jax.Array,  # (B, Hkv, S, d)
    v_cache: jax.Array,  # (B, Hkv, S, d)
    lengths: jax.Array,  # (B,) int32
    *,
    scale: float | None = None,
    block_s: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, d = q.shape
    _, Hkv, S, _ = k_cache.shape
    assert Hq % Hkv == 0 and S % block_s == 0
    group = Hq // Hkv
    scale_v = scale if scale is not None else 1.0 / float(np.sqrt(d))
    q4 = q[:, :, None, :]  # (B, Hq, 1, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, S // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda b, h, s, L: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda b, h, s, L: (b, h // group, s, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda b, h, s, L: (b, h // group, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda b, h, s, L: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, scale=scale_v,
                               block_s=block_s)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q4, k_cache, v_cache)
    return out[:, :, 0, :]

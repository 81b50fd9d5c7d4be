"""Attention: GQA/MHA/MQA with RoPE + KV cache, and MLA (DeepSeek-V3).

Three attention implementations, selected by ``impl``:

* ``"full"``    — materialized S×S logits (oracle; small configs only).
* ``"chunked"`` — online-softmax streamed over KV blocks in pure JAX
  (``lax.scan``): the template's decoupled KV streaming expressed at the
  XLA level; memory stays O(S·d) per step.  Default for long sequences and
  the dry-run path.
* ``"pallas"``  — the kernels/flash_attention.py Pallas kernels (TPU).

The KV-cache decode step is the framework's canonical "memory operation"
per the paper's classification: a data-dependent HBM stream (the cache)
feeding a small amount of compute, decoupled from the projection GEMMs.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from . import layers
from ..kernels import ops as kops


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(rng, cfg) -> dict:
    d = cfg.d_model
    hd = cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "w_q": layers._dense_init(ks[0], d, cfg.num_heads * hd, cfg.np_dtype),
        "w_k": layers._dense_init(ks[1], d, cfg.num_kv_heads * hd,
                                  cfg.np_dtype),
        "w_v": layers._dense_init(ks[2], d, cfg.num_kv_heads * hd,
                                  cfg.np_dtype),
        "w_o": layers._dense_init(ks[3], cfg.num_heads * hd, d,
                                  cfg.np_dtype),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((cfg.num_heads * hd,), cfg.np_dtype)
        p["b_k"] = jnp.zeros((cfg.num_kv_heads * hd,), cfg.np_dtype)
        p["b_v"] = jnp.zeros((cfg.num_kv_heads * hd,), cfg.np_dtype)
    return p


def _project_qkv(params, x, cfg, positions):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = x @ params["w_q"]
    k = x @ params["w_k"]
    v = x @ params["w_v"]
    if cfg.qkv_bias:
        q = q + params["b_q"]
        k = k + params["b_k"]
        v = v + params["b_v"]
    q = q.reshape(B, S, cfg.num_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    q = layers.apply_rope(q, positions[:, None, :], cfg.rope_theta)
    k = layers.apply_rope(k, positions[:, None, :], cfg.rope_theta)
    return q, k, v


def _chunked_attention(q, k, v, *, causal: bool, chunk: int = 1024,
                       q_offset: int = 0):
    """Online-softmax over KV chunks via lax.scan (flash-in-XLA).

    Head dims may differ between q/k (d) and v (dv) — MLA uses 192/128.
    """
    B, H, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    dv = v.shape[-1]
    group = H // Hkv
    scale = 1.0 / np.sqrt(d)
    nchunks = (Sk + chunk - 1) // chunk
    pad = nchunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = k.reshape(B, Hkv, nchunks, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, nchunks, chunk, dv).transpose(2, 0, 1, 3, 4)
    qf = q.astype(jnp.float32)
    qi = jnp.arange(Sq) + q_offset

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, ci = inp
        kb = jnp.repeat(kb, group, axis=1).astype(jnp.float32)
        vb = jnp.repeat(vb, group, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb) * scale
        ki = ci * chunk + jnp.arange(chunk)
        mask = ki[None, :] < Sk
        if causal:
            mask = mask & (ki[None, :] <= qi[:, None])
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kc, vc, jnp.arange(nchunks)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype)


def _full_attention(q, k, v, *, causal: bool, q_offset: int = 0):
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    B, H, Sq, d = q.shape
    Sk = k.shape[2]
    scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(Sq)[:, None] + q_offset
        ki = jnp.arange(Sk)[None, :]
        s = jnp.where(ki <= qi, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def gqa_apply(params: dict, x: jax.Array, cfg, *,
              positions: jax.Array | None = None) -> jax.Array:
    """Training / prefill forward (causal)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(params, x, cfg, positions)
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "chunked" if S > 2048 else "full"
    if impl == "pallas":
        out = kops.flash_attention(q, k, v, causal=True)
    elif impl == "chunked":
        out = _chunked_attention(q, k, v, causal=True)
    else:
        out = _full_attention(q, k, v, causal=True)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, -1)
    return out @ params["w_o"]


def gqa_prefill(params: dict, x: jax.Array, cfg, max_len: int
                ) -> tuple[jax.Array, dict]:
    """Forward over the prompt AND build the decode cache in one pass."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(params, x, cfg, positions)
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "chunked" if S > 2048 else "full"
    if impl == "pallas":
        out = kops.flash_attention(q, k, v, causal=True)
    elif impl == "chunked":
        out = _chunked_attention(q, k, v, causal=True)
    else:
        out = _full_attention(q, k, v, causal=True)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, -1)
    pad = ((0, 0), (0, 0), (0, max_len - S), (0, 0))
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        spad = ((0, 0), (0, 0), (0, max_len - S), (0, 0))
        cache = {"k": jnp.pad(kq, pad), "v": jnp.pad(vq, pad),
                 "k_scale": jnp.pad(ks, spad),
                 "v_scale": jnp.pad(vs, spad)}
    else:
        cache = {"k": jnp.pad(k, pad).astype(cfg.np_dtype),
                 "v": jnp.pad(v, pad).astype(cfg.np_dtype)}
    return out @ params["w_o"], cache


def mla_prefill(params: dict, x: jax.Array, cfg, max_len: int
                ) -> tuple[jax.Array, dict]:
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q_nope, q_pe, c_kv, k_pe = _mla_qkv(params, x, cfg, positions)
    out = _mla_attend(params, q_nope, q_pe, c_kv, k_pe, cfg, causal=True)
    pe_pad = ((0, 0), (0, 0), (0, _rope_len(max_len) - S))
    cache = {"c_kv": jnp.pad(c_kv, ((0, 0), (0, max_len - S), (0, 0))
                             ).astype(cfg.np_dtype),
             "k_pe": jnp.pad(k_pe.transpose(0, 2, 1), pe_pad
                             ).astype(cfg.np_dtype)}
    return out, cache


def _kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-vector symmetric int8: x (..., hd) → (int8, f16 scale (..., 1))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _kv_dequantize(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def gqa_init_cache(cfg, batch: int, max_len: int) -> dict:
    shape = (batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        # §Perf: int8 KV halves decode's dominant HBM stream (the cache
        # read); per-vector f16 scales add hd/2 bytes per 128-wide vector.
        sshape = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float16),
                "v_scale": jnp.zeros(sshape, jnp.float16)}
    return {"k": jnp.zeros(shape, cfg.np_dtype),
            "v": jnp.zeros(shape, cfg.np_dtype)}


def gqa_decode_entries(params: dict, x: jax.Array, length: jax.Array,
                       cfg) -> tuple[jax.Array, dict]:
    """This token's query and its cache entries, the decode step's cache
    write stage: ``(q (B, H, 1, d), entries)``, with every entry shaped like
    the cache leaf of the same key but one position long (the writer casts
    it to the cache's dtype).

    x: (B, 1, d); length: scalar int32 (tokens already in cache).
    """
    B = x.shape[0]
    positions = jnp.broadcast_to(length[None], (B,))[:, None]  # (B, 1)
    q, k, v = _project_qkv(params, x, cfg, positions)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        return q, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return q, {"k": k, "v": v}


def gqa_decode_attend(params: dict, q: jax.Array, stack: dict,
                      rep: jax.Array, length: jax.Array, cfg) -> jax.Array:
    """Attend over the valid prefix of layer ``rep``'s cache in the
    segment's stacked cache ``stack``, which already holds this token's
    entries at ``length``."""
    B = q.shape[0]
    cache = layers.layer_view(stack, rep)
    lengths = jnp.full((B,), length + 1, jnp.int32)
    if cfg.kv_cache_dtype == "int8":
        out = _decode_chunked(q[:, :, 0], cache["k"], cache["v"], lengths,
                              k_scale=cache["k_scale"],
                              v_scale=cache["v_scale"])
    elif cfg.attn_impl == "pallas":
        out = kops.decode_attention(q[:, :, 0], cache["k"], cache["v"],
                                    lengths)
    else:
        out = _decode_chunked(q[:, :, 0], cache["k"], cache["v"], lengths)
    return out.reshape(B, 1, -1) @ params["w_o"]


def _decode_chunked(q, k_cache, v_cache, lengths, chunk: int = 2048,
                    k_scale=None, v_scale=None):
    """(B,H,d) vs (B,Hkv,S,d) ragged cache — streamed online softmax.
    Optional per-vector scales dequantize an int8 cache chunk-by-chunk (the
    dequant fuses into the chunk body; HBM only streams int8)."""
    S = k_cache.shape[2]
    return _decode_masked_scan(q, k_cache, v_cache, lengths,
                               chunk=min(chunk, S),
                               k_scale=k_scale, v_scale=v_scale)


def _chunkify(x, nchunks, chunk, pad):
    B, Hkv = x.shape[:2]
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return xp.reshape(B, Hkv, nchunks, chunk,
                      x.shape[-1]).transpose(2, 0, 1, 3, 4)


def _decode_masked_scan(q, k_cache, v_cache, lengths, chunk: int,
                        k_scale=None, v_scale=None):
    B, H, d = q.shape
    _, Hkv, S, _ = k_cache.shape
    group = H // Hkv
    scale = 1.0 / np.sqrt(d)
    nchunks = (S + chunk - 1) // chunk
    pad = nchunks * chunk - S
    kc = _chunkify(k_cache, nchunks, chunk, pad)
    vc = _chunkify(v_cache, nchunks, chunk, pad)
    quant = k_scale is not None
    if quant:
        ksc = _chunkify(k_scale, nchunks, chunk, pad)
        vsc = _chunkify(v_scale, nchunks, chunk, pad)
    else:  # dummy zero-width scales keep the scan structure uniform
        ksc = jnp.zeros((nchunks, B, Hkv, chunk, 0), jnp.float16)
        vsc = ksc
    qf = q.astype(jnp.float32)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, ksb, vsb, ci = inp
        if quant:
            kb = _kv_dequantize(kb, ksb, jnp.float32)
            vb = _kv_dequantize(vb, vsb, jnp.float32)
        kb = jnp.repeat(kb, group, axis=1).astype(jnp.float32)
        vb = jnp.repeat(vb, group, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhd,bhkd->bhk", qf, kb) * scale
        ki = ci * chunk + jnp.arange(chunk)
        mask = ki[None, None, :] < lengths[:, None, None]
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhk,bhkd->bhd", p, vb)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H), jnp.float32)
    a0 = jnp.zeros((B, H, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kc, vc, ksc, vsc,
                                   jnp.arange(nchunks)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2/V3, arXiv:2412.19437)
# ---------------------------------------------------------------------------
#
# The KV cache stores only the compressed latent c_kv (kv_lora_rank) plus the
# decoupled RoPE key (rope_head_dim) — the memory stage shrinks by ~an order
# of magnitude, which is precisely the paper's "customize the memory
# interface per access stream" (§III-B2) applied to the KV cache.  The RoPE
# key is kept position-minor, (B, rope, S'), with S' the cache length
# rounded up to whole 128-lane tiles: with a 64-wide or a ragged minor axis
# the TPU's default layout puts the batch minor, unlike the layout the
# decode scan reads the key in, and the compiler then copies the whole
# stacked cache in and out of the scan every step.

def _rope_len(max_len: int) -> int:
    """Positions of the RoPE key cache: ``max_len`` in whole 128s."""
    return -(-max_len // 128) * 128


def mla_init(rng, cfg) -> dict:
    m = cfg.mla
    d = cfg.d_model
    H = cfg.num_heads
    ks = jax.random.split(rng, 8)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        query = {
            "w_dq": layers._dense_init(ks[0], d, m.q_lora_rank,
                                       cfg.np_dtype),
            "q_norm": layers.rmsnorm_init(m.q_lora_rank, cfg.np_dtype),
            "w_uq": layers._dense_init(ks[1], m.q_lora_rank, H * qk_head,
                                       cfg.np_dtype)}
    else:
        query = {"w_q": layers._dense_init(ks[0], d, H * qk_head,
                                           cfg.np_dtype)}
    return {
        **query,
        "w_dkv": layers._dense_init(
            ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, cfg.np_dtype),
        "kv_norm": layers.rmsnorm_init(m.kv_lora_rank, cfg.np_dtype),
        "w_ukv": layers._dense_init(
            ks[3], m.kv_lora_rank,
            H * (m.qk_nope_head_dim + m.v_head_dim), cfg.np_dtype),
        "w_o": layers._dense_init(ks[4], H * m.v_head_dim, d, cfg.np_dtype),
    }


def _mla_qkv(params, x, cfg, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    norm = layers.make_norm("rmsnorm", cfg.norm_eps)[1]
    # query path: through the query LoRA, or projected directly
    if m.q_lora_rank:
        q = norm(params["q_norm"], x @ params["w_dq"]) @ params["w_uq"]
    else:
        q = x @ params["w_q"]
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = layers.apply_rope(
        q_pe.transpose(0, 2, 1, 3), positions[:, None, :],
        cfg.rope_theta).transpose(0, 2, 1, 3)
    # kv latent path
    ckv_full = x @ params["w_dkv"]
    c_kv, k_pe = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    c_kv = norm(params["kv_norm"], c_kv)
    k_pe = layers.apply_rope(k_pe[:, None], positions[:, None, :],
                             cfg.rope_theta)[:, 0]
    return q_nope, q_pe, c_kv, k_pe


def _mla_attend(params, q_nope, q_pe, c_kv, k_pe, cfg, *, causal,
                q_offset: int = 0):
    m = cfg.mla
    B, Sq, H, _ = q_nope.shape
    kv = (c_kv @ params["w_ukv"]).reshape(
        c_kv.shape[0], c_kv.shape[1], H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    qh = jnp.concatenate([q_nope, q_pe], axis=-1).transpose(0, 2, 1, 3)
    kh = jnp.concatenate(
        [k_nope,
         jnp.broadcast_to(k_pe[:, :, None],
                          k_nope.shape[:2] + (H, m.qk_rope_head_dim))],
        axis=-1).transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    if cfg.attn_impl in ("chunked", "auto") and qh.shape[2] > 2048:
        out = _chunked_attention(qh, kh, vh, causal=causal,
                                 q_offset=q_offset)
    else:
        out = _full_attention(qh, kh, vh, causal=causal, q_offset=q_offset)
    out = out.transpose(0, 2, 1, 3).reshape(B, Sq, H * m.v_head_dim)
    return out @ params["w_o"]


def mla_apply(params: dict, x: jax.Array, cfg, *,
              positions: jax.Array | None = None) -> jax.Array:
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q_nope, q_pe, c_kv, k_pe = _mla_qkv(params, x, cfg, positions)
    return _mla_attend(params, q_nope, q_pe, c_kv, k_pe, cfg, causal=True)


def mla_init_cache(cfg, batch: int, max_len: int) -> dict:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), cfg.np_dtype),
        "k_pe": jnp.zeros((batch, m.qk_rope_head_dim, _rope_len(max_len)),
                          cfg.np_dtype),
    }


def mla_decode_entries(params: dict, x: jax.Array, length: jax.Array,
                       cfg) -> tuple[tuple, dict]:
    """MLA's query parts and this token's latent cache entries (see
    :func:`gqa_decode_entries`)."""
    B = x.shape[0]
    positions = jnp.broadcast_to(length[None], (B,))[:, None]
    q_nope, q_pe, c_kv, k_pe = _mla_qkv(params, x, cfg, positions)
    return (q_nope, q_pe), {"c_kv": c_kv, "k_pe": k_pe.transpose(0, 2, 1)}


def mla_decode_path() -> str:
    """The absorbed MLA decode's attention on the default backend:
    ``"pallas"`` (:func:`repro.kernels.ops.mla_decode`, one pass over the
    live blocks of the stacked latent cache) where the kernel compiles
    natively, on a TPU; ``"xla"`` (two einsums over a layer's whole cache)
    elsewhere, so a CPU never interprets a kernel inside every layer."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def mla_decode_blocks(length: int, max_len: int) -> tuple[int, int]:
    """``(read, total)``: the 128-position blocks of one row's latent cache
    that a layer's absorbed decode at ``length`` reads on this backend's
    path (the kernel reads positions ``0 .. length``, the XLA path all of
    them), and the blocks the cache has."""
    total = -(-max_len // kops.MLA_BLOCK)
    if mla_decode_path() == "pallas":
        return length // kops.MLA_BLOCK + 1, total
    return total, total


def mla_decode_attend(params: dict, q: tuple, stack: dict, rep: jax.Array,
                      length: jax.Array, cfg) -> jax.Array:
    q_nope, q_pe = q
    if getattr(cfg, "mla_absorbed", False):
        return _mla_decode_absorbed(params, q_nope, q_pe, stack, rep,
                                    length, cfg)
    # naive: decompress the whole cache and attend (baseline)
    cache = layers.layer_view(stack, rep)
    S = cache["c_kv"].shape[1]
    return _mla_attend(params, q_nope, q_pe, cache["c_kv"],
                       cache["k_pe"][:, :, :S].transpose(0, 2, 1), cfg,
                       causal=True, q_offset=length)


def _mla_decode_absorbed(params, q_nope, q_pe, stack, rep, length,
                         cfg) -> jax.Array:
    """Absorbed MLA decode (DeepSeek-V2 §Inference): fold W_uk into the
    query and W_uv into the output so attention runs directly in the
    compressed latent space — the per-step cache decompression
    (S·H·(nope+v) GEMM + its S·H·192 materialization) disappears.

    Beyond-paper §Perf optimization; numerically identical to the naive
    path (same linear algebra, reassociated).  The attention over layer
    ``rep`` of the stacked latent cache runs on :func:`mla_decode_path`.
    """
    m = cfg.mla
    B, _, H, _ = q_nope.shape
    r = m.kv_lora_rank
    w_ukv = params["w_ukv"].reshape(r, H, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = w_ukv[:, :, :m.qk_nope_head_dim]          # (r, H, nope)
    w_uv = w_ukv[:, :, m.qk_nope_head_dim:]          # (r, H, v)

    # absorb: q_lat (B, H, r) = q_nope · W_uk^T
    q_lat = jnp.einsum("bqhn,rhn->bhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if mla_decode_path() == "pallas":
        o_lat = kops.mla_decode(q_lat, q_pe[:, 0], stack["c_kv"],
                                stack["k_pe"], rep, length, scale=scale)
    else:
        cache = layers.layer_view(stack, rep)
        o_lat = _latent_attention(q_lat, q_pe, cache["c_kv"],
                                  cache["k_pe"], length, scale)
    out = jnp.einsum("bhr,rhv->bhv", o_lat,
                     w_uv.astype(jnp.float32))       # (B, H, v)
    out = out.reshape(B, 1, H * m.v_head_dim).astype(q_nope.dtype)
    return out @ params["w_o"]


def _latent_attention(q_lat, q_pe, c_cache, p_cache, length, scale):
    """``o_lat`` (B, H, r) of one layer's latent cache in XLA: the scores
    over the whole cache, masked past ``length``, then their softmax
    applied to the whole cache."""
    S = c_cache.shape[1]
    cf = c_cache.astype(jnp.float32)                 # (B, S, r)
    pf = p_cache.astype(jnp.float32)                 # (B, rope, S')
    logits = (jnp.einsum("bhr,bsr->bhs", q_lat, cf)
              + jnp.einsum("bqhp,bps->bhs", q_pe.astype(jnp.float32),
                           pf)[..., :S]) * scale
    mask = jnp.arange(S)[None, None, :] <= length
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)              # (B, H, S)
    return jnp.einsum("bhs,bsr->bhr", w, cf)         # (B, H, r)

"""Shared model layers: norms, rotary embeddings, MLPs, embeddings.

Pure-functional: params are nested dicts of arrays, every layer is
``apply(params, x, cfg) -> y`` with a matching ``init(rng, cfg) -> params``.
All inits work under ``jax.eval_shape`` (the dry-run never allocates).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _dense_init(rng, in_dim: int, out_dim: int, dtype,
                scale: float | None = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim), jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(params: dict, x: jax.Array,
                    eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    if "scale" in params:
        out = out * params["scale"].astype(jnp.float32)
    if "bias" in params:
        out = out + params["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def nonparametric_ln_apply(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """OLMo-style LayerNorm without learnable affine [arXiv:2402.00838]."""
    return layernorm_apply({}, x, eps)


def make_norm(kind: str, eps: float | None = None):
    """Returns (init(d, dtype) -> params, apply(params, x) -> y); ``eps``
    None keeps the norm's own default."""
    kw = {} if eps is None else {"eps": eps}
    if kind == "rmsnorm":
        return rmsnorm_init, functools.partial(rmsnorm_apply, **kw)
    if kind == "layernorm":
        return layernorm_init, functools.partial(layernorm_apply, **kw)
    if kind == "nonparametric_ln":
        return (lambda d, dtype: {}), (
            lambda params, x: nonparametric_ln_apply(x, **kw))
    raise ValueError(f"unknown norm {kind!r}")


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e4) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 1e4) -> jax.Array:
    """x: (..., S, d) with d even; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_init(rng, d: int, d_ff: int, act: str, dtype) -> dict:
    ks = jax.random.split(rng, 3)
    p = {"w_up": _dense_init(ks[0], d, d_ff, dtype),
         "w_down": _dense_init(ks[1], d_ff, d, dtype)}
    if act == "silu":  # SwiGLU: separate gate
        p["w_gate"] = _dense_init(ks[2], d, d_ff, dtype)
    return p


def mlp_apply(params: dict, x: jax.Array, act: str = "silu") -> jax.Array:
    up = x @ params["w_up"]
    if act == "silu":
        gate = jax.nn.silu((x @ params["w_gate"]).astype(jnp.float32))
        h = (gate * up.astype(jnp.float32)).astype(x.dtype)
    elif act == "gelu":
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    else:
        raise ValueError(act)
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def embedding_init(rng, vocab: int, d: int, dtype) -> dict:
    return {"table": (jax.random.normal(rng, (vocab, d), jnp.float32)
                      * 0.02).astype(dtype)}


def embedding_apply(params: dict, tokens: jax.Array) -> jax.Array:
    return params["table"][tokens]


def unembed_apply(params: dict, x: jax.Array) -> jax.Array:
    """Logits in fp32 (loss numerics)."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      params["table"].astype(jnp.float32))

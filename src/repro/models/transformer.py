"""Config-driven decoder LM: dense / MoE / hybrid / SSM in one builder.

Layers are grouped into config-declared *segments* (a repeating unit of
≤8 layer specs, scanned ``repeats`` times).  Per-repeat parameters are
stacked on a leading axis so ``lax.scan`` keeps the HLO proportional to the
unit size, not the depth — 61-layer DeepSeek and 72-layer Jamba lower in
seconds and the dry-run's compiled artifact stays tractable.

Decode carries a pytree of caches with the same (segments → repeats →
sublayer) structure; each segment's stacked cache rides through the scan's
carry and is written in place, one token's entries per layer.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import attention, layers, moe, ssm
from ..configs.base import LayerSpec, ModelConfig


# ---------------------------------------------------------------------------
# RWKV channel mix (the FFN used with rwkv mixer layers)
# ---------------------------------------------------------------------------

def _cmix_init(rng, cfg) -> dict:
    d = cfg.d_model
    dh = int(3.5 * d)
    ks = jax.random.split(rng, 3)
    return {
        "mu_k": jnp.full((d,), 0.5, cfg.np_dtype),
        "w_k": layers._dense_init(ks[0], d, dh, cfg.np_dtype),
        "w_v": layers._dense_init(ks[1], dh, d, cfg.np_dtype),
        "w_r": layers._dense_init(ks[2], d, d, cfg.np_dtype),
    }


def _cmix_apply(params, x, prev=None):
    xs = ssm._token_shift(x, prev)
    xk = ssm._rwkv_mix(x, xs, params["mu_k"])
    k = jnp.square(jax.nn.relu((xk @ params["w_k"]).astype(jnp.float32)))
    r = jax.nn.sigmoid((x @ params["w_r"]).astype(jnp.float32))
    return (r * (k.astype(x.dtype) @ params["w_v"]).astype(jnp.float32)
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Sub-layer init/apply dispatch
# ---------------------------------------------------------------------------

def _mixer_init(rng, spec: LayerSpec, cfg) -> dict:
    if spec.mixer == "attn":
        return attention.gqa_init(rng, cfg)
    if spec.mixer == "mla":
        return attention.mla_init(rng, cfg)
    if spec.mixer == "mamba":
        return ssm.mamba_init(rng, cfg)
    if spec.mixer == "rwkv":
        return ssm.rwkv6_init(rng, cfg)
    raise ValueError(spec.mixer)


def _mlp_init(rng, spec: LayerSpec, cfg) -> dict:
    if spec.mlp == "dense":
        return layers.mlp_init(rng, cfg.d_model, cfg.d_ff, cfg.act,
                               cfg.np_dtype)
    if spec.mlp == "moe":
        return moe.moe_init(rng, cfg)
    if spec.mlp == "rwkv_cmix":
        return _cmix_init(rng, cfg)
    raise ValueError(spec.mlp)


def _layer_init(rng, spec: LayerSpec, cfg) -> dict:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    ninit, _ = layers.make_norm(cfg.norm, cfg.norm_eps)
    return {
        "norm1": ninit(cfg.d_model, cfg.np_dtype),
        "mixer": _mixer_init(k1, spec, cfg),
        "norm2": ninit(cfg.d_model, cfg.np_dtype),
        "mlp": _mlp_init(k2, spec, cfg),
    }


def _layer_apply(params: dict, x: jax.Array, spec: LayerSpec, cfg,
                 aux_acc: dict) -> jax.Array:
    _, napply = layers.make_norm(cfg.norm, cfg.norm_eps)
    h1 = napply(params["norm1"], x)
    if spec.mixer == "attn":
        mix = attention.gqa_apply(params["mixer"], h1, cfg)
    elif spec.mixer == "mla":
        mix = attention.mla_apply(params["mixer"], h1, cfg)
    elif spec.mixer == "mamba":
        mix = ssm.mamba_apply(params["mixer"], h1, cfg)
    elif spec.mixer == "rwkv":
        mix = ssm.rwkv6_apply(params["mixer"], h1, cfg)
    else:
        raise ValueError(spec.mixer)

    if cfg.parallel_block:
        # Cohere-style: attn and mlp both read the same normed input
        if spec.mlp == "moe":
            ff, aux = moe.moe_apply(params["mlp"], h1, cfg)
            aux_acc["lb_loss"] = aux_acc.get("lb_loss", 0.0) + aux["lb_loss"]
        elif spec.mlp == "rwkv_cmix":
            ff = _cmix_apply(params["mlp"], h1)
        else:
            ff = layers.mlp_apply(params["mlp"], h1, cfg.act)
        return x + mix + ff

    x = x + mix
    h2 = napply(params["norm2"], x)
    if spec.mlp == "moe":
        ff, aux = moe.moe_apply(params["mlp"], h2, cfg)
        aux_acc["lb_loss"] = aux_acc.get("lb_loss", 0.0) + aux["lb_loss"]
    elif spec.mlp == "rwkv_cmix":
        ff = _cmix_apply(params["mlp"], h2)
    else:
        ff = layers.mlp_apply(params["mlp"], h2, cfg.act)
    return x + ff


# ---------------------------------------------------------------------------
# Decode (cache-carrying) sub-layer apply
# ---------------------------------------------------------------------------

_ATTN_DECODE = {
    "attn": (attention.gqa_decode_entries, attention.gqa_decode_attend),
    "mla": (attention.mla_decode_entries, attention.mla_decode_attend),
}
_STATE_DECODE = {"mamba": ssm.mamba_decode, "rwkv": ssm.rwkv6_decode}


def _layer_view(stack: Any, rep: jax.Array) -> Any:
    """One repeat's slice of a stacked (repeats-first) cache subtree."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, rep, keepdims=False),
        stack)


# Rows written around this token's row: a window of one row along the
# sequence axis makes the TPU compiler lay the whole carried cache out
# sequence-major, and so copy it in and out of the layer scan every step.
_ROW_WINDOW = 8


def _store(stack: Any, entries: Any, rep: jax.Array,
           length: jax.Array) -> Any:
    """Write one layer's new entries into the stacked cache in place.

    An entry shaped like the layer's leaf is recurrent state and replaces it
    whole; an entry one position long on an axis of the leaf is this
    token's row, written at ``length`` on that axis (as part of a
    ``_ROW_WINDOW``-row window read back unchanged around it).  One
    ``dynamic_update_slice`` per leaf of the carried stack, which XLA
    performs on the loop's buffer without copying it.
    """
    def put(a, e):
        e = e.astype(a.dtype)[None]
        rows = [k for k in range(1, a.ndim) if e.shape[k] != a.shape[k]]
        if not rows:
            return jax.lax.dynamic_update_index_in_dim(a, e, rep, 0)
        (ax,) = rows
        w = min(_ROW_WINDOW, a.shape[ax])
        start = jnp.minimum(length - length % w, a.shape[ax] - w)
        idx = [rep] + [start if k == ax else 0 for k in range(1, a.ndim)]
        size = [1] + [w if k == ax else a.shape[k] for k in range(1, a.ndim)]
        window = jax.lax.dynamic_slice(a, idx, size)
        hit = jax.lax.broadcasted_iota(jnp.int32, size, ax) == length - start
        return jax.lax.dynamic_update_slice(a, jnp.where(hit, e, window), idx)
    return jax.tree_util.tree_map(put, stack, entries)


#: an expert layer's weights that the serving path reads from the whole
#: stack of the segment's repeats (see :func:`_with_expert_stacks`)
_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _expert_stacks(stacked: list, seg) -> list:
    """Each sublayer's expert weights stacked over the segment's repeats
    (None for a sublayer without experts)."""
    return [{n: stacked[j]["mlp"][n] for n in _EXPERT_LEAVES}
            if spec.mlp == "moe" else None
            for j, spec in enumerate(seg.unit)]


def _with_expert_stacks(rep_params: dict, stacks: dict | None) -> dict:
    """One repeat's sublayer parameters with its experts' weights replaced
    by their stack over the repeats.  The grouped matmul is a custom call
    that needs its weights as one buffer: a repeat's slice of the stack
    would be copied out of it every layer, so the dispatch takes the whole
    stack and the repeat's index instead."""
    if stacks is None:
        return rep_params
    return {**rep_params, "mlp": {**rep_params["mlp"], **stacks}}


def _serve_mlp(params: dict, norm: dict, x: jax.Array, rep: jax.Array,
               spec: LayerSpec, cfg, cmix
               ) -> tuple[jax.Array, jax.Array | None]:
    """The serving path's MLP over ``norm``-normed ``x``: ``(out, pairs per
    held expert or None)``.  An expert layer routes the normed input in
    float32, dispatches every pair and drops none; its experts' weights
    are stacked over the repeats, ``rep`` this repeat."""
    _, napply = layers.make_norm(cfg.norm, cfg.norm_eps)
    if spec.mlp == "moe":
        ff, aux = moe.moe_apply(params, napply(norm, x.astype(jnp.float32)),
                                cfg, layer=rep)
        return ff, aux["load"]
    h = napply(norm, x)
    if spec.mlp == "rwkv_cmix":
        return cmix(h), None
    return layers.mlp_apply(params, h, cfg.act), None


def _layer_decode(params: dict, x: jax.Array, stack: dict, rep: jax.Array,
                  length: jax.Array, spec: LayerSpec, cfg
                  ) -> tuple[jax.Array, dict, jax.Array | None]:
    """One sublayer of repeat ``rep`` over the segment's stacked cache
    ``stack``; returns the stack with this token's entries written, and
    an expert layer's pairs per held expert."""
    _, napply = layers.make_norm(cfg.norm, cfg.norm_eps)
    h1 = napply(params["norm1"], x)
    new_stack = dict(stack)
    if spec.mixer in _ATTN_DECODE:
        entries_fn, attend_fn = _ATTN_DECODE[spec.mixer]
        q, entries = entries_fn(params["mixer"], h1, length, cfg)
        new_stack["mixer"] = _store(stack["mixer"], entries, rep, length)
        mix = attend_fn(params["mixer"], q,
                        _layer_view(new_stack["mixer"], rep), length, cfg)
    elif spec.mixer in _STATE_DECODE:
        mix, state = _STATE_DECODE[spec.mixer](
            params["mixer"], h1, _layer_view(stack["mixer"], rep), cfg)
        new_stack["mixer"] = _store(stack["mixer"], state, rep, length)
    else:
        raise ValueError(spec.mixer)

    def cmix(h):
        prev = _layer_view(stack["cmix_prev"], rep)
        new_stack["cmix_prev"] = _store(stack["cmix_prev"], h, rep, length)
        return _cmix_apply(params["mlp"], h, prev=prev)

    if cfg.parallel_block:
        ff, load = _serve_mlp(params["mlp"], params["norm1"], x, rep, spec,
                              cfg, cmix)
        return x + mix + ff, new_stack, load

    x = x + mix
    ff, load = _serve_mlp(params["mlp"], params["norm2"], x, rep, spec, cfg,
                          cmix)
    return x + ff, new_stack, load


def _layer_prefill(params: dict, x: jax.Array, rep: jax.Array | None,
                   spec: LayerSpec, cfg, max_len: int
                   ) -> tuple[jax.Array, dict, jax.Array | None]:
    """Forward over the prompt, emitting this layer's decode cache (and an
    expert layer's pairs per held expert)."""
    _, napply = layers.make_norm(cfg.norm, cfg.norm_eps)
    h1 = napply(params["norm1"], x)
    new_cache: dict[str, Any] = {}
    if spec.mixer == "attn":
        mix, mcache = attention.gqa_prefill(params["mixer"], h1, cfg,
                                            max_len)
    elif spec.mixer == "mla":
        mix, mcache = attention.mla_prefill(params["mixer"], h1, cfg,
                                            max_len)
    elif spec.mixer == "mamba":
        mix, mcache = ssm.mamba_apply(params["mixer"], h1, cfg,
                                      return_cache=True)
    elif spec.mixer == "rwkv":
        mix, mcache = ssm.rwkv6_apply(params["mixer"], h1, cfg,
                                      return_cache=True)
    else:
        raise ValueError(spec.mixer)
    new_cache["mixer"] = mcache

    def cmix(h):
        new_cache["cmix_prev"] = h[:, -1:, :]
        return _cmix_apply(params["mlp"], h)

    if cfg.parallel_block:
        ff, load = _serve_mlp(params["mlp"], params["norm1"], x, rep, spec,
                              cfg, cmix)
        return x + mix + ff, new_cache, load

    x = x + mix
    ff, load = _serve_mlp(params["mlp"], params["norm2"], x, rep, spec, cfg,
                          cmix)
    return x + ff, new_cache, load


def _loads(per_segment: list) -> jax.Array:
    """Stacked per-repeat loads of every segment → (expert layers, held)."""
    return jnp.concatenate([a.reshape(-1, a.shape[-1])
                            for a in per_segment], axis=0)


def prefill(params: dict, tokens_or_embeds: jax.Array, cfg: ModelConfig,
            max_len: int, *, expert_load: bool = False):
    """Prompt forward + cache build.  Returns (last-position logits, cache),
    and with ``expert_load`` the pairs that each expert layer routed to
    each held expert, (expert layers, held) int32."""
    if cfg.frontend_stub and tokens_or_embeds.ndim == 3:
        x = tokens_or_embeds.astype(cfg.np_dtype)
    else:
        x = layers.embedding_apply(params["embed"], tokens_or_embeds)
    cache: dict[str, Any] = {}
    loads = []
    for si, seg in enumerate(cfg.segments):
        stacked = params[f"segment_{si}"]
        experts = _expert_stacks(stacked, seg)

        def body(carry, rep_params, seg=seg, experts=experts):
            x, rep = carry
            rep_cache, rep_load = [], []
            for j, spec in enumerate(seg.unit):
                x, c, load = _layer_prefill(
                    _with_expert_stacks(rep_params[j], experts[j]), x, rep,
                    spec, cfg, max_len)
                rep_cache.append(c)
                if load is not None:
                    rep_load.append(load)
            return (x, None if rep is None else rep + 1), (rep_cache,
                                                           rep_load)

        # only a segment with expert layers counts its repeats
        rep0 = jnp.zeros((), jnp.int32) if any(experts) else None
        (x, _), (seg_cache, seg_load) = jax.lax.scan(body, (x, rep0),
                                                     stacked)
        cache[f"segment_{si}"] = seg_cache
        loads += seg_load

    _, napply = layers.make_norm(cfg.norm, cfg.norm_eps)
    x = napply(params["final_norm"], x[:, -1:, :])
    emb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = layers.unembed_apply(emb, x)[:, 0]
    if expert_load:
        return logits, cache, _loads(loads)
    return logits, cache


def _layer_init_cache(spec: LayerSpec, cfg, batch: int,
                      max_len: int) -> dict:
    c: dict[str, Any] = {}
    if spec.mixer == "attn":
        c["mixer"] = attention.gqa_init_cache(cfg, batch, max_len)
    elif spec.mixer == "mla":
        c["mixer"] = attention.mla_init_cache(cfg, batch, max_len)
    elif spec.mixer == "mamba":
        c["mixer"] = ssm.mamba_init_cache(cfg, batch)
    elif spec.mixer == "rwkv":
        c["mixer"] = ssm.rwkv6_init_cache(cfg, batch)
    if spec.mlp == "rwkv_cmix":
        c["cmix_prev"] = jnp.zeros((batch, 1, cfg.d_model), cfg.np_dtype)
    return c


# ---------------------------------------------------------------------------
# Whole-model init / forward / decode
# ---------------------------------------------------------------------------

def init_params(rng, cfg: ModelConfig) -> dict:
    keys = jax.random.split(rng, len(cfg.segments) + 2)
    params: dict[str, Any] = {
        "embed": layers.embedding_init(keys[0], cfg.vocab_size, cfg.d_model,
                                       cfg.np_dtype),
    }
    ninit, _ = layers.make_norm(cfg.norm, cfg.norm_eps)
    params["final_norm"] = ninit(cfg.d_model, cfg.np_dtype)
    if not cfg.tie_embeddings:
        params["unembed"] = layers.embedding_init(
            keys[1], cfg.vocab_size, cfg.d_model, cfg.np_dtype)

    for si, seg in enumerate(cfg.segments):
        seg_keys = jax.random.split(keys[2 + si], seg.repeats)

        def one_repeat(k):
            lk = jax.random.split(k, len(seg.unit))
            return [
                _layer_init(lk[j], spec, cfg)
                for j, spec in enumerate(seg.unit)
            ]

        stacked = jax.vmap(one_repeat)(seg_keys)
        params[f"segment_{si}"] = stacked
    return params


def forward(params: dict, tokens_or_embeds: jax.Array,
            cfg: ModelConfig, *,
            return_hidden: bool = False) -> tuple[jax.Array, dict]:
    """Full-sequence causal forward.  Returns (logits, aux)."""
    if cfg.frontend_stub and tokens_or_embeds.ndim == 3:
        x = tokens_or_embeds.astype(cfg.np_dtype)
    else:
        x = layers.embedding_apply(params["embed"], tokens_or_embeds)

    from ..runtime.sharding import sp_constrain

    total_aux = {"lb_loss": jnp.zeros((), jnp.float32)}
    for si, seg in enumerate(cfg.segments):
        stacked = params[f"segment_{si}"]

        def body(x, rep_params, seg=seg):
            aux_acc: dict[str, Any] = {}
            for j, spec in enumerate(seg.unit):
                x = _layer_apply(rep_params[j], x, spec, cfg, aux_acc)
                x = sp_constrain(x)  # §Perf B3: no-op unless SP enabled
            lb = jnp.asarray(aux_acc.get("lb_loss", 0.0), jnp.float32)
            return x, lb

        if cfg.remat:
            # activation checkpointing: store only the per-repeat residual,
            # recompute layer internals in backward (trades ~1/3 more
            # flops for O(depth) less live activation memory)
            body = jax.checkpoint(body)

        x, lbs = jax.lax.scan(body, x, stacked)
        total_aux["lb_loss"] = total_aux["lb_loss"] + lbs.sum()

    _, napply = layers.make_norm(cfg.norm, cfg.norm_eps)
    x = napply(params["final_norm"], x)
    emb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = layers.unembed_apply(emb, x)
    if return_hidden:
        total_aux["hidden"] = x
    return logits, total_aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    cache: dict[str, Any] = {}
    for si, seg in enumerate(cfg.segments):

        def one_repeat(_):
            return [_layer_init_cache(spec, cfg, batch, max_len)
                    for spec in seg.unit]

        cache[f"segment_{si}"] = jax.vmap(one_repeat)(
            jnp.arange(seg.repeats))
    return cache


def decode_step(params: dict, token: jax.Array, cache: dict,
                length: jax.Array, cfg: ModelConfig, *,
                expert_load: bool = False):
    """One new token for every sequence.  token: (B,) int32; returns
    (logits (B, vocab), new_cache), and with ``expert_load`` the pairs per
    held expert as :func:`prefill` gives them.

    Each segment's stacked cache rides through the layer scan in the carry,
    and every layer writes only this token's entries into it, so the step
    never copies a layer's cache; jitted with the cache donated, the update
    is in place across steps too."""
    x = layers.embedding_apply(params["embed"], token[:, None])
    length = jnp.asarray(length, jnp.int32)
    new_cache: dict[str, Any] = {}
    loads = []
    for si, seg in enumerate(cfg.segments):
        stacked = params[f"segment_{si}"]
        experts = _expert_stacks(stacked, seg)

        def body(carry, rep_params, seg=seg, experts=experts):
            x, seg_cache, rep = carry
            seg_cache = list(seg_cache)
            rep_load = []
            for j, spec in enumerate(seg.unit):
                x, seg_cache[j], load = _layer_decode(
                    _with_expert_stacks(rep_params[j], experts[j]), x,
                    seg_cache[j], rep, length, spec, cfg)
                if load is not None:
                    rep_load.append(load)
            return (x, seg_cache, rep + 1), rep_load

        (x, new_cache[f"segment_{si}"], _), seg_load = jax.lax.scan(
            body, (x, cache[f"segment_{si}"], jnp.zeros((), jnp.int32)),
            stacked)
        loads += seg_load

    _, napply = layers.make_norm(cfg.norm, cfg.norm_eps)
    x = napply(params["final_norm"], x)
    emb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = layers.unembed_apply(emb, x)[:, 0]
    if expert_load:
        return logits, new_cache, _loads(loads)
    return logits, new_cache

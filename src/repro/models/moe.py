"""Mixture-of-Experts: top-k routing over all experts, the held experts'
FFNs, shared experts.

MoE dispatch is the framework's second canonical "memory operation" in the
paper's taxonomy: a data-dependent scatter (tokens → expert buffers)
followed by a gather (expert outputs → token order), with the expert GEMMs
as the long-latency compute stage in between.  Algorithm 1 therefore cuts
stages exactly at dispatch and combine — which is how the layer is written:
dispatch → batched expert FFN → combine.

The router scores all ``num_experts`` experts (softmax, or DeepSeek-V3's
sigmoid with a selection-only correction bias and a routed scale).  A
layer may hold only some of them — the chip's share under expert
parallelism (``MoEConfig.held_experts``): it computes the pairs routed to
its own experts, and pairs routed to experts held elsewhere add nothing
here.  Two dispatches:

* **grouped** (serving: prefill and decode, ``layer`` given): the
  (token, held expert) pairs are sorted by expert and each expert's FFN
  runs once over its own rows — every pair is computed exactly once, none
  is dropped, no expert sees a token that did not choose it.  The
  experts' weights come stacked over the layers of a segment, and
  ``layer`` picks this one.  On a TPU the matmuls are the Pallas grouped
  matmul (``kernels/grouped_matmul.py``), which reads each held expert's
  weights once a call; elsewhere ``jax.lax.ragged_dot``
  (:func:`grouped_path`).
* **capacity** (training ``forward``): per-expert buffers of
  ``C = ceil(k·T/E · capacity_factor)`` rows, overflow pairs dropped
  (their residual passes through — standard Switch behaviour).  The
  fixed-shape (E, C, d) buffer is what GSPMD partitions over the expert
  axis as an all-to-all, which the dry-run's int8 wire and device-limited
  routing knobs act on.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from . import layers
from ..kernels import ops as kops

#: tokens per grouped dispatch: a longer input (a long prefill) runs in
#: blocks of this many tokens, so that its k·T sorted rows and their FFN
#: activations stay a bounded buffer
GROUP_TOKENS = 4096


def moe_init(rng, cfg) -> dict:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(rng, 5)
    E = m.held
    p = {
        "router": layers._dense_init(ks[0], d, m.num_experts, jnp.float32,
                                     scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (E, d, m.d_ff), jnp.float32)
                   / np.sqrt(d)).astype(cfg.np_dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, m.d_ff), jnp.float32)
                 / np.sqrt(d)).astype(cfg.np_dtype),
        "w_down": (jax.random.normal(ks[3], (E, m.d_ff, d), jnp.float32)
                   / np.sqrt(m.d_ff)).astype(cfg.np_dtype),
    }
    if m.score_bias:
        p["router_bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    if m.num_shared > 0:
        p["shared"] = layers.mlp_init(ks[4], d, m.d_ff * m.num_shared,
                                      cfg.act, cfg.np_dtype)
    return p


def route(params: dict, xt: jax.Array, m
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k over all ``m.num_experts``: ``(ids (T, k), weights (T, k) f32,
    scores (T, E))``.  The correction bias, if any, moves the choice only;
    the weights are the chosen experts' scores, normalised if configured,
    times ``m.routed_scale``."""
    T = xt.shape[0]
    E, k = m.num_experts, m.top_k
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    if m.router_fn == "sigmoid":   # DeepSeek-V3 style
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores
    if m.score_bias:
        choice = choice + params["router_bias"].astype(jnp.float32)
    if m.route_groups > 1 and m.route_device_limit > 0:
        # §Perf: device-limited routing (DeepSeek-V3 node-limited routing):
        # keep only the top-M expert groups per token before the top-k, so
        # each token's dispatch fans out to ≤ M EP devices.
        G = m.route_groups
        gs = choice.reshape(T, G, E // G).max(axis=-1)      # (T, G)
        _, top_g = jax.lax.top_k(gs, m.route_device_limit)
        gmask = jax.nn.one_hot(top_g, G, dtype=jnp.bool_).any(1)
        choice = jnp.where(jnp.repeat(gmask, E // G, axis=1), choice,
                           -jnp.inf)
    _, top_ids = jax.lax.top_k(choice, k)                  # (T, k)
    top_w = jnp.take_along_axis(scores, top_ids, axis=1)
    if m.normalize_weights:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_ids, top_w * m.routed_scale, scores


def _held_key(top_ids: jax.Array, m) -> jax.Array:
    """Each pair's held expert (0 .. held-1), or ``held`` for a pair routed
    to an expert held elsewhere."""
    local = top_ids - m.first_held
    return jnp.where((local >= 0) & (local < m.held), local, m.held)


def grouped_path() -> str:
    """The grouped dispatch's matmuls on the default backend: ``"pallas"``
    (:func:`repro.kernels.ops.grouped_matmul`) where the kernel compiles
    natively, on a TPU; ``"ragged_dot"`` (``jax.lax.ragged_dot``)
    elsewhere, so a CPU never interprets a kernel inside every layer."""
    return "pallas" if jax.default_backend() == "tpu" else "ragged_dot"


def grouped_blocks(tokens: int) -> int:
    """Grouped dispatches of one expert layer over ``tokens`` tokens."""
    return -(-tokens // GROUP_TOKENS)


def _grouped(params: dict, xt: jax.Array, top_ids: jax.Array,
             top_w: jax.Array, m, layer) -> tuple[jax.Array, jax.Array]:
    """Every (token, held expert) pair once: ``(y (T, d) f32, pairs per held
    expert (held,) int32)``.  The expert weights are stacked over layers
    and this is layer ``layer``: the grouped matmuls take the whole stack
    (a layer's slice of the stack would be copied out of it for the
    custom call)."""
    T, d = xt.shape
    k = top_ids.shape[1]
    pairs = T * k
    key = _held_key(top_ids, m).reshape(pairs)
    # a counting sort by expert (pairs held elsewhere last), stable: each
    # pair's rank among its expert's pairs
    onehot = (key[:, None] == jnp.arange(m.held + 1)).astype(jnp.int32)
    seen = jnp.cumsum(onehot, axis=0)
    counts = seen[-1]
    rank = (seen * onehot).sum(axis=1) - 1
    sizes = counts[:m.held]
    n_all = params["w_gate"].shape[0] * m.held
    w = [params[n].reshape((n_all,) + params[n].shape[2:])
         for n in ("w_gate", "w_up", "w_down")]
    grouped = (_grouped_pallas if grouped_path() == "pallas"
               else _grouped_ragged)
    out, back = grouped(xt, w, key, rank, counts, m, layer, k)
    # each pair's output back in token order, one of a token's k pairs at a
    # time (no (T, k, d) float32 buffer); a row past the held pairs belongs
    # to no group and holds anything: select, never scale
    back = back.reshape(T, k)
    held = (key < m.held).reshape(T, k)
    y = jnp.zeros((T, d), jnp.float32)
    for j in range(k):
        y += jnp.where(held[:, j, None], out[back[:, j]].astype(jnp.float32)
                       * top_w[:, j, None], 0.0)
    return y, sizes


def _grouped_ragged(xt, w, key, rank, counts, m, layer, k):
    """The held experts' SwiGLU by ``ragged_dot`` over all the sorted
    pairs, every other layer's groups of the stack empty: ``(out (T·k,
    d), each pair's row)``."""
    pairs = key.shape[0]
    back = (jnp.cumsum(counts) - counts)[key] + rank
    order = jnp.zeros((pairs,), jnp.int32).at[back].set(
        jnp.arange(pairs, dtype=jnp.int32))
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((w[0].shape[0],), jnp.int32), counts[:m.held],
        (layer * m.held,))
    rows = xt[order // k]
    gate = jax.lax.ragged_dot(rows, w[0], groups)
    up = jax.lax.ragged_dot(rows, w[1], groups)
    h = (jax.nn.silu(gate.astype(jnp.float32))
         * up.astype(jnp.float32)).astype(xt.dtype)
    return jax.lax.ragged_dot(h, w[2], groups), back


def _grouped_pallas(xt, w, key, rank, counts, m, layer, k):
    """The held experts' SwiGLU by the grouped-matmul kernel, in two calls
    (gate and up fused): ``(out (rows, d), each pair's row)``.  Each held
    expert's rows start at a whole row tile, and the pairs held elsewhere
    get no row (their ``back`` is any row)."""
    pairs = key.shape[0]
    tm = kops.grouped_block_rows(pairs, m.num_experts)
    rows = kops.grouped_rows(pairs, m.held, tm)
    tiles = kops.group_tiles(counts[:m.held], tm)
    first = jnp.append(tiles[:m.held] * tm, rows)
    back = first[key] + rank
    src = jnp.zeros((rows,), jnp.int32).at[back].set(
        jnp.arange(pairs, dtype=jnp.int32) // k, mode="drop")
    h = kops.grouped_matmul(xt[src], w[0], tiles, layer, w_up=w[1],
                           block_rows=tm)
    out = kops.grouped_matmul(h, w[2], tiles, layer, block_rows=tm)
    return out, jnp.minimum(back, rows - 1)


def _grouped_blocks(params, xt, top_ids, top_w, m, layer):
    """:func:`_grouped` over blocks of at most :data:`GROUP_TOKENS`."""
    T, d = xt.shape
    if T <= GROUP_TOKENS:
        return _grouped(params, xt, top_ids, top_w, m, layer)
    n = grouped_blocks(T)
    pad = n * GROUP_TOKENS - T
    # padded tokens are routed to no held expert
    ids = jnp.pad(top_ids, ((0, pad), (0, 0)),
                  constant_values=m.first_held + m.held)
    blocks = (jnp.pad(xt, ((0, pad), (0, 0))), ids,
              jnp.pad(top_w, ((0, pad), (0, 0))))
    y, sizes = jax.lax.map(
        lambda b: _grouped(params, *b, m, layer),
        jax.tree_util.tree_map(
            lambda a: a.reshape((n, GROUP_TOKENS) + a.shape[1:]), blocks))
    return y.reshape(n * GROUP_TOKENS, d)[:T], sizes.sum(axis=0)


def moe_apply(params: dict, x: jax.Array, cfg, *,
              layer: jax.Array | None = None) -> tuple[jax.Array, dict]:
    """x: (B, S, d) → (y, aux), y in the model's dtype.  The router reads
    ``x`` as given (float32 for exact routing), the experts in the model's
    dtype.  With ``layer``, the grouped dispatch: the expert weights are
    stacked over layers and this is that one.  Without, the capacity
    dispatch over one layer's weights, and ``aux`` also has the
    load-balance loss and the share of held pairs dropped.
    ``aux["load"]`` (held,) int32 is the pairs routed to each held
    expert."""
    m = cfg.moe
    B, S, d = x.shape
    top_ids, top_w, scores = route(params, x.reshape(B * S, d), m)
    xt = x.reshape(B * S, d).astype(cfg.np_dtype)
    if layer is not None:
        y, load = _grouped_blocks(params, xt, top_ids, top_w, m, layer)
        aux = {"load": load}
    else:
        y, aux = _capacity(params, xt, top_ids, top_w, scores, m)
    y = y.astype(cfg.np_dtype)
    # --- shared experts (always-on streaming partition) ---------------------
    if m.num_shared > 0:
        y = y + layers.mlp_apply(params["shared"], xt, cfg.act)
    return y.reshape(B, S, d), aux


def _capacity(params, xt, top_ids, top_w, scores, m):
    """The capacity dispatch over the held experts: ``(y (T, d), aux)``."""
    T, d = xt.shape
    E, k = m.held, m.top_k
    key = _held_key(top_ids, m)                            # (T, k)
    held = key < E

    # --- capacity + position within expert --------------------------------
    cap = int(np.ceil(k * T / m.num_experts * m.capacity_factor))
    onehot = jax.nn.one_hot(key, E, dtype=jnp.int32)       # (T, k, E)
    flat = onehot.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) - flat                  # pos in expert
    pos = (pos * flat).sum(-1).reshape(T, k)               # (T, k)
    keep = held & (pos < cap)
    slot = jnp.where(held, key, 0) * cap + pos      # (T, k) in [0,E*cap)

    # --- scatter (dispatch: the memory stage) ------------------------------
    # §Perf knob: int8 dispatch — quantize the token payload before the
    # scatter (the expert-parallel all-to-all moves the scattered buffer,
    # so this halves its wire bytes); per-token f16 scales ride along.
    src = jnp.repeat(xt[:, None, :], k, axis=1)            # (T, k, d)
    src = jnp.where(keep[..., None], src, 0)
    if m.dispatch_dtype == "int8":
        s8 = jnp.max(jnp.abs(src.astype(jnp.float32)), -1,
                     keepdims=True) / 127.0
        s8 = jnp.maximum(s8, 1e-8)
        src_q = jnp.clip(jnp.round(src.astype(jnp.float32) / s8),
                         -127, 127).astype(jnp.int8)
        xe_q = jnp.zeros((E * cap, d), jnp.int8)
        xe_q = xe_q.at[slot.reshape(-1)].add(src_q.reshape(T * k, d))
        se = jnp.zeros((E * cap, 1), jnp.float16)
        se = se.at[slot.reshape(-1)].add(
            s8.reshape(T * k, 1).astype(jnp.float16))
        xe = (xe_q.astype(jnp.float32)
              * se.astype(jnp.float32)).astype(xt.dtype)
    else:
        xe = jnp.zeros((E * cap, d), xt.dtype)
        xe = xe.at[slot.reshape(-1)].add(src.reshape(T * k, d))
    xe = xe.reshape(E, cap, d)

    # --- expert FFN (the long-latency stage) -------------------------------
    gate = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    h = (jax.nn.silu(gate.astype(jnp.float32))
         * up.astype(jnp.float32)).astype(xt.dtype)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])   # (E, cap, d)

    # --- gather (combine: the second memory stage) --------------------------
    yk = ye.reshape(E * cap, d)[slot.reshape(-1)].reshape(T, k, d)
    yk = yk * (top_w * keep).astype(jnp.float32)[..., None]
    y = yk.sum(axis=1)

    # --- aux: load-balance loss (Switch-style) ------------------------------
    me = scores[:, m.first_held:m.first_held + E].mean(axis=0)   # (E,)
    load = onehot.sum(axis=(0, 1))
    ce = load.astype(jnp.float32) / T * (m.num_experts / k)
    aux = {
        "lb_loss": (me * ce).sum() * m.num_experts,
        "dropped_frac": 1.0 - keep.sum() / jnp.maximum(held.sum(), 1),
        "load": load,
    }
    return y, aux

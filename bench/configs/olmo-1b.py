"""OLMo-1B: weights from the seed and the plain float32 reference.

``make_params`` draws the weights on the device in one jitted call, in the
type they are served in, laid out as the program's parameter tree expects
(``embed``, ``final_norm`` and one stacked ``segment_0`` unit).  The
reference draws the same weights again from the seed; it takes nothing
that the program made.

The reference is OLMo's forward as the paper describes it, written out in
float32 at the highest matmul precision: token embedding, then per block
a non-parametric LayerNorm, causal multi-head attention with rotary
embeddings (rotate-half, over the whole head), a residual add, another
LayerNorm, a SwiGLU MLP and a residual add; a final LayerNorm and the
tied embedding as output head.  No biases.  Departures from the paper:
none in the mathematics; the weights are random, not trained.

``precision="fp8"`` is the control: every matmul's operands are rounded
to float8 e4m3 with one scale per tensor, as an fp8 serving path would.
"""

from __future__ import annotations

import functools

import numpy as np


def dims(cfg: dict) -> dict:
    d = cfg["d_model"]
    # OLMo's MLP input width: mlp_hidden_size, or mlp_ratio * d_model where
    # that is null; SwiGLU splits it into gate and up halves
    hidden = cfg["mlp_hidden_size"] or cfg["mlp_ratio"] * d
    return {"D": d, "H": cfg["n_heads"], "hd": d // cfg["n_heads"],
            "L": cfg["n_layers"], "F": hidden // 2,
            "V": cfg["embedding_size"], "eps": cfg["layer_norm_eps"],
            "theta": float(cfg["rope_theta"])}


def seed_key(seed: int):
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.lru_cache(maxsize=None)
def _param_maker(D: int, F: int, V: int, L: int, init_std: float):
    import jax
    import jax.numpy as jnp

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(jnp.bfloat16)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 8)
        layer = {
            "norm1": {},
            "mixer": {"w_q": dense(ks[0], (L, D, D), D),
                      "w_k": dense(ks[1], (L, D, D), D),
                      "w_v": dense(ks[2], (L, D, D), D),
                      "w_o": dense(ks[3], (L, D, D), D)},
            "norm2": {},
            "mlp": {"w_gate": dense(ks[4], (L, D, F), D),
                    "w_up": dense(ks[5], (L, D, F), D),
                    "w_down": dense(ks[6], (L, F, D), F)},
        }
        table = (jax.random.normal(ks[7], (V, D), jnp.float32)
                 * init_std).astype(jnp.bfloat16)
        return {"embed": {"table": table}, "final_norm": {},
                "segment_0": [layer]}

    return make


def make_params(cfg: dict, seed: int):
    """The served weights (bfloat16, on the default device)."""
    d = dims(cfg)
    return _param_maker(d["D"], d["F"], d["V"], d["L"],
                        float(cfg["init_std"]))(seed_key(seed))


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------

def _round(x, precision: str):
    """Matmul operand as the given precision sees it."""
    import jax.numpy as jnp
    if precision == "fp32":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, precision):
    return _round(a, precision) @ _round(b, precision)


def _layer_norm(x, eps):
    import jax.numpy as jnp
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """x: (B, T, H, hd); rotate-half rotary embedding at positions 0..T-1."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


@functools.lru_cache(maxsize=None)
def _block_fn(H: int, eps: float, theta: float, precision: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
        B, T, D = x.shape
        hd = D // H
        h = _layer_norm(x, eps)
        q = _mm(h, w["w_q"], precision).reshape(B, T, H, hd)
        k = _mm(h, w["w_k"], precision).reshape(B, T, H, hd)
        v = _mm(h, w["w_v"], precision).reshape(B, T, H, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        s = jnp.einsum("bqhd,bkhd->bhqk", _round(q, precision),
                       _round(k, precision)) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", _round(p, precision),
                       _round(v, precision)).reshape(B, T, D)
        x = x + _mm(o, w["w_o"], precision)
        h = _layer_norm(x, eps)
        g = jax.nn.silu(_mm(h, w["w_gate"], precision))
        u = _mm(h, w["w_up"], precision)
        return x + _mm(g * u, w["w_down"], precision)

    return block


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, precision: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, table, pos):
        """Logits (B, G, V) at positions ``pos`` (B, G)."""
        h = _layer_norm(jnp.take_along_axis(x, pos[..., None], axis=1), eps)
        return _mm(h, table.astype(jnp.float32).T, precision)

    return head


def logits_at(cfg: dict, params, tokens, pos, precision: str = "fp32"):
    """Reference logits (B, G, V) at positions ``pos`` (B, G) of the
    token rows ``tokens`` (B, T), as a numpy array."""
    import jax
    import jax.numpy as jnp
    d = dims(cfg)
    block = _block_fn(d["H"], d["eps"], d["theta"], precision)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], jnp.asarray(tokens),
                     axis=0).astype(jnp.float32)
        layer = params["segment_0"][0]
        for i in range(d["L"]):
            w = {name: layer[part][name][i]
                 for part, names in (("mixer", ("w_q", "w_k", "w_v", "w_o")),
                                     ("mlp", ("w_gate", "w_up", "w_down")))
                 for name in names}
            x = block(x, w)
        out = _head_fn(d["eps"], precision)(x, params["embed"]["table"],
                                            jnp.asarray(pos))
        return np.asarray(jax.device_get(out))


def reference_gaps(cfg: dict, seed: int, samples: list[dict], length: int,
                   rows: int, precision: str | None = None,
                   params=None) -> list[dict]:
    """Teacher-forced reference over each sample's prompt and served
    tokens.  ``samples``: dicts with ``prompt`` and ``tokens`` (int
    lists).  Sequences are right-padded to ``length`` (causal, so the
    padding changes no position that is read) and run ``rows`` at a
    time.  Returns, per sample, ``gaps``: for each served token, how far
    the float32 reference's logit of it lies below its best; and, with a
    control ``precision``, ``control_gaps``: the same for the token that
    the reference in that precision puts first at each position."""
    if params is None:
        params = make_params(cfg, seed)
    G = max(len(s["tokens"]) for s in samples)
    out = []
    for lo in range(0, len(samples), rows):
        block = samples[lo:lo + rows]
        toks = np.zeros((rows, length), np.int32)
        pos = np.zeros((rows, G), np.int32)
        for r, s in enumerate(block):
            seq = list(s["prompt"]) + list(s["tokens"][:-1])
            toks[r, :len(seq)] = seq
            P = len(s["prompt"])
            pos[r] = np.minimum(np.arange(P - 1, P - 1 + G), len(seq) - 1)
        ref = logits_at(cfg, params, toks, pos)
        low = None if precision is None else \
            logits_at(cfg, params, toks, pos, precision)
        for r, s in enumerate(block):
            n = len(s["tokens"])
            lg = ref[r, :n]
            best = lg.max(axis=1)
            at = lg[np.arange(n), np.asarray(s["tokens"], np.int64)]
            item = {"gaps": (best - at).tolist()}
            if low is not None:
                first = low[r, :n].argmax(axis=1)
                item["control_gaps"] = (best - lg[np.arange(n),
                                                  first]).tolist()
            out.append(item)
    return out

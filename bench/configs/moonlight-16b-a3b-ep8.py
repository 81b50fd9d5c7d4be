"""Moonlight-16B-A3B as one chip's share of 8-way expert parallelism:
weights from the seed, the plain float32 reference, and the shapes'
operations and bytes.

``make_params`` draws the weights on the device in one jitted call, in the
type they are served in, laid out as the program's parameter tree expects
(``embed``, ``unembed``, ``final_norm``, ``segment_0`` with the dense first
layer and ``segment_1`` with the 26 expert layers, each stacked).  Each
expert layer holds the routed experts of the configuration's
``deployment.experts_held`` (8 of 64) and a router over all 64.  The
reference draws the same weights again from the seed; it takes nothing
that the program made and imports nothing of it.

The reference is the DeepSeek-V3 block as the published modelling code
writes it, in float32 at the highest matmul precision, one layer and one
block of rows at a time (the bf16 weights are upcast a layer at a time: the
whole model in float32 would not fit beside them).  Per layer: RMSNorm;
MLA with a direct query projection (no query LoRA), the latent
``c_kv`` RMS-normed and decompressed into per-head keys and values (the
naive form, not the absorbed one), a RoPE key shared by the heads, causal
softmax attention at scale 1/sqrt(nope + rope); a residual add; RMSNorm;
then the dense SwiGLU MLP, or the expert layer: sigmoid scores of all 64
experts, the top 6 of the scores plus the correction bias, weights the
unbiased scores of those 6 normalised to sum 1 and times the routed scale,
each held expert's SwiGLU over every token and for each token the sum of
its selected held experts' outputs times their weights (experts held
elsewhere add nothing), plus the shared experts' SwiGLU; a residual add.
A final RMSNorm and the untied output head.  Departures from the published
code: rotate-half RoPE pairs (see the configuration's ``assumed``); the
normalisation divides by max(sum, 1e-9) where the published code adds
1e-20; random weights.

The controls put the reference in the program's place: ``"fp8"`` rounds
every matmul's operands to float8 e4m3 with one scale per tensor, as an
fp8 serving path would; ``"bf16"`` rounds them to bfloat16, as the
program computes; :func:`zero_held_expert` plants a fault in the weights.
"""

from __future__ import annotations

import functools

import numpy as np


def dims(cfg: dict) -> dict:
    dep = cfg["deployment"]
    return {
        "D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "L": cfg["num_hidden_layers"], "dense": cfg["first_k_dense_replace"],
        "F": cfg["intermediate_size"], "Fe": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "E": dep["router_outputs"], "held": cfg["n_routed_experts"],
        "first": dep["experts_held"][0], "k": cfg["num_experts_per_tok"],
        "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "V": cfg["vocab_size"], "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "scale": float(cfg["routed_scaling_factor"]),
    }


def seed_key(seed: int):
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _param_maker(d: tuple, init_std: float, dtype: str):
    import jax
    import jax.numpy as jnp
    d = dict(d)
    dtype = jnp.dtype(dtype)
    D, H, r, rope = d["D"], d["H"], d["r"], d["rope"]
    qk = d["nope"] + rope

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def layer(key, n, moe):
        ks = jax.random.split(key, 12)
        p = {
            "norm1": {"scale": ones(n, D)},
            "mixer": {"w_q": dense(ks[0], (n, D, H * qk), D),
                      "w_dkv": dense(ks[1], (n, D, r + rope), D),
                      "kv_norm": {"scale": ones(n, r)},
                      "w_ukv": dense(ks[2], (n, r, H * (d["nope"] + d["v"])),
                                     r),
                      "w_o": dense(ks[3], (n, H * d["v"], D), H * d["v"])},
            "norm2": {"scale": ones(n, D)},
        }
        if not moe:
            p["mlp"] = {"w_gate": dense(ks[4], (n, D, d["F"]), D),
                        "w_up": dense(ks[5], (n, D, d["F"]), D),
                        "w_down": dense(ks[6], (n, d["F"], D), d["F"])}
            return p
        E, Fe, S = d["held"], d["Fe"], d["shared"]
        p["mlp"] = {
            "router": dense(ks[4], (n, D, d["E"]), D),
            "router_bias": jax.random.normal(ks[5], (n, d["E"]),
                                             jnp.float32) * 0.02,
            "w_gate": dense(ks[6], (n, E, D, Fe), D),
            "w_up": dense(ks[7], (n, E, D, Fe), D),
            "w_down": dense(ks[8], (n, E, Fe, D), Fe),
            "shared": {"w_gate": dense(ks[9], (n, D, S), D),
                       "w_up": dense(ks[10], (n, D, S), D),
                       "w_down": dense(ks[11], (n, S, D), S)},
        }
        return p

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 4)

        def table(k):
            return (jax.random.normal(k, (d["V"], D), jnp.float32)
                    * init_std).astype(dtype)
        return {"embed": {"table": table(ks[0])},
                "unembed": {"table": table(ks[1])},
                "final_norm": {"scale": ones(D)},
                "segment_0": [layer(ks[2], d["dense"], False)],
                "segment_1": [layer(ks[3], d["L"] - d["dense"], True)]}

    return make


def make_params(cfg: dict, seed: int):
    """The served weights (in ``served_dtype``; the correction bias
    float32), on the default device."""
    return _param_maker(tuple(sorted(dims(cfg).items())),
                        float(cfg["init_std"]),
                        cfg["served_dtype"])(seed_key(seed))


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------

def _round(x, precision: str):
    """Matmul operand as the given precision sees it."""
    import jax.numpy as jnp
    if precision == "fp32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, precision):
    return _round(a, precision) @ _round(b, precision)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, theta):
    """x: (B, T, ..., n); rotate-half rotary embedding at positions
    0..T-1."""
    import jax.numpy as jnp
    n = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (n,)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1)).reshape(shape)
    sin = jnp.sin(jnp.concatenate([ang, ang], -1)).reshape(shape)
    x1, x2 = x[..., :n // 2], x[..., n // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(h, w, precision):
    import jax
    return _mm(jax.nn.silu(_mm(h, w["w_gate"], precision))
               * _mm(h, w["w_up"], precision), w["w_down"], precision)


def _route(h, w, d):
    """Top-k ids (T, k) of the biased scores and their weights, from the
    unbiased scores."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(h @ w["router"])
    _, ids = jax.lax.top_k(scores + w["router_bias"], d["k"])
    wt = jnp.take_along_axis(scores, ids, axis=-1)
    wt = wt / jnp.maximum(wt.sum(-1, keepdims=True), 1e-9)
    return ids, wt * d["scale"]


def expert_layer(h, m, d: dict, precision: str = "fp32"):
    """The expert layer over tokens ``h`` (T, D) in float32, with the
    layer's weights ``m`` (router, correction bias, the held experts and
    the shared experts): ``(y (T, D), tokens whose top-k set moves when the
    router's operands are rounded to bf16)``."""
    import jax.numpy as jnp
    ids, wt = _route(h, m, d)
    ids16, _ = _route(_round(h, "bf16"),
                      {**m, "router": _round(m["router"], "bf16")}, d)
    moved = (jnp.sort(ids, -1) != jnp.sort(ids16, -1)).any(-1).sum()
    # every held expert over every token, then each token's own
    ys = jnp.stack([_swiglu(h, {n: m[n][e] for n in
                                ("w_gate", "w_up", "w_down")}, precision)
                    for e in range(d["held"])], 1)           # (T, held, D)
    local = ids - d["first"]
    here = (local >= 0) & (local < d["held"])
    picked = jnp.take_along_axis(
        ys, jnp.where(here, local, 0)[..., None], axis=1)    # (T, k, D)
    y = (picked * jnp.where(here, wt, 0.0)[..., None]).sum(1)
    return y + _swiglu(h, m["shared"], precision), moved


@functools.lru_cache(maxsize=None)
def _block_fn(d: tuple, moe: bool, precision: str):
    import jax
    import jax.numpy as jnp
    d = dict(d)
    H, nope, rope, dv = d["H"], d["nope"], d["rope"], d["v"]

    @jax.jit
    def block(x, w):
        """One layer over rows x (R, T, D); returns the new rows and, for
        an expert layer, the tokens whose top-k set moves when the router's
        operands are rounded to bf16."""
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
        R, T, D = x.shape
        h = _rms(x, w["norm1"], d["eps"])
        q = _mm(h, w["w_q"], precision).reshape(R, T, H, nope + rope)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                                  d["theta"])], -1)
        ckv = _mm(h, w["w_dkv"], precision)
        c = _rms(ckv[..., :d["r"]], w["kv_norm"], d["eps"])
        k_pe = _rope(ckv[..., d["r"]:], d["theta"])          # (R, T, rope)
        kv = _mm(c, w["w_ukv"], precision).reshape(R, T, H, nope + dv)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, :, None], (R, T, H, rope))], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", _round(q, precision),
                       _round(k, precision)) / np.sqrt(nope + rope)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", _round(p, precision),
                       _round(kv[..., nope:], precision)).reshape(R, T, -1)
        x = x + _mm(o, w["w_o"], precision)
        h = _rms(x, w["norm2"], d["eps"]).reshape(R * T, D)
        if not moe:
            return x + _swiglu(h, w["mlp"], precision).reshape(R, T, D), 0
        y, moved = expert_layer(h, w["mlp"], d, precision)
        return x + y.reshape(R, T, D), moved

    return block


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, precision: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, norm, table, pos):
        """Logits (R, G, V) at positions ``pos`` (R, G)."""
        h = _rms(jnp.take_along_axis(x, pos[..., None], axis=1),
                 norm.astype(jnp.float32), eps)
        return _mm(h, table.astype(jnp.float32).T, precision)

    return head


def _layer(params, i: int, dense: int):
    """Layer ``i``'s weights (bf16 slices of the stacked segments)."""
    seg, j = (params["segment_0"][0], i) if i < dense else \
        (params["segment_1"][0], i - dense)
    import jax
    return jax.tree_util.tree_map(lambda a: a[j], seg), i >= dense


def hidden(cfg: dict, params, tokens, precision: str = "fp32"):
    """The last layer's rows (R, T, D) for token rows ``tokens`` (R, T),
    and the count of token-layers whose top-k set moves when the router's
    operands are rounded to bf16."""
    import jax
    import jax.numpy as jnp
    d = dims(cfg)
    key = tuple(sorted(d.items()))
    moved = 0
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], jnp.asarray(tokens),
                     axis=0).astype(jnp.float32)
        for i in range(d["L"]):
            w, moe = _layer(params, i, d["dense"])
            w = {"norm1": w["norm1"]["scale"], "norm2": w["norm2"]["scale"],
                 **{n: a for n, a in w["mixer"].items() if n != "kv_norm"},
                 "kv_norm": w["mixer"]["kv_norm"]["scale"], "mlp": w["mlp"]}
            x, m = _block_fn(key, moe, precision)(x, w)
            moved += int(m)
    return x, moved


def logits_at(cfg: dict, params, tokens, pos, precision: str = "fp32"):
    """Reference logits (R, G, V) at positions ``pos`` (R, G) of the token
    rows ``tokens`` (R, T), as a numpy array."""
    import jax
    import jax.numpy as jnp
    d = dims(cfg)
    x, _ = hidden(cfg, params, tokens, precision)
    with jax.default_matmul_precision("highest"):
        out = _head_fn(d["eps"], precision)(
            x, params["final_norm"]["scale"], params["unembed"]["table"],
            jnp.asarray(pos))
    return np.asarray(jax.device_get(out))


@functools.lru_cache(maxsize=None)
def _gap_fn(eps: float, control: str | None):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(x, xc, norm, table, pos, tok):
        """Per position: how far the reference logit of ``tok`` lies below
        the reference's best, or, with a control, how far the logit of the
        token that the control (its last rows ``xc``) puts first does."""
        lg = _head_fn(eps, "fp32")(x, norm, table, pos)
        if control is not None:
            tok = _head_fn(eps, control)(xc, norm, table, pos).argmax(-1)
        return lg.max(-1) - jnp.take_along_axis(lg, tok[..., None],
                                                -1)[..., 0]

    return gaps


def reference_gaps(cfg: dict, seed: int, samples: list[dict], length: int,
                   rows: int, controls: dict | None = None,
                   params=None) -> list[dict]:
    """Teacher-forced reference over each sample's prompt and served
    tokens, ``rows`` sequences at a time, each right-padded to the
    samples' longest in whole 128s (at most ``length``; causal, so the
    padding changes no position that is read).  Returns, per sample,
    ``gaps``: for each served token, how far the float32 reference's logit
    of it lies below its best.  ``controls`` maps a name to ``(precision,
    weights or None)``: the reference in that precision, with those
    weights (default the seed's), in the program's place; each sample then
    also has ``control_gaps[name]``, the same gap for the token that the
    control puts first at each position, and ``route_moved`` /
    ``route_tokens`` for the block it was in: token-layers whose top-k set
    moves when the router's operands are rounded to bf16."""
    import jax
    import jax.numpy as jnp
    d = dims(cfg)
    if params is None:
        params = make_params(cfg, seed)
    controls = controls or {}
    G = max(len(s["tokens"]) for s in samples)
    T = min(length, -(-max(len(s["prompt"]) + len(s["tokens"]) - 1
                           for s in samples) // 128) * 128)
    head = (params["final_norm"]["scale"], params["unembed"]["table"])
    out = []
    for lo in range(0, len(samples), rows):
        block = samples[lo:lo + rows]
        toks = np.zeros((rows, T), np.int32)
        pos = np.zeros((rows, G), np.int32)
        tok = np.zeros((rows, G), np.int32)
        for r, s in enumerate(block):
            seq = list(s["prompt"]) + list(s["tokens"][:-1])
            toks[r, :len(seq)] = seq
            P = len(s["prompt"])
            pos[r] = np.minimum(np.arange(P - 1, P - 1 + G), len(seq) - 1)
            tok[r, :len(s["tokens"])] = s["tokens"]
        pos, tok = jnp.asarray(pos), jnp.asarray(tok)
        x, moved = hidden(cfg, params, toks)
        with jax.default_matmul_precision("highest"):
            g = jax.device_get(_gap_fn(d["eps"], None)(x, x, *head, pos,
                                                       tok))
        cg = {}
        for name, (precision, weights) in controls.items():
            xc, _ = hidden(cfg, params if weights is None else weights,
                           toks, precision)
            with jax.default_matmul_precision("highest"):
                cg[name] = jax.device_get(_gap_fn(d["eps"], precision)(
                    x, xc, *head, pos, tok))
        moe_layers = d["L"] - d["dense"]
        for r, s in enumerate(block):
            n = len(s["tokens"])
            item = {"gaps": g[r, :n].tolist()}
            if controls:
                item["control_gaps"] = {name: c[r, :n].tolist()
                                        for name, c in cg.items()}
                item["route_moved"] = moved
                item["route_tokens"] = rows * T * moe_layers
            out.append(item)
    return out


def zero_held_expert(params, expert: int):
    """The weights with held expert ``expert``'s down projection zeroed in
    every expert layer: the planted fault of a layer that loses that
    expert's pairs."""
    seg = params["segment_1"][0]
    mlp = dict(seg["mlp"], w_down=seg["mlp"]["w_down"].at[:, expert].set(0))
    return {**params, "segment_1": [{**seg, "mlp": mlp}]}


# ---------------------------------------------------------------------------
# The program's configuration, operations and bytes
# ---------------------------------------------------------------------------

def config_differences(pcfg, cfg: dict) -> list[str]:
    """Fields in which the program's configuration departs from the
    configuration file."""
    d = dims(cfg)
    mla, moe = pcfg.mla, pcfg.moe
    kinds = [(s.mixer, s.mlp) for seg in pcfg.segments
             for _ in range(seg.repeats) for s in seg.unit]
    want = {
        "d_model": (pcfg.d_model, d["D"]),
        "num_heads": (pcfg.num_heads, d["H"]),
        "num_layers": (pcfg.num_layers, d["L"]),
        "d_ff": (pcfg.d_ff, d["F"]),
        "vocab_size": (pcfg.vocab_size, d["V"]),
        "tie_embeddings": (pcfg.tie_embeddings, cfg["tie_word_embeddings"]),
        "norm": (pcfg.norm, "rmsnorm"),
        "norm_eps": (pcfg.norm_eps, d["eps"]),
        "act": (pcfg.act, cfg["hidden_act"]),
        "qkv_bias": (pcfg.qkv_bias, cfg["attention_bias"]),
        "rope_theta": (pcfg.rope_theta, d["theta"]),
        "dtype": (pcfg.dtype, cfg["served_dtype"]),
        "parallel_block": (pcfg.parallel_block, False),
        "layers": (kinds, [("mla", "dense")] * d["dense"]
                   + [("mla", "moe")] * (d["L"] - d["dense"])),
        "mla.q_lora_rank": (mla.q_lora_rank, cfg["q_lora_rank"] or 0),
        "mla.kv_lora_rank": (mla.kv_lora_rank, d["r"]),
        "mla.qk_nope_head_dim": (mla.qk_nope_head_dim, d["nope"]),
        "mla.qk_rope_head_dim": (mla.qk_rope_head_dim, d["rope"]),
        "mla.v_head_dim": (mla.v_head_dim, d["v"]),
        "moe.num_experts": (moe.num_experts, d["E"]),
        "moe.held_experts": (moe.held_experts, d["held"]),
        "moe.first_held": (moe.first_held, d["first"]),
        "moe.top_k": (moe.top_k, d["k"]),
        "moe.d_ff": (moe.d_ff, d["Fe"]),
        "moe.num_shared": (moe.num_shared, cfg["n_shared_experts"]),
        "moe.router_fn": (moe.router_fn, cfg["scoring_func"]),
        "moe.normalize_weights": (moe.normalize_weights,
                                  cfg["norm_topk_prob"]),
        "moe.score_bias": (moe.score_bias, cfg["topk_method"] == "noaux_tc"),
        "moe.routed_scale": (moe.routed_scale, d["scale"]),
        "moe.groups": ((moe.route_groups, moe.route_device_limit),
                       (0, 0) if cfg["n_group"] == 1 else None),
    }
    return [k for k, (got, ref) in want.items() if got != ref]


def _per_position(d: dict) -> tuple[float, float, float]:
    """Forward FLOPs per position per layer outside attention's key loop:
    (MLA projections, dense MLP, expert-layer MLP with the held experts'
    expected share)."""
    D, H = d["D"], d["H"]
    mla = 2 * (D * H * (d["nope"] + d["rope"]) + D * (d["r"] + d["rope"])
               + d["r"] * H * (d["nope"] + d["v"]) + H * d["v"] * D)
    dense = 2 * 3 * D * d["F"]
    # uniform routing sends k * held / E of a token's pairs here
    experts = d["k"] * d["held"] / d["E"]
    moe = 2 * (D * d["E"] + 3 * D * d["shared"] + experts * 3 * D * d["Fe"])
    return mla, dense, moe


def request_flops(cfg: dict, prompt: int, new: int) -> float:
    """Forward FLOPs on this chip to read a ``prompt``-token prompt and
    produce ``new`` tokens: every prompt position and every generated token
    but the last passes through the 27 layers once, attention reads the
    positions before it (scores over nope + rope, values over v), and the
    output head runs once for each produced token.  The held experts count
    at their expected share under uniform routing (6 x 8 / 64 pairs a
    token).  A multiply-add counts 2."""
    d = dims(cfg)
    mla, dense, moe = _per_position(d)
    positions = prompt + max(new - 1, 0)
    keys = positions * (positions + 1) // 2
    attn = 2 * d["H"] * (d["nope"] + d["rope"] + d["v"]) * keys
    layers = d["dense"] * (mla + dense) + (d["L"] - d["dense"]) * (mla + moe)
    return float(layers * positions + d["L"] * attn
                 + 2 * d["D"] * d["V"] * new)


def decode_step_bytes(cfg: dict, batch: int, length: int) -> float:
    """Bytes a decode step must read from HBM, at least: every weight held
    here once (bf16; the correction bias f32) but the token embedding, of
    which only the batch's rows, and the latent cache (``c_kv`` and the
    RoPE key, bf16) of every layer up to ``length`` positions a row, this
    token's included."""
    d = dims(cfg)
    D, H = d["D"], d["H"]
    mla = (D * H * (d["nope"] + d["rope"]) + D * (d["r"] + d["rope"]) + d["r"]
           + d["r"] * H * (d["nope"] + d["v"]) + H * d["v"] * D + 2 * D)
    dense = 3 * D * d["F"]
    moe = D * d["E"] + 3 * D * d["shared"] + d["held"] * 3 * D * d["Fe"]
    weights = 2 * (d["dense"] * (mla + dense)
                   + (d["L"] - d["dense"]) * (mla + moe)
                   + D * d["V"] + D + batch * D)
    bias = 4 * d["E"] * (d["L"] - d["dense"])
    cache = 2 * batch * length * d["L"] * (d["r"] + d["rope"])
    return float(weights + bias + cache)

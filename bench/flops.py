"""Operations a request needs, from the configuration's shapes, and the
table of device peaks."""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peak(kind: str, what: str) -> float:
    """A published peak of one chip of ``kind`` (as JAX names it)."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return float(table[kind][what])


def mlp_hidden(cfg: dict) -> int:
    """The MLP's input projection width (both SwiGLU halves): stated, or
    ``mlp_ratio`` times the model width where the configuration leaves it
    null."""
    return cfg["mlp_hidden_size"] or cfg["mlp_ratio"] * cfg["d_model"]


def dense_request_flops(cfg: dict, prompt: int, new: int) -> float:
    """Forward FLOPs of a dense decoder to read a ``prompt``-token prompt
    and produce ``new`` tokens: every prompt position and every
    generated token but the last passes through the blocks once, causal
    attention reads the positions before it, and the output head runs
    once for each produced token.  A multiply-add counts 2."""
    D, L = cfg["d_model"], cfg["n_layers"]
    F = mlp_hidden(cfg) // 2
    V = cfg["embedding_size"]
    matmul = 2 * (4 * D * D + 3 * D * F)       # q, k, v, o; gate, up, down
    positions = prompt + max(new - 1, 0)
    # sum over positions t of 4 * D * (t + 1): QK^T and PV over t+1 keys
    attn = 4 * D * positions * (positions + 1) // 2
    return float(L * (matmul * positions + attn) + 2 * D * V * new)


def mfu(run: dict) -> float | None:
    """The forward FLOPs that the window's completed requests needed, over
    the window, as a share (%) of the chips' bf16 peak."""
    flops = sum(u["flops"] for u in run["units"])
    if run["window_s"] <= 0 or not flops:
        return None
    dev = run["device"]
    rate = peak(dev["kind"], "bf16_flops_per_s") * dev["count"]
    return 100.0 * flops / run["window_s"] / rate

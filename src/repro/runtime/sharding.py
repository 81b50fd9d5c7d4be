"""Sharding rules: logical param/activation axes → PartitionSpecs.

The production mesh is fixed — ``(data, model)`` in-pod, ``(pod, data,
model)`` across pods — and ten very different architectures must lower on
it.  Rules are therefore *adaptive*: each rule states a preference list of
mesh axes per tensor dimension, and :func:`safe_spec` keeps an axis only if
it divides the dimension (and is not already used), falling back to
replication otherwise.  This is what lets smollm's 9 heads, DeepSeek's 256
experts and Command-R's 256k vocab share one code path.

Layout summary (train):
  * 2-D weight sharding: FSDP over ``data`` on one dim + Megatron TP over
    ``model`` on the other (column-parallel in-proj, row-parallel out-proj).
  * experts: EP over ``model`` on the expert dim + FSDP over ``data``.
  * activations: batch over (``pod``, ``data``); MoE/FFN internals over
    ``model``; gradients psum over (``pod``, ``data``) automatically.
Serve:
  * weights TP-only when a model-shard fits HBM, 2-D otherwise
    (:func:`serve_weight_policy`); KV caches shard over batch + heads (or
    sequence when head count doesn't divide the axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# v5e hardware constants (also used by the roofline)
HBM_BYTES_PER_CHIP = 16 * 2**30
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW_PER_LINK = 50e9


import contextvars

#: when set (by the launcher) to the data-parallel axis names, model code
#: applies sequence-parallel activation constraints (§Perf B3): residual
#: activations shard (batch→dp, seq→model) between blocks, so GSPMD turns
#: each TP all-reduce into reduce-scatter + all-gather (≈half wire bytes).
_SP_AXES: contextvars.ContextVar = contextvars.ContextVar(
    "sp_axes", default=None)


def sequence_parallel_axes():
    return _SP_AXES.get()


class sequence_parallel:
    """Context manager enabling SP constraints during tracing/lowering."""

    def __init__(self, dp_axes=("data",), tp_axis="model"):
        self.value = (tuple(dp_axes), tp_axis)

    def __enter__(self):
        self._token = _SP_AXES.set(self.value)
        return self

    def __exit__(self, *exc):
        _SP_AXES.reset(self._token)
        return False


def sp_constrain(x):
    """Apply the sequence-parallel residual constraint if enabled."""
    axes = _SP_AXES.get()
    if axes is None or x.ndim != 3:
        return x
    dp_axes, tp = axes
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    return jax.lax.with_sharding_constraint(
        x, P(dp, tp, None))


def axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def safe_spec(mesh: Mesh, shape: Sequence[int],
              prefs: Sequence[Any]) -> P:
    """Build a PartitionSpec keeping only divisible, unused axes.

    ``prefs[i]`` is an axis name, a tuple of axis names, a list of
    *candidate* axes (first that fits wins), or None.
    """
    used: set[str] = set()
    out: list[Any] = []
    for dim, pref in zip(shape, list(prefs) + [None] * (len(shape)
                                                        - len(prefs))):
        cands = pref if isinstance(pref, list) else [pref]
        chosen = None
        for cand in cands:
            if cand is None:
                continue
            names = cand if isinstance(cand, tuple) else (cand,)
            if any(n in used for n in names):
                continue
            if all(n in mesh.shape for n in names) and dim % axis_size(
                    mesh, cand) == 0 and axis_size(mesh, cand) > 1:
                chosen = cand
                used.update(names)
                break
        out.append(chosen)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Logical roles of the physical mesh axes."""
    dp: Any = ("data",)          # batch / FSDP axes (may include "pod")
    tp: str = "model"            # tensor/expert-parallel axis

    @property
    def dp_spec(self):
        return tuple(self.dp) if len(self.dp) > 1 else self.dp[0]


def mesh_axes_for(mesh: Mesh) -> MeshAxes:
    if "pod" in mesh.shape:
        return MeshAxes(dp=("pod", "data"), tp="model")
    return MeshAxes(dp=("data",), tp="model")


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# name-keyed rules: map the LAST path component to (dim prefs), where
# "IN" = FSDP axis (data), "OUT" = TP axis (model).  Stacked segment params
# get a leading None (the scan/repeats dim) automatically.
_COL = ("IN", "OUT")     # column-parallel: (d_in, d_out·TP)
_ROW = ("OUT", "IN")     # row-parallel:    (d_in·TP, d_out)

_PARAM_RULES: dict[str, tuple] = {
    # embeddings: vocab over TP, features over FSDP
    "table": ("OUT", "IN"),
    # attention
    "w_q": _COL, "w_k": _COL, "w_v": _COL, "w_o": _ROW,
    "b_q": ("OUT",), "b_k": ("OUT",), "b_v": ("OUT",),
    # MLA
    "w_dq": _COL, "w_uq": _COL, "w_dkv": _COL, "w_ukv": _COL,
    # MLP
    "w_up": _COL, "w_gate": _COL, "w_down": _ROW,
    # MoE (leading expert dim handled by shape: 3-D tensors)
    "router": ("IN", None),
    # Mamba
    "w_in": _COL, "w_x": _COL, "w_dt": ("IN", "OUT"), "w_out": _ROW,
    "conv_w": (None, "OUT"), "conv_b": ("OUT",),
    "A_log": ("OUT", None), "D": ("OUT",), "dt_bias": ("OUT",),
    # RWKV
    "w_r": _COL, "w_g": _COL, "decay_A": _COL, "decay_B": _ROW,
    "decay_w0": ("OUT",), "bonus_u": (None, None),
    "mu_r": (), "mu_k": (), "mu_v": (), "mu_w": (), "mu_g": (),
    # misc
    "proj": _COL,
    "scale": (), "bias": (),
}


def _resolve(pref, axes: MeshAxes):
    if pref == "IN":
        return [axes.dp_spec, None]
    if pref == "OUT":
        return [axes.tp, None]
    return [pref]


def param_pspec(mesh: Mesh, path: tuple, leaf: Any,
                axes: MeshAxes | None = None) -> P:
    """PartitionSpec for one parameter leaf given its tree path."""
    axes = axes or mesh_axes_for(mesh)
    names = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
    last = names[-1] if names else ""
    shape = tuple(leaf.shape)
    stacked = any(n.startswith("segment_") for n in names)

    rule = _PARAM_RULES.get(last)
    if rule is None:
        return P()  # replicate unknowns (safe default)

    shape_core = shape[1:] if stacked else shape
    # MoE expert tensors: 3-D (E, in, out) — expert-parallel on dim 0
    if len(shape_core) == 3 and last in ("w_gate", "w_up", "w_down"):
        prefs = [[axes.tp, None], [axes.dp_spec, None], [None]]
    else:
        prefs = [_resolve(p, axes) for p in rule[:len(shape_core)]]
    spec = safe_spec(mesh, shape_core, prefs)
    if stacked:
        spec = P(None, *spec)
    return spec


def params_shardings(mesh: Mesh, params: Any,
                     axes: MeshAxes | None = None) -> Any:
    axes = axes or mesh_axes_for(mesh)
    flat = jax.tree_util.tree_flatten_with_path(params)
    leaves, treedef = flat
    out = [NamedSharding(mesh, param_pspec(mesh, path, leaf, axes))
           for path, leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Batch / cache rules
# ---------------------------------------------------------------------------

def batch_pspec(mesh: Mesh, shape: Sequence[int],
                axes: MeshAxes | None = None) -> P:
    """Token batches: batch dim over (pod, data); seq dim over model if the
    batch doesn't shard (long-context, batch=1)."""
    axes = axes or mesh_axes_for(mesh)
    ndim = len(shape)
    if ndim == 0:
        return P()
    prefs: list = [[axes.dp_spec, axes.dp[-1], None]]
    if ndim >= 2:
        prefs.append([None])
    return safe_spec(mesh, shape, prefs)


def cache_pspec(mesh: Mesh, path: tuple, leaf: Any,
                axes: MeshAxes | None = None) -> P:
    """KV/state caches.  Dim heuristics by tensor rank and name."""
    axes = axes or mesh_axes_for(mesh)
    names = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
    last = names[-1] if names else ""
    shape = tuple(leaf.shape)
    stacked = any(n.startswith("segment_") for n in names)
    core = shape[1:] if stacked else shape
    dp = [axes.dp_spec, axes.dp[-1], None]

    if last in ("k", "v") and len(core) == 4:        # (B, Hkv, S, hd)
        prefs = [dp, [axes.tp, None], [axes.tp, None], [None]]
    elif last == "c_kv" and len(core) == 3:          # (B, S, r)
        prefs = [dp, [axes.tp, None], [None]]
    elif last == "k_pe" and len(core) == 3:          # (B, rope, S)
        prefs = [dp, [None], [axes.tp, None]]
    elif last == "h" and len(core) == 3:             # (B, dI, N)
        prefs = [dp, [axes.tp, None], [None]]
    elif last == "conv" and len(core) == 3:          # (B, K-1, dI)
        prefs = [dp, [None], [axes.tp, None]]
    elif last == "S" and len(core) == 4:             # (B, H, hd, hd)
        prefs = [dp, [axes.tp, None], [None], [None]]
    else:
        prefs = [dp] + [[None]] * (len(core) - 1)
    spec = safe_spec(mesh, core, prefs)
    if stacked:
        spec = P(None, *spec)
    return spec


def tree_shardings(mesh: Mesh, tree: Any, spec_fn) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = [NamedSharding(mesh, spec_fn(mesh, path, leaf))
           for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Serving weight policy
# ---------------------------------------------------------------------------

def serve_weight_policy(param_bytes: int, mesh: Mesh,
                        *, budget_frac: float = 0.5) -> str:
    """"tp" when one TP shard of the weights fits comfortably in HBM
    (no per-step weight gathering at decode), else "2d" (FSDP+TP)."""
    tp = mesh.shape.get("model", 1)
    if param_bytes / tp <= budget_frac * HBM_BYTES_PER_CHIP:
        return "tp"
    return "2d"


def params_shardings_serve(mesh: Mesh, params: Any, param_bytes: int,
                           *, ep_serve: bool = False) -> Any:
    """Serving layouts.

    * ``tp``  — weights sharded over ``model`` only (small models): no
      per-step weight movement.
    * ``2d``  — FSDP+TP (big models): fits, but gathers weights each step.
    * ``ep_serve`` (§Perf) — expert tensors sharded over ALL chips
      (``data × model`` on the expert dim): weights stay resident and only
      token activations cross the wire — the paper's "customize the memory
      interface per region" applied to expert weights.
    """
    policy = serve_weight_policy(param_bytes, mesh)
    axes = mesh_axes_for(mesh)
    tp_axes = MeshAxes(dp=("_none_",), tp=axes.tp)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", getattr(p, "name", p)))
                 for p in path]
        last = names[-1] if names else ""
        stacked = any(n.startswith("segment_") for n in names)
        is_expert = (last in ("w_gate", "w_up", "w_down")
                     and leaf.ndim - (1 if stacked else 0) == 3)
        if ep_serve and is_expert:
            all_axes = tuple(a for a in ("pod", "data", "model")
                             if a in mesh.shape)
            core = leaf.shape[1:] if stacked else leaf.shape
            spec = safe_spec(mesh, core,
                             [[all_axes, axes.tp], [None], [None]])
            if stacked:
                spec = P(None, *spec)
            out.append(NamedSharding(mesh, spec))
            continue
        if policy == "2d" and not (ep_serve and is_expert):
            spec = param_pspec(mesh, path, leaf, axes)
        else:
            spec = param_pspec(mesh, path, leaf, tp_axes)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)

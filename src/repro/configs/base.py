"""Config system: one frozen dataclass per architecture, explicit segments.

A model is a stack of *segments*; each segment is a repeating unit of
layer specs scanned ``repeats`` times (keeps the HLO small and compile
times bounded for 61–72 layer models).  ``LayerSpec`` picks the sequence
mixer (attn / mla / mamba / rwkv) and the MLP kind (dense / moe /
rwkv_cmix) per layer — this is how Jamba's 1:7 interleave, DeepSeek's
first-3-dense and uniform dense archs are all expressed in one model
builder.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Literal

import jax.numpy as jnp

Mixer = Literal["attn", "mla", "mamba", "rwkv"]
MLPKind = Literal["dense", "moe", "rwkv_cmix"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: Mixer = "attn"
    mlp: MLPKind = "dense"


@dataclasses.dataclass(frozen=True)
class Segment:
    unit: tuple[LayerSpec, ...]
    repeats: int

    @property
    def num_layers(self) -> int:
        return len(self.unit) * self.repeats


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff: int
    num_shared: int = 0
    capacity_factor: float = 1.25
    router_fn: str = "softmax"        # "softmax" | "sigmoid" (DeepSeek-V3)
    normalize_weights: bool = True
    #: §Perf knob: dispatch payload dtype ("bf16" | "int8") — int8 halves
    #: the expert-parallel all-to-all wire bytes
    dispatch_dtype: str = "bf16"
    #: §Perf knob: DeepSeek-style device-limited routing — restrict each
    #: token's experts to the top ``route_device_limit`` expert groups
    #: (groups = EP devices), bounding all-to-all fan-out.  0 = unlimited.
    route_groups: int = 0
    route_device_limit: int = 0
    #: the chip's share under expert parallelism: this layer holds the
    #: routed experts ``first_held .. first_held + held_experts - 1`` of
    #: ``num_experts`` (0 = all of them).  The router still scores all
    #: ``num_experts``; pairs routed to experts held elsewhere add nothing
    #: here.
    held_experts: int = 0
    first_held: int = 0
    #: DeepSeek-V3's selection-only correction bias (``noaux_tc``): a
    #: per-expert bias added to the scores for the top-k choice only
    score_bias: bool = False
    #: factor on the combine weights after their normalisation
    #: (DeepSeek-V3's ``routed_scaling_factor``)
    routed_scale: float = 1.0

    @property
    def held(self) -> int:
        """Routed experts whose weights this layer holds."""
        return self.held_experts or self.num_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    #: 0: no query LoRA, a direct query projection (DeepSeek-V2-Lite,
    #: Moonlight)
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_inner: int = 0
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0
    scan_impl: str = "sequential"     # "sequential" | "chunked"
    chunk: int = 16


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 → d_model // num_heads
    segments: tuple[Segment, ...] = ()
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    attention: str = "gqa"            # "gqa" | "mla"
    attn_impl: str = "auto"           # "auto" | "full" | "chunked" | "pallas"
    qkv_bias: bool = False
    norm: str = "rmsnorm"
    norm_eps: float | None = None     # None: the norm's own default
    act: str = "silu"
    rope_theta: float = 1e4
    parallel_block: bool = False      # Cohere-style attn ∥ mlp
    tie_embeddings: bool = False
    frontend_stub: bool = False       # audio/vlm: inputs are embeddings
    rwkv_heads: int = 0
    rwkv_decay_lora: int = 64
    dtype: str = "bfloat16"
    mtp_depth: int = 0                # DeepSeek multi-token-prediction heads
    source: str = ""                  # citation tag
    # ---- §Perf hillclimb knobs (see EXPERIMENTS.md) -----------------------
    mla_absorbed: bool = False        # absorbed MLA decode (latent-space)
    kv_cache_dtype: str = "bf16"      # "bf16" | "int8" quantized KV cache
    remat: bool = False               # activation checkpointing per layer

    # ---- derived -----------------------------------------------------------

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.num_heads)
        if not self.segments:
            object.__setattr__(
                self, "segments",
                (Segment(unit=(LayerSpec(),), repeats=self.num_layers),))
        total = sum(s.num_layers for s in self.segments)
        assert total == self.num_layers, (
            f"{self.name}: segments cover {total} != {self.num_layers}")

    @property
    def np_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def subquadratic(self) -> bool:
        """True if decode state does not grow with context (SSM/hybrid)."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (for 6·N·D roofline math)."""
        return self._param_count_exact()

    def _param_count_exact(self) -> int:
        d = self.d_model
        n = self.vocab_size * d
        if not self.tie_embeddings:
            n += self.vocab_size * d

        def layer_params(spec: LayerSpec) -> int:
            p = 0
            if spec.mixer == "attn":
                p += d * self.num_heads * self.head_dim
                p += 2 * d * self.num_kv_heads * self.head_dim
                p += self.num_heads * self.head_dim * d
            elif spec.mixer == "mla":
                m = self.mla
                qk = m.qk_nope_head_dim + m.qk_rope_head_dim
                p += (d * m.q_lora_rank + m.q_lora_rank * self.num_heads * qk
                      if m.q_lora_rank else d * self.num_heads * qk)
                p += d * (m.kv_lora_rank + m.qk_rope_head_dim)
                p += m.kv_lora_rank * self.num_heads * (
                    m.qk_nope_head_dim + m.v_head_dim)
                p += self.num_heads * m.v_head_dim * d
            elif spec.mixer == "mamba":
                s = self.ssm
                p += d * 2 * s.d_inner
                p += s.d_inner * (s.dt_rank + 2 * s.d_state)
                p += s.dt_rank * s.d_inner + s.d_inner * d
            elif spec.mixer == "rwkv":
                p += 5 * d * d + 2 * d * self.rwkv_decay_lora
            if spec.mlp == "dense":
                p += (3 if self.act == "silu" else 2) * d * self.d_ff
            elif spec.mlp == "moe":
                m = self.moe
                p += d * m.num_experts + (m.num_experts if m.score_bias
                                          else 0)
                p += m.held * 3 * d * m.d_ff
                p += m.num_shared * 3 * d * m.d_ff
            elif spec.mlp == "rwkv_cmix":
                p += 2 * d * int(3.5 * d) + d * d
            return p

        for seg in self.segments:
            n += seg.repeats * sum(layer_params(s) for s in seg.unit)
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k + shared experts)."""
        if self.moe is None:
            return self._param_count_exact()
        d = self.d_model
        m = self.moe
        full_expert = m.held * 3 * d * m.d_ff
        active_expert = min(m.top_k, m.held) * 3 * d * m.d_ff
        n_moe_layers = sum(
            seg.repeats * sum(1 for s in seg.unit if s.mlp == "moe")
            for seg in self.segments)
        return (self._param_count_exact()
                - n_moe_layers * (full_expert - active_expert))


# ---------------------------------------------------------------------------
# Input shapes (assigned to every LM arch)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str           # "train" | "prefill" | "decode"


SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


ARCH_IDS = [
    "jamba-1.5-large-398b",
    "qwen2.5-14b",
    "olmo-1b",
    "smollm-135m",
    "command-r-plus-104b",
    "rwkv6-1.6b",
    "deepseek-v3-671b",
    "llama4-scout-17b-a16e",
    "musicgen-large",
    "chameleon-34b",
    "moonlight-16b-a3b",
]


def load_config(arch: str) -> ModelConfig:
    """Load ``src/repro/configs/<arch>.py``'s CONFIG (dashes → underscores)."""
    mod = importlib.import_module(
        f"repro.configs.{arch.replace('-', '_').replace('.', '_')}")
    return mod.CONFIG


def cell_is_applicable(cfg: ModelConfig, shape: InputShape) -> bool:
    """long_500k requires sub-quadratic decode state (SSM/hybrid)."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False
    return True


def reduced(cfg: ModelConfig, *, d_model: int = 64,
            max_repeats: int = 2) -> ModelConfig:
    """Shrink a config for CPU smoke tests, preserving the layer structure
    (same segment/unit pattern, same mixer/MLP kinds, fewer repeats and
    tiny widths).  The FULL configs are exercised only via the dry-run."""
    heads = 4
    kv = max(1, heads * cfg.num_kv_heads // cfg.num_heads)
    if cfg.num_kv_heads == cfg.num_heads:
        kv = heads
    new_segments = tuple(
        dataclasses.replace(s, repeats=min(s.repeats, max_repeats))
        for s in cfg.segments)
    num_layers = sum(s.num_layers for s in new_segments)
    changes: dict = dict(
        num_layers=num_layers,
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=d_model // heads,
        d_ff=2 * d_model,
        vocab_size=256,
        segments=new_segments,
        dtype="float32",
        attn_impl="full",
    )
    if cfg.moe is not None:
        changes["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, top_k=min(cfg.moe.top_k, 2),
            d_ff=2 * d_model,
            held_experts=2 if cfg.moe.held_experts else 0, first_held=0)
    if cfg.mla is not None:
        changes["mla"] = MLAConfig(
            q_lora_rank=32 if cfg.mla.q_lora_rank else 0,
            kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16)
        changes["head_dim"] = 16
    if cfg.ssm is not None:
        changes["ssm"] = dataclasses.replace(
            cfg.ssm, d_inner=2 * d_model, d_state=8, dt_rank=8)
    if cfg.rwkv_heads:
        changes["rwkv_heads"] = heads
        changes["num_heads"] = heads
        changes["num_kv_heads"] = heads
        changes["rwkv_decay_lora"] = 16
    return dataclasses.replace(cfg, **changes)

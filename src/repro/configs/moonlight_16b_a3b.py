"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, DeepSeek-V3 block].

MLA with no query LoRA (kv_lora 512, nope/rope/v 128/64/128), 27 layers:
the first dense (d_ff 11264), then 26 MoE layers of 64 routed experts
(d_ff 1408, top-6) and 2 shared experts; sigmoid router with the
selection-only correction bias and routed scale 2.446; RMSNorm eps 1e-5;
RoPE theta 5e4; untied 163,840-token vocabulary.

As served here it is one chip's share of an 8-way expert-parallel
deployment with data-parallel attention (DeepSeek-V3's decode layout,
arXiv:2412.19437 §3.4): each MoE layer holds routed experts 0-7 of 64,
and attention, the dense layer, the shared experts, the embedding and the
head are whole.  The router keeps its 64 outputs and top-6.
"""

from .base import LayerSpec, MLAConfig, ModelConfig, MoEConfig, Segment

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=11264,            # the dense first layer
    vocab_size=163840,
    attention="mla",
    segments=(
        Segment(unit=(LayerSpec(mixer="mla", mlp="dense"),), repeats=1),
        Segment(unit=(LayerSpec(mixer="mla", mlp="moe"),), repeats=26),
    ),
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff=1408, num_shared=2,
                  router_fn="sigmoid", normalize_weights=True,
                  held_experts=8, first_held=0, score_bias=True,
                  routed_scale=2.446),
    norm_eps=1e-5,
    rope_theta=5e4,
    mla_absorbed=True,
    source="hf:moonshotai/Moonlight-16B-A3B; arXiv:2412.19437",
)

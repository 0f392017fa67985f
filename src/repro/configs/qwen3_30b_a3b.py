"""qwen3-30b-a3b — 128-expert top-8 MoE with GQA and qk_norm.

[hf:Qwen/Qwen3-30B-A3B; hf] 48L d_model=2048 32H (GQA kv=4) head_dim=128,
every layer sparse: 128 experts of width 768, top-8 renormalised, no
shared expert; untied vocab=151936 head. ``d_ff`` is the published
``intermediate_size``, which no layer uses (``mlp_only_layers`` is
empty). The Thinker of Qwen3-Omni-30B-A3B is this decoder.
"""
from repro.configs.registry import MoEConfig, ModelConfig, register

CONFIG = register(ModelConfig(
    name="qwen3-30b-a3b",
    family="moe",
    num_layers=48,
    d_model=2048,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=6144,
    vocab_size=151936,
    mlp_kind="swiglu",
    qk_norm=True,
    rope_theta=1_000_000.0,
    tie_embeddings=False,
    moe=MoEConfig(num_experts=128, top_k=8, num_shared_experts=0,
                  d_ff_expert=768),
    source="hf:Qwen/Qwen3-30B-A3B",
))

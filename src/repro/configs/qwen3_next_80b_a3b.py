"""qwen3-next-80b-a3b [gdn] — Gated DeltaNet layers with gated full
attention every 4th layer; 512 experts top-10 and a gated shared expert in
every layer [hf:Qwen/Qwen3-Next-80B-A3B-Instruct config.json].

Left out: the multi-token-prediction head (not on a non-speculative
serving path).  The layout of the fused projections is the program's own.
"""

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-next-80b-a3b",
    family="gdn",
    n_layers=48,
    d_model=2048,
    n_heads=16,
    n_kv_heads=2,
    head_dim=256,
    d_ff=0,                  # every layer is an expert layer
    vocab_size=151936,
    qk_norm=True,
    rope_theta=1e7,
    partial_rotary_factor=0.25,
    attn_output_gate=True,
    norm_zero_centred=True,
    n_experts=512,
    top_k=10,
    d_expert=512,
    shared_expert=True,      # shared_expert_intermediate_size 512 = d_expert
    shared_expert_gate=True,
    full_attn_every=4,
    lin_k_heads=16,
    lin_v_heads=32,
    lin_head_dim=128,
    conv_width=4,
)

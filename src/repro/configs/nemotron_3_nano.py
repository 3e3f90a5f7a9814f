"""nemotron-3-nano-30b-a3b [hybrid] — Nemotron-H: 52 single-mixer layers
(23 Mamba2, 23 MoE, 6 attention) read from ``hybrid_override_pattern``;
Mamba2 of 64 heads of 64 with 8 groups of state 128; a sigmoid-routed
MoE of 128 relu^2 experts (top 6, normalised, scaled 2.5) and one shared
relu^2 expert of 3712; GQA 32:2 of 128 without rotary positions
[hf: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json]."""
from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="nemotron-3-nano-30b-a3b", family="hybrid", block_pattern="nemotron_h",
    layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    n_layers=52, d_model=2688, n_heads=32, n_kv_heads=2, d_head=128,
    d_ff=1856, vocab=131072, rope=False, rope_theta=1e4,
    n_experts=128, moe_top_k=6, moe_d_ff=1856, n_shared_experts=1,
    moe_shared_d_ff=3712, moe_routed_scale=2.5,
    ssm_n_heads=64, ssm_headdim=64, ssm_state=128, ssm_groups=8,
    ssm_conv=4, ssm_chunk=128, ssm_norm_eps=1e-5, norm_eps=1e-5,
    source="https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
))

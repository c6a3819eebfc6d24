"""deepseek-v2-lite-16b [moe] — MLA (kv_lora=512, decoupled RoPE 64,
RMSNorm on the latent, YaRN ×40 over 4096 positions) + 64 routed experts
top-6 (softmax router, greedy, no renormalisation, per-sequence aux loss
at 0.001) + 2 shared experts, first layer dense.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]

Assigned: 27L d_model=2048 16H (kv=16) expert d_ff=1408 vocab=102400.
MLA latent decode cache -> runs long_500k natively (DESIGN.md §3.4).
"""
from repro.models.common import ModelSpec, YarnScaling

SPEC = ModelSpec(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,                 # routed-expert FF width
    vocab_size=102400,
    mlp_type="swiglu",
    rope_theta=10000.0,
    rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    attention_type="mla",
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    dense_d_ff=10944,
    norm_topk_prob=False,
    router_aux="seq",
    router_aux_weight=0.001,
)

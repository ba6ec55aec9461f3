"""deepseek-v2-lite [moe] — MLA kv_lora=512, no query low-rank; 2 shared +
64 routed experts, top-6, softmax scores left unnormalised; YaRN rope.
[hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]

27L d_model=2048 16H; layer 0 dense (intermediate 10944), layers 1-26 MoE
(moe_intermediate 1408); vocab=102400.  MLA: qk_nope=128, qk_rope=64,
v_head=128, so the softmax scale is 192^-0.5 * mscale^2 with YaRN's
mscale = 0.1 * 0.707 * ln(40) + 1.  rope_scaling: yarn, factor 40 over an
original 4096 positions, beta_fast 32, beta_slow 1, mscale =
mscale_all_dim = 0.707.  ``n_group``/``topk_group`` are 1 (greedy top-k
over all 64 experts), as the stack routes.

The routed experts are dropless (``capacity_factor`` 0), as the published
model computes them: every token reaches its six experts.
"""
from repro.configs.base import ModelConfig, RopeScaling

YARN = RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    attention="mla",
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    n_experts=64,
    n_shared_experts=2,
    experts_per_token=6,
    d_expert=1408,
    first_k_dense=1,
    capacity_factor=0.0,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    rope_theta=10_000.0,
    rope_scaling=YARN,
    norm_eps=1e-6,
    mlp_act="silu",
    citation="hf:deepseek-ai/DeepSeek-V2-Lite",
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    arch_type="moe",
    n_layers=3,
    d_model=128,
    n_heads=4,
    n_kv_heads=4,
    d_ff=256,
    vocab_size=256,
    attention="mla",
    q_lora_rank=0,
    kv_lora_rank=32,
    qk_nope_dim=16,
    qk_rope_dim=8,
    v_head_dim=16,
    n_experts=4,
    n_shared_experts=2,
    experts_per_token=2,
    d_expert=32,
    first_k_dense=1,
    capacity_factor=0.0,
    norm_topk_prob=False,
    rope_scaling=RopeScaling(factor=40.0, original_max_position_embeddings=16,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    mlp_act="silu",
)

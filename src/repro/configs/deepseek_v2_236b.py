"""deepseek-v2-236b [moe] — MLA kv_lora=512; 2 shared + 160 routed top-6.
[arXiv:2405.04434]

60L d_model=5120 128H (kv=128) vocab=102400; layer 0 dense with
intermediate 12288 (first_k_dense=1), the MoE layers' experts 1536 wide.
MLA: q_lora=1536, kv_lora=512, qk_nope=128, qk_rope=64, v_head=128; YaRN
rope (factor 40 over 4096 positions, mscale = mscale_all_dim = 0.707).
Top-6 softmax weights unnormalised, scaled by routed_scaling_factor 16.

Departure: the published model routes group-limited (``n_group`` 8,
``topk_group`` 3: the top-6 are taken from the 3 best of 8 expert groups);
the stack takes a greedy top-6 over all 160 experts.
"""
from repro.configs.base import ModelConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    arch_type="moe",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,
    d_ff=12288,
    vocab_size=102400,
    attention="mla",
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    n_experts=160,
    n_shared_experts=2,
    experts_per_token=6,
    d_expert=1536,
    first_k_dense=1,
    norm_topk_prob=False,
    routed_scaling_factor=16.0,
    rope_theta=10_000.0,
    rope_scaling=RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    mlp_act="silu",
    citation="arXiv:2405.04434",
)

SMOKE = ModelConfig(
    name="deepseek-v2-smoke",
    arch_type="moe",
    n_layers=2,
    d_model=256,
    n_heads=8,
    n_kv_heads=8,
    d_ff=256,
    vocab_size=512,
    attention="mla",
    q_lora_rank=96,
    kv_lora_rank=64,
    qk_nope_dim=32,
    qk_rope_dim=16,
    v_head_dim=32,
    n_experts=4,
    n_shared_experts=1,
    experts_per_token=2,
    d_expert=128,
    first_k_dense=1,
    mlp_act="silu",
)

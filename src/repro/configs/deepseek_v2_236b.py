"""deepseek-v2-236b [moe] — MLA kv_lora=512, 2 shared + 160 routed top-6
(arXiv:2405.04434). 60L d_model=5120 128H, per-expert d_ff=1536, vocab=102400.
Routing as published: softmax over 160 experts, group-limited greedy (top 3
of 8 groups), top-6 gates x16 with no renormalisation; YaRN rope (factor 40
over an original 4,096 positions, mscale_all_dim 0.707)."""
import dataclasses
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-236b", family="moe",
    n_layers=60, d_model=5120, n_heads=128, n_kv_heads=128,
    d_ff=1536, vocab=102400,
    n_experts=160, n_shared_experts=2, moe_top_k=6, moe_d_ff=1536,
    first_dense_layers=1, dense_d_ff=12288,
    moe_renorm=False, moe_scale=16.0, moe_groups=8, moe_topk_groups=3,
    kv_lora=512, q_lora=1536, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    yarn_factor=40.0, yarn_original_max=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
)

def smoke() -> ArchConfig:
    return dataclasses.replace(
        CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=32, n_experts=8, n_shared_experts=1, moe_top_k=2, moe_d_ff=32,
        first_dense_layers=1, dense_d_ff=128, vocab=256, moe_capacity_factor=2.0,
        moe_groups=4, moe_topk_groups=2,
        kv_lora=32, q_lora=48, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)

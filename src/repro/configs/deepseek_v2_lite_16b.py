"""deepseek-v2-lite-16b: 27L MLA, one leading dense layer then 26 MoE
layers (64 routed experts top-6, 2 shared), YaRN rope scaling.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]
MLA: kv_lora_rank=512, rope_dim=64, nope=128, v=128, no q compression;
first_k_dense_replace=1 (d_ff 10944); moe_intermediate_size=1408;
softmax scoring with norm_topk_prob=false; rope_scaling yarn (factor 40
over 4096 positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim
= 0.707).  The paper-technique router (soft top-k via permutahedron
projection) is the DEFAULT here; `--router softmax_topk` restores the
standard baseline.
"""
from repro.configs.base import ArchConfig, Yarn, register

register(ArchConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,           # qk_nope + qk_rope
    d_ff=10944,
    vocab_size=102400,
    block_cycle=("mla_moe",),
    first_dense_layers=1,
    num_experts=64,
    experts_per_token=6,
    num_shared_experts=2,
    moe_d_ff=1408,
    router="soft_topk",
    router_eps=1.0,
    norm_topk_prob=False,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    mlp_variant="swiglu",
    rope_theta=10_000.0,
    rope_yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    fsdp=True,
    seq_shard_activations=True,
    remat="full",
    grad_accum=8,
))

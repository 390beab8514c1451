"""deepseek-v2-lite-16b [moe] — DeepSeek-V2-Lite as published
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434].

27 layers, hidden 2048, 16 heads. MLA without q-LoRA: kv_lora_rank 512,
qk_nope_head_dim 128 (``head_dim``), qk_rope_head_dim 64 (``rope_head_dim``),
v_head_dim 128. Layer 0 is dense (``first_k_dense_replace`` 1, SwiGLU of
``intermediate_size`` 10944); layers 1–26 are MoE: 64 routed experts of
width 1408 (``moe_intermediate_size``, here ``d_ff``), top-6 greedy over a
softmax, ``norm_topk_prob`` false, ``routed_scaling_factor`` 1, plus 2 shared
experts. YaRN rope: factor 40 over 4096 original positions, beta_fast 32,
beta_slow 1, mscale = mscale_all_dim = 0.707, θ 10000. Vocabulary 102400,
RMSNorm eps 1e-6.

Departure: DeepSeek-V2 de-interleaves the rope dims of q and k before the
rotate-half rotation; that is a fixed permutation of the rope projections'
output columns, which random weights absorb, so it is not applied here.
"""

from repro.configs.base import ModelConfig

_YARN = dict(rope_scaling="yarn", rope_factor=40.0,
             original_max_position_embeddings=4096, beta_fast=32.0,
             beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=1408,
    vocab_size=102400,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    first_k_dense_replace=1,
    intermediate_size=10944,
    mla=True,
    kv_lora_rank=512,
    rope_head_dim=64,
    rope_theta=10000.0,
    **_YARN,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-16b-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    head_dim=16,
    d_ff=48,
    vocab_size=1024,
    n_experts=4,
    n_experts_held=2,
    n_shared_experts=1,
    top_k=2,
    norm_topk_prob=False,
    first_k_dense_replace=1,
    intermediate_size=96,
    mla=True,
    kv_lora_rank=16,
    rope_head_dim=8,
    embedding_rank=2,
    head_rank=2,
    **{**_YARN, "rope_factor": 4.0, "original_max_position_embeddings": 64},
)

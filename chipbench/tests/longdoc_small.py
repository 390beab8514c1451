"""Small sizes of the DeepSeek-V2 cell, for its CPU tests (the sizes of
``small.py``'s cells, in the published config's keys)."""

import small

LONGDOC = "deepseek-v2-lite-16b.serve-longdoc"


def deepseek(cell: str = LONGDOC) -> dict:
    """A dense layer and two MoE layers holding 2 of 4 routed experts."""
    d, H, Dh, R, Lr, V = 64, 4, 16, 8, 16, 1024
    program = dict(num_layers=3, d_model=d, num_heads=H, num_kv_heads=H, head_dim=Dh,
                   rope_head_dim=R, kv_lora_rank=Lr, d_ff=32, intermediate_size=96,
                   n_experts=4, n_experts_held=2, top_k=2, n_shared_experts=1,
                   vocab_size=V, embedding_rank=2, head_rank=2, rope_factor=4.0,
                   original_max_position_embeddings=64)
    emb = small._factors(d, V, 2)
    config = dict(num_hidden_layers=3, hidden_size=d, num_attention_heads=H,
                  qk_nope_head_dim=Dh, qk_rope_head_dim=R, v_head_dim=Dh,
                  kv_lora_rank=Lr, intermediate_size=96, moe_intermediate_size=32,
                  n_routed_experts=2, router_experts=4, num_experts_per_tok=2,
                  n_shared_experts=1, vocab_size=V, factors={"embed": emb, "head": emb},
                  rope_scaling={"type": "yarn", "factor": 4.0, "beta_fast": 32,
                                "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                                "original_max_position_embeddings": 64})
    serve = small.overrides("qwen3-1.7b.serve-chat", "dense")
    return {"program": program, "config": config, "traffic": serve["traffic"],
            "workload": {**serve["workload"], "limits": {"serve_gap": 0.05}}}


"""Work of a DeepSeek-V2 serving tick, from the configuration's published
keys (``configs/deepseek-v2-lite-16b.json``), whatever implements it.

Model FLOPs (a multiply-add counts 2; norms, rotary embedding, softmax and
the embedding's LayerNorm left out):

* per token advanced, every layer: the MLA projections (W_q, W_dkv, W_kpe,
  the absorption of q_nope into the latent space, the latent context's
  W_uv and W_o) ``2·(d·H·(Dn+R) + d·Lr + d·R + H·Dn·Lr + H·Lr·Dv + H·Dv·d)``;
* absorbed attention per (query, earlier-or-same position) pair, every
  layer: ``2·H·(Lr+R)`` for the scores and ``2·H·Lr`` for the context;
* per token: the leading layers' dense SwiGLU ``6·d·intermediate_size``;
  every MoE layer's router ``2·d·router_experts`` and shared experts
  ``6·d·n_shared·moe_width``;
* per row a held expert receives (the tick's ``moe_rows``, summed over
  layers): ``6·d·moe_width``;
* per token the word2ketXS column, per live slot the Kronecker head
  (``model.py``'s counts).

Bytes a tick must move: the parameters as stored (f32), read once, plus
every live slot's cached latents, ``(Lr + R)·2 B`` a layer per token.
"""

import bench

M = bench.work("model")


def _c(conf):
    return (conf["hidden_size"], conf["num_attention_heads"], conf["qk_nope_head_dim"],
            conf["qk_rope_head_dim"], conf["v_head_dim"], conf["kv_lora_rank"])


def n_layers(conf):
    """(leading dense layers, MoE layers)."""
    lead = conf["first_k_dense_replace"]
    return lead, conf["num_hidden_layers"] - lead


def mla_flops_per_token(conf) -> float:
    d, H, Dn, R, Dv, Lr = _c(conf)
    return 2 * (d * H * (Dn + R) + d * Lr + d * R + H * Dn * Lr + H * Lr * Dv + H * Dv * d)


def attention_flops(conf, ctx_pairs) -> float:
    _, H, _, R, _, Lr = _c(conf)
    return (2 * H * (Lr + R) + 2 * H * Lr) * conf["num_hidden_layers"] * ctx_pairs


def token_flops(conf) -> float:
    """Everything a token costs but attention and the routed experts."""
    d = conf["hidden_size"]
    lead, moe = n_layers(conf)
    f = conf["moe_intermediate_size"]
    return (conf["num_hidden_layers"] * mla_flops_per_token(conf)
            + lead * 6 * d * conf["intermediate_size"]
            + moe * (2 * d * conf["router_experts"] + 6 * d * conf["n_shared_experts"] * f)
            + M.embed_flops(conf))


def expert_flops(conf, rows) -> float:
    return rows * 6 * conf["hidden_size"] * conf["moe_intermediate_size"]


def param_count(conf) -> int:
    d, H, Dn, R, Dv, Lr = _c(conf)
    lead, moe = n_layers(conf)
    f, ff = conf["moe_intermediate_size"], conf["intermediate_size"]
    attn = d * H * (Dn + R) + d * Lr + d * R + Lr + Lr * H * Dn + Lr * H * Dv + H * Dv * d
    experts = (d * conf["router_experts"] + 3 * conf["n_routed_experts"] * d * f
               + 3 * d * conf["n_shared_experts"] * f)
    ket = lambda g: g["rank"] * sum(q * t for q, t in zip(g["q"], g["t"]))
    fac = conf["factors"]
    return ((lead + moe) * (attn + 2 * d) + lead * 3 * d * ff + moe * experts
            + d + ket(fac["embed"]) + ket(fac["head"]))


def tick_flops(conf, n_tokens, ctx_pairs, live, moe_rows) -> float:
    return (n_tokens * token_flops(conf) + attention_flops(conf, ctx_pairs)
            + expert_flops(conf, moe_rows) + live * M.head_flops(conf))


def tick_bytes(conf, kv_tokens, param_bytes=4, kv_bytes=2) -> float:
    per_tok = conf["num_hidden_layers"] * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"]) * kv_bytes
    return param_count(conf) * param_bytes + kv_tokens * per_tok

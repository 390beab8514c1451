"""Work of the whole model, from the configuration's sizes.

Model FLOPs per token (a multiply-add counts 2; norms, rotary embedding,
softmax and the embedding's LayerNorm left out):

* projections: dense ``2·d_in·d_out`` each; ket ``2·r·(q1·q2·t1 + t1·q2·t2)``
  each (the factor chain, see ``kron_matmul``): q, k, v, o and the three FFN
  projections of every layer;
* attention: ``4·H·Dh`` per (query, earlier-or-same position) pair: a token
  at position p (from 0) attends to p + 1 positions;
* embedding: ``r·q1·q2`` products per token (word2ketXS column);
* head: the Kronecker chain over the whole vocabulary, as ``kron_logits``.

Training counts the forward and a backward of twice the forward, nothing
recomputed. A serving tick counts the tokens it advances, their attention
over each slot's context, and the head for every live slot (the tick
computes logits for each).

Bytes a serving tick must move: the parameters as stored, read once, plus
every live slot's cached keys and values (``2·L·KVH·Dh·kv_bytes`` per
cached token), read once.
"""

import math


def _ket(f):
    (q1, q2), (t1, t2), r = f["q"], f["t"], f["rank"]
    return 2 * r * (q1 * q2 * t1 + t1 * q2 * t2)


def projection_flops(conf) -> float:
    """Per token, all layers."""
    d, H, KVH, Dh, ff = (conf["d_model"], conf["num_heads"], conf["num_kv_heads"],
                         conf["head_dim"], conf["d_ff"])
    if conf["linear_kind"] == "ket":
        f = conf["factors"]
        per = (_ket(f["wq"]) + _ket(f["wk"]) + _ket(f["wv"]) + _ket(f["wo"])
               + 2 * _ket(f["wi"]) + _ket(f["ffn_wo"]))
    else:
        per = 2 * (d * H * Dh + 2 * d * KVH * Dh + H * Dh * d + 3 * d * ff)
    return conf["num_layers"] * per


def attention_flops(conf, ctx_pairs) -> float:
    """``ctx_pairs``: Σ over tokens of the positions each attends to."""
    return 4 * conf["num_heads"] * conf["head_dim"] * conf["num_layers"] * ctx_pairs


def head_flops(conf) -> float:
    return _ket(conf["factors"]["head"])


def embed_flops(conf) -> float:
    f = conf["factors"]["embed"]
    return f["rank"] * math.prod(f["q"])


def train_flops_per_token(conf, seq_len: int) -> float:
    """Forward + backward (3x forward) per token of a causal sequence."""
    pairs = (seq_len + 1) / 2  # mean positions attended
    fwd = (projection_flops(conf) + attention_flops(conf, pairs)
           + head_flops(conf) + embed_flops(conf))
    return 3 * fwd


def param_count(conf) -> int:
    d, H, KVH, Dh, ff, L = (conf["d_model"], conf["num_heads"], conf["num_kv_heads"],
                            conf["head_dim"], conf["d_ff"], conf["num_layers"])
    f = conf["factors"]
    ket = lambda g: g["rank"] * sum(q * t for q, t in zip(g["q"], g["t"]))
    if conf["linear_kind"] == "ket":
        lin = (ket(f["wq"]) + ket(f["wk"]) + ket(f["wv"]) + ket(f["wo"])
               + 2 * ket(f["wi"]) + ket(f["ffn_wo"]))
    else:
        lin = d * H * Dh + 2 * d * KVH * Dh + H * Dh * d + 3 * d * ff
    norms = 2 * d + 2 * Dh
    return L * (lin + norms) + d + ket(f["embed"]) + ket(f["head"])


def tick_flops(conf, n_tokens, ctx_pairs, live) -> float:
    return (n_tokens * (projection_flops(conf) + embed_flops(conf))
            + attention_flops(conf, ctx_pairs) + live * head_flops(conf))


def tick_bytes(conf, kv_tokens, param_bytes=4, kv_bytes=2) -> float:
    per_tok = 2 * conf["num_layers"] * conf["num_kv_heads"] * conf["head_dim"] * kv_bytes
    return param_count(conf) * param_bytes + kv_tokens * per_tok

"""Plain float32 reference of DeepSeek-V2(-Lite) with word2ketXS embedding
and Kronecker vocab head, for the share of the experts one device holds.

Written from the published equations (DeepSeek-V2, arXiv:2405.04434, and
its ``modeling_deepseek.py``), in straightforward ``jax.numpy`` at
``Precision.HIGHEST``; it imports nothing of the program. No kernel, no
cache, no batching of requests. The configuration's keys are the published
``config.json``'s.

* embedding and head: ``references/qwen3.py``'s word2ketXS column and dense
  Σ_k A_k ⊗ B_k;
* attention (MLA, no q-LoRA): q = h W_q split into q_nope (qk_nope_head_dim)
  and q_pe (qk_rope_head_dim); c = RMSNorm(h W_dkv) (kv_lora_rank);
  k_pe = h W_kpe shared by every head; k_nope = c W_uk, v = c W_uv; rotary
  embedding (rotate-half) on q_pe and k_pe with YaRN frequencies; causal
  softmax of [q_nope, q_pe]·[k_nope, k_pe] at (nope + rope)^-1/2 ×
  yarn_get_mscale(factor, mscale_all_dim)²; out = o W_o. Attention runs in
  blocks of queries so that 16384 positions fit;
* layer 0 .. first_k_dense_replace - 1: dense SwiGLU of intermediate_size;
* MoE layers: softmax over all ``router_experts`` outputs in f32, greedy
  top-``num_experts_per_tok``; the gates are renormalised only under
  ``norm_topk_prob``, else scaled by ``routed_scaling_factor``. Only the
  ``n_routed_experts`` held experts (the first of them) are
  computed (each over every token, weighted by its gate, zero where not
  chosen); what the other experts would add is left out, as in the
  program. The shared experts (one SwiGLU of n_shared × moe width) are
  added once;
* RMSNorm eps ``rms_norm_eps``.

Departure from the published model: its rotary embedding de-interleaves
the rope dims of q_pe and k_pe first, a fixed permutation of W_q's rope
columns and of W_kpe that random weights absorb; it is not applied.

``quant="fp8"`` computes every projection, every expert and the head with
both operands rounded to float8_e4m3 under a per-tensor scale: the control
of the comparison. The router stays in float32.

The parameters are laid out as the program lays them out: the leading
dense layers in ``lead``, the MoE layers stacked on a leading axis in
``groups``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import bench

Q = bench.load_module("references", "qwen3.py")
HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # queries per attention block


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _ket(conf, key):
    f = conf["factors"][key]
    return [(f["rank"], q, t) for q, t in zip(f["q"], f["t"])]


def _layer(conf, lead: tuple, dense: bool) -> dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    Dn, R, Dv = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"]
    Lr = conf["kv_lora_rank"]
    attn = {"wq": lead + (d, H, Dn + R), "w_dkv": lead + (d, Lr),
            "w_krope": lead + (d, R), "kv_norm": {"scale": lead + (Lr,)},
            "w_uk": lead + (Lr, H, Dn), "w_uv": lead + (Lr, H, Dv),
            "wo": lead + (H, Dv, d)}
    layer = {"ln1": {"scale": lead + (d,)}, "attn": attn,
             "ln2": {"scale": lead + (d,)}}
    if dense:
        ff = conf["intermediate_size"]
        layer["ffn"] = {"wi": lead + (d, ff), "wg": lead + (d, ff), "wo": lead + (ff, d)}
    else:
        f, E = conf["moe_intermediate_size"], conf["n_routed_experts"]
        fs = conf["n_shared_experts"] * f
        layer["moe"] = {"router": lead + (d, conf["router_experts"]),
                        "wi": lead + (E, d, f), "wg": lead + (E, d, f),
                        "wo": lead + (E, f, d),
                        "shared": {"wi": lead + (d, fs), "wg": lead + (d, fs),
                                   "wo": lead + (fs, d)}}
    return layer


def shapes(conf: dict) -> dict:
    """The parameter tree's shapes, as tuples, in the program's layout."""
    n_lead = conf["first_k_dense_replace"]
    n_moe = conf["num_hidden_layers"] - n_lead
    tree = {"embed": {"factors": _ket(conf, "embed")},
            "groups": [_layer(conf, (n_moe,), dense=False)] if n_moe else [],
            "rem": [],
            "final_norm": {"scale": (conf["hidden_size"],)},
            "head": {"factors": _ket(conf, "head")}}
    if n_lead:
        tree["lead"] = [_layer(conf, (), dense=True) for _ in range(n_lead)]
    return tree


# fan-in of each weight by its name (the parent's name for an FFN's)
_FAN_IN = {"wq": "d", "w_dkv": "d", "w_krope": "d", "w_uk": "lora",
           "w_uv": "lora", "router": "d", "wi": "d", "wg": "d"}


def _std(conf, path, shape, siblings) -> float:
    if path[-1] == "scale":
        return 0.0  # norms start at one
    if "factors" in path:
        return Q._leaf_std(path, shape, siblings)
    name = path[-1]
    if name in _FAN_IN:
        fan = {"d": conf["hidden_size"], "lora": conf["kv_lora_rank"]}[_FAN_IN[name]]
    elif path[-2] == "attn":           # attention out: H × v_head_dim
        fan = conf["num_attention_heads"] * conf["v_head_dim"]
    else:                              # an FFN's or an expert's wo
        fan = shape[-2]
    return 1.0 / math.sqrt(fan)


def init(conf: dict, key) -> dict:
    """Parameters from ``key``: normal draws at 1/sqrt(fan-in) (the ket
    factors at qwen3's scale), norm scales at one; float32. Jittable."""
    count = [0]

    def build(node, path, siblings=None):
        if Q._is_shape(node):
            i = count[0]
            count[0] += 1
            std = _std(conf, path, node, siblings or [node])
            if std == 0.0:
                return jnp.ones(node, jnp.float32)
            return std * jax.random.normal(jax.random.fold_in(key, i), node, jnp.float32)
        if isinstance(node, dict):
            return {k: build(node[k], path + (k,)) for k in sorted(node)}
        if path[-1] == "factors":
            return [build(v, path, node) for v in node]
        return [build(v, path + (str(j),)) for j, v in enumerate(node)]

    return build(shapes(conf), ("root",))


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(conf, pos):
    """cos, sin (S, R/2) of DeepSeek-V2's YaRN rotary embedding."""
    R, base = conf["qk_rope_head_dim"], conf["rope_theta"]
    rs = conf.get("rope_scaling")
    i = jnp.arange(0, R, 2, dtype=jnp.float32)
    freq_extra = 1.0 / base ** (i / R)
    if not rs:
        inv_freq, m = freq_extra, 1.0
    else:
        factor, orig = rs["factor"], rs["original_max_position_embeddings"]

        def corr_dim(rot):
            return R * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
        high = min(math.ceil(corr_dim(rs["beta_slow"])), R - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(R // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
        mask = 1.0 - ramp
        inv_freq = (freq_extra / factor) * (1 - mask) + freq_extra * mask
        m = (yarn_get_mscale(factor, rs["mscale"])
             / yarn_get_mscale(factor, rs["mscale_all_dim"]))
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rotate(x, cos, sin):
    """x (S, ..., R) rotate-half; cos/sin (S, R/2)."""
    half = x.shape[-1] // 2
    shp = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shp), sin.reshape(shp)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def softmax_scale(conf) -> float:
    scale = (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5
    rs = conf.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
        scale *= m * m
    return scale


def rms(scale, x, conf):
    return Q.rmsnorm(scale, x, conf["rms_norm_eps"])


def mla(conf, a, h, cos, sin, quant):
    """h (S, d) -> attention output (S, d)."""
    S, d = h.shape
    H = conf["num_attention_heads"]
    Dn, R, Dv = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"]
    Lr = conf["kv_lora_rank"]
    q = Q.matmul(h, a["wq"].reshape(d, H * (Dn + R)), quant).reshape(S, H, Dn + R)
    q = jnp.concatenate([q[..., :Dn], rotate(q[..., Dn:], cos, sin)], -1)
    c = rms(a["kv_norm"]["scale"], Q.matmul(h, a["w_dkv"], quant), conf)
    k_pe = rotate(Q.matmul(h, a["w_krope"], quant), cos, sin)          # (S, R)
    k_nope = Q.matmul(c, a["w_uk"].reshape(Lr, H * Dn), quant).reshape(S, H, Dn)
    v = Q.matmul(c, a["w_uv"].reshape(Lr, H * Dv), quant).reshape(S, H, Dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None], (S, H, R))], -1)
    scale = softmax_scale(conf)
    kpos = jnp.arange(S)
    qb = math.gcd(S, Q_BLOCK)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)

    o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, H * Dv)
    return Q.matmul(o, a["wo"].reshape(H * Dv, d), quant)


def swiglu(p, h, quant):
    return Q.matmul(jax.nn.silu(Q.matmul(h, p["wg"], quant)) * Q.matmul(h, p["wi"], quant),
                    p["wo"], quant)


def moe(conf, m, h, quant):
    """The held experts' part of the routed output plus the shared experts."""
    k = conf["num_experts_per_tok"]
    logits = jnp.einsum("sd,de->se", h, m["router"], precision=HI)
    probs = jax.nn.softmax(logits, -1)
    top, ids = jax.lax.top_k(probs, k)
    if conf["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    else:
        top = top * conf["routed_scaling_factor"]
    def expert(y, e):
        we = {n: m[n][e] for n in ("wi", "wg", "wo")}
        gate = jnp.sum(jnp.where(ids == e, top, 0.0), -1)     # (S,)
        return y + gate[:, None] * swiglu(we, h, quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(conf["n_routed_experts"]))
    return y + swiglu(m["shared"], h, quant)


def block(conf, p, x, cos, sin, quant):
    """One decoder layer on one sequence x (S, d)."""
    x = x + mla(conf, p["attn"], rms(p["ln1"]["scale"], x, conf), cos, sin, quant)
    h = rms(p["ln2"]["scale"], x, conf)
    if "ffn" in p:
        return x + swiglu(p["ffn"], h, quant)
    return x + moe(conf, p["moe"], h, quant)


def hidden(conf, params, tokens, quant=None):
    """tokens (S,) -> final normed hidden states (S, d)."""
    S = tokens.shape[0]
    x = Q.embed({"d_model": conf["hidden_size"]}, params["embed"]["factors"], tokens)
    cos, sin = rope_tables(conf, jnp.arange(S))
    for p in params.get("lead", []):
        x = block(conf, p, x, cos, sin, quant)
    if params["groups"]:
        x, _ = jax.lax.scan(lambda x, p: (block(conf, p, x, cos, sin, quant), None),
                            x, params["groups"][0])
    return rms(params["final_norm"]["scale"], x, conf)


def head_matrix(conf, params):
    return Q.kron_dense(params["head"]["factors"], conf["hidden_size"],
                        conf["vocab_size"])


def logits(conf, w_head, h, quant=None):
    return Q.matmul(h, w_head, quant)

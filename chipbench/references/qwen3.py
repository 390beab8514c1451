"""Plain float32 reference of the qwen3 decoder with word2ketXS embedding,
Kronecker vocab head and (optionally) Kronecker "ket" linear layers.

Written from the equations, in straightforward ``jax.numpy`` at
``Precision.HIGHEST``; it imports nothing of the program. Each
Σ_k A_k ⊗ B_k is built as a dense matrix. No kernel, no cache, no batching
of requests: attention is a full causal softmax over the sequence.

* embedding (word2ketXS, paper §3.2): the column of token ``i`` is
  Σ_k LN(A_k[:, i1] ⊗ B_k[:, i2]) with ``(i1, i2)`` the mixed-radix digits of
  ``i`` in radices ``t``, LN non-affine (eps 1e-5) over the ``q1·q2`` entries,
  sliced to ``d_model``;
* block: ``x += o(attn(rms(x)))``, ``x += ffn(rms(x))``; RMSNorm eps 1e-6;
  q/k RMSNorm over the head dim; rotary embedding, rotate-half, frequencies
  ``theta^(-j/half)``; grouped-query heads (head h reads kv head h // G);
  SwiGLU ``wo(silu(x wg) * (x wi))``;
* head: logits = h @ (Σ_k A_k ⊗ B_k)[:d_model, :vocab];
* ket linear: y = x @ (Σ_k A_k ⊗ B_k)[:d_in, :d_out];
* loss: mean token cross-entropy; AdamW with global-norm clipping,
  bias correction and decoupled weight decay on every leaf.

``quant="fp8"`` computes every projection and the head with both operands
rounded to float8_e4m3 under a per-tensor scale: the lower precision that
serves as the control of the comparison.

The parameters are laid out as the program lays them out (leaves of all
layers stacked on a leading axis), so the benchmark makes them once from
the seed with :func:`init` and the reference makes the same ones again.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _ket(conf, shape_key: str, L=None):
    """Factor stack shapes of one Kronecker operator (``conf`` lists them)."""
    f = conf["factors"][shape_key]
    r = f["rank"]
    lead = () if L is None else (L,)
    return [lead + (r, q, t) for q, t in zip(f["q"], f["t"])]


def shapes(conf: dict) -> dict:
    """The parameter tree's shapes, as tuples, in the program's layout."""
    L, d = conf["num_layers"], conf["d_model"]
    H, KVH, Dh, ff = (conf["num_heads"], conf["num_kv_heads"],
                      conf["head_dim"], conf["d_ff"])
    if conf["linear_kind"] == "ket":
        attn = {"wq": {"factors": _ket(conf, "wq", L)},
                "wk": {"factors": _ket(conf, "wk", L)},
                "wv": {"factors": _ket(conf, "wv", L)},
                "wo": {"factors": _ket(conf, "wo", L)}}
        ffn = {"wi": {"factors": _ket(conf, "wi", L)},
               "wo": {"factors": _ket(conf, "ffn_wo", L)},
               "wg": {"factors": _ket(conf, "wi", L)}}
    else:
        attn = {"wq": (L, d, H, Dh), "wk": (L, d, KVH, Dh),
                "wv": (L, d, KVH, Dh), "wo": (L, H, Dh, d)}
        ffn = {"wi": (L, d, ff), "wo": (L, ff, d), "wg": (L, d, ff)}
    attn["q_norm"] = {"scale": (L, Dh)}
    attn["k_norm"] = {"scale": (L, Dh)}
    layer = {"ln1": {"scale": (L, d)}, "attn": attn,
             "ln2": {"scale": (L, d)}, "ffn": ffn}
    return {"embed": {"factors": _ket(conf, "embed")},
            "groups": [layer], "rem": [],
            "final_norm": {"scale": (d,)},
            "head": {"factors": _ket(conf, "head")}}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(v, int) for v in x)


def _leaf_std(path: tuple, shape: tuple, siblings: list) -> float:
    """Init scale: entries of the operator at O(1/sqrt(fan-in))."""
    if path[-1] == "scale":
        return 0.0  # norms start at one
    if "factors" in path:
        n = len(siblings)
        r = shape[-3]
        fan = math.prod(s[-2] for s in siblings)
        return (1.0 / (math.sqrt(r) * math.sqrt(fan))) ** (1.0 / n)
    if path[-1] in ("wq", "wk", "wv"):
        return 1.0 / math.sqrt(shape[1])
    return 1.0 / math.sqrt(math.prod(shape[1:-1]))


def init(conf: dict, key) -> dict:
    """Parameters from ``key``: normal draws at the scale of
    :func:`_leaf_std`, norm scales at one; float32. Jittable."""
    count = [0]

    def build(node, path, siblings=None):
        if _is_shape(node):
            i = count[0]
            count[0] += 1
            std = _leaf_std(path, node, siblings or [node])
            if std == 0.0:
                return jnp.ones(node, jnp.float32)
            return std * jax.random.normal(jax.random.fold_in(key, i), node,
                                           jnp.float32)
        if isinstance(node, dict):
            return {k: build(node[k], path + (k,)) for k in sorted(node)}
        if path[-1] == "factors":
            return [build(v, path, node) for v in node]
        return [build(v, path + (str(j),)) for j, v in enumerate(node)]

    return build(shapes(conf), ("root",))


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def fake_fp8(x):
    """x rounded to float8_e4m3 under one per-tensor scale, back in f32.
    The gradient passes straight through (the backward works on the rounded
    operands, in float32)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def kron_dense(factors, d_in: int, d_out: int):
    """(Σ_k A_k ⊗ B_k)[:d_in, :d_out] for A (r, q1, t1), B (r, q2, t2)."""
    a, b = factors
    r, q1, t1 = a.shape
    _, q2, t2 = b.shape
    w = jnp.einsum("kab,kcd->acbd", a, b, precision=HI)
    return w.reshape(q1 * q2, t1 * t2)[:d_in, :d_out]


def matmul(x, w, quant):
    if quant == "fp8":
        x, w = fake_fp8(x), fake_fp8(w)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def rmsnorm(scale, x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def layernorm(x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def embed(conf, factors, ids):
    """word2ketXS columns of ``ids`` (...,) -> (..., d_model)."""
    a, b = factors
    t2 = b.shape[2]
    i1, i2 = ids // t2, ids % t2
    va = jnp.moveaxis(jnp.take(a, i1, axis=2), (0, 1), (-2, -1))  # (..., r, q1)
    vb = jnp.moveaxis(jnp.take(b, i2, axis=2), (0, 1), (-2, -1))  # (..., r, q2)
    v = (va[..., :, None] * vb[..., None, :]).reshape(*va.shape[:-1], -1)
    return jnp.sum(layernorm(v), axis=-2)[..., :conf["d_model"]]


def rope(x, pos, theta):
    """x (S, H, Dh) at positions pos (S,), rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _weight(conf, p, d_in, d_out):
    if conf["linear_kind"] == "ket":
        return kron_dense(p["factors"], d_in, d_out)
    return p.reshape(d_in, d_out)


def block(conf, p, x, quant):
    """One decoder layer on one sequence x (S, d)."""
    d, H, KVH, Dh, ff = (conf["d_model"], conf["num_heads"],
                         conf["num_kv_heads"], conf["head_dim"], conf["d_ff"])
    S = x.shape[0]
    pos = jnp.arange(S)
    a = p["attn"]
    h = rmsnorm(p["ln1"]["scale"], x)
    q = matmul(h, _weight(conf, a["wq"], d, H * Dh), quant).reshape(S, H, Dh)
    k = matmul(h, _weight(conf, a["wk"], d, KVH * Dh), quant).reshape(S, KVH, Dh)
    v = matmul(h, _weight(conf, a["wv"], d, KVH * Dh), quant).reshape(S, KVH, Dh)
    q = rope(rmsnorm(a["q_norm"]["scale"], q), pos, conf["rope_theta"])
    k = rope(rmsnorm(a["k_norm"]["scale"], k), pos, conf["rope_theta"])
    g = H // KVH
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * Dh ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + matmul(o.reshape(S, H * Dh), _weight(conf, a["wo"], H * Dh, d),
                   quant)
    f = p["ffn"]
    h = rmsnorm(p["ln2"]["scale"], x)
    up = matmul(h, _weight(conf, f["wi"], d, ff), quant)
    gate = matmul(h, _weight(conf, f["wg"], d, ff), quant)
    return x + matmul(jax.nn.silu(gate) * up,
                      _weight(conf, f["wo"], ff, d), quant)


def hidden(conf, params, tokens, quant=None, remat=False):
    """tokens (S,) -> final normed hidden states (S, d)."""
    x = embed(conf, params["embed"]["factors"], tokens)
    fn = lambda x, p: (block(conf, p, x, quant), None)
    if remat:
        fn = jax.checkpoint(fn)
    x, _ = jax.lax.scan(fn, x, params["groups"][0])
    return rmsnorm(params["final_norm"]["scale"], x)


def head_matrix(conf, params):
    return kron_dense(params["head"]["factors"], conf["d_model"],
                      conf["vocab_size"])


def logits(conf, w_head, h, quant=None):
    return matmul(h, w_head, quant)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def loss(conf, params, tokens, labels, quant=None, ce_block=1024):
    """Mean cross-entropy over every token of tokens/labels (B, S)."""
    w = head_matrix(conf, params)
    h = jax.vmap(lambda t: hidden(conf, params, t, quant, remat=True))(tokens)
    h = h.reshape(-1, h.shape[-1])
    y = labels.reshape(-1)
    ce_block = math.gcd(h.shape[0], ce_block)
    n = h.shape[0] // ce_block

    @jax.checkpoint
    def ce(hb, yb):
        z = logits(conf, w, hb, quant)
        lse = jax.nn.logsumexp(z, -1)
        return jnp.sum(lse - jnp.take_along_axis(z, yb[:, None], -1)[:, 0])

    parts = jax.lax.map(lambda a: ce(*a), (h.reshape(n, ce_block, -1),
                                           y.reshape(n, ce_block)))
    return jnp.sum(parts) / y.shape[0]


def adamw(opt: dict, step: int, grads, m, v, params):
    """One AdamW update (``opt``: lr, warmup, total, floor, b1, b2, eps,
    weight_decay, grad_clip) at 1-based ``step``; returns (params, m, v)."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    t = float(step)
    if t < opt["warmup"]:
        lr = opt["lr"] * t / max(opt["warmup"], 1)
    else:
        prog = min(max((t - opt["warmup"]) / max(opt["total"] - opt["warmup"], 1),
                       0.0), 1.0)
        lr = opt["lr"] * (opt["floor"] + (1 - opt["floor"]) * 0.5
                          * (1 + math.cos(math.pi * prog)))
    b1, b2 = opt["b1"], opt["b2"]
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(g, m_, v_, p):
        g = g * scale
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        u = (m_ / c1) / (jnp.sqrt(v_ / c2) + opt["eps"]) + opt["weight_decay"] * p
        return p - lr * u, m_, v_

    out = jax.tree_util.tree_map(upd, grads, m, v, params)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)

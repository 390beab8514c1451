"""Attention: GQA/MQA/MHA with RoPE, qk-norm, local windows, MLA; flash-style
chunked softmax (pure JAX, lax.scan over KV chunks — never materializes the
full (Sq, Skv) score matrix, which is mandatory at the 32k prefill shapes).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.common import (apply_rope, dense_init, linear_init,
                                 linear_opts, out_proj, qkv_proj, rmsnorm,
                                 yarn_get_mscale)

NEG = jnp.float32(-1e30)


# ---------------------------------------------------------------------------
# Chunked (flash) attention — training / prefill
# ---------------------------------------------------------------------------

def flash_attention(
    q: jax.Array,  # (B, Sq, H, Dh)
    k: jax.Array,  # (B, Skv, KVH, Dh)
    v: jax.Array,  # (B, Skv, KVH, Dh)
    *,
    causal: bool = True,
    window: int = 0,  # 0 = unlimited; >0 = local sliding window
    chunk: int = 1024,
    kv_valid_len: jax.Array | None = None,  # (B,) mask for padded caches
    q_offset: jax.Array | None = None,  # (B,) absolute position of query 0
    kv_pos: jax.Array | None = None,  # (B, Skv) absolute key positions; <0 invalid
    scale: float | None = None,  # softmax scale; None = Dh^-0.5
) -> jax.Array:
    """Chunked-softmax attention; never materializes the (Sq, Skv) matrix.

    The positional args serve chunked prefill (serve/decode.py): ``q_offset``
    shifts each sequence's query positions (queries are cache continuations
    at per-slot offsets), and ``kv_pos`` overrides the implicit arange key
    positions (ring-buffer caches carry out-of-order absolute positions).
    Both default to the classic positions-from-zero behavior.
    """
    B, Sq, H, Dh = q.shape
    _, Skv, KVH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KVH
    scale = Dh ** -0.5 if scale is None else scale
    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qf = qf.reshape(B, Sq, KVH, G, Dh)

    C = min(chunk, Skv)
    pad = -Skv % C
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_pos is not None:  # padded keys: position -1 == always invalid
            kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    n_chunks = (Skv + pad) // C
    # qpos: (1, Sq) or (B, Sq) when per-slot offsets are given
    if q_offset is None:
        qpos = jnp.arange(Sq)[None]
    else:
        qpos = q_offset[:, None] + jnp.arange(Sq)[None]

    # checkpoint: backward recomputes the (Sq, C) score tile per chunk instead
    # of saving it — without this, grad-of-scan stores the full S² matrix.
    @jax.checkpoint
    def body(carry, c):
        m, l, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, c * C, C, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, c * C, C, axis=1)
        kpos = c * C + jnp.arange(C)
        s = jnp.einsum("bqkgd,bckd->bkgqc", qf, kc, preferred_element_type=jnp.float32)
        if kv_pos is None:
            abs_k = kpos[None, None, :]  # (1, 1, C)
            valid = (kpos < Skv)[None, None, :] & jnp.ones((1, Sq, 1), bool)
        else:
            abs_k = jax.lax.dynamic_slice_in_dim(kv_pos, c * C, C, axis=1)[:, None, :]
            valid = (abs_k >= 0) & jnp.ones((1, Sq, 1), bool)  # (B, Sq, C)
        if causal:
            valid &= abs_k <= qpos[:, :, None]
        if window > 0:
            valid &= abs_k > qpos[:, :, None] - window
        if kv_valid_len is not None:
            valid &= abs_k < kv_valid_len[:, None, None]
        mask = valid[:, None, None]  # (B|1, 1, 1, Sq, C)
        s = jnp.where(mask, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KVH, G, Sq), NEG)
    l0 = jnp.zeros((B, KVH, G, Sq))
    a0 = jnp.zeros((B, KVH, G, Sq, Dv))
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, Dv)  # (B,KVH,G,Sq,Dv)->(B,Sq,H,Dv)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, H, Dh) — one new token per sequence
    k_cache: jax.Array,  # (B, S, KVH, Dh)
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,) number of valid positions
    *,
    window: int = 0,
) -> jax.Array:
    """Single-step attention over a (possibly seq-sharded) KV cache.

    Local path; the model-axis seq-sharded flash-decoding combine lives in
    repro/serve/decode.py (shard_map around this function).
    """
    B, S, KVH, Dh = k_cache.shape
    H = q.shape[1]
    G = H // KVH
    qf = (q.astype(jnp.float32) * (Dh ** -0.5)).astype(q.dtype).reshape(B, KVH, G, Dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache, preferred_element_type=jnp.float32)
    pos = jnp.arange(S)
    valid = pos[None, :] < cache_len[:, None]
    if window > 0:
        valid &= pos[None, :] >= (cache_len[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(B, H, Dh)
    return out.astype(q.dtype)


def decode_attention_partial(q, k_cache, v_cache, cache_len, *, window=0, pos_offset=0):
    """Partial-softmax stats for flash-decoding combines: returns (m, l, o).

    q (B,H,Dh); k/v (B,S_loc,KVH,Dh); positions are pos_offset + arange(S_loc).
    """
    B, S, KVH, Dh = k_cache.shape
    H = q.shape[1]
    G = H // KVH
    qf = (q.astype(jnp.float32) * (Dh ** -0.5)).astype(q.dtype).reshape(B, KVH, G, Dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache, preferred_element_type=jnp.float32)
    pos = pos_offset + jnp.arange(S)
    valid = pos[None, :] < cache_len[:, None]
    if window > 0:
        valid &= pos[None, :] >= (cache_len[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return m, l, o  # (B,KVH,G), (B,KVH,G), (B,KVH,G,Dh)


# ---------------------------------------------------------------------------
# Standard GQA attention block
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype=None) -> dict:
    dtype = dtype or cfg.param_dtype
    d, H, KVH, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    if getattr(cfg, "linear_kind", "dense") == "ket":
        kw = dict(kind="ket", order=cfg.linear_order, rank=cfg.linear_rank,
                  quant=getattr(cfg, "quant", "none"))
        p = {
            "wq": linear_init(ks[0], d, H * Dh, dtype, **kw),
            "wk": linear_init(ks[1], d, KVH * Dh, dtype, **kw),
            "wv": linear_init(ks[2], d, KVH * Dh, dtype, **kw),
            "wo": linear_init(ks[3], H * Dh, d, dtype, **kw),
        }
    else:
        p = {
            "wq": dense_init(ks[0], (d, H, Dh), dtype, fan_in=d),
            "wk": dense_init(ks[1], (d, KVH, Dh), dtype, fan_in=d),
            "wv": dense_init(ks[2], (d, KVH, Dh), dtype, fan_in=d),
            "wo": dense_init(ks[3], (H, Dh, d), dtype, fan_in=H * Dh),
        }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((Dh,), dtype)}
        p["k_norm"] = {"scale": jnp.ones((Dh,), dtype)}
    return p


def _maybe_qk_norm(cfg, params, q, k):
    if not cfg.qk_norm:
        return q, k
    return rmsnorm(params["q_norm"], q), rmsnorm(params["k_norm"], k)


def attention_qkv(params, cfg, x, cos, sin, *, rope: bool = True):
    """x (B,S,d) -> q (B,S,H,Dh), k,v (B,S,KVH,Dh), rope+qknorm applied."""
    dt = cfg.dtype
    opts = linear_opts(cfg)
    q = qkv_proj(params["wq"], x, dt, cfg.num_heads, cfg.head_dim, **opts)
    k = qkv_proj(params["wk"], x, dt, cfg.num_kv_heads, cfg.head_dim, **opts)
    v = qkv_proj(params["wv"], x, dt, cfg.num_kv_heads, cfg.head_dim, **opts)
    q, k = _maybe_qk_norm(cfg, params, q, k)
    if rope:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def attention_out(params, cfg, o):
    """o (..., H, Dh) -> (..., d_model) through wo (dense or ket)."""
    return out_proj(params["wo"], o, cfg.dtype, cfg.d_model,
                    **linear_opts(cfg))


def attention_block(params, cfg, x, cos, sin, *, local: bool = False,
                    causal: bool = True, chunk: int = 1024):
    q, k, v = attention_qkv(params, cfg, x, cos, sin)
    window = cfg.local_window if local else 0
    out = flash_attention(q, k, v, causal=causal, window=window, chunk=chunk)
    return attention_out(params, cfg, out)


def cross_attention_block(params, cfg, x, enc_k, enc_v, chunk: int = 1024):
    """Decoder cross-attention: q from x, k/v precomputed from encoder."""
    dt = cfg.dtype
    q = qkv_proj(params["wq"], x, dt, cfg.num_heads, cfg.head_dim,
                 **linear_opts(cfg))
    out = flash_attention(q, enc_k, enc_v, causal=False, chunk=chunk)
    return attention_out(params, cfg, out)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 latent attention)
# ---------------------------------------------------------------------------

def mla_softmax_scale(cfg) -> float:
    """(qk_nope + qk_rope)^-1/2, times yarn_get_mscale(factor,
    mscale_all_dim)² under YaRN (DeepSeek-V2's ``softmax_scale``)."""
    scale = (cfg.head_dim + cfg.rope_head_dim) ** -0.5
    if cfg.rope_scaling == "yarn" and cfg.mscale_all_dim:
        m = yarn_get_mscale(cfg.rope_factor, cfg.mscale_all_dim)
        scale *= m * m
    return scale


def init_mla(key, cfg, dtype=None) -> dict:
    dtype = dtype or cfg.param_dtype
    d, H, Dh = cfg.d_model, cfg.num_heads, cfg.head_dim
    L, R = cfg.kv_lora_rank, cfg.rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (d, H, Dh + R), dtype, fan_in=d),
        "w_dkv": dense_init(ks[1], (d, L), dtype, fan_in=d),
        "w_krope": dense_init(ks[2], (d, R), dtype, fan_in=d),
        "kv_norm": {"scale": jnp.ones((L,), dtype)},
        "w_uk": dense_init(ks[3], (L, H, Dh), dtype, fan_in=L),
        "w_uv": dense_init(ks[4], (L, H, Dh), dtype, fan_in=L),
        "wo": dense_init(ks[5], (H, Dh, d), dtype, fan_in=H * Dh),
    }


def mla_latents(params, cfg, x, cos, sin):
    """x (B, S, d) -> latent cache entries (c (B, S, L), k_rope (B, S, R)).

    The single source of the w_dkv/kv_norm/w_krope projection — shared by
    training-prefill cache capture, single-token decode, and chunked
    prefill so the three paths cannot drift."""
    dt = cfg.dtype
    c = jnp.einsum("bsd,dl->bsl", x, params["w_dkv"].astype(dt))
    c = rmsnorm(params["kv_norm"], c)
    kr = apply_rope(
        jnp.einsum("bsd,dr->bsr", x, params["w_krope"].astype(dt))[:, :, None, :],
        cos, sin)[:, :, 0, :]
    return c, kr


def mla_absorbed_q(params, cfg, x, cos, sin):
    """x (B, S, d) -> (q_abs (B, S, H, L), q_rope (B, S, H, R)).

    Queries for the absorbed-matmul score against a latent cache:
    score = q_abs·c + q_rope·k_rope at :func:`mla_softmax_scale`."""
    dt = cfg.dtype
    Dh = cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    q_nope, q_rope = q[..., :Dh], q[..., Dh:]
    q_rope = apply_rope(q_rope, cos, sin)
    q_abs = jnp.einsum("bshk,lhk->bshl", q_nope, params["w_uk"].astype(dt))
    return q_abs, q_rope


def mla_block(params, cfg, x, cos, sin, *, chunk: int = 1024):
    """Training/prefill MLA: latent c is up-projected; full softmax attention."""
    dt = cfg.dtype
    H, Dh, R = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    q_nope, q_rope = q[..., :Dh], q[..., Dh:]
    q_rope = apply_rope(q_rope, cos, sin)

    c, k_rope = mla_latents(params, cfg, x, cos, sin)
    k_rope = k_rope[:, :, None, :]  # (B,S,1,R) shared across heads
    k_nope = jnp.einsum("bsl,lhk->bshk", c, params["w_uk"].astype(dt))
    v = jnp.einsum("bsl,lhk->bshk", c, params["w_uv"].astype(dt))

    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    kk = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (R,))], axis=-1)
    out = flash_attention(qq, kk, v, causal=True, chunk=chunk,
                          scale=mla_softmax_scale(cfg))
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))


def mla_cache_step(params, cfg, x_tok, cos, sin):
    """New latent cache entries for one decoded token: (c (B,L), k_rope (B,R))."""
    c, kr = mla_latents(params, cfg, x_tok[:, None], cos, sin)
    return c[:, 0], kr[:, 0]

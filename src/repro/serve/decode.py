"""Decode + chunked prefill over dense or paged KV caches.

Single-token decode (``serve_step``) supports two cache layouts behind one
interface (the layout is detected from the cache pytree, see serve/cache.py):

* **dense** — (B, S, ...) per-slot tensors, including the distributed
  flash-decoding leg: at 32k+ context the cache is sharded along the
  *sequence* axis over the model mesh axis and attention uses the 3-scalar
  ``pmax``/``psum`` combine instead of any KV all-gather.
* **paged** — (num_pages, page_size, ...) pools + a slot→page table; reads
  go through the Pallas paged-read kernel on TPU (kernels/flash_attn/paged)
  or the XLA gather reference elsewhere, writes scatter one token into the
  slot's current page.

Chunked prefill (``prefill_step``) consumes C prompt tokens per call through
the full forward path — flash attention over [cache ∪ chunk] at per-slot
position offsets, chunk-parallel SSM/RG-LRU scans continuing the decode
state — so a P-token prompt warms its cache in ⌈P/C⌉ engine ticks instead
of P (serve/engine.py).

Cache layout mirrors the parameter layout: {"groups": [stacked per pattern
position], "rem": [...]} so both steps scan over layer groups exactly like
the forward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, embedding_for
from repro.core.embedding import embed_lookup
from repro.kernels.flash_attn import ops as FOPS
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.common import (linear_opts, out_proj, qkv_proj, rmsnorm,
                                 rope_angles, yarn_of)
from repro.models.transformer import ffn_or_moe, lm_logits_last
from repro.parallel import meshctx
from repro.serve.cache import gather_pages
from repro.serve.cache import init_cache  # noqa: F401  (compat re-export)
from repro.serve.cache import init_layer_cache  # noqa: F401  (compat re-export)

NEG = jnp.float32(-1e30)
# most bytes of f32 scores one block of the paged MLA read may hold
MLA_SCORE_BUDGET = 128 << 20


# ---------------------------------------------------------------------------
# Sharded KV write + flash-decoding attention (dense layout)
# ---------------------------------------------------------------------------

def _model_axis_active(cfg: ModelConfig) -> bool:
    mesh = meshctx.get_mesh()
    return mesh is not None and "model" in mesh.axis_names and mesh.shape["model"] > 1


def _batch_axes(batch: int):
    """Maximal DP prefix whose product divides the (global) decode batch
    (one layout authority: ``parallel.sharding.batch_axes_for``)."""
    mesh = meshctx.get_mesh()
    if mesh is None:
        return ()
    from repro.parallel.sharding import batch_axes_for
    return batch_axes_for(mesh, batch)


def _scatter_kv(cache, new, slot):
    """cache (B,S,KVH,Dh) <- new (B,KVH,Dh) at per-slot positions slot (B,)."""
    B = cache.shape[0]
    return cache.at[jnp.arange(B), slot].set(new.astype(cache.dtype))


def kv_decode_attention(cfg, q, k_new, v_new, cache_k, cache_v, slot, valid_len, window=0):
    """Write (k_new, v_new) at per-slot `slot` (B,) and attend; seq-sharded
    under a mesh (flash-decoding combine).

    q (B,H,Dh); k_new/v_new (B,KVH,Dh); cache (B,S,KVH,Dh); slot/valid_len (B,).
    Returns (out (B,H,Dh), cache_k, cache_v).
    """
    if not _model_axis_active(cfg):
        cache_k = _scatter_kv(cache_k, k_new, slot)
        cache_v = _scatter_kv(cache_v, v_new, slot)
        out = A.decode_attention(q, cache_k, cache_v, valid_len, window=window)
        return out, cache_k, cache_v

    mesh = meshctx.get_mesh()
    baxes = _batch_axes(q.shape[0])

    def inner(q, k_new, v_new, ck, cv, slot, valid_len):
        S_loc = ck.shape[1]
        idx = jax.lax.axis_index("model")
        local_slot = jnp.clip(slot - idx * S_loc, 0, S_loc - 1)
        owns = (slot >= idx * S_loc) & (slot < (idx + 1) * S_loc)  # (B,)
        ck = jnp.where(owns[:, None, None, None], _scatter_kv(ck, k_new, local_slot), ck)
        cv = jnp.where(owns[:, None, None, None], _scatter_kv(cv, v_new, local_slot), cv)
        m, l, o = A.decode_attention_partial(
            q, ck, cv, valid_len, window=window, pos_offset=idx * S_loc)
        gm = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - gm)
        gl = jax.lax.psum(l * corr, "model")
        go = jax.lax.psum(o * corr[..., None], "model")
        out = (go / jnp.maximum(gl, 1e-30)[..., None])
        B, KVH, G, Dh = out.shape[0], out.shape[1], out.shape[2], out.shape[3]
        return out.reshape(B, KVH * G, Dh).astype(q.dtype), ck, cv

    return meshctx.shard_map(
        inner, mesh=mesh,
        in_specs=(P(baxes), P(baxes), P(baxes),
                  P(baxes, "model"), P(baxes, "model"), P(baxes), P(baxes)),
        out_specs=(P(baxes), P(baxes, "model"), P(baxes, "model")),
        check_vma=False,
    )(q, k_new, v_new, cache_k, cache_v, slot, valid_len)


def mla_decode_attention(cfg, p_attn, x_tok, cache_c, cache_krope, slot, valid_len, cos, sin):
    """Absorbed MLA decode with a seq-sharded latent cache. slot/valid (B,)."""
    dt = cfg.dtype
    c_new, kr_new = A.mla_cache_step(p_attn, cfg, x_tok, cos, sin)
    q_abs, q_rope = A.mla_absorbed_q(p_attn, cfg, x_tok[:, None], cos, sin)
    q_abs, q_rope = q_abs[:, 0], q_rope[:, 0]
    scale = A.mla_softmax_scale(cfg)

    def partial_attn(qa, qr, cc, ckr, vlen, pos_offset):
        s = jnp.einsum("bhl,bsl->bhs", qa, cc, preferred_element_type=jnp.float32)
        s += jnp.einsum("bhr,bsr->bhs", qr, ckr, preferred_element_type=jnp.float32)
        s *= scale
        pos = pos_offset + jnp.arange(cc.shape[1])
        s = jnp.where((pos[None, :] < vlen[:, None])[:, None], s, NEG)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhs,bsl->bhl", p.astype(cc.dtype), cc,
                       preferred_element_type=jnp.float32)
        return m, l, o

    def _scatter(cache, new, sl):
        B = cache.shape[0]
        return cache.at[jnp.arange(B), sl].set(new.astype(cache.dtype))

    if not _model_axis_active(cfg):
        cache_c = _scatter(cache_c, c_new, slot)
        cache_krope = _scatter(cache_krope, kr_new, slot)
        m, l, o = partial_attn(q_abs, q_rope, cache_c, cache_krope, valid_len, 0)
        ctx_l = (o / jnp.maximum(l, 1e-30)[..., None]).astype(dt)
    else:
        mesh = meshctx.get_mesh()
        baxes = _batch_axes(x_tok.shape[0])

        # q_abs/q_rope/slot/valid are explicit shard_map args (batch-sharded);
        # closure capture would replicate them at global batch against local
        # caches.
        def inner(qa, qr, cc, ckr, cn, krn, sl, vlen):
            S_loc = cc.shape[1]
            idx = jax.lax.axis_index("model")
            local_slot = jnp.clip(sl - idx * S_loc, 0, S_loc - 1)
            owns = (sl >= idx * S_loc) & (sl < (idx + 1) * S_loc)
            cc = jnp.where(owns[:, None, None], _scatter(cc, cn, local_slot), cc)
            ckr = jnp.where(owns[:, None, None], _scatter(ckr, krn, local_slot), ckr)
            m, l, o = partial_attn(qa, qr, cc, ckr, vlen, idx * S_loc)
            gm = jax.lax.pmax(m, "model")
            corr = jnp.exp(m - gm)
            gl = jax.lax.psum(l * corr, "model")
            go = jax.lax.psum(o * corr[..., None], "model")
            return (go / jnp.maximum(gl, 1e-30)[..., None]).astype(dt), cc, ckr

        ctx_l, cache_c, cache_krope = meshctx.shard_map(
            inner, mesh=mesh,
            in_specs=(P(baxes), P(baxes), P(baxes, "model"), P(baxes, "model"),
                      P(baxes), P(baxes), P(baxes), P(baxes)),
            out_specs=(P(baxes), P(baxes, "model"), P(baxes, "model")),
            check_vma=False,
        )(q_abs, q_rope, cache_c, cache_krope, c_new, kr_new, slot, valid_len)

    ctx = jnp.einsum("bhl,lhk->bhk", ctx_l, p_attn["w_uv"].astype(dt))
    out = jnp.einsum("bhk,hkd->bd", ctx, p_attn["wo"].astype(dt))
    return out, cache_c, cache_krope


# ---------------------------------------------------------------------------
# Paged write + read
# ---------------------------------------------------------------------------

def _page_write(pool, ptab, pos, new):
    """pool (P, ps, ...) <- new (B, ...) at logical positions pos (B,).

    Idle slots carry all-zero ptab rows, so their writes land in the trash
    page (serve/cache.py) — colliding updates there are never read.
    """
    ps = pool.shape[1]
    B = pos.shape[0]
    pid = ptab[jnp.arange(B), pos // ps]  # (B,)
    return pool.at[pid, pos % ps].set(new.astype(pool.dtype))


def _scatter_chunk(leaf, positions, valid, new):
    """leaf (B, S, ...) <- new (B, C, ...) at per-slot positions (B, C);
    invalid lanes are redirected one past the end and dropped. The single
    home of the drop-sentinel idiom for dense chunk writes (ring, full
    attention, MLA latents)."""
    S_ = leaf.shape[1]
    idx = jnp.where(valid, positions, S_)
    b_idx = jnp.arange(leaf.shape[0])[:, None]
    return leaf.at[b_idx, idx].set(new.astype(leaf.dtype), mode="drop")


def _page_write_chunk(pool, ptab, step, lens, new):
    """pool <- new (B, C, ...) at logical positions step+i for i < lens;
    the ragged tail is redirected to the trash page."""
    ps = pool.shape[1]
    B, C = new.shape[0], new.shape[1]
    pos = step[:, None] + jnp.arange(C)  # (B, C)
    valid = jnp.arange(C)[None] < lens[:, None]
    pid = ptab[jnp.arange(B)[:, None], jnp.minimum(pos // ps, ptab.shape[1] - 1)]
    pid = jnp.where(valid, pid, 0)
    return pool.at[pid, pos % ps].set(new.astype(pool.dtype))


def paged_kv_decode_attention(cfg, q, k_new, v_new, pool_k, pool_v, ptab, step):
    """Paged decode read: write the new token into its slot's current page,
    then attend over the slot's logical view.

    The read is the split-KV (flash-decoding) algorithm: compiled Pallas on
    TPU, its fused-XLA host executor elsewhere; ``cfg.decode_kv_splits``
    (pinned by the engine from the "paged_attn" autotune family) fixes the
    split count so every trace shares one static grid.

    Prefix-cache sharing (serve/cache.PrefixCache) relies on this split:
    the paged READ is position-blind — any ptab row may point several slots
    at the same physical page — while the single WRITE targets the slot's
    current page only, which the engine guarantees is private (copy-on-write
    in engine._grow repoints the ptab before the tick ever runs).
    """
    pool_k = _page_write(pool_k, ptab, step, k_new)
    pool_v = _page_write(pool_v, ptab, step, v_new)
    out = FOPS.paged_attention(q, pool_k, pool_v, ptab, step + 1,
                               use_kernel=cfg.use_kernels,
                               kv_splits=cfg.decode_kv_splits)
    return out.astype(q.dtype), pool_k, pool_v


def paged_mla_attend(cfg, p_attn, q_abs, q_rope, pool_c, pool_krope, ptab, step, hi):
    """Absorbed MLA read of paged latent pools, blocked over each slot's
    logical view with an online softmax.

    q_abs (B, C, H, L), q_rope (B, C, H, R): queries at positions step + i
    (step (B,)); a query attends to every position at or before its own.
    ``hi`` (B,): one past the last position a valid query holds; blocks
    wholly past the largest are not read. A block gathers its pages alone
    and its f32 scores stay within ``MLA_SCORE_BUDGET``, so neither the
    whole (B, max_len, L) view nor (B, C, H, max_len) scores are made.
    Returns (B, C, d) through w_uv and wo."""
    dt = cfg.dtype
    B, C, H, L = q_abs.shape
    ps, NP = pool_c.shape[1], ptab.shape[1]
    bp = int(max(1, min(NP, MLA_SCORE_BUDGET // (B * H * C * 4 * ps))))
    n_blocks = -(-NP // bp)
    if n_blocks * bp != NP:  # pad with the trash page: masked, past max_len
        ptab = jnp.pad(ptab, ((0, 0), (0, n_blocks * bp - NP)))
    S = bp * ps
    qpos = step[:, None] + jnp.arange(C)  # (B, C)
    scale = A.mla_softmax_scale(cfg)

    def body(i, carry):
        m, l, acc = carry
        pids = jax.lax.dynamic_slice_in_dim(ptab, i * bp, bp, axis=1)
        cc = pool_c[pids].reshape(B, S, L)
        ckr = pool_krope[pids].reshape(B, S, -1)
        s = jnp.einsum("bchl,bsl->bhcs", q_abs, cc, preferred_element_type=jnp.float32)
        s += jnp.einsum("bchr,bsr->bhcs", q_rope, ckr,
                        preferred_element_type=jnp.float32)
        s *= scale
        kpos = i * S + jnp.arange(S)
        s = jnp.where((kpos[None, None] <= qpos[:, :, None])[:, None], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhcs,bsl->bhcl", p.astype(dt), cc, preferred_element_type=jnp.float32)
        return m_new, l, acc

    n = jnp.minimum((jnp.max(hi) + S - 1) // S, n_blocks)
    init = (jnp.full((B, H, C), NEG), jnp.zeros((B, H, C)),
            jnp.zeros((B, H, C, L)))
    _, l, acc = jax.lax.fori_loop(0, n, body, init)
    ctx_l = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dt)
    ctx = jnp.einsum("bhcl,lhk->bchk", ctx_l, p_attn["w_uv"].astype(dt))
    return jnp.einsum("bchk,hkd->bcd", ctx, p_attn["wo"].astype(dt))


def paged_mla_decode_attention(cfg, p_attn, x_tok, pool_c, pool_krope, ptab, step, cos, sin):
    """Absorbed MLA decode over paged latent pools (blocked read)."""
    c_new, kr_new = A.mla_cache_step(p_attn, cfg, x_tok, cos, sin)
    pool_c = _page_write(pool_c, ptab, step, c_new)
    pool_krope = _page_write(pool_krope, ptab, step, kr_new)
    q_abs, q_rope = A.mla_absorbed_q(p_attn, cfg, x_tok[:, None], cos, sin)
    out = paged_mla_attend(cfg, p_attn, q_abs, q_rope, pool_c, pool_krope, ptab,
                           step, step + 1)
    return out[:, 0], pool_c, pool_krope


# ---------------------------------------------------------------------------
# Per-block decode step
# ---------------------------------------------------------------------------

def decode_block(p, cfg: ModelConfig, kind: str, x, cache, step, cos, sin,
                 cos_r=None, sin_r=None, ptab=None, live=None):
    """x (B, d) one token at per-slot positions step (B,); returns (x, cache,
    rows): the rows each held expert of a MoE layer received (``live``
    (B, 1) leaves idle slots out), None for a layer without held experts."""
    dt = cfg.dtype
    h = rmsnorm(p["ln1"], x)
    opts = linear_opts(cfg)
    paged = "k_pages" in cache or "c_pages" in cache
    if kind in ("attn", "local_attn"):
        q = qkv_proj(p["attn"]["wq"], h, dt, cfg.num_heads, cfg.head_dim, **opts)
        k = qkv_proj(p["attn"]["wk"], h, dt, cfg.num_kv_heads, cfg.head_dim, **opts)
        v = qkv_proj(p["attn"]["wv"], h, dt, cfg.num_kv_heads, cfg.head_dim, **opts)
        if cfg.qk_norm:
            q = rmsnorm(p["attn"]["q_norm"], q)
            k = rmsnorm(p["attn"]["k_norm"], k)
        q = A.apply_rope(q[:, None], cos, sin)[:, 0]
        k = A.apply_rope(k[:, None], cos, sin)[:, 0]
        if paged:  # full attention only; local_attn rings stay dense
            o, pk, pv = paged_kv_decode_attention(
                cfg, q, k, v, cache["k_pages"], cache["v_pages"], ptab, step)
            new_cache = {"k_pages": pk, "v_pages": pv}
        else:
            W = cache["k"].shape[1]
            if kind == "local_attn":
                slot = step % W  # per-slot ring buffer
                valid = jnp.minimum(step + 1, W)
            else:
                slot = step
                valid = step + 1
            o, ck, cv = kv_decode_attention(cfg, q, k, v, cache["k"], cache["v"],
                                            slot, valid)
            new_cache = {"k": ck, "v": cv}
        x = x + out_proj(p["attn"]["wo"], o, dt, cfg.d_model, **opts)
        x = x + F.ffn(p["ffn"], rmsnorm(p["ln2"], x)[:, None], cfg.mlp_type, dt,
                      dims=(cfg.d_model, cfg.d_ff), **opts)[:, 0]
        return x, new_cache, None
    if kind == "moe_attn":
        if cfg.mla:
            if paged:
                o, cc, ckr = paged_mla_decode_attention(
                    cfg, p["attn"], h, cache["c_pages"], cache["krope_pages"],
                    ptab, step, cos_r, sin_r)
                new_cache = {"c_pages": cc, "krope_pages": ckr}
            else:
                o, cc, ckr = mla_decode_attention(
                    cfg, p["attn"], h, cache["c"], cache["krope"], step, step + 1,
                    cos_r, sin_r)
                new_cache = {"c": cc, "krope": ckr}
        else:
            q = qkv_proj(p["attn"]["wq"], h, dt, cfg.num_heads, cfg.head_dim, **opts)
            k = qkv_proj(p["attn"]["wk"], h, dt, cfg.num_kv_heads, cfg.head_dim, **opts)
            v = qkv_proj(p["attn"]["wv"], h, dt, cfg.num_kv_heads, cfg.head_dim, **opts)
            if cfg.qk_norm:  # must mirror training/prefill (attention_qkv)
                q = rmsnorm(p["attn"]["q_norm"], q)
                k = rmsnorm(p["attn"]["k_norm"], k)
            q = A.apply_rope(q[:, None], cos, sin)[:, 0]
            k = A.apply_rope(k[:, None], cos, sin)[:, 0]
            if paged:
                o, pk, pv = paged_kv_decode_attention(
                    cfg, q, k, v, cache["k_pages"], cache["v_pages"], ptab, step)
                new_cache = {"k_pages": pk, "v_pages": pv}
            else:
                o, ck, cv = kv_decode_attention(cfg, q, k, v, cache["k"], cache["v"],
                                                step, step + 1)
                new_cache = {"k": ck, "v": cv}
            o = out_proj(p["attn"]["wo"], o, dt, cfg.d_model, **opts)
        x = x + o
        y, metrics = ffn_or_moe(p, cfg, rmsnorm(p["ln2"], x)[:, None], live)
        return x + y[:, 0], new_cache, metrics.get("moe_rows")
    if kind == "ssm":
        out, new_cache = S.ssm_decode_step(p["ssm"], cfg, h, cache)
        return x + out, new_cache, None
    if kind == "rglru":
        out, new_cache = R.rglru_decode_step(p["rec"], cfg, h, cache)
        x = x + out
        x = x + F.ffn(p["ffn"], rmsnorm(p["ln2"], x)[:, None], "geglu", dt,
                      dims=(cfg.d_model, cfg.d_ff), **opts)[:, 0]
        return x, new_cache, None
    raise ValueError(kind)


def _sum_rows(rows):
    """Sum of the per-expert row counts given; None where there are none."""
    rows = [r for r in rows if r is not None]
    return sum(rows) if rows else None


def _run_layers(params, cfg: ModelConfig, cache: dict, x, block):
    """The leading layers, the scanned groups and the remainder, in order.

    ``block(p, kind, x, layer_cache)`` returns (x, cache, rows): the rows
    each held expert of the layer received, or None for a layer without
    held experts. Returns (x, the new layer caches, rows summed over
    layers, or None where no layer holds experts)."""
    pattern = cfg.layer_pattern
    rows = []

    new_lead = []
    for p_layer, c in zip(params.get("lead", []), cache.get("lead", [])):
        x, nc, r = block(p_layer, "moe_attn", x, c)
        new_lead.append(nc)
        rows.append(r)

    new_groups = []
    if params["groups"]:
        def scan_body(x, xs):
            per_group_params, per_group_cache = xs
            new_caches, acc = [], []
            for pos_i, kind in enumerate(pattern):
                x, nc, r = block(per_group_params[pos_i], kind, x, per_group_cache[pos_i])
                new_caches.append(nc)
                acc.append(r)
            return x, (tuple(new_caches), _sum_rows(acc))

        x, (stacked_new, r) = jax.lax.scan(
            scan_body, x, (tuple(params["groups"]), tuple(cache["groups"])))
        new_groups = list(stacked_new)
        rows.append(None if r is None else jnp.sum(r, axis=0))

    new_rem = []
    for i, p_layer in enumerate(params["rem"]):
        x, nc, r = block(p_layer, pattern[i % len(pattern)], x, cache["rem"][i])
        new_rem.append(nc)
        rows.append(r)

    new_cache = {"groups": new_groups, "rem": new_rem}
    if "lead" in cache:
        new_cache["lead"] = new_lead
    return x, new_cache, _sum_rows(rows)


def serve_step(params, cfg: ModelConfig, cache: dict, tokens: jax.Array):
    """tokens (B,) -> (logits (B, vocab), new cache). One decode step at
    per-slot positions cache["step"] (B,). Cache layout (dense vs paged) is
    detected from the pytree. A config whose MoE layers run the held-expert
    path also returns the rows each held expert received, summed over
    layers (experts_held,) int32; a paged slot that maps no page (idle)
    sends no rows."""
    step = cache["step"]  # (B,)
    ptab = cache.get("ptab")
    ecfg = embedding_for(cfg)
    x = embed_lookup(ecfg, params["embed"], tokens).astype(cfg.dtype)
    yarn = yarn_of(cfg)
    cos, sin = rope_angles(step[:, None], cfg.head_dim, cfg.rope_theta, yarn)  # (B,1,half)
    cos_r, sin_r = rope_angles(step[:, None], cfg.rope_head_dim, cfg.rope_theta, yarn)
    live = None  # MoE: idle slots keep all-zero (trash) page rows
    if ptab is not None and cfg.n_experts:
        live = ptab[:, :1] != 0

    def block(p, kind, x, c):
        return decode_block(p, cfg, kind, x, c, step, cos, sin, cos_r, sin_r,
                            ptab, live)

    x, new_cache, rows = _run_layers(params, cfg, cache, x, block)
    x = rmsnorm(params["final_norm"], x)
    logits = lm_logits_last(params, cfg, x)
    new_cache["step"] = step + 1
    if ptab is not None:
        new_cache["ptab"] = ptab
    return (logits, new_cache) if rows is None else (logits, new_cache, rows)


# ---------------------------------------------------------------------------
# Chunked prefill: C prompt tokens per call through the full forward path
# ---------------------------------------------------------------------------

def _chunk_attention(cfg, kind, p_attn, h, cache, ptab, step, lens, cos, sin):
    """Attention for a prompt chunk h (B, C, d) continuing per-slot caches.

    Full attention: scatter the chunk's K/V into the cache (fresh positions,
    write-before-read is safe), then flash-attend over the slot's whole
    logical view with per-slot query offsets. Local-window attention:
    attend over [ring ∪ chunk] with explicit absolute key positions FIRST,
    then scatter — the chunk may overwrite ring entries that earlier chunk
    positions still need. Returns (o (B, C, H, Dh), new layer cache).
    """
    C = h.shape[1]
    q, k, v = A.attention_qkv(p_attn, cfg, h, cos, sin)
    pos = step[:, None] + jnp.arange(C)  # (B, C) absolute positions
    valid = jnp.arange(C)[None] < lens[:, None]

    if kind == "local_attn":  # dense ring buffer
        ck, cv = cache["k"], cache["v"]
        RS = ck.shape[1]
        if C > RS:
            raise ValueError(
                f"prefill_chunk={C} exceeds the local-attention ring ({RS}); "
                "clamp the chunk (serve/engine.py does) or shrink it")
        # reconstruct each ring slot's absolute position: the largest
        # p ≡ j (mod RS) with p < step_b; -1 marks never-written slots
        j = jnp.arange(RS)[None]
        base = step[:, None] - 1
        ring_pos = base - ((base - j) % RS)
        ring_pos = jnp.where((step[:, None] > 0) & (ring_pos >= 0), ring_pos, -1)
        kv_pos = jnp.concatenate(
            [ring_pos, jnp.where(valid, pos, -1)], axis=1)  # (B, RS+C)
        k_cat = jnp.concatenate([ck, k.astype(ck.dtype)], axis=1)
        v_cat = jnp.concatenate([cv, v.astype(cv.dtype)], axis=1)
        o = A.flash_attention(q, k_cat, v_cat, causal=True,
                              window=cfg.local_window, chunk=cfg.attn_chunk,
                              q_offset=step, kv_pos=kv_pos)
        ck = _scatter_chunk(ck, pos % RS, valid, k)
        cv = _scatter_chunk(cv, pos % RS, valid, v)
        return o, {"k": ck, "v": cv}

    if "k_pages" in cache:  # paged full attention
        pk = _page_write_chunk(cache["k_pages"], ptab, step, lens, k)
        pv = _page_write_chunk(cache["v_pages"], ptab, step, lens, v)
        gk, gv = gather_pages(pk, ptab), gather_pages(pv, ptab)
        o = A.flash_attention(q, gk, gv, causal=True, chunk=cfg.attn_chunk,
                              q_offset=step)
        return o, {"k_pages": pk, "v_pages": pv}

    ck = _scatter_chunk(cache["k"], pos, valid, k)  # dense full attention
    cv = _scatter_chunk(cache["v"], pos, valid, v)
    o = A.flash_attention(q, ck, cv, causal=True, chunk=cfg.attn_chunk,
                          q_offset=step)
    return o, {"k": ck, "v": cv}


def _chunk_mla_attention(cfg, p_attn, h, cache, ptab, step, lens, cos_r, sin_r):
    """Absorbed MLA over a chunk: scatter latents, then causal-masked scores
    against the slot's logical latent view (paged: :func:`paged_mla_attend`,
    blocked). h (B, C, d) -> (B, C, d)."""
    dt = cfg.dtype
    C = h.shape[1]
    c_new, kr_new = A.mla_latents(p_attn, cfg, h, cos_r, sin_r)
    q_abs, q_rope = A.mla_absorbed_q(p_attn, cfg, h, cos_r, sin_r)

    if "c_pages" in cache:
        pc = _page_write_chunk(cache["c_pages"], ptab, step, lens, c_new)
        pkr = _page_write_chunk(cache["krope_pages"], ptab, step, lens, kr_new)
        out = paged_mla_attend(cfg, p_attn, q_abs, q_rope, pc, pkr, ptab,
                               step, step + lens)
        return out, {"c_pages": pc, "krope_pages": pkr}

    pos_w = step[:, None] + jnp.arange(C)
    valid = jnp.arange(C)[None] < lens[:, None]
    cc = _scatter_chunk(cache["c"], pos_w, valid, c_new)
    ckr = _scatter_chunk(cache["krope"], pos_w, valid, kr_new)
    new_cache = {"c": cc, "krope": ckr}
    scale = A.mla_softmax_scale(cfg)

    s = jnp.einsum("bchl,bsl->bhcs", q_abs, cc, preferred_element_type=jnp.float32)
    s += jnp.einsum("bchr,bsr->bhcs", q_rope, ckr, preferred_element_type=jnp.float32)
    s *= scale
    kpos = jnp.arange(cc.shape[1])
    qpos = step[:, None] + jnp.arange(C)
    mask = kpos[None, None, :] <= qpos[:, :, None]  # (B, C, S)
    s = jnp.where(mask[:, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    ctx_l = jnp.einsum("bhcs,bsl->bchl", p.astype(dt), cc)
    ctx = jnp.einsum("bchl,lhk->bchk", ctx_l, p_attn["w_uv"].astype(dt))
    return jnp.einsum("bchk,hkd->bcd", ctx, p_attn["wo"].astype(dt)), new_cache


def prefill_block(p, cfg: ModelConfig, kind: str, x, cache, ptab, step, lens,
                  cos, sin, cos_r=None, sin_r=None):
    """x (B, C, d) chunk continuing per-slot caches at offsets step (B,);
    rows past lens_b are garbage (ignored downstream). Returns (x, cache,
    rows) as :func:`decode_block`."""
    dt = cfg.dtype
    opts = linear_opts(cfg)
    h = rmsnorm(p["ln1"], x)
    if kind in ("attn", "local_attn"):
        o, new_cache = _chunk_attention(cfg, kind, p["attn"], h, cache, ptab,
                                        step, lens, cos, sin)
        x = x + out_proj(p["attn"]["wo"], o, dt, cfg.d_model, **opts)
        x = x + F.ffn(p["ffn"], rmsnorm(p["ln2"], x), cfg.mlp_type, dt,
                      dims=(cfg.d_model, cfg.d_ff), **opts)
        return x, new_cache, None
    if kind == "moe_attn":
        if cfg.mla:
            o, new_cache = _chunk_mla_attention(cfg, p["attn"], h, cache, ptab,
                                                step, lens, cos_r, sin_r)
        else:
            o, new_cache = _chunk_attention(cfg, kind, p["attn"], h, cache, ptab,
                                            step, lens, cos, sin)
            o = out_proj(p["attn"]["wo"], o, dt, cfg.d_model, **opts)
        x = x + o
        valid = jnp.arange(x.shape[1])[None] < lens[:, None]
        y, metrics = ffn_or_moe(p, cfg, rmsnorm(p["ln2"], x), valid)
        return x + y, new_cache, metrics.get("moe_rows")
    if kind == "ssm":
        out, new_cache = S.ssm_prefill_chunk(p["ssm"], cfg, h, lens, cache)
        return x + out, new_cache, None
    if kind == "rglru":
        out, new_cache = R.rglru_prefill_chunk(p["rec"], cfg, h, lens, cache)
        x = x + out
        x = x + F.ffn(p["ffn"], rmsnorm(p["ln2"], x), "geglu", dt,
                      dims=(cfg.d_model, cfg.d_ff), **opts)
        return x, new_cache, None
    raise ValueError(kind)


def prefill_step(params, cfg: ModelConfig, cache: dict, tokens: jax.Array,
                 lens: jax.Array):
    """Chunked batched prefill: tokens (B, C) prompt chunks at per-slot
    offsets cache["step"], per-slot valid lengths lens (B,) (0 = idle slot).

    Returns (logits (B, vocab) at each slot's LAST VALID chunk position —
    meaningful only for slots whose prompt ends in this chunk — and the new
    cache with step advanced by lens), and for a held-expert MoE config the
    rows each held expert received from valid positions, as
    :func:`serve_step`.
    One call == one engine tick; a P-token prompt prefills in ⌈P/C⌉ ticks.
    """
    step = cache["step"]  # (B,)
    ptab = cache.get("ptab")
    B, C = tokens.shape
    ecfg = embedding_for(cfg)
    x = embed_lookup(ecfg, params["embed"], tokens).astype(cfg.dtype)  # (B,C,d)
    pos = step[:, None] + jnp.arange(C)  # (B, C)
    yarn = yarn_of(cfg)
    cos, sin = rope_angles(pos, cfg.head_dim, cfg.rope_theta, yarn)  # (B,C,half)
    cos_r, sin_r = rope_angles(pos, cfg.rope_head_dim, cfg.rope_theta, yarn)

    def block(p, kind, x, c):
        return prefill_block(p, cfg, kind, x, c, ptab, step, lens, cos, sin,
                             cos_r, sin_r)

    x, new_cache, rows = _run_layers(params, cfg, cache, x, block)
    x = rmsnorm(params["final_norm"], x)
    last = jnp.clip(lens - 1, 0, C - 1)
    x_last = x[jnp.arange(B), last]  # (B, d) each slot's last valid position
    logits = lm_logits_last(params, cfg, x_last)
    new_cache["step"] = step + lens
    if ptab is not None:
        new_cache["ptab"] = ptab
    return (logits, new_cache) if rows is None else (logits, new_cache, rows)

"""Decoder-only LM assembly (dense / MoE / SSM / hybrid / VLM-backbone).

Layers are grouped by the config's ``layer_pattern`` (e.g. recurrentgemma's
("rglru", "rglru", "local_attn")); full groups are *stacked* and executed
under ``lax.scan`` (one trace per pattern position — keeps HLO size and
compile time independent of depth), remainder layers run unrolled. A MoE
config's ``first_k_dense_replace`` leading layers ("lead": attention with a
dense SwiGLU FFN of width ``intermediate_size``, DeepSeek-V2's layer 0) run
unrolled before the groups; configs without them carry no "lead" key.

Three modes share the block definitions:
  * train:   full-sequence forward -> fused CE loss (logits never materialized)
  * prefill: full-sequence forward that also emits per-layer decode caches
  * decode:  single-token step updating the caches in place
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, embedding_for, head_for
from repro.core.embedding import embed_lookup, init_embedding
from repro.core.logits import head_ce_loss, head_logits, init_head
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.common import (init_rmsnorm, linear_opts, rmsnorm,
                                 rope_angles, yarn_of)

KINDS_WITH_FFN = {"attn", "local_attn", "rglru"}


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, kind: str, dense_ffn: bool = False) -> dict:
    """One layer's parameters; ``dense_ffn`` gives a "moe_attn" layer a dense
    SwiGLU FFN of ``cfg.dense_ffn_width`` in place of its MoE (a leading
    dense layer)."""
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": init_rmsnorm(cfg.d_model, cfg.param_dtype)}
    lin = dict(kind=cfg.linear_kind, order=cfg.linear_order, rank=cfg.linear_rank,
               quant=cfg.quant)
    if kind in ("attn", "local_attn"):
        p["attn"] = A.init_attention(ks[0], cfg)
        p["ln2"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
        p["ffn"] = F.init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_type,
                              cfg.param_dtype, **lin)
    elif kind == "moe_attn":
        p["attn"] = A.init_mla(ks[0], cfg) if cfg.mla else A.init_attention(ks[0], cfg)
        p["ln2"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
        if dense_ffn:
            p["ffn"] = F.init_ffn(ks[1], cfg.d_model, cfg.dense_ffn_width,
                                  "swiglu", cfg.param_dtype, **lin)
        else:
            p["moe"] = M.init_moe(ks[1], cfg)
    elif kind == "ssm":
        p["ssm"] = S.init_ssm(ks[0], cfg)
    elif kind == "rglru":
        p["rec"] = R.init_rglru(ks[0], cfg)
        p["ln2"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
        p["ffn"] = F.init_ffn(ks[1], cfg.d_model, cfg.d_ff, "geglu",
                              cfg.param_dtype, **lin)
    else:
        raise ValueError(kind)
    return p


def init_lm(key, cfg: ModelConfig) -> dict:
    pattern = cfg.layer_pattern
    n_lead = cfg.first_k_dense_replace
    n_groups = (cfg.num_layers - n_lead) // len(pattern)
    rem = (cfg.num_layers - n_lead) % len(pattern)
    keys = jax.random.split(key, 4)

    def stack(pos: int, kind: str):
        layer_keys = jax.random.split(jax.random.fold_in(keys[0], pos), n_groups)
        layers = [init_layer(k, cfg, kind) for k in layer_keys]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)

    params = {
        "embed": init_embedding(keys[1], embedding_for(cfg)),
        "groups": [stack(pos, kind) for pos, kind in enumerate(pattern)] if n_groups else [],
        "rem": [
            init_layer(jax.random.fold_in(keys[2], i), cfg, pattern[i % len(pattern)])
            for i in range(rem)
        ],
        "final_norm": init_rmsnorm(cfg.d_model, cfg.param_dtype),
    }
    if n_lead:
        params["lead"] = [
            init_layer(jax.random.fold_in(keys[2], (1 << 20) + i), cfg, "moe_attn",
                       dense_ffn=True)
            for i in range(n_lead)]
    if not getattr(cfg, "tie_embeddings", False):
        params["head"] = init_head(keys[3], head_for(cfg))
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Block application (full sequence). Returns (x, aux, cache_entry)
# ---------------------------------------------------------------------------

def apply_block(p, cfg: ModelConfig, kind: str, x, cos, sin, *, want_cache: bool,
                scan_chunk: int = 256, attn_chunk: int = 1024):
    aux = jnp.zeros((), jnp.float32)
    cache = {}
    h = rmsnorm(p["ln1"], x)
    if kind in ("attn", "local_attn"):
        q, k, v = A.attention_qkv(p["attn"], cfg, h, cos, sin)
        window = cfg.local_window if kind == "local_attn" else 0
        o = A.flash_attention(q, k, v, causal=True, window=window, chunk=attn_chunk)
        x = x + A.attention_out(p["attn"], cfg, o)
        x = x + F.ffn(p["ffn"], rmsnorm(p["ln2"], x), cfg.mlp_type, cfg.dtype,
                      dims=(cfg.d_model, cfg.d_ff), **linear_opts(cfg))
        if want_cache:
            if kind == "local_attn":  # ring buffer: last `window` positions
                W = min(cfg.local_window, k.shape[1])
                cache = {"k": k[:, -W:], "v": v[:, -W:]}
            else:
                cache = {"k": k, "v": v}
    elif kind == "moe_attn":
        if cfg.mla:
            o = A.mla_block(p["attn"], cfg, h, cos, sin, chunk=attn_chunk)
            if want_cache:
                c, kr = A.mla_latents(p["attn"], cfg, h, cos, sin)
                cache = {"c": c, "krope": kr}
        else:
            q, k, v = A.attention_qkv(p["attn"], cfg, h, cos, sin)
            o = A.flash_attention(q, k, v, causal=True, chunk=attn_chunk)
            o = A.attention_out(p["attn"], cfg, o)
            if want_cache:
                cache = {"k": k, "v": v}
        x = x + o
        y, metrics = ffn_or_moe(p, cfg, rmsnorm(p["ln2"], x))
        x = x + y
        aux = metrics.get("moe_aux", aux)
    elif kind == "ssm":
        x = x + S.ssm_block(p["ssm"], cfg, h, scan_chunk=scan_chunk)
        if want_cache:
            # prefill cache = final states; recompute cheaply for the last chunk
            cache = _ssm_prefill_cache(p["ssm"], cfg, h)
    elif kind == "rglru":
        x = x + R.rglru_block(p["rec"], cfg, h, scan_chunk=scan_chunk)
        if want_cache:
            cache = _rglru_prefill_cache(p["rec"], cfg, h)
        x = x + F.ffn(p["ffn"], rmsnorm(p["ln2"], x), "geglu", cfg.dtype,
                      dims=(cfg.d_model, cfg.d_ff), **linear_opts(cfg))
    else:
        raise ValueError(kind)
    return x, aux, cache


def ffn_or_moe(p, cfg: ModelConfig, h, valid=None):
    """The FFN half of a "moe_attn" layer: a leading layer's dense SwiGLU
    (metrics {}) or the MoE (``moe_block``'s metrics). h (B, S, d)."""
    if "ffn" in p:
        return F.ffn(p["ffn"], h, "swiglu", cfg.dtype,
                     dims=(cfg.d_model, cfg.dense_ffn_width), **linear_opts(cfg)), {}
    return M.moe_block(p["moe"], cfg, h, valid)


def _ssm_prefill_cache(p, cfg, h_in):
    di = cfg.d_inner
    xz = jnp.einsum("bsd,de->bse", h_in, p["in_proj"].astype(cfg.dtype))
    x_in = xz[..., :di]
    x_conv, conv_state = S.causal_depthwise_conv(
        x_in, p["conv_w"].astype(cfg.dtype), p["conv_b"].astype(cfg.dtype))
    x_conv = jax.nn.silu(x_conv)
    a, b, _ = S._ssm_inputs(p, cfg, x_conv)
    h0 = jnp.zeros((h_in.shape[0], di, cfg.ssm_state), jnp.float32)
    _, h_last = S.chunked_linear_scan(a, b, h0)
    return {"conv": conv_state, "h": h_last}


def _rglru_prefill_cache(p, cfg, h_in):
    u = jnp.einsum("bsd,de->bse", h_in, p["wx"].astype(cfg.dtype))
    u, conv_state = R.causal_depthwise_conv(
        u, p["conv_w"].astype(cfg.dtype), p["conv_b"].astype(cfg.dtype))
    a, drive = R._gates(p, cfg, u)
    h0 = jnp.zeros((h_in.shape[0], u.shape[-1]), jnp.float32)
    _, h_last = R.chunked_linear_scan(a, drive, h0)
    return {"conv": conv_state, "h": h_last}


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)  # "full"


def forward(params, cfg: ModelConfig, tokens, *, extra_prefix=None, want_cache=False,
            scan_chunk: int | None = None, attn_chunk: int | None = None):
    scan_chunk = scan_chunk if scan_chunk is not None else getattr(cfg, "scan_chunk", 256)
    attn_chunk = attn_chunk if attn_chunk is not None else getattr(cfg, "attn_chunk", 1024)
    """tokens (B, S_text) -> hidden (B, S, d), aux, caches.

    extra_prefix: optional (B, S_img, d) precomputed embeddings (VLM stub)
    prepended to the token embeddings.
    """
    ecfg = embedding_for(cfg)
    x = embed_lookup(ecfg, params["embed"], tokens).astype(cfg.dtype)
    if extra_prefix is not None:
        x = jnp.concatenate([extra_prefix.astype(cfg.dtype), x], axis=1)
    B, Stot = x.shape[0], x.shape[1]
    yarn = yarn_of(cfg)
    cos, sin = rope_angles(jnp.arange(Stot), cfg.head_dim, cfg.rope_theta, yarn)
    cos_r, sin_r = rope_angles(jnp.arange(Stot), cfg.rope_head_dim, cfg.rope_theta, yarn)
    pattern = cfg.layer_pattern

    def group_fn(x, group_params):
        aux = jnp.zeros((), jnp.float32)
        caches = []
        for pos, kind in enumerate(pattern):
            cs = (cos_r, sin_r) if (kind == "moe_attn" and cfg.mla) else (cos, sin)
            x, a, cache = apply_block(group_params[pos], cfg, kind, x, *cs,
                                      want_cache=want_cache, scan_chunk=scan_chunk,
                                      attn_chunk=attn_chunk)
            aux = aux + a
            caches.append(cache)
        return x, (aux, tuple(caches))

    group_fn = _remat_wrap(group_fn, cfg.remat)

    auxs = jnp.zeros((), jnp.float32)
    lead_caches = []
    for p_layer in params.get("lead", []):
        cs = (cos_r, sin_r) if cfg.mla else (cos, sin)
        x, a, cache = _remat_wrap(
            lambda x, p, cs=cs: apply_block(p, cfg, "moe_attn", x, *cs,
                                            want_cache=want_cache,
                                            attn_chunk=attn_chunk),
            cfg.remat)(x, p_layer)
        auxs = auxs + a
        lead_caches.append(cache)

    caches_stacked = None
    if params["groups"]:
        stacked = tuple(params["groups"])

        def scan_body(x, per_group):
            x, (aux, caches) = group_fn(x, per_group)
            return x, (aux, caches)

        x, (aux_seq, caches_stacked) = jax.lax.scan(scan_body, x, stacked)
        auxs = auxs + jnp.sum(aux_seq)

    rem_caches = []
    for i, p_layer in enumerate(params["rem"]):
        kind = pattern[i % len(pattern)]
        cs = (cos_r, sin_r) if (kind == "moe_attn" and cfg.mla) else (cos, sin)
        x, a, cache = apply_block(p_layer, cfg, kind, x, *cs, want_cache=want_cache,
                                  scan_chunk=scan_chunk, attn_chunk=attn_chunk)
        auxs = auxs + a
        rem_caches.append(cache)

    x = rmsnorm(params["final_norm"], x)
    caches = {"groups": caches_stacked, "rem": rem_caches} if want_cache else None
    if want_cache and "lead" in params:
        caches["lead"] = lead_caches
    return x, auxs, caches


def head_params(params, cfg):
    """Head parameter subtree (the embedding table when weights are tied)."""
    if getattr(cfg, "tie_embeddings", False):
        return params["embed"]
    return params["head"]


def constrain_ce_inputs(cfg, x, labels, mask=None):
    """Flatten tokens and pin their sharding BEFORE the streamed-CE loop.

    Without this GSPMD can leave an x reshard *inside* the vocab-tile while
    loop (loop-invariant collectives are not hoisted out of HLO whiles) —
    measured at ~1 TB/device/step on the 256-chip mesh. With
    cfg.ce_token_shard == "data_model", tokens are additionally split over
    the model axis (sequence-parallel CE: removes the model-axis redundancy
    of head compute)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PS

    from repro.parallel import meshctx

    mesh = meshctx.get_mesh()
    if mesh is None:
        x2 = x.reshape(-1, x.shape[-1])
        return x2, labels.reshape(-1), (mask.reshape(-1) if mask is not None else None)

    def dp_axes(n, names):
        axes: list[str] = []
        prod = 1
        for name in names:
            if name in mesh.axis_names and n % (prod * mesh.shape[name]) == 0:
                axes.append(name)
                prod *= mesh.shape[name]
        return tuple(axes)

    # Pin BOTH sides of the reshard boundary: without the batch-side pin the
    # backward cotangent keeps the (data, model) token sharding and the whole
    # layer-scan backward reshards per group (measured +450 GB/dev of
    # all-reduce on recurrentgemma — §Perf cell A, iter 2).
    dp = dp_axes(x.shape[0], ("pod", "data"))
    x = jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PS(dp if dp else None, None, None)))
    x2 = x.reshape(-1, x.shape[-1])
    y = labels.reshape(-1)
    m = mask.reshape(-1) if mask is not None else None
    N = x2.shape[0]
    names = ("pod", "data") + (("model",) if cfg.ce_token_shard == "data_model" else ())
    axes = dp_axes(N, names)
    tok = PS(axes) if axes else PS()
    x2 = jax.lax.with_sharding_constraint(x2, NamedSharding(mesh, PS(axes or None, None)))
    y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, tok))
    if m is not None:
        m = jax.lax.with_sharding_constraint(m, NamedSharding(mesh, tok))
    return x2, y, m


def lm_loss(params, cfg: ModelConfig, batch: dict, *, scan_chunk: int | None = None,
            attn_chunk: int | None = None) -> tuple[jax.Array, dict]:
    """batch: tokens (B,S), labels (B,S) [, image_embeds (B,P,d), label_mask]."""
    x, aux, _ = forward(params, cfg, batch["tokens"],
                        extra_prefix=batch.get("image_embeds"),
                        scan_chunk=scan_chunk, attn_chunk=attn_chunk)
    if cfg.vision_prefix:
        x = x[:, cfg.vision_prefix:]
    hcfg = head_for(cfg)
    x2, y, m = constrain_ce_inputs(cfg, x, batch["labels"], batch.get("label_mask"))
    ce = head_ce_loss(hcfg, head_params(params, cfg), x2, y, m)
    loss = ce + 0.01 * aux
    return loss, {"loss": loss, "ce": ce, "moe_aux": aux}


def lm_logits_last(params, cfg: ModelConfig, x_last: jax.Array) -> jax.Array:
    """x_last (B, d) -> (B, vocab) full logits (decode path)."""
    return head_logits(head_for(cfg), head_params(params, cfg), x_last)

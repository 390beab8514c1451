"""Unified model API: init / loss / prefill / serve dispatch + input specs."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import encdec as ED
from repro.models import transformer as T
from repro.serve import decode as D

__all__ = ["init_params", "loss_fn", "serve_step_fn", "init_cache", "input_specs",
           "prefill_fn", "prefill_chunk_fn", "shape_is_applicable"]


def init_params(key, cfg: ModelConfig) -> dict:
    if cfg.family == "encdec":
        return ED.init_encdec(key, cfg)
    return T.init_lm(key, cfg)


def loss_fn(params, cfg: ModelConfig, batch: dict, **kw):
    if cfg.family == "encdec":
        return ED.encdec_loss(params, cfg, batch)
    return T.lm_loss(params, cfg, batch, **kw)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               paged: bool = False, num_pages: int | None = None,
               page_size: int | None = None) -> dict:
    """Decode cache pytree: dense slots by default, paged KV pools with
    ``paged=True`` (serve/cache.py; num_pages counts the trash page)."""
    if cfg.family == "encdec":
        if paged:
            raise NotImplementedError("paged caches target LM decode paths")
        return ED.encdec_init_cache(cfg, batch, max_len)
    if paged:
        from repro.serve.cache import init_paged_cache, logical_pages
        if num_pages is None:  # full capacity: every slot can reach max_len
            num_pages = batch * logical_pages(max_len, page_size or cfg.page_size) + 1
        return init_paged_cache(cfg, batch, max_len, num_pages=num_pages,
                                page_size=page_size)
    return D.init_cache(cfg, batch, max_len)


def serve_step_fn(params, cfg: ModelConfig, cache: dict, tokens: jax.Array):
    """One decode step -> (logits, new cache). (The serving engine calls
    serve/decode.serve_step, which adds a MoE config's expert row counts.)"""
    if cfg.family == "encdec":
        return ED.encdec_serve_step(params, cfg, cache, tokens)
    return D.serve_step(params, cfg, cache, tokens)[:2]


def prefill_chunk_fn(params, cfg: ModelConfig, cache: dict, tokens: jax.Array,
                     lens: jax.Array):
    """Chunked batched prefill (serve/decode.prefill_step): tokens (B, C)
    at per-slot offsets, lens (B,) valid counts; -> (last-position logits,
    new cache)."""
    if cfg.family == "encdec":
        raise NotImplementedError("chunked prefill targets LM decode paths")
    return D.prefill_step(params, cfg, cache, tokens, lens)[:2]


def prefill_fn(params, cfg: ModelConfig, batch: dict):
    """Full-sequence forward emitting decode caches + last-position hidden."""
    if cfg.family == "encdec":
        enc = ED.encode(params, cfg, batch["enc_frames"])
        return enc
    x, aux, caches = T.forward(params, cfg, batch["tokens"],
                               extra_prefix=batch.get("image_embeds"), want_cache=True)
    return x[:, -1], caches


def shape_is_applicable(cfg: ModelConfig, shape: ShapeSpec) -> tuple[bool, str]:
    """Policy for the assigned (arch × shape) grid."""
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return False, "full-attention arch: 524k dense KV decode is quadratic-cost (skip per spec)"
    return True, ""


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this (arch, shape).

    train/prefill: token batches (+ stub modality embeddings);
    decode: one new token per sequence + the KV/state cache pytree.
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    if shape.mode in ("train", "prefill"):
        if cfg.family == "encdec":
            return {
                "enc_frames": jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model), cfg.dtype),
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
        if cfg.family == "vlm":
            P = cfg.vision_prefix
            return {
                "tokens": jax.ShapeDtypeStruct((B, S - P), i32),
                "image_embeds": jax.ShapeDtypeStruct((B, P, cfg.d_model), cfg.dtype),
                "labels": jax.ShapeDtypeStruct((B, S - P), i32),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }

    # decode: serve_step(params, cache, tokens) with a seq_len-deep cache
    cache = jax.eval_shape(lambda: init_cache(cfg, B, S))
    return {"cache": cache, "tokens": jax.ShapeDtypeStruct((B,), i32)}

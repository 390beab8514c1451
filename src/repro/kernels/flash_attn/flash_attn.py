"""Pallas TPU kernel: flash attention (causal / local-window / bidirectional,
GQA-aware) with online softmax over KV tiles.

VMEM tiling: grid = (batch·heads, q_tiles, kv_tiles) with the KV dimension
innermost (sequential on TPU); the output tile and the running (m, l)
statistics live in revisited VMEM blocks across KV steps. GQA is expressed in
the K/V BlockSpec index maps (query head h reads KV head h // G) — no
materialized head broadcast. Block shapes default to (128, head_dim) — MXU
aligned for head_dim ∈ {64, 96, 128, 256}.

This is the TPU-target hot path for the 8 attention-bearing archs; models use
the XLA reference (models/attention.py) on CPU, and tests assert both against
kernels/flash_attn/ref.py across shape/GQA/window sweeps in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30  # plain float: jnp constants would be captured by the kernel


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *, bq, bk, causal, window,
            sq, skv, scale):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (bq, Dh)
    k = k_ref[0].astype(jnp.float32)  # (bk, Dh)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    valid = (qpos < sq) & (kpos < skv)
    if causal:
        valid &= kpos <= qpos
    if window > 0:
        valid &= kpos > qpos - window
    s = jnp.where(valid, s, NEG)

    m_old = m_ref[...]
    l_old = l_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_old - m_new)
    l_new = l_old * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.dot(p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
    o_ref[...] = o_ref[...] * corr[None] + pv[None]
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = o_ref[...] / jnp.maximum(l_ref[...], 1e-30)[None]


def flash_attention_pallas(q, k, v, *, causal=True, window=0, block_q=128,
                           block_k=128, interpret=True):
    """q (B,Sq,H,Dh); k,v (B,Skv,KVH,Dh) -> (B,Sq,H,Dv)."""
    B, Sq, H, Dh = q.shape
    _, Skv, KVH, Dv = *k.shape[:3], v.shape[-1]
    G = H // KVH
    scale = Dh ** -0.5

    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Skv))
    sq_pad, skv_pad = -Sq % bq, -Skv % bk
    qq = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, Dh)
    kk = jnp.moveaxis(k, 2, 1).reshape(B * KVH, Skv, Dh)
    vv = jnp.moveaxis(v, 2, 1).reshape(B * KVH, Skv, Dv)
    if sq_pad:
        qq = jnp.pad(qq, ((0, 0), (0, sq_pad), (0, 0)))
    if skv_pad:
        kk = jnp.pad(kk, ((0, 0), (0, skv_pad), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, skv_pad), (0, 0)))
    nq, nk = qq.shape[1] // bq, kk.shape[1] // bk

    def kv_index(b, i, j):  # query head -> its KV head (GQA)
        return ((b // H) * KVH + (b % H) // G, j, 0)

    kernel = functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, sq=Sq, skv=Skv, scale=scale)
    out, _, _ = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, Dh), kv_index),
            pl.BlockSpec((1, bk, Dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((bq, 1), lambda b, i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda b, i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq + sq_pad, Dv), jnp.float32),
            jax.ShapeDtypeStruct((Sq + sq_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((Sq + sq_pad, 1), jnp.float32),
        ],
        name="flash_attn_fwd",
        interpret=interpret,
    )(qq, kk, vv)
    out = out[:, :Sq].reshape(B, H, Sq, Dv)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)

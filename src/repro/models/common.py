"""Shared building blocks: norms, rotary embeddings, initializers, and the
ket-aware linear-projection helpers.

A *ket linear* stores a (d_in, d_out) weight as word2ketXS-style Kronecker
factor stacks ({"factors": [(rank, q_j, t_j), ...]}, core/ketops) instead of
a dense array, and applies it with the factor chain matmul. The ``proj``
helpers below accept either representation so every attention/FFN/decode
call site stays a one-liner and a config flip (``linear_kind="ket"``)
swaps the storage model-wide.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["rmsnorm", "init_rmsnorm", "dense_init", "apply_rope", "rope_angles",
           "softcap", "linear_init", "linear_apply", "qkv_proj", "out_proj",
           "is_ket_param", "linear_opts", "yarn_get_mscale", "yarn_of"]


def init_rmsnorm(dim: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def dense_init(key, shape, dtype=jnp.float32, fan_in: int | None = None):
    """Truncated-normal-ish init scaled by 1/sqrt(fan_in)."""
    fi = fan_in if fan_in is not None else shape[0]
    return jax.random.normal(key, shape, dtype) * (1.0 / math.sqrt(fi))


def is_ket_param(p) -> bool:
    """True when a projection parameter is a ket factor dict, not an array."""
    return isinstance(p, dict)


def linear_init(key, d_in: int, d_out: int, dtype=jnp.float32, *,
                kind: str = "dense", order: int = 2, rank: int = 8,
                quant: str = "none"):
    """A (d_in, d_out) projection: dense array or ket Kronecker factors.

    The ket init targets the same O(1/sqrt(d_in)) effective-entry scale as
    ``dense_init`` (core/ketops._leaf_scale). ``quant`` stores the ket
    factors in the int8/fp8 wire format (serving-only; dense ignores it).
    """
    if kind == "dense":
        return dense_init(key, (d_in, d_out), dtype, fan_in=d_in)
    if kind != "ket":
        raise ValueError(f"unknown linear kind {kind!r}")
    from repro.core import ketops
    spec = ketops.KronSpec(in_dim=d_in, out_dim=d_out, order=order, rank=rank,
                           use_layernorm=False, dtype=dtype, quant=quant)
    return ketops.init(key, spec)


def linear_opts(cfg) -> dict:
    """The ket-linear apply knobs of a ModelConfig, as ``linear_apply`` /
    ``qkv_proj`` / ``out_proj`` / ``ffn`` kwargs: the t1 column tile, the
    kron_matmul kernel routing (tri-state ``use_kernel``, token block), and
    the mesh-native rank-sharding decision (``shard_rank``; None = the
    measured comms-profile rule, resolved by pin_kernel_blocks)."""
    return {
        "tile": getattr(cfg, "linear_tile", None),
        "use_kernel": getattr(cfg, "linear_use_kernel", None),
        "block_b": getattr(cfg, "linear_block_b", None),
        "shard_rank": getattr(cfg, "ket_shard_rank", None),
    }


def linear_apply(p, x: jax.Array, dtype, d_out: int, *, tile=None,
                 use_kernel=None, block_b=None, shard_rank=None) -> jax.Array:
    """x (..., d_in) @ p -> (..., d_out); p is a 2-D dense array or ket dict.

    ``use_kernel``/``block_b`` route ket params through the fused
    ``kron_matmul`` kernel (core/ketops ``apply_matrix_factors`` resolution);
    ``shard_rank`` pins the kernel's mesh-native rank-vs-t1 strategy under an
    ambient mesh; dense params ignore them.
    """
    if is_ket_param(p):
        from repro.core import ketops
        return ketops.apply_matrix_factors(
            p["factors"], x.astype(dtype), d_out, tile=tile,
            use_kernel=use_kernel, block_b=block_b, shard_rank=shard_rank)
    return jnp.einsum("...i,io->...o", x, p.astype(dtype))


def qkv_proj(p, x: jax.Array, dtype, n_heads: int, head_dim: int, *, tile=None,
             use_kernel=None, block_b=None, shard_rank=None) -> jax.Array:
    """x (..., d) -> (..., n_heads, head_dim). Dense p: (d, n_heads, head_dim);
    ket p: factors covering d -> n_heads·head_dim."""
    if is_ket_param(p):
        y = linear_apply(p, x, dtype, n_heads * head_dim, tile=tile,
                         use_kernel=use_kernel, block_b=block_b,
                         shard_rank=shard_rank)
        return y.reshape(*x.shape[:-1], n_heads, head_dim)
    return jnp.einsum("...d,dhk->...hk", x, p.astype(dtype))


def out_proj(p, o: jax.Array, dtype, d_model: int, *, tile=None,
             use_kernel=None, block_b=None, shard_rank=None) -> jax.Array:
    """o (..., H, Dh) -> (..., d_model). Dense p: (H, Dh, d); ket p: factors
    covering H·Dh -> d."""
    if is_ket_param(p):
        o2 = o.reshape(*o.shape[:-2], o.shape[-2] * o.shape[-1])
        return linear_apply(p, o2, dtype, d_model, tile=tile,
                            use_kernel=use_kernel, block_b=block_b,
                            shard_rank=shard_rank)
    return jnp.einsum("...hk,hkd->...d", o, p.astype(dtype))


def yarn_get_mscale(scale: float, mscale: float) -> float:
    """DeepSeek-V2's attention-scale correction for a rope stretched by
    ``scale``: 0.1·mscale·ln(scale) + 1 (1 when nothing is stretched)."""
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_of(cfg):
    """The YaRN parameters of a config as a static tuple, None without it."""
    if getattr(cfg, "rope_scaling", "none") != "yarn":
        return None
    return (cfg.rope_factor, cfg.original_max_position_embeddings,
            cfg.beta_fast, cfg.beta_slow, cfg.mscale, cfg.mscale_all_dim)


def _yarn_freqs(head_dim: int, theta: float, yarn) -> tuple[jax.Array, float]:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``: θ^(-2i/d) kept for
    the high-frequency dims, divided by the factor for the low ones, blended
    by a linear ramp over the correction range; cos/sin scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    factor, orig, beta_fast, beta_slow, mscale, mscale_all_dim = yarn
    half = head_dim // 2

    def correction_dim(rotations):
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0, 1)
    extra = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    inter = extra / factor
    keep = 1.0 - ramp
    freqs = inter * (1 - keep) + extra * keep
    scale = yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor, mscale_all_dim)
    return freqs, scale


def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                yarn=None) -> tuple[jax.Array, jax.Array]:
    """positions (..., S) -> cos/sin of shape (..., S, head_dim//2).
    ``yarn``: :func:`yarn_of`'s tuple for YaRN-scaled frequencies."""
    half = head_dim // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.cos(ang), jnp.sin(ang)
    freqs, scale = _yarn_freqs(head_dim, theta, yarn)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., S, H, Dh); cos/sin (..., S, Dh//2). Rotate-half convention."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[..., None, :], sin[..., None, :]  # broadcast over heads
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dt)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)

"""Jit'd public op: fused ket-linear matmul with a dedicated backward.

``kron_matmul`` is a ``jax.custom_vjp`` pair: the forward runs F_1 column
tiles through the rank-folded chain, and the backward walks the same tiling
a second time, recomputing the chain intermediates per tile instead of
saving them — the residuals are just ``(factors, x)``, so autodiff keeps no
``(B, r, t_1, Πq_rest)`` intermediate alive from forward to backward.

Which executor runs the chain:

  * TPU, float factors (ket linears, the Kronecker head's logits): the
    chain as whole-batch GEMMs that XLA compiles (``kron_matmul_xla`` /
    ``kron_matmul_bwd_xla``) at ``Precision.HIGHEST`` with f32
    accumulation, the tile taken from a byte budget on the chain
    intermediate (``chain_t1_tile``), not from the arguments below;
  * TPU, int8/fp8 factors (``kron_matmul_quant``): the order-2 Pallas
    bodies with the dequant fused in;
  * elsewhere: the host executors, the same tiled chain as one XLA
    computation at the tile the arguments resolve (interpret-mode grid
    emulation would cost more than the math).

The plain chain VJP is kept as an oracle and fallback:
``set_backward_impl("ref")`` or ``REPRO_KRON_BWD=ref`` route the backward
through ``jax.vjp`` of ``ref.kron_matmul_ref`` — exactly the pre-kernel
gradient path.

``kron_matmul_quant`` is the forward-only dequant-fused leg for int8/fp8
wire-format factors (core/quant): payloads + per-rank scales go into the
kernel, dequant runs per block in VMEM (per tile on the host), and fp32
factor copies are never materialized up front.

``t1_block=None`` / ``block_b=None`` (the defaults) resolve from the
autotune table (op family ``"kron_matmul"``, quantized shapes under their
payload dtype's key) at trace time, for the Pallas bodies and the host
executors.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.kron_matmul.kron_matmul import (
    kron_matmul_bwd_host,
    kron_matmul_bwd_xla,
    kron_matmul_host,
    kron_matmul_pallas,
    kron_matmul_xla,
)
from repro.kernels.kron_matmul.ref import kron_matmul_ref

_backward_impl = os.environ.get("REPRO_KRON_BWD", "kernel")  # "kernel" | "ref"
if _backward_impl not in ("kernel", "ref"):
    raise ValueError(
        f"REPRO_KRON_BWD={_backward_impl!r} — expected 'kernel' or 'ref'")


def set_backward_impl(name: str) -> None:
    """Select the backward implementation: "kernel" (default) or "ref"."""
    global _backward_impl
    if name not in ("kernel", "ref"):
        raise ValueError(f"unknown backward impl {name!r}")
    _backward_impl = name


def get_backward_impl() -> str:
    return _backward_impl


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_blocks(
    factors: Sequence[jax.Array],
    t1_block: Optional[int],
    block_b: Optional[int],
) -> tuple[int, int]:
    if t1_block is not None and t1_block <= 0:
        # the chain contract spells "untiled" as tile<=0; the kernel always
        # tiles, so an untiled request means "pick the tile yourself"
        t1_block = None
    if t1_block is not None and block_b is not None:
        return t1_block, block_b
    cfg = autotune.get_block_config(
        "kron_matmul",
        factors[0].shape[0],
        tuple(f.shape[1] for f in factors),
        tuple(f.shape[2] for f in factors),
        dtype=jnp.dtype(factors[0].dtype).name,
    )
    return (t1_block or cfg.t1_block, block_b or cfg.block_b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _kron_matmul_local(
    factors: Sequence[jax.Array],
    x: jax.Array,  # (B, d_in)
    out_dim: int,
    t1_block: Optional[int] = None,
    block_b: Optional[int] = None,
) -> jax.Array:
    if _on_tpu():
        out = kron_matmul_xla(list(factors), x)
    else:
        t1b, _ = _resolve_blocks(factors, t1_block, block_b)
        out = kron_matmul_host(list(factors), x, t1_block=t1b)
    return out[:, :out_dim].astype(x.dtype)


def kron_matmul(
    factors: Sequence[jax.Array],
    x: jax.Array,  # (B, d_in)
    out_dim: int,
    t1_block: Optional[int] = None,
    block_b: Optional[int] = None,
    shard_rank: Optional[bool] = None,
) -> jax.Array:
    """Fused ket-linear matmul with a mesh-aware route.

    Under an ambient multi-device mesh the kernel runs per shard inside
    ``meshctx.shard_map`` (kernels/shard.py): factors replicated or rank-/
    t1-sharded per the strategy rule there, with a psum at the rank fold
    for the rank strategy. ``shard_rank`` pins the rank-vs-t1 choice
    (None = the measured compute-vs-collective decision,
    ``autotune.choose_shard_rank``). Single-device (or already inside a
    shard_map body) it is the bare custom-VJP kernel.
    """
    from repro.kernels import shard
    mesh = shard.mesh_route()
    if mesh is not None:
        return shard.sharded_kron_matmul(
            mesh, list(factors), x, out_dim, t1_block, block_b,
            shard_rank=shard_rank)
    return _kron_matmul_local(factors, x, out_dim, t1_block, block_b)


def kron_matmul_quant(
    factors_q: Sequence[jax.Array],
    scales: Sequence[jax.Array],
    x: jax.Array,  # (B, d_in)
    out_dim: int,
    t1_block: Optional[int] = None,
    block_b: Optional[int] = None,
    shard_rank: Optional[bool] = None,
) -> jax.Array:
    """Dequant-fused matmul over quantized factor stacks (serving path).

    ``factors_q`` are int8/fp8 payloads ``(rank, q_j, t_j)`` with per-rank
    ``scales`` ``(rank, 1, 1)``. Forward-only — quantized payloads are a
    wire format, not trainable parameters (no VJP is defined). Mesh-aware
    like :func:`kron_matmul`; scales shard exactly like their payloads.
    """
    from repro.kernels import shard
    mesh = shard.mesh_route()
    if mesh is not None:
        return shard.sharded_kron_matmul(
            mesh, list(factors_q), x, out_dim, t1_block, block_b,
            scales=list(scales), shard_rank=shard_rank)
    t1b, bb = _resolve_blocks(factors_q, t1_block, block_b)
    if _on_tpu():
        out = kron_matmul_pallas(
            list(factors_q), x, t1_block=t1b, block_b=bb, interpret=False,
            scales=list(scales))
    else:
        out = kron_matmul_host(
            [(f, s) for f, s in zip(factors_q, scales)], x, t1_block=t1b)
    return out[:, :out_dim].astype(x.dtype)


def _fwd(factors, x, out_dim, t1_block, block_b):
    return _kron_matmul_local(factors, x, out_dim, t1_block, block_b), \
        (tuple(factors), x)


def _bwd(out_dim, t1_block, block_b, res, g):
    factors, x = res
    if _backward_impl == "ref":
        t1b, _ = _resolve_blocks(factors, t1_block, block_b)
        _, vjp = jax.vjp(
            lambda fs, xx: kron_matmul_ref(fs, xx, out_dim, tile=t1b),
            list(factors), x)
        dfactors, dx = vjp(g.astype(x.dtype))
        return (dfactors, dx)
    # zero-pad the cotangent past out_dim: those columns were sliced away,
    # so their contribution is identically zero
    T = int(math.prod(f.shape[2] for f in factors))
    g32 = g.astype(jnp.float32)
    if T > g32.shape[-1]:
        g32 = jnp.pad(g32, ((0, 0), (0, T - g32.shape[-1])))
    if _on_tpu():
        dx, dfactors = kron_matmul_bwd_xla(list(factors), x, g32)
    else:
        t1b, _ = _resolve_blocks(factors, t1_block, block_b)
        dx, dfactors = kron_matmul_bwd_host(
            list(factors), x, g32, t1_block=t1b)
    dfactors = [df.astype(f.dtype) for df, f in zip(dfactors, factors)]
    return (dfactors, dx[:, : x.shape[-1]].astype(x.dtype))


_kron_matmul_local.defvjp(_fwd, _bwd)

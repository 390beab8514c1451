"""Fused ket-linear matmul ``y = x · (Σ_k ⊗_j F_jk)`` (fwd + bwd): Pallas
kernels, the host executors of the same tiled algorithm, and the compiled
XLA route that runs it on TPU.

This is the kron_logits streaming pattern with the CE head cut off — the op
that every ket linear layer (``linear_kind="ket"``: FFN wi/wg/wo, attention
qkv/out) runs on both the train and serving-decode hot paths.

Grid ``(token_blocks, t1_blocks)``; per step:

  * the activation block ``(block_b, P)`` is revisited across the t1 axis;
  * ``F_1`` streams in ``(rank, q_1, t1_block)`` column tiles (BlockSpec);
    the remaining factors are pinned whole in VMEM — they are KBs;
  * the tile's output columns come from the **rank-folded** factor chain
    (``common.chain_fused_forward``): the last contraction folds the rank
    sum into one fat ``(B·Πt_{<n}, r·q_n) @ (r·q_n, t_n)`` GEMM, so the
    ``(block_b, rank, Πt)`` pre-sum tensor never exists and the widest live
    intermediate is the ``(block_b, rank, t1_block, Πq_rest)`` chain tile.

Backward (:func:`kron_matmul_bwd_pallas`) walks the SAME grid a second
time: per step it recomputes the tile's chain intermediates from
``(x, F-tiles)`` (nothing is saved but the primal inputs) and pushes the
output-cotangent tile through the rank-folded chain VJP
(``common.chain_fused_vjp``) — ``dx`` accumulates across t1 tiles into the
revisited ``(block_b, P)`` block, ``dF_1`` accumulates into the ``j``-th t1
slice of a constant-resident ``(rank, q_1, t_1)`` block via a dynamic
store, and the non-streamed factors accumulate into constant-resident
blocks (the kron_logits accumulation pattern verbatim).

The dequant-fused leg reads int8/fp8 payloads with their per-rank
``(rank, 1, 1)`` scales pinned in VMEM and dequantizes per block inside the
kernel — quantized factor stacks stream from HBM at 1 byte/param and never
round-trip as fp32 copies.

Order-2 factor stacks (every registry config) run bodies built on
``common.chain2_tile``: operands are pre-arranged in HBM so the TPU compiler
sees only 2-D and leading-batch matmuls, and the output tile is written as
``(t1_block, block_b, t_2)`` slabs of a ``(t_1, B, t_2)`` array that one XLA
transpose turns back into ``(B, prod t)``. Higher orders keep the generic
bodies (interpret mode).

Which code the public op (``ops.kron_matmul``) runs: on TPU, float factor
stacks take the compiled route at the end of this file
(``kron_matmul_xla`` / ``kron_matmul_bwd_xla``: the host executors' tile
loop and rank-folded contractions compiled by XLA at ``Precision.HIGHEST``,
the tile from a byte budget), which feeds the MXU whole-batch GEMMs where
the order-2 bodies feed it rank-sized ones; on TPU the Pallas bodies run
only the int8/fp8 dequant-fused leg (``ops.kron_matmul_quant``). Off-TPU
both directions run the host executors (no grid emulation). The
interpret-mode Pallas kernels stay the validation target
(tests/test_kron_matmul.py pins pallas ≡ host ≡ TPU route ≡ dense oracle).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common as C


def _fwd_kernel(x_ref, *refs, q_dims, t_dims, t1_block, quantized):
    n = len(q_dims)
    if quantized:
        factor_refs, scale_refs, out_ref = refs[:n], refs[n:2 * n], refs[2 * n]
    else:
        factor_refs, scale_refs, out_ref = refs[:n], None, refs[n]
    x = x_ref[...].astype(jnp.float32)  # (Bblk, P)
    factors = []
    for j, f_ref in enumerate(factor_refs):
        f = f_ref[...].astype(jnp.float32)
        if scale_refs is not None:  # in-VMEM dequant, (rank,1,1) broadcast
            f = f * scale_refs[j][...].astype(jnp.float32)
        factors.append(f)
    out_ref[...] = C.chain_fused_forward(x, factors).astype(out_ref.dtype)


def _bwd_kernel(x_ref, g_ref, *refs, q_dims, t_dims, t1_block):
    n = len(q_dims)
    factor_refs, (dx_ref, df0_ref, *dfrest_refs) = refs[:n], refs[n:]
    i, j = pl.program_id(0), pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)  # (Bblk, P)
    g = g_ref[...].astype(jnp.float32)  # (Bblk, t1_block·Πt_rest); 0 on pads
    factors = [f_ref[...] for f_ref in factor_refs]  # [f0 tile, rest…]

    dx, dfs = C.chain_fused_vjp(x, factors, g)

    @pl.when(j == 0)
    def _dx_init():
        dx_ref[...] = dx

    @pl.when(j > 0)
    def _dx_acc():
        dx_ref[...] += dx

    # dF_1 lives whole in VMEM across the grid; each step touches its t1 slice
    @pl.when((i == 0) & (j == 0))
    def _df0_zero():
        df0_ref[...] = jnp.zeros_like(df0_ref)

    df0_ref[:, :, pl.ds(j * t1_block, t1_block)] += dfs[0]

    for df_ref, df in zip(dfrest_refs, dfs[1:]):
        @pl.when((i == 0) & (j == 0))
        def _init(df_ref=df_ref, df=df):
            df_ref[...] = df

        @pl.when((i > 0) | (j > 0))
        def _acc(df_ref=df_ref, df=df):
            df_ref[...] += df


# ---------------------------------------------------------------------------
# order-2 bodies (the compiled TPU path; also what interpret mode runs at n=2)
# ---------------------------------------------------------------------------

def _fwd_kernel2(xs_ref, f1_ref, f2_ref, *refs, t1_block, quantized):
    s_ref, out_ref = refs if quantized else (None, refs[0])
    q2, bblk, q1 = xs_ref.shape
    xs2 = xs_ref[...].reshape(q2 * bblk, q1)
    f2b = f2_ref[...]
    scale = s_ref[...] if quantized else None

    def body(ii, carry):
        out_ref[ii] = C.chain2_tile(xs2, f1_ref[ii], f2b, scale)[0]
        return carry

    jax.lax.fori_loop(0, t1_block, body, 0)


def _bwd_kernel2(xs_ref, g_ref, f1_ref, f2_ref, dxs_ref, df1_ref, df2_ref, *,
                 t1_block):
    i, j = pl.program_id(0), pl.program_id(1)
    q2, bblk, q1 = xs_ref.shape

    @pl.when(j == 0)
    def _dx_init():
        dxs_ref[...] = jnp.zeros_like(dxs_ref)

    @pl.when((i == 0) & (j == 0))
    def _df_init():
        df1_ref[...] = jnp.zeros_like(df1_ref)
        df2_ref[...] = jnp.zeros_like(df2_ref)

    xs2 = xs_ref[...].reshape(q2 * bblk, q1)
    f2b = f2_ref[...]

    def body(ii, dxs):
        f1i = f1_ref[ii]
        _, z = C.chain2_tile(xs2, f1i, f2b)
        dx, df1, df2 = C.chain2_tile_vjp(xs2, z, f1i, f2b, g_ref[ii])
        df2_ref[...] += df2
        df1_ref[j * t1_block + ii] += df1
        return dxs + dx

    dxs = jax.lax.fori_loop(0, t1_block, body,
                            jnp.zeros((q2 * bblk, q1), jnp.float32))
    dxs_ref[...] += dxs.reshape(q2, bblk, q1)


def _matmul_call2(factors, x2, blk, nt, block_b, interpret, scales):
    q1, q2 = factors[0].shape[1], factors[1].shape[1]
    t1, t2 = factors[0].shape[2], factors[1].shape[2]
    Bp = x2.shape[0]
    f1i, f2b = C.chain2_factors(*factors)
    inputs = [C.chain2_x(x2.astype(jnp.float32), q1, q2), f1i, f2b]
    in_specs = [
        pl.BlockSpec((q2, block_b, q1), lambda i, j: (0, i, 0)),
        pl.BlockSpec((blk,) + f1i.shape[1:], lambda i, j: (j, 0, 0)),
        pl.BlockSpec(f2b.shape, lambda i, j: (0, 0, 0)),
    ]
    if scales is not None:  # both factors' per-rank scales fold into z
        inputs.append((scales[0].reshape(1, -1) * scales[1].reshape(1, -1)
                       ).astype(jnp.float32))
        in_specs.append(pl.BlockSpec(inputs[-1].shape, lambda i, j: (0, 0)))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel2, t1_block=blk,
                          quantized=scales is not None),
        grid=(Bp // block_b, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((blk, block_b, t2), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((t1, Bp, t2), jnp.float32),
        name="kron_matmul_fwd",
        interpret=interpret,
        compiler_params=C.compiler_params(interpret),
    )(*inputs)
    return jnp.transpose(out, (1, 0, 2)).reshape(Bp, t1 * t2)


def _matmul_bwd_call2(factors, x2, g32, blk, nt, block_b, interpret):
    q1, q2 = factors[0].shape[1], factors[1].shape[1]
    t1, t2 = factors[0].shape[2], factors[1].shape[2]
    Bp = x2.shape[0]
    f1i, f2b = C.chain2_factors(*[f.astype(jnp.float32) for f in factors])
    xs = C.chain2_x(x2.astype(jnp.float32), q1, q2)
    g3 = jnp.transpose(g32.reshape(Bp, t1, t2), (1, 0, 2))
    xs_spec = pl.BlockSpec((q2, block_b, q1), lambda i, j: (0, i, 0))
    dxs, df1i, df2b = pl.pallas_call(
        functools.partial(_bwd_kernel2, t1_block=blk),
        grid=(Bp // block_b, nt),
        in_specs=[
            xs_spec,
            pl.BlockSpec((blk, block_b, t2), lambda i, j: (j, i, 0)),
            pl.BlockSpec((blk,) + f1i.shape[1:], lambda i, j: (j, 0, 0)),
            pl.BlockSpec(f2b.shape, lambda i, j: (0, 0, 0)),
        ],
        out_specs=[xs_spec,
                   pl.BlockSpec(f1i.shape, lambda i, j: (0, 0, 0)),
                   pl.BlockSpec(f2b.shape, lambda i, j: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(xs.shape, jnp.float32),
                   jax.ShapeDtypeStruct(f1i.shape, jnp.float32),
                   jax.ShapeDtypeStruct(f2b.shape, jnp.float32)],
        name="kron_matmul_bwd",
        interpret=interpret,
        compiler_params=C.compiler_params(interpret),
    )(xs, g3, f1i, f2b)
    return C.chain2_dx(dxs), list(C.chain2_factor_grads(df1i, df2b))


def _prep(factors, x, block_b, t1_block):
    """Shared fwd/bwd padding + tile-size resolution (x already (B, d_in))."""
    q_dims = tuple(f.shape[1] for f in factors)
    t_dims = tuple(f.shape[2] for f in factors)
    P = int(math.prod(q_dims))
    x2 = x
    if P > x2.shape[-1]:
        x2 = jnp.pad(x2, ((0, 0), (0, P - x2.shape[-1])))
    B = x2.shape[0]
    block_b = C.clamp_block_b(block_b, B)
    bpad = -B % block_b
    if bpad:
        x2 = jnp.pad(x2, ((0, bpad), (0, 0)))
    t1 = t_dims[0]
    blk = C.largest_divisor_leq(t1, min(t1_block, t1))
    return x2, B, q_dims, t_dims, P, blk, t1 // blk, block_b


def kron_matmul_pallas(
    factors: Sequence[jax.Array],
    x: jax.Array,  # (B, d_in)
    *,
    t1_block: int = 16,
    block_b: int = 256,
    interpret: bool = True,
    scales: Optional[Sequence[jax.Array]] = None,
) -> jax.Array:
    """``x @ (Σ_k ⊗_j F_jk)`` -> ``(B, prod t)`` fp32; caller slices columns.

    With ``scales`` the factors are int8/fp8 payloads and the per-rank
    dequant is fused into the kernel body (serving fast path).
    """
    x2, B, q_dims, t_dims, P, blk, nt, block_b = _prep(
        factors, x, block_b, t1_block)
    if len(factors) == 2:
        return _matmul_call2(factors, x2, blk, nt, block_b, interpret,
                             scales)[:B]
    C.require_interpret(interpret, "kron_matmul", len(factors))
    nb = x2.shape[0] // block_b
    t_rest = int(math.prod(t_dims[1:]))
    tile_cols = blk * t_rest

    kernel = functools.partial(
        _fwd_kernel, q_dims=q_dims, t_dims=t_dims, t1_block=blk,
        quantized=scales is not None,
    )
    f0 = factors[0]
    in_specs = [
        pl.BlockSpec((block_b, P), lambda i, j: (i, 0)),
        pl.BlockSpec((f0.shape[0], q_dims[0], blk), lambda i, j: (0, 0, j)),
        *[
            pl.BlockSpec(f.shape, lambda i, j: (0, 0, 0))  # pinned in VMEM
            for f in factors[1:]
        ],
    ]
    inputs = [x2, f0, *factors[1:]]
    if scales is not None:  # (rank, 1, 1) per factor, pinned like the factors
        inputs += list(scales)
        in_specs += [pl.BlockSpec(s.shape, lambda i, j: (0, 0, 0))
                     for s in scales]
    out = pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, tile_cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], nt * tile_cols),
                                       jnp.float32),
        name="kron_matmul_fwd",
        interpret=interpret,
    )(*inputs)
    return out[:B]


def kron_matmul_bwd_pallas(
    factors: Sequence[jax.Array],
    x: jax.Array,  # (B, d_in)
    g: jax.Array,  # (B, prod t) output cotangent, zeros past out_dim
    *,
    t1_block: int = 16,
    block_b: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, list[jax.Array]]:
    """Dedicated backward: ``(dL/dx (B, P), [dL/dF_j])``, all fp32."""
    rank = factors[0].shape[0]
    x2, B, q_dims, t_dims, P, blk, nt, block_b = _prep(
        factors, x, block_b, t1_block)
    nb = x2.shape[0] // block_b
    t_rest = int(math.prod(t_dims[1:]))
    tile_cols = blk * t_rest
    bpad = x2.shape[0] - B
    g32 = jnp.pad(g.astype(jnp.float32), ((0, bpad), (0, 0)))  # pad rows inert
    if len(factors) == 2:
        dx, dfs = _matmul_bwd_call2(factors, x2, g32, blk, nt, block_b,
                                    interpret)
        return dx[:B], dfs
    C.require_interpret(interpret, "kron_matmul backward", len(factors))

    kernel = functools.partial(
        _bwd_kernel, q_dims=q_dims, t_dims=t_dims, t1_block=blk,
    )
    f0 = factors[0]
    dx, df0, *dfrest = pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((block_b, P), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, tile_cols), lambda i, j: (i, j)),
            pl.BlockSpec((rank, q_dims[0], blk), lambda i, j: (0, 0, j)),
            *[
                pl.BlockSpec(f.shape, lambda i, j: (0, 0, 0))
                for f in factors[1:]
            ],
        ],
        out_specs=[
            pl.BlockSpec((block_b, P), lambda i, j: (i, 0)),
            pl.BlockSpec(f0.shape, lambda i, j: (0, 0, 0)),
            *[
                pl.BlockSpec(f.shape, lambda i, j: (0, 0, 0))
                for f in factors[1:]
            ],
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, jnp.float32),
            *[jax.ShapeDtypeStruct(f.shape, jnp.float32) for f in factors],
        ],
        name="kron_matmul_bwd",
        interpret=interpret,
    )(x2, g32, f0, *factors[1:])
    return dx[:B], [df0, *dfrest]


# ---------------------------------------------------------------------------
# Host executors — the same tiled algorithm as one fused XLA computation
# ---------------------------------------------------------------------------

def kron_matmul_host(
    factors: Sequence,
    x: jax.Array,  # (B, d_in)
    *,
    t1_block: int = 16,
) -> jax.Array:
    """Host (non-Pallas) executor of the SAME forward algorithm.

    Off-TPU the interpret-mode grid emulation costs more than the math; this
    runs the identical t1-tiled, rank-folded chain (shared ``common``
    helpers) as a statically unrolled loop inside one XLA computation — the
    widest intermediate stays the per-tile ``(B, r, t1_block, Πq_rest)``
    chain tile, cache-resident instead of round-tripping through RAM.
    Factors may be quantized ``(payload, scale)`` pairs (dequant at use).
    Returns ``(B, prod t)`` fp32; the caller slices columns.
    """
    q_dims, t_dims = C.factor_dims(factors)
    P = int(math.prod(q_dims))
    x2 = x
    if P > x2.shape[-1]:
        x2 = jnp.pad(x2, ((0, 0), (0, P - x2.shape[-1])))
    t1 = t_dims[0]
    blk = C.largest_divisor_leq(t1, min(t1_block, t1))
    if blk == t1:
        return C.chain_fused_forward(x2, list(factors))
    f0, rest = factors[0], list(factors[1:])
    outs = [
        C.chain_fused_forward(
            x2, [C.slice_factor_t(f0, slice(i * blk, (i + 1) * blk))] + rest)
        for i in range(t1 // blk)
    ]
    # chain column order is mixed-radix over (t1, t2, …): contiguous t1
    # tiles are contiguous column blocks
    return jnp.concatenate(outs, axis=-1)


def kron_matmul_bwd_host(
    factors: Sequence[jax.Array],
    x: jax.Array,  # (B, d_in)
    g: jax.Array,  # (B, prod t) output cotangent, zeros past out_dim
    *,
    t1_block: int = 16,
) -> tuple[jax.Array, list[jax.Array]]:
    """Host executor of the dedicated backward: per t1 tile, recompute the
    chain intermediates and run the rank-folded VJP; ``dx`` and the
    non-streamed ``dF_j`` accumulate across tiles, ``dF_1`` concatenates its
    column tiles. Returns ``(dx (B, P), [dF_j])``, all fp32."""
    q_dims, t_dims = C.factor_dims(factors)
    P = int(math.prod(q_dims))
    x2 = x.astype(jnp.float32)
    if P > x2.shape[-1]:
        x2 = jnp.pad(x2, ((0, 0), (0, P - x2.shape[-1])))
    g32 = g.astype(jnp.float32)
    t1 = t_dims[0]
    blk = C.largest_divisor_leq(t1, min(t1_block, t1))
    if blk == t1:
        return C.chain_fused_vjp(x2, list(factors), g32)
    t_rest = int(math.prod(t_dims[1:]))
    f0, rest = factors[0], list(factors[1:])
    dx = jnp.zeros_like(x2)
    df0_tiles = []
    dfrest = None
    for i in range(t1 // blk):
        gi = g32[:, i * blk * t_rest:(i + 1) * blk * t_rest]
        dxi, dfs = C.chain_fused_vjp(
            x2, [C.slice_factor_t(f0, slice(i * blk, (i + 1) * blk))] + rest, gi)
        dx = dx + dxi
        df0_tiles.append(dfs[0])
        dfrest = (dfs[1:] if dfrest is None
                  else [a + b for a, b in zip(dfrest, dfs[1:])])
    return dx, [jnp.concatenate(df0_tiles, axis=2), *(dfrest or [])]


# ---------------------------------------------------------------------------
# Compiled route on TPU: the host executors, compiled by XLA
# ---------------------------------------------------------------------------
#
# On the chip the order-2 Pallas bodies above feed the MXU rank-sized
# operands (an N of rank per first-digit column, a K of rank per second
# step, the rank axis padded to 128 lanes in VMEM). The same rank-folded
# chain as two whole-batch GEMMs — ``(B·Πq_rest, q_1) @ (q_1, r·t_1)``, then
# ``(B·Πt_{<n}, r·q_n) @ (r·q_n, t_n)`` — is what XLA compiles instead, at
# the kernels' f32 contract (HIGHEST, f32 accumulation). Unquantized factor
# stacks take this route on TPU; the int8/fp8 leg keeps the Pallas bodies.

# Bytes of the f32 chain intermediate ``(B, r, t1_tile, Πq_rest)`` one tile
# may hold in HBM. At 256 MiB the rank-8 ket linears of qwen3-1.7b at 4096
# tokens a call run untiled except the FFN's (d_model -> d_ff in two t_1
# tiles, d_ff -> d_model in two), and its 8x1024 train step compiles to
# 12.7 GiB of temporaries for a v5e; 384 MiB takes 14.8 GiB, 512 MiB 15.9.
CHAIN_BUDGET_BYTES = 256 << 20


def chain_t1_tile(rank: int, q_dims: Sequence[int], t_dims: Sequence[int],
                  n_tokens: int) -> int:
    """t_1 tile of the compiled route: t_1 itself (untiled) when the widest
    f32 chain intermediate of ``n_tokens`` rows fits ``CHAIN_BUDGET_BYTES``,
    else the widest divisor of t_1 whose tile fits (1 at least)."""
    n = len(q_dims)
    col = 4 * n_tokens * rank * max(
        (math.prod(t_dims[1:i]) * math.prod(q_dims[i:]) for i in range(1, n)),
        default=1)  # bytes per t_1 column, widest chain step
    return C.largest_divisor_leq(t_dims[0], max(1, CHAIN_BUDGET_BYTES // col))


def _route_tile(factors, x) -> int:
    q_dims, t_dims = C.factor_dims(factors)
    return chain_t1_tile(factors[0].shape[0], q_dims, t_dims, x.shape[0])


def kron_matmul_xla(factors: Sequence[jax.Array], x: jax.Array) -> jax.Array:
    """The compiled TPU route's forward: :func:`kron_matmul_host` tiled by
    :func:`chain_t1_tile`, under the ``kron_matmul_fwd`` scope (a profile's
    op metadata names the layer). Returns ``(B, prod t)`` fp32; the caller
    slices columns."""
    with jax.named_scope("kron_matmul_fwd"):
        return kron_matmul_host(factors, x, t1_block=_route_tile(factors, x))


def kron_matmul_bwd_xla(
    factors: Sequence[jax.Array],
    x: jax.Array,  # (B, d_in)
    g: jax.Array,  # (B, prod t) output cotangent, zeros past out_dim
) -> tuple[jax.Array, list[jax.Array]]:
    """The compiled TPU route's backward: :func:`kron_matmul_bwd_host` at
    the same tile, under the ``kron_matmul_bwd`` scope. Returns
    ``(dx (B, P), [dF_j])``, all fp32."""
    with jax.named_scope("kron_matmul_bwd"):
        return kron_matmul_bwd_host(factors, x, g,
                                    t1_block=_route_tile(factors, x))

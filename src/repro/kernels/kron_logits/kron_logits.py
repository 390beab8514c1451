"""Pallas TPU kernels: fused Kronecker vocab head + online-softmax CE (fwd+bwd).

The memory-critical op of large-vocab LMs is ``loss = CE(h @ W_unembed)``:
the (tokens × vocab) logits tensor (e.g. 1M × 256k) dwarfs every other
activation. With a word2ketXS (pure Kronecker) head the logits tile for a
block of first-digit columns is two small matmuls per rank, so we stream
vocabulary tiles through VMEM and keep only the running (max, sumexp,
label-logit) statistics — logits never reach HBM.

Grid: (token_blocks, t1_blocks); the t1 axis is the innermost (sequential on
TPU) dimension and accumulates into revisited (Bblk,) output blocks, exactly
the flash-attention pattern applied to the vocabulary axis.

Forward, per grid step:   tile logits via the factor chain  (MXU)
                          online (m, l, ylogit) update      (VPU)

Backward (:func:`kron_ce_bwd_pallas`) walks the SAME grid a second time: it
recomputes each tile's logits from (x, factor tiles), turns them into the
softmax cotangent ``g · (softmax − onehot)`` using the forward's saved
``(m, l)`` statistics, and pushes it through the analytic chain VJP
(`common.chain_vjp`) — ``dh`` accumulates across t1 tiles into the revisited
(Bblk, P) block, the non-streamed factors accumulate into constant-resident
(rank, q_j, t_j) blocks, and ``dF_1`` accumulates into the ``j``-th t1 slice
of a constant-resident (rank, q_1, t_1) block via a dynamic store. Logits
never reach HBM in the backward either.

Order-2 heads (every registry config) run bodies built on
``common.chain2_tile`` — the same grid and online softmax, with operands
pre-arranged so the TPU compiler sees only 2-D and leading-batch matmuls
(see common.py). The per-token statistics ride as ``(B, 1)`` columns, the
block shape the compiler accepts for per-row values. Higher orders keep
the generic bodies (interpret mode).
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common as C


def _fwd_kernel(
    x_ref, y_ref, *refs, q_dims, t_dims, t1_block, vocab_size
):
    *factor_refs, m_ref, l_ref, ylog_ref = refs
    j = pl.program_id(1)
    bblk = x_ref.shape[0]
    t_rest = int(math.prod(t_dims[1:]))
    tile_cols = t1_block * t_rest

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full((bblk,), -1e30, jnp.float32)
        l_ref[...] = jnp.zeros((bblk,), jnp.float32)
        ylog_ref[...] = jnp.zeros((bblk,), jnp.float32)

    x = x_ref[...].astype(jnp.float32)  # (Bblk, P)
    logits = C.chain_forward(x, [f_ref[...] for f_ref in factor_refs])

    col0 = j * tile_cols
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, tile_cols), 1)
    logits = jnp.where(cols < vocab_size, logits, -1e30)

    y = y_ref[...]  # (Bblk,) int32
    m_old, l_old, ylog = m_ref[...], l_ref[...], ylog_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=-1))
    l_new = l_old * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(logits - m_new[:, None]), axis=-1
    )
    in_tile = (y >= col0) & (y < col0 + tile_cols)
    # gather the label logit with a one-hot dot (MXU-friendly, no vmem gather)
    local = jnp.clip(y - col0, 0, tile_cols - 1)
    picked = jnp.sum(C.one_hot(local, tile_cols) * logits, axis=-1)
    m_ref[...] = m_new
    l_ref[...] = l_new
    ylog_ref[...] = jnp.where(in_tile, picked, ylog)


def _bwd_kernel(
    x_ref, y_ref, g_ref, m_ref, l_ref, *refs,
    q_dims, t_dims, t1_block, vocab_size,
):
    n = len(q_dims)
    factor_refs, (dx_ref, df0_ref, *dfrest_refs) = refs[:n], refs[n:]
    i, j = pl.program_id(0), pl.program_id(1)
    t_rest = int(math.prod(t_dims[1:]))
    tile_cols = t1_block * t_rest

    x = x_ref[...].astype(jnp.float32)  # (Bblk, P)
    y = y_ref[...]
    g = g_ref[...].astype(jnp.float32)  # (Bblk,) loss cotangent; 0 on pad rows
    m = m_ref[...]
    l = l_ref[...]

    factors = [f_ref[...] for f_ref in factor_refs]  # [f0 tile, rest…]
    logits = C.chain_forward(x, factors)  # (Bblk, tile_cols)

    col0 = j * tile_cols
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, tile_cols), 1)
    # softmax from the saved forward statistics (no second online pass)
    p = jnp.exp(logits - m[:, None]) / l[:, None]
    p = jnp.where(cols < vocab_size, p, 0.0)
    in_tile = (y >= col0) & (y < col0 + tile_cols)
    local = jnp.clip(y - col0, 0, tile_cols - 1)
    onehot = C.one_hot(local, tile_cols) * in_tile[:, None].astype(jnp.float32)
    dlogits = g[:, None] * (p - onehot)

    dx, dfs = C.chain_vjp(x, factors, dlogits)

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

def _tile_cols(j, ii, t1_block, t2, bblk):
    col0 = (j * t1_block + ii) * t2
    return col0 + jax.lax.broadcasted_iota(jnp.int32, (bblk, t2), 1)


def _fwd_kernel2(xs_ref, y_ref, f1_ref, f2_ref, m_ref, l_ref, ylog_ref, *,
                 t1_block, vocab_size):
    j = pl.program_id(1)
    q2, bblk, q1 = xs_ref.shape
    t2 = f2_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full((bblk, 1), -1e30, jnp.float32)
        l_ref[...] = jnp.zeros((bblk, 1), jnp.float32)
        ylog_ref[...] = jnp.zeros((bblk, 1), jnp.float32)

    xs2 = xs_ref[...].reshape(q2 * bblk, q1)
    f2b = f2_ref[...]
    y = y_ref[...]  # (Bblk, 1) int32

    def body(ii, carry):
        m, l, ylog = carry
        logits, _ = C.chain2_tile(xs2, f1_ref[ii], f2b)
        cols = _tile_cols(j, ii, t1_block, t2, bblk)
        logits = jnp.where(cols < vocab_size, logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, -1, keepdims=True))
        l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(logits - m_new), -1,
                                             keepdims=True)
        hit = cols == y
        picked = jnp.sum(jnp.where(hit, logits, 0.0), -1, keepdims=True)
        in_tile = jnp.max(hit.astype(jnp.float32), -1, keepdims=True) > 0
        return m_new, l, jnp.where(in_tile, picked, ylog)

    m, l, ylog = jax.lax.fori_loop(
        0, t1_block, body, (m_ref[...], l_ref[...], ylog_ref[...]))
    m_ref[...] = m
    l_ref[...] = l
    ylog_ref[...] = ylog


def _bwd_kernel2(xs_ref, y_ref, g_ref, m_ref, l_ref, f1_ref, f2_ref,
                 dxs_ref, df1_ref, df2_ref, *, t1_block, vocab_size):
    i, j = pl.program_id(0), pl.program_id(1)
    q2, bblk, q1 = xs_ref.shape
    t2 = f2_ref.shape[2]

    @pl.when(j == 0)
    def _dx_init():
        dxs_ref[...] = jnp.zeros_like(dxs_ref)

    @pl.when((i == 0) & (j == 0))
    def _df_init():
        df1_ref[...] = jnp.zeros_like(df1_ref)
        df2_ref[...] = jnp.zeros_like(df2_ref)

    xs2 = xs_ref[...].reshape(q2 * bblk, q1)
    f2b = f2_ref[...]
    y, g, m, l = y_ref[...], g_ref[...], m_ref[...], l_ref[...]

    def body(ii, dxs):
        f1i = f1_ref[ii]
        logits, z = C.chain2_tile(xs2, f1i, f2b)
        cols = _tile_cols(j, ii, t1_block, t2, bblk)
        # softmax from the saved forward statistics (no second online pass)
        p = jnp.where(cols < vocab_size, jnp.exp(logits - m) / l, 0.0)
        dlogits = g * (p - (cols == y).astype(jnp.float32))
        dx, df1, df2 = C.chain2_tile_vjp(xs2, z, f1i, f2b, dlogits)
        df2_ref[...] += df2
        df1_ref[j * t1_block + ii] += df1
        return dxs + dx

    dxs = jax.lax.fori_loop(0, t1_block, body,
                            jnp.zeros((q2 * bblk, q1), jnp.float32))
    dxs_ref[...] += dxs.reshape(q2, bblk, q1)


def _specs2(factors, block_b, blk):
    """Shared order-2 operand layouts + BlockSpecs (fwd and bwd)."""
    q1, q2 = factors[0].shape[1], factors[1].shape[1]
    f1i, f2b = C.chain2_factors(*factors)
    xs_spec = pl.BlockSpec((q2, block_b, q1), lambda i, j: (0, i, 0))
    col = pl.BlockSpec((block_b, 1), lambda i, j: (i, 0))
    f1_spec = pl.BlockSpec((blk,) + f1i.shape[1:], lambda i, j: (j, 0, 0))
    f2_spec = pl.BlockSpec(f2b.shape, lambda i, j: (0, 0, 0))
    return f1i, f2b, xs_spec, col, f1_spec, f2_spec


def _ce_call2(factors, x, labels, vocab_size, blk, nb, nt, block_b,
              interpret):
    q1, q2 = factors[0].shape[1], factors[1].shape[1]
    f1i, f2b, xs_spec, col, f1_spec, f2_spec = _specs2(factors, block_b, blk)
    kernel = functools.partial(_fwd_kernel2, t1_block=blk,
                               vocab_size=vocab_size)
    m, l, ylog = pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[xs_spec, col, f1_spec, f2_spec],
        out_specs=[col] * 3,
        out_shape=[jax.ShapeDtypeStruct((x.shape[0], 1), jnp.float32)] * 3,
        name="fused_kron_ce_fwd",
        interpret=interpret,
        compiler_params=C.compiler_params(interpret),
    )(C.chain2_x(x, q1, q2), labels[:, None].astype(jnp.int32), f1i, f2b)
    return m[:, 0], l[:, 0], ylog[:, 0]


def _ce_bwd_call2(factors, x, labels, g32, m32, l32, vocab_size, blk, nb, nt,
                  block_b, interpret):
    q1, q2 = factors[0].shape[1], factors[1].shape[1]
    f1i, f2b, xs_spec, col, f1_spec, f2_spec = _specs2(factors, block_b, blk)
    xs = C.chain2_x(x, q1, q2)
    kernel = functools.partial(_bwd_kernel2, t1_block=blk,
                               vocab_size=vocab_size)
    dxs, df1i, df2b = pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[xs_spec, col, col, col, col, f1_spec, f2_spec],
        out_specs=[xs_spec,
                   pl.BlockSpec(f1i.shape, lambda i, j: (0, 0, 0)),
                   pl.BlockSpec(f2b.shape, lambda i, j: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(xs.shape, jnp.float32),
                   jax.ShapeDtypeStruct(f1i.shape, jnp.float32),
                   jax.ShapeDtypeStruct(f2b.shape, jnp.float32)],
        name="fused_kron_ce_bwd",
        interpret=interpret,
        compiler_params=C.compiler_params(interpret),
    )(xs, labels[:, None].astype(jnp.int32), g32[:, None], m32[:, None],
      l32[:, None], f1i, f2b)
    return C.chain2_dx(dxs), list(C.chain2_factor_grads(df1i, df2b))


def _prep(factors, h, labels, block_b, t1_block):
    """Shared fwd/bwd padding + tile-size resolution."""
    q_dims = tuple(f.shape[1] for f in factors)
    t_dims = tuple(f.shape[2] for f in factors)
    P = int(math.prod(q_dims))
    x = h.astype(jnp.float32)
    if P > x.shape[-1]:
        x = jnp.pad(x, ((0, 0), (0, P - x.shape[-1])))
    B = x.shape[0]
    block_b = C.clamp_block_b(block_b, B)
    bpad = -B % block_b
    if bpad:
        x = jnp.pad(x, ((0, bpad), (0, 0)))
        labels = jnp.pad(labels, (0, bpad))
    t1 = t_dims[0]
    blk = C.largest_divisor_leq(t1, min(t1_block, t1))
    return x, labels, B, q_dims, t_dims, P, blk, t1 // blk, block_b


def kron_ce_pallas(
    factors: Sequence[jax.Array],
    h: jax.Array,  # (B, p)
    labels: jax.Array,  # (B,) int32
    vocab_size: int,
    *,
    t1_block: int = 16,
    block_b: int = 256,
    interpret: bool = True,
    return_stats: bool = False,
):
    """Per-token CE losses (B,) without materializing logits.

    With ``return_stats=True`` also returns the online-softmax ``(m, l)``
    statistics — the residuals the backward kernel needs.
    """
    rank = factors[0].shape[0]
    x, labels, B, q_dims, t_dims, P, blk, nt, block_b = _prep(
        factors, h, labels, block_b, t1_block)
    nb = x.shape[0] // block_b
    if len(factors) == 2:
        m, l, ylog = _ce_call2(factors, x, labels, vocab_size, blk, nb, nt,
                               block_b, interpret)
        loss = (m + jnp.log(l) - ylog)[:B]
        return (loss, m[:B], l[:B]) if return_stats else loss
    C.require_interpret(interpret, "fused_kron_ce", len(factors))

    kernel = functools.partial(
        _fwd_kernel, q_dims=q_dims, t_dims=t_dims, t1_block=blk,
        vocab_size=vocab_size,
    )
    out_shape = [jax.ShapeDtypeStruct((x.shape[0],), jnp.float32)] * 3
    f0 = factors[0]
    m, l, ylog = pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((block_b, P), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b,), lambda i, j: (i,)),
            pl.BlockSpec((rank, q_dims[0], blk), lambda i, j: (0, 0, j)),
            *[
                pl.BlockSpec(f.shape, lambda i, j: (0, 0, 0))
                for f in factors[1:]
            ],
        ],
        out_specs=[pl.BlockSpec((block_b,), lambda i, j: (i,))] * 3,
        out_shape=out_shape,
        name="fused_kron_ce_fwd",
        interpret=interpret,
    )(x, labels, f0, *factors[1:])
    loss = (m + jnp.log(l) - ylog)[:B]
    if return_stats:
        return loss, m[:B], l[:B]
    return loss


def kron_ce_bwd_pallas(
    factors: Sequence[jax.Array],
    h: jax.Array,  # (B, p)
    labels: jax.Array,  # (B,) int32
    m: jax.Array,  # (B,) forward online-max residual
    l: jax.Array,  # (B,) forward sumexp residual
    g: jax.Array,  # (B,) per-token loss cotangent
    vocab_size: int,
    *,
    t1_block: int = 16,
    block_b: int = 256,
    interpret: bool = True,
) -> tuple[list[jax.Array], jax.Array]:
    """Dedicated backward: ``([dL/dF_j], dL/dh)``, both fp32."""
    rank = factors[0].shape[0]
    x, labels, B, q_dims, t_dims, P, blk, nt, block_b = _prep(
        factors, h, labels, block_b, t1_block)
    nb = x.shape[0] // block_b
    bpad = x.shape[0] - B
    g32 = jnp.pad(g.astype(jnp.float32), (0, bpad))  # zero ⇒ pad rows inert
    m32 = jnp.pad(m.astype(jnp.float32), (0, bpad))
    l32 = jnp.pad(l.astype(jnp.float32), (0, bpad), constant_values=1.0)
    if len(factors) == 2:
        dx, dfs = _ce_bwd_call2(factors, x, labels, g32, m32, l32, vocab_size,
                                blk, nb, nt, block_b, interpret)
        return dfs, dx[:B, : h.shape[-1]]
    C.require_interpret(interpret, "fused_kron_ce backward", len(factors))

    kernel = functools.partial(
        _bwd_kernel, q_dims=q_dims, t_dims=t_dims, t1_block=blk,
        vocab_size=vocab_size,
    )
    f0 = factors[0]
    dx, df0, *dfrest = pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((block_b, P), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b,), lambda i, j: (i,)),
            pl.BlockSpec((block_b,), lambda i, j: (i,)),
            pl.BlockSpec((block_b,), lambda i, j: (i,)),
            pl.BlockSpec((block_b,), lambda i, j: (i,)),
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
            jax.ShapeDtypeStruct(x.shape, jnp.float32),
            *[jax.ShapeDtypeStruct(f.shape, jnp.float32) for f in factors],
        ],
        name="fused_kron_ce_bwd",
        interpret=interpret,
    )(x, labels, g32, m32, l32, f0, *factors[1:])
    dh = dx[:B, : h.shape[-1]]
    return [df0, *dfrest], dh

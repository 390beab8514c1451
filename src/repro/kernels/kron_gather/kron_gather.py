"""Pallas TPU kernels: fused word2ketXS embedding lookup (fwd + bwd).

TPU adaptation of the paper's "lazy tensor" row reconstruction (§3.2):

  * the factor stacks F_j (rank, q_j, t_j) are a few KB–MB — they are pinned
    whole in VMEM for every grid step (BlockSpec with constant index_map), so
    the embedding's parameter traffic never touches HBM bandwidth after the
    first load;
  * the per-token factor-column gather is executed as a one-hot matmul
    ``one_hot(digit_j, t_j) @ F_j^T`` — dense MXU work instead of a
    scatter/gather (TPUs have no efficient VMEM pointer-chase);
  * the balanced tensor-product tree (with the paper's non-affine LayerNorm at
    each node) and the rank-sum run entirely in registers/VMEM and write only
    the (block_b, prod_q) output tile.

Three entry points share one 1-D token-block grid (digits are computed
in-kernel with integer ops from the token ids):

  * :func:`kron_gather_pallas` — inference forward;
  * :func:`kron_gather_fwd_pallas` — forward that additionally stashes the
    per-node LayerNorm statistics (mean, rstd) as a ``(B, 2·#nodes, rank)``
    residual for the backward kernel;
  * :func:`kron_gather_bwd_pallas` — dedicated backward: re-gathers the
    leaves (one-hot matmuls), replays the tree with the *saved* statistics
    (bitwise-consistent, no second moment pass), runs the reverse tree sweep
    in VMEM, and scatters ``dL/dF_j`` as ``one_hotᵀ @ dleaf`` matmuls into
    factor-shaped accumulators that stay resident across the whole grid.

Order-2 factorizations (every registry config) run a second body written in
the TPU compiler's layout vocabulary: per-rank 2-D matmuls only, with no
in-kernel reshape of the lane axis (Mosaic refuses those). The digits ride
as ``(B, 1)`` columns, a rank slice of a factor is a leading-axis index
(gathered by a ``one_hot @ F_rᵀ`` matmul), the Kronecker product of the two leaves is spelled
as ``(u @ E_a) * (v @ E_b)`` with 0/1 expansion matrices ``E_a (q_1, P)``,
``E_b (q_2, P)``, and the root LayerNorm moments come from the separable
identities ``mean(u⊗v) = mean(u)·mean(v)``, ``mean((u⊗v)²) =
mean(u²)·mean(v²)``. Higher orders keep the generic body (interpret mode).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import kron as K
from repro.kernels import common as C


def _factors_2d(factor_refs, t_dims, rank, q_dims, scale_refs=None):
    """Factor refs -> fp32 ``(t_j, rank·q_j)`` views; with ``scale_refs``
    (quantized wire format) the dequant runs here, in-kernel per block —
    int8/fp8 payloads never round-trip through HBM as floats."""
    out = []
    for j, (f_ref, qj, tj) in enumerate(zip(factor_refs, q_dims, t_dims)):
        f = f_ref[...].astype(jnp.float32)
        if scale_refs is not None:
            f = f * scale_refs[j][...].astype(jnp.float32)  # (r,1,1) broadcast
        out.append(f.transpose(2, 0, 1).reshape(tj, rank * qj))
    return out


def _fwd_kernel(ids_ref, *refs, t_dims, rank, q_dims, use_layernorm, with_stats,
                quantized=False):
    n = len(q_dims)
    if with_stats:
        *refs, out_ref, stats_ref = refs
    else:
        *refs, out_ref = refs
    factor_refs, scale_refs = (refs[:n], refs[n:]) if quantized else (refs, None)
    ids = ids_ref[...]  # (Bblk,) int32

    f2d = _factors_2d(factor_refs, t_dims, rank, q_dims, scale_refs)
    leaves, _ = C.gather_leaves(ids, f2d, t_dims, rank, q_dims)
    root, (_, means, rstds) = C.tree_forward(leaves, use_layernorm)
    out_ref[...] = jnp.sum(root, axis=1).astype(out_ref.dtype)

    if with_stats:
        # residual layout: stats[:, 2k] = mean_k, stats[:, 2k+1] = rstd_k
        cols = []
        for mu, rstd in zip(means, rstds):
            cols += [mu[..., 0], rstd[..., 0]]  # (Bblk, rank) each
        stats_ref[...] = jnp.stack(cols, axis=1)  # (Bblk, 2·nodes, rank)


def _bwd_kernel(ids_ref, g_ref, *refs, t_dims, rank, q_dims, use_layernorm):
    if use_layernorm:
        stats_ref, *refs = refs
    n = len(q_dims)
    factor_refs, dfactor_refs = refs[:n], refs[n:]
    ids = ids_ref[...]
    g = g_ref[...].astype(jnp.float32)  # (Bblk, P); zero rows for pad tokens
    bblk = ids.shape[0]

    f2d = _factors_2d(factor_refs, t_dims, rank, q_dims)
    leaves, onehots = C.gather_leaves(ids, f2d, t_dims, rank, q_dims)

    stats = None
    if use_layernorm:
        raw = stats_ref[...].astype(jnp.float32)  # (Bblk, 2·nodes, rank)
        n_nodes = C.num_tree_nodes(n)
        means = [raw[:, 2 * k, :][..., None] for k in range(n_nodes)]
        rstds = [raw[:, 2 * k + 1, :][..., None] for k in range(n_nodes)]
        stats = (means, rstds)
    # replay below the root only — the separable root split in tree_backward
    # never materializes the (Bblk, rank, P) root or its cotangent
    _, res = C.tree_forward(leaves, use_layernorm, stats=stats, skip_root=True)
    dleaves = C.tree_backward(n, g, use_layernorm, res)

    i = pl.program_id(0)
    for df_ref, oh, dleaf, qj in zip(dfactor_refs, onehots, dleaves, q_dims):
        # scatter-add as a matmul: (t_j, Bblk) @ (Bblk, rank·q_j)
        contrib = jnp.dot(oh.T, dleaf.reshape(bblk, rank * qj),
                          preferred_element_type=jnp.float32)
        contrib = contrib.reshape(oh.shape[1], rank, qj).transpose(1, 2, 0)

        @pl.when(i == 0)
        def _init(df_ref=df_ref, contrib=contrib):
            df_ref[...] = contrib

        @pl.when(i > 0)
        def _acc(df_ref=df_ref, contrib=contrib):
            df_ref[...] += contrib


# ---------------------------------------------------------------------------
# order-2 bodies (the compiled TPU path; also what interpret mode runs at n=2)
# ---------------------------------------------------------------------------

def _onehot2(d_ref, t):
    d = d_ref[...]  # (Bblk, 1) int32 digit column
    iota = jax.lax.broadcasted_iota(jnp.int32, (d.shape[0], t), 1)
    return (d == iota).astype(jnp.float32)


def _rank_leaves2(rr, oh1, oh2, f1_ref, f2_ref, s_ref):
    """Leaves of rank ``rr``: ``u (Bblk, q_1)``, ``v (Bblk, q_2)``."""
    f1 = f1_ref[rr].astype(jnp.float32)  # (q_1, t_1)
    f2 = f2_ref[rr].astype(jnp.float32)  # (q_2, t_2)
    if s_ref is not None:  # quantized payloads: per-rank scales from SMEM
        f1 = f1 * s_ref[0, rr]
        f2 = f2 * s_ref[1, rr]
    return C.dot_nt(oh1, f1), C.dot_nt(oh2, f2)


def _root_moments2(u, v, eps):
    """Root-node LayerNorm moments of ``u ⊗ v`` from the leaf moments."""
    mu = jnp.mean(u, -1, keepdims=True) * jnp.mean(v, -1, keepdims=True)
    ez2 = (jnp.mean(u * u, -1, keepdims=True)
           * jnp.mean(v * v, -1, keepdims=True))
    return mu, jax.lax.rsqrt(jnp.maximum(ez2 - mu * mu, 0.0) + eps)


def _fwd_kernel2(d1_ref, d2_ref, f1_ref, f2_ref, ea_ref, eb_ref, *refs,
                 rank, use_layernorm, with_stats, quantized):
    s_ref = refs[0] if quantized else None
    refs = refs[1:] if quantized else refs
    out_ref = refs[0]
    t1, t2 = f1_ref.shape[2], f2_ref.shape[2]
    oh1, oh2 = _onehot2(d1_ref, t1), _onehot2(d2_ref, t2)
    ea, eb = ea_ref[...], eb_ref[...]
    bblk, P = out_ref.shape
    lane_r = jax.lax.broadcasted_iota(jnp.int32, (1, rank), 1)

    def body(rr, carry):
        acc, mus, rstds = carry
        u, v = _rank_leaves2(rr, oh1, oh2, f1_ref, f2_ref, s_ref)
        if not use_layernorm:
            return acc + C.dot(u, ea) * C.dot(v, eb), mus, rstds
        mu, rstd = _root_moments2(u, v, C.LN_EPS)
        acc = acc + C.dot(rstd * u, ea) * C.dot(v, eb) - rstd * mu
        sel = (lane_r == rr).astype(jnp.float32)
        return acc, mus + mu * sel, rstds + rstd * sel

    zr = jnp.zeros((bblk, rank), jnp.float32)
    acc, mus, rstds = jax.lax.fori_loop(
        0, rank, body, (jnp.zeros((bblk, P), jnp.float32), zr, zr))
    out_ref[...] = acc.astype(out_ref.dtype)
    if with_stats:
        refs[1][...] = mus
        refs[2][...] = rstds


def _bwd_kernel2(d1_ref, d2_ref, g_ref, *refs, rank, use_layernorm):
    if use_layernorm:
        mu_ref, rstd_ref, *refs = refs
    f1_ref, f2_ref, ea_ref, eb_ref, df1_ref, df2_ref = refs
    t1, t2 = f1_ref.shape[2], f2_ref.shape[2]
    oh1, oh2 = _onehot2(d1_ref, t1), _onehot2(d2_ref, t2)
    ea, eb = ea_ref[...], eb_ref[...]
    g = g_ref[...]  # (Bblk, P); zero rows for pad tokens
    P = g.shape[1]
    mbar = jnp.mean(g, -1, keepdims=True)
    lane_r = jax.lax.broadcasted_iota(jnp.int32, (1, rank), 1)

    @pl.when(pl.program_id(0) == 0)
    def _zero():
        df1_ref[...] = jnp.zeros_like(df1_ref)
        df2_ref[...] = jnp.zeros_like(df2_ref)

    def body(rr, carry):
        u, v = _rank_leaves2(rr, oh1, oh2, f1_ref, f2_ref, None)
        ue, ve = C.dot(u, ea), C.dot(v, eb)  # (Bblk, P) each
        Dv = C.dot_nt(ve * g, ea)  # Σ_b D[a, b] v[b]  -> (Bblk, q_1)
        Du = C.dot_nt(ue * g, eb)  # Σ_a D[a, b] u[a]  -> (Bblk, q_2)
        if use_layernorm:
            sel = (lane_r == rr).astype(jnp.float32)
            mu = jnp.sum(mu_ref[...] * sel, -1, keepdims=True)
            rstd = jnp.sum(rstd_ref[...] * sel, -1, keepdims=True)
            su1 = jnp.sum(u, -1, keepdims=True)
            su2 = jnp.sum(u * u, -1, keepdims=True)
            sv1 = jnp.sum(v, -1, keepdims=True)
            sv2 = jnp.sum(v * v, -1, keepdims=True)
            c = rstd * (jnp.sum(u * Dv, -1, keepdims=True) - mu * P * mbar) / P
            du = rstd * ((Dv - mbar * sv1) - c * rstd * (u * sv2 - mu * sv1))
            dv = rstd * ((Du - mbar * su1) - c * rstd * (v * su2 - mu * su1))
        else:
            du, dv = Dv, Du
        df1_ref[rr] += C.dot_tn(du, oh1)  # scatter-add as dleafᵀ @ one_hot
        df2_ref[rr] += C.dot_tn(dv, oh2)
        return carry

    jax.lax.fori_loop(0, rank, body, 0)


def _expanders2(q1: int, q2: int):
    """0/1 matrices with ``(u @ E_a) * (v @ E_b) == (u ⊗ v)`` row-wise."""
    p = jnp.arange(q1 * q2)
    ea = (p[None, :] // q2 == jnp.arange(q1)[:, None]).astype(jnp.float32)
    eb = (p[None, :] % q2 == jnp.arange(q2)[:, None]).astype(jnp.float32)
    return ea, eb


def _prep2(factors, ids, block_b):
    """Order-2 operands: digit columns and the expanders."""
    t_dims = tuple(f.shape[2] for f in factors)
    ids_p, B = _pad_ids(ids, block_b)
    d1, d2 = K.mixed_radix_digits(ids_p.astype(jnp.int32), t_dims)
    ea, eb = _expanders2(factors[0].shape[1], factors[1].shape[1])
    return B, d1[:, None], d2[:, None], ea, eb


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _gather_call2(factors, ids, use_layernorm, block_b, interpret, out_dtype,
                  *, with_stats, scales=None):
    rank = factors[0].shape[0]
    P = factors[0].shape[1] * factors[1].shape[1]
    B, d1, d2, ea, eb = _prep2(factors, ids, block_b)
    Bp = d1.shape[0]
    with_stats = with_stats and use_layernorm
    col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    inputs = [d1, d2, *factors, ea, eb]
    in_specs = [col, col, *[_whole(x.shape) for x in inputs[2:]]]
    if scales is not None:
        inputs.append(jnp.stack([s.reshape(rank) for s in scales]
                                ).astype(jnp.float32))  # (2, rank)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    out_shape = [jax.ShapeDtypeStruct((Bp, P), out_dtype)]
    out_specs = [pl.BlockSpec((block_b, P), lambda i: (i, 0))]
    if with_stats:
        out_shape += [jax.ShapeDtypeStruct((Bp, rank), jnp.float32)] * 2
        out_specs += [pl.BlockSpec((block_b, rank), lambda i: (i, 0))] * 2
    kernel = functools.partial(
        _fwd_kernel2, rank=rank, use_layernorm=use_layernorm,
        with_stats=with_stats, quantized=scales is not None)
    outs = pl.pallas_call(
        kernel, grid=(Bp // block_b,), in_specs=in_specs,
        name="kron_gather_fwd",
        out_specs=out_specs, out_shape=out_shape, interpret=interpret,
        compiler_params=C.compiler_params(interpret),
    )(*inputs)
    if with_stats:
        # the generic residual layout: stats[:, 0] = mean, stats[:, 1] = rstd
        return outs[0][:B], jnp.stack([outs[1], outs[2]], axis=1)[:B]
    return outs[0][:B]


def _bwd_call2(factors, ids, g32, stats, use_layernorm, block_b, interpret):
    rank = factors[0].shape[0]
    B, d1, d2, ea, eb = _prep2(factors, ids, block_b)
    Bp = d1.shape[0]
    g32 = jnp.pad(g32, ((0, Bp - B), (0, 0)))
    col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    inputs = [d1, d2, g32]
    in_specs = [col, col, pl.BlockSpec((block_b, g32.shape[1]), lambda i: (i, 0))]
    if use_layernorm:
        st = jnp.pad(stats.astype(jnp.float32), ((0, Bp - B), (0, 0), (0, 0)))
        inputs += [st[:, 0], st[:, 1]]
        in_specs += [pl.BlockSpec((block_b, rank), lambda i: (i, 0))] * 2
    inputs += [*factors, ea, eb]
    in_specs += [_whole(x.shape) for x in (*factors, ea, eb)]
    kernel = functools.partial(_bwd_kernel2, rank=rank,
                               use_layernorm=use_layernorm)
    dfactors = pl.pallas_call(
        kernel, grid=(Bp // block_b,), in_specs=in_specs,
        out_specs=[_whole(f.shape) for f in factors],
        out_shape=[jax.ShapeDtypeStruct(f.shape, jnp.float32) for f in factors],
        name="kron_gather_bwd",
        interpret=interpret, compiler_params=C.compiler_params(interpret),
    )(*inputs)
    return list(dfactors)


def _pad_ids(ids: jax.Array, block_b: int):
    B = ids.shape[0]
    bpad = -B % block_b
    return (jnp.pad(ids, (0, bpad)) if bpad else ids), B


def kron_gather_pallas(
    factors: Sequence[jax.Array],
    ids: jax.Array,
    *,
    use_layernorm: bool = True,
    block_b: int = 256,
    interpret: bool = True,
    out_dtype=jnp.float32,
    scales: Optional[Sequence[jax.Array]] = None,
) -> jax.Array:
    """ids (B,) -> (B, prod q). Caller slices to embed_dim and reshapes.

    With ``scales`` the factors are quantized payloads (int8/fp8) and the
    per-rank dequant is fused into the kernel body (serving fast path).
    """
    out = _gather_call(factors, ids, use_layernorm, block_b, interpret,
                       out_dtype, with_stats=False, scales=scales)
    return out


def kron_gather_fwd_pallas(
    factors: Sequence[jax.Array],
    ids: jax.Array,
    *,
    use_layernorm: bool = True,
    block_b: int = 256,
    interpret: bool = True,
    out_dtype=jnp.float32,
) -> tuple[jax.Array, Optional[jax.Array]]:
    """Forward + stashed per-node LN stats ``(B, 2·#nodes, rank)`` (or None)."""
    if not use_layernorm:  # no moments to stash — the bwd recompute is exact
        out = _gather_call(factors, ids, use_layernorm, block_b, interpret,
                           out_dtype, with_stats=False)
        return out, None
    return _gather_call(factors, ids, use_layernorm, block_b, interpret,
                        out_dtype, with_stats=True)


def _gather_call(factors, ids, use_layernorm, block_b, interpret, out_dtype,
                 *, with_stats, scales=None):
    if len(factors) == 2:
        return _gather_call2(factors, ids, use_layernorm, block_b, interpret,
                             out_dtype, with_stats=with_stats, scales=scales)
    C.require_interpret(interpret, "kron_gather", len(factors))
    rank = factors[0].shape[0]
    q_dims = tuple(f.shape[1] for f in factors)
    t_dims = tuple(f.shape[2] for f in factors)
    P = int(math.prod(q_dims))
    ids_p, B = _pad_ids(ids, block_b)
    n_blocks = ids_p.shape[0] // block_b
    n_nodes = C.num_tree_nodes(len(factors))

    kernel = functools.partial(
        _fwd_kernel, t_dims=t_dims, rank=rank, q_dims=q_dims,
        use_layernorm=use_layernorm, with_stats=with_stats,
        quantized=scales is not None,
    )
    out_shape = [jax.ShapeDtypeStruct((ids_p.shape[0], P), out_dtype)]
    out_specs = [pl.BlockSpec((block_b, P), lambda i: (i, 0))]
    if with_stats:
        out_shape.append(
            jax.ShapeDtypeStruct((ids_p.shape[0], 2 * n_nodes, rank), jnp.float32))
        out_specs.append(
            pl.BlockSpec((block_b, 2 * n_nodes, rank), lambda i: (i, 0, 0)))
    inputs = [ids_p, *factors]
    in_specs = [
        pl.BlockSpec((block_b,), lambda i: (i,)),
        *[
            pl.BlockSpec(f.shape, lambda i: (0, 0, 0))  # whole factor in VMEM
            for f in factors
        ],
    ]
    if scales is not None:  # (rank, 1, 1) per factor, pinned like the factors
        inputs += list(scales)
        in_specs += [pl.BlockSpec(s.shape, lambda i: (0, 0, 0)) for s in scales]
    outs = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=out_specs if with_stats else out_specs[0],
        out_shape=out_shape if with_stats else out_shape[0],
        name="kron_gather_fwd",
        interpret=interpret,
    )(*inputs)
    if with_stats:
        return outs[0][:B], outs[1][:B]
    return outs[:B]


def kron_gather_bwd_pallas(
    factors: Sequence[jax.Array],
    ids: jax.Array,
    g: jax.Array,  # (B, embed_dim) output cotangent
    stats: Optional[jax.Array],  # (B, 2·#nodes, rank) from the fwd, or None
    *,
    use_layernorm: bool = True,
    block_b: int = 256,
    interpret: bool = True,
) -> list[jax.Array]:
    """Dedicated backward: returns fp32 ``dL/dF_j`` (rank, q_j, t_j) each."""
    rank = factors[0].shape[0]
    q_dims = tuple(f.shape[1] for f in factors)
    t_dims = tuple(f.shape[2] for f in factors)
    P = int(math.prod(q_dims))
    n_nodes = C.num_tree_nodes(len(factors))

    g32 = g.astype(jnp.float32)
    # pad the cotangent to (padded_B, P): the slice-to-embed_dim columns and
    # the pad tokens both contribute exactly zero
    g32 = jnp.pad(g32, ((0, 0), (0, P - g32.shape[1])))
    if len(factors) == 2:
        return _bwd_call2(factors, ids, g32, stats, use_layernorm, block_b,
                          interpret)
    C.require_interpret(interpret, "kron_gather backward", len(factors))
    ids_p, B = _pad_ids(ids, block_b)
    bpad = ids_p.shape[0] - B
    g32 = jnp.pad(g32, ((0, bpad), (0, 0)))
    inputs = [ids_p, g32]
    in_specs = [
        pl.BlockSpec((block_b,), lambda i: (i,)),
        pl.BlockSpec((block_b, P), lambda i: (i, 0)),
    ]
    if use_layernorm:
        assert stats is not None, "LayerNorm backward needs the stashed stats"
        stats_p = jnp.pad(stats, ((0, bpad), (0, 0), (0, 0)))
        inputs.append(stats_p)
        in_specs.append(
            pl.BlockSpec((block_b, 2 * n_nodes, rank), lambda i: (i, 0, 0)))
    inputs += list(factors)
    in_specs += [pl.BlockSpec(f.shape, lambda i: (0, 0, 0)) for f in factors]

    kernel = functools.partial(
        _bwd_kernel, t_dims=t_dims, rank=rank, q_dims=q_dims,
        use_layernorm=use_layernorm,
    )
    dfactors = pl.pallas_call(
        kernel,
        grid=(ids_p.shape[0] // block_b,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(f.shape, lambda i: (0, 0, 0)) for f in factors],
        out_shape=[jax.ShapeDtypeStruct(f.shape, jnp.float32) for f in factors],
        name="kron_gather_bwd",
        interpret=interpret,
    )(*inputs)
    return list(dfactors)


def kron_gather_bwd_host(
    factors: Sequence[jax.Array],
    ids: jax.Array,
    g: jax.Array,  # (B, embed_dim) output cotangent
    stats: Optional[jax.Array],  # (B, 2·#nodes, rank) from the fwd, or None
    *,
    use_layernorm: bool = True,
) -> list[jax.Array]:
    """Host (non-Pallas) executor of the SAME dedicated backward algorithm.

    Off-TPU the interpret-mode grid emulation costs more than the math; this
    runs the identical sweep (shared ``common`` helpers, incl. the separable
    root split) as one fused XLA computation, with the two TPU-isms swapped
    for their host-optimal primitives: leaves via ``jnp.take`` instead of
    one-hot matmuls, ``dF_j`` via ``segment_sum`` instead of ``one_hotᵀ @``.
    Used by ``ops.kron_gather``'s backward whenever the forward ran in
    interpret mode; returns fp32 ``dL/dF_j``.
    """
    rank = factors[0].shape[0]
    q_dims = tuple(f.shape[1] for f in factors)
    t_dims = tuple(f.shape[2] for f in factors)
    P = int(math.prod(q_dims))
    B = ids.shape[0]
    n = len(factors)

    digits = K.mixed_radix_digits(ids, t_dims)
    leaves = [
        jnp.moveaxis(jnp.take(f, d, axis=2), (0, 1), (-2, -1)).astype(jnp.float32)
        for f, d in zip(factors, digits)
    ]
    sts = None
    if use_layernorm:
        assert stats is not None, "LayerNorm backward needs the stashed stats"
        raw = stats.astype(jnp.float32)
        n_nodes = C.num_tree_nodes(n)
        sts = ([raw[:, 2 * k, :][..., None] for k in range(n_nodes)],
               [raw[:, 2 * k + 1, :][..., None] for k in range(n_nodes)])
    _, res = C.tree_forward(leaves, use_layernorm, stats=sts, skip_root=True)
    g32 = g.astype(jnp.float32)
    g32 = jnp.pad(g32, ((0, 0), (0, P - g32.shape[1])))
    dleaves = C.tree_backward(n, g32, use_layernorm, res)
    dfactors = []
    for d, dleaf, qj, tj in zip(digits, dleaves, q_dims, t_dims):
        seg = jax.ops.segment_sum(dleaf.reshape(B, rank * qj), d, num_segments=tj)
        dfactors.append(seg.reshape(tj, rank, qj).transpose(1, 2, 0))
    return dfactors

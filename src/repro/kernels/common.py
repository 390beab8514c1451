"""Shared math for the kron_gather / kron_logits Pallas kernels.

Everything here is plain jnp on *values* (not refs) so the same code runs
inside a Pallas kernel body, in interpret mode, and in the pure-JAX oracles:

  * :func:`one_hot` — the iota-compare one-hot used to phrase every gather /
    scatter as an MXU matmul (TPUs have no efficient VMEM pointer-chase);
  * the balanced tensor-product tree (paper §2.3) as an explicit
    forward-with-residuals / backward-sweep pair, so the backward kernel can
    re-walk the exact pairing structure of the forward;
  * the Kronecker factor chain (lazy ``x · (Σ_k ⊗_j F_jk)``) as a
    forward / analytic-VJP pair for the CE kernels.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

LN_EPS = 1e-5

# Scoped-VMEM ceiling handed to the TPU compiler for the kron kernels. The
# default scope (16 MiB on v5e) is a quarter of what one core holds; the
# pinned factor stacks plus the per-step chain intermediates of the
# order-2 bodies need more at the heads' widths (autotune sizes block_b
# against ``autotune._BUDGET_BYTES``, well inside this).
VMEM_LIMIT_BYTES = 96 << 20

_HI = jax.lax.Precision.HIGHEST


def compiler_params(interpret: bool):
    """TPU compiler parameters for a compiled kernel (None in interpret mode)."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def require_interpret(interpret: bool, op: str, order: int) -> None:
    """The generic (any-order) kernel bodies reshape the lane axis, which
    the TPU compiler refuses; only the order-2 bodies compile."""
    if not interpret:
        raise NotImplementedError(
            f"{op}: the compiled TPU kernel covers order-2 factorizations; "
            f"order {order} runs only in interpret mode")


def clamp_block_b(block_b: int, n_tokens: int) -> int:
    """Token block no larger than the (8-rounded) token count: a decode
    call with 8 tokens must not pad itself to a 256-token block."""
    return max(1, min(int(block_b), -(-max(n_tokens, 1) // 8) * 8))


def largest_divisor_leq(n: int, k: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``k`` (``1 ≤ k``; ``k ≥ n`` -> n).

    O(√n) divisor enumeration — the tile-clamping rule every column-tiled
    consumer (ketops ``apply_matrix_factors``, the kron_logits/kron_matmul
    kernels) shares, replacing the old O(t1) decrement loop.
    """
    if k <= 0:
        raise ValueError(f"tile clamp needs k >= 1, got {k}")
    if k >= n:
        return n
    best = 1
    i = 1
    while i * i <= n:
        if n % i == 0:
            if i <= k and i > best:
                best = i
            j = n // i
            if j <= k and j > best:
                best = j
        i += 1
    return best


def as_f32_factor(f) -> jax.Array:
    """Factor-at-use dequant: a plain array casts to fp32; a quantized
    ``(payload, scale)`` pair dequantizes here, at its consumption point, so
    the chain never holds more than one expanded fp32 factor copy."""
    if isinstance(f, tuple):
        payload, scale = f
        return payload.astype(jnp.float32) * scale
    return f.astype(jnp.float32)


def factor_dims(factors) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(q_dims, t_dims) of a factor list whose entries are ``(rank, q, t)``
    arrays or quantized ``(payload, scale)`` pairs."""
    shapes = [(f[0].shape if isinstance(f, tuple) else f.shape) for f in factors]
    return tuple(s[1] for s in shapes), tuple(s[2] for s in shapes)


def slice_factor_t(f, sl: slice):
    """Slice a factor's t axis; quantized ``(payload, scale)`` pairs slice
    the payload and keep the ``(rank, 1, 1)`` scale. The one home of the
    wire-format-aware tile slice (ketops chain, kron_matmul kernel + ref)."""
    if isinstance(f, tuple):
        return (f[0][:, :, sl], f[1])
    return f[:, :, sl]


def one_hot(idx: jax.Array, n: int, dtype=jnp.float32) -> jax.Array:
    """(B,) int -> (B, n) one-hot via broadcasted iota (MXU-friendly)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    return (idx[:, None] == iota).astype(dtype)


# ---------------------------------------------------------------------------
# Balanced tensor-product tree (fwd with residuals + bwd sweep)
# ---------------------------------------------------------------------------

def tree_plan(n_leaves: int) -> tuple[list, tuple]:
    """Pairing structure of the balanced kron tree.

    Returns ``(plan, root)`` where ``plan`` is a list of
    ``(node_token, left_token, right_token)`` in creation order and tokens are
    ``("leaf", j)`` / ``("node", k)``. ``k`` is also the index into the
    stashed per-node statistics. An odd leftover at any level carries up
    unchanged (same rule as the forward kernels).
    """
    level: list = [("leaf", j) for j in range(n_leaves)]
    plan = []
    k = 0
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level) - 1, 2):
            tok = ("node", k)
            plan.append((tok, level[i], level[i + 1]))
            nxt.append(tok)
            k += 1
        if len(level) % 2 == 1:
            nxt.append(level[-1])
        level = nxt
    return plan, level[0]


def num_tree_nodes(n_leaves: int) -> int:
    return n_leaves - 1


def _pair_kron(a: jax.Array, b: jax.Array) -> jax.Array:
    out = a[..., :, None] * b[..., None, :]
    return out.reshape(*a.shape[:-1], a.shape[-1] * b.shape[-1])


def tree_forward(
    leaves: Sequence[jax.Array],
    use_layernorm: bool,
    eps: float = LN_EPS,
    stats: Optional[tuple[Sequence[jax.Array], Sequence[jax.Array]]] = None,
    skip_root: bool = False,
):
    """Balanced kron tree over (..., q_j) leaves with optional per-node LN.

    Returns ``(root, residuals)`` where residuals hold every node value plus
    the LN moments — exactly what :func:`tree_backward` needs. When ``stats``
    (``(means, rstds)`` lists indexed by node id) is given, the saved moments
    are used instead of recomputing them, making a backward-pass recompute
    bitwise-consistent with the forward kernel.

    ``skip_root=True`` skips materializing the final (root) node value — the
    backward's separable root split (see :func:`tree_backward`) never reads
    it, and at the root the node is the full (..., prod q) tensor, so the
    replay then touches nothing larger than the children. Requires saved
    ``stats`` when LayerNorm is on (the root moments can't be recomputed
    without the root value).
    """
    plan, root = tree_plan(len(leaves))
    vals: dict = {("leaf", j): v for j, v in enumerate(leaves)}
    means: list = []
    rstds: list = []
    for idx, (tok, lt, rt) in enumerate(plan):
        last = idx == len(plan) - 1
        if skip_root and last:
            if use_layernorm:
                assert stats is not None, "skip_root with LN needs saved stats"
                means.append(stats[0][tok[1]])
                rstds.append(stats[1][tok[1]])
            vals[tok] = None
            break
        z = _pair_kron(vals[lt], vals[rt])
        if use_layernorm:
            k = tok[1]
            if stats is not None:
                mu, rstd = stats[0][k], stats[1][k]
            else:
                mu = jnp.mean(z, axis=-1, keepdims=True)
                rstd = jax.lax.rsqrt(jnp.var(z, axis=-1, keepdims=True) + eps)
            z = (z - mu) * rstd
            means.append(mu)
            rstds.append(rstd)
        vals[tok] = z
    return vals[root], (vals, means, rstds)


def tree_backward(
    n_leaves: int,
    d_root2d: jax.Array,
    use_layernorm: bool,
    residuals,
) -> list[jax.Array]:
    """Cotangents of the tree leaves given the *rank-summed* root cotangent.

    ``d_root2d`` is the ``(B, prod q)`` output cotangent (identical across
    rank — the forward ends in a rank sum). ``residuals`` is the second
    return of :func:`tree_forward` (``skip_root=True`` is fine).

    The root split exploits the Kronecker structure: with ``z = u ⊗ v``,
    every LN-VJP term factors through the children
    (``Σ(u⊗v) = Σu·Σv``, ``Σ(u⊗v)² = Σu²·Σv²``, and the dense cotangent
    contraction is one batched matmul against the reshaped ``(B, M, N)``
    cotangent), so **no (B, rank, prod q) intermediate is ever built** —
    the dominant backward traffic drops from O(B·r·P) to O(B·P).
    Lower nodes (≤ √P wide) use the generic dense sweep; their LN VJP is the
    non-affine form ``dz = rstd · (dy − mean(dy) − y · mean(dy · y))``.
    """
    vals, means, rstds = residuals
    plan, root = tree_plan(n_leaves)
    if not plan:  # single leaf: root == leaf, cotangent broadcasts over rank
        leaf = vals[("leaf", 0)]
        return [jnp.broadcast_to(d_root2d[:, None, :], leaf.shape)]

    # ---- separable root split (no O(B·r·P) intermediates) -----------------
    tok, lt, rt = plan[-1]
    u, v = vals[lt], vals[rt]  # (B, r, M), (B, r, N)
    bsz, M = d_root2d.shape[0], u.shape[-1]
    N = v.shape[-1]
    pn = M * N
    D = d_root2d.reshape(bsz, M, N)
    Dv = jnp.einsum("bmn,brn->brm", D, v, preferred_element_type=jnp.float32)
    Du = jnp.einsum("bmn,brm->brn", D, u, preferred_element_type=jnp.float32)
    if use_layernorm:
        mu, rstd = means[tok[1]], rstds[tok[1]]  # (B, r, 1)
        su1 = jnp.sum(u, -1, keepdims=True)
        su2 = jnp.sum(u * u, -1, keepdims=True)
        sv1 = jnp.sum(v, -1, keepdims=True)
        sv2 = jnp.sum(v * v, -1, keepdims=True)
        mbar = jnp.mean(d_root2d, -1)[:, None, None]  # (B, 1, 1)
        # c = mean(dy·y) with y = rstd·(u⊗v − μ):  Σ dy·y = rstd·(uᵀDv − μ·P·m̄)
        udv = jnp.sum(u * Dv, -1, keepdims=True)
        c = rstd * (udv - mu * pn * mbar) / pn
        du = rstd * ((Dv - mbar * sv1) - c * rstd * (u * sv2 - mu * sv1))
        dv = rstd * ((Du - mbar * su1) - c * rstd * (v * su2 - mu * su1))
    else:
        du, dv = Dv, Du
    cot = {lt: du, rt: dv}

    # ---- generic dense sweep below the root -------------------------------
    for tok, lt, rt in reversed(plan[:-1]):
        dy = cot.pop(tok)
        a, b = vals[lt], vals[rt]
        if use_layernorm:
            y = vals[tok]
            rstd = rstds[tok[1]]
            dz = rstd * (
                dy
                - jnp.mean(dy, axis=-1, keepdims=True)
                - y * jnp.mean(dy * y, axis=-1, keepdims=True)
            )
        else:
            dz = dy
        dzr = dz.reshape(*a.shape, b.shape[-1])
        cot[lt] = jnp.sum(dzr * b[..., None, :], axis=-1)
        cot[rt] = jnp.sum(dzr * a[..., :, None], axis=-2)
    return [cot[("leaf", j)] for j in range(n_leaves)]


# ---------------------------------------------------------------------------
# Kronecker factor chain (fwd + analytic VJP)
# ---------------------------------------------------------------------------

def chain_forward(x: jax.Array, factors: Sequence) -> jax.Array:
    """``x (B, P)`` → logits ``(B, prod t)`` fp32 via the factor chain.

    Column order is ``(t_1, …, t_n)`` row-major, matching mixed-radix ids.
    Factors may be tiles (e.g. F_1 pre-sliced along t_1) — only their own
    shapes matter — and may be quantized ``(payload, scale)`` pairs
    (dequantized at their use point, see :func:`as_f32_factor`). ``x`` keeps
    its dtype on the way in; every contraction accumulates in fp32.
    """
    q_dims, _ = factor_dims(factors)
    n = len(factors)
    b = x.shape[0]
    z = x.reshape((b,) + q_dims)
    for i, f in enumerate(factors):
        if i == 0:
            z = jnp.einsum("bq...,rqt->brt...", z, as_f32_factor(f),
                           preferred_element_type=jnp.float32)
        else:
            z = jnp.einsum("brq...,rqt->brt...", z, as_f32_factor(f),
                           preferred_element_type=jnp.float32)
        z = jnp.moveaxis(z, 2, 2 + (n - 1))
    z = jnp.sum(z, axis=1)  # rank
    return z.reshape(b, -1)


def chain_fused_forward(x: jax.Array, factors: Sequence) -> jax.Array:
    """:func:`chain_forward` with the rank sum folded into the last
    contraction.

    The plain chain carries the rank axis to the very end and reduces it in
    a separate pass — its widest tensor is ``(B, r, t_1, Πq_rest)`` and the
    final step runs as r thin batched GEMMs. Folding ``Σ_r`` into the last
    einsum turns that step into ONE fat GEMM
    ``(B·Πt_{<n}, r·q_n) @ (r·q_n, t_n)`` and never materializes the
    ``(B, r, Πt)`` pre-sum tensor — the kron_matmul kernel's core trick
    (measured ~2× fwd on the bench arch shapes). Same output, bitwise-close
    (fp32 accumulation either way). Every contraction runs at
    ``Precision.HIGHEST`` (the kernels' fp32 contract, also on the MXU).
    """
    mm = functools.partial(jnp.einsum, precision=_HI,
                           preferred_element_type=jnp.float32)
    q_dims, _ = factor_dims(factors)
    n = len(factors)
    b = x.shape[0]
    z = x.reshape((b,) + q_dims)
    if n == 1:
        # single factor: fold the rank sum straight into the one GEMM
        return mm("bq,rqt->bt", z, as_f32_factor(factors[0]))
    for i, f in enumerate(factors[:-1]):
        spec = "bq...,rqt->brt..." if i == 0 else "brq...,rqt->brt..."
        z = mm(spec, z, as_f32_factor(f))
        z = jnp.moveaxis(z, 2, 2 + (n - 1))
    # layout here: (B, r, q_n, t_1..t_{n-1}); contract q_n AND the rank axis
    z = mm("brq...,rqt->b...t", z, as_f32_factor(factors[-1]))
    return z.reshape(b, -1)


def chain_fused_vjp(
    x: jax.Array,
    factors: Sequence,
    d_out: jax.Array,
) -> tuple[jax.Array, list[jax.Array]]:
    """Analytic VJP of :func:`chain_fused_forward`: ``(dx, [dF_j])``.

    Mirrors :func:`chain_vjp` but keeps the rank fold: the output cotangent
    ``(B, Πt)`` is never broadcast to ``(B, r, Πt)`` — the last factor's
    backward contractions are the transposed fat GEMMs of the forward
    (``dz = g·F_nᵀ``, ``dF_n = z_{n-1}ᵀ·g``), and the remaining factors run
    the standard reverse sweep. Chain intermediates are recomputed, not
    saved (same rematerialization budget as the forward kernel).
    """
    mm = functools.partial(jnp.einsum, precision=_HI,
                           preferred_element_type=jnp.float32)
    q_dims, t_dims = factor_dims(factors)
    n = len(factors)
    b = x.shape[0]
    f32 = [as_f32_factor(f) for f in factors]

    if n == 1:
        d = d_out  # (B, t_1)
        # y = Σ_r x·F_r: every rank slice sees the same cotangent
        df = mm("bq,bt->qt", x.reshape(b, -1).astype(jnp.float32), d)
        dfs = [jnp.broadcast_to(df[None], f32[0].shape)]
        dx = mm("bt,qt->bq", d, jnp.sum(f32[0], axis=0))
        return dx, dfs

    zs = []
    z = x.reshape((b,) + q_dims)
    for i, f in enumerate(f32[:-1]):
        zs.append(z)
        spec = "bq...,rqt->brt..." if i == 0 else "brq...,rqt->brt..."
        z = mm(spec, z, f)
        z = jnp.moveaxis(z, 2, 2 + (n - 1))
    # z layout: (B, r, q_n, t_1..t_{n-1}) — the fused last step's input
    dfactors: list = [None] * n
    d = d_out.reshape((b,) + t_dims)  # (B, t_1..t_n), no rank broadcast
    dfactors[n - 1] = mm("brq...,b...t->rqt", z, d)
    d = mm("b...t,rqt->brq...", d, f32[-1])
    # d is now in the post-step-(n−2) layout; the rest is chain_vjp's sweep
    for i in range(n - 2, -1, -1):
        d_moved = jnp.moveaxis(d, 2 + (n - 1), 2)  # t_i back to axis 2
        if i == 0:
            dfactors[0] = mm("bq...,brt...->rqt", zs[0], d_moved)
            d = mm("brt...,rqt->bq...", d_moved, f32[i])
        else:
            dfactors[i] = mm("brq...,brt...->rqt", zs[i], d_moved)
            d = mm("brt...,rqt->brq...", d_moved, f32[i])
    dx = d.reshape(b, -1)
    return dx, dfactors


def chain_vjp(
    x: jax.Array,
    factors: Sequence[jax.Array],
    d_logits: jax.Array,
) -> tuple[jax.Array, list[jax.Array]]:
    """Analytic VJP of :func:`chain_forward`: ``(dx, [dF_j])``.

    Recomputes the chain intermediates (they are never saved — same
    rematerialization budget as the forward kernel) and runs the reverse
    sweep with one ``(z_i, dL)`` and one ``(dL, F_i)`` contraction per factor.
    """
    q_dims = tuple(f.shape[1] for f in factors)
    t_dims = tuple(f.shape[2] for f in factors)
    n = len(factors)
    b = x.shape[0]

    zs = []
    z = x.reshape((b,) + q_dims)
    for i, f in enumerate(factors):
        zs.append(z)
        spec = "bq...,rqt->brt..." if i == 0 else "brq...,rqt->brt..."
        z = jnp.einsum(spec, z, f.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        z = jnp.moveaxis(z, 2, 2 + (n - 1))

    rank = factors[0].shape[0]
    d = d_logits.reshape((b,) + t_dims)
    d = jnp.broadcast_to(d[:, None], (b, rank) + t_dims)  # undo the rank sum
    dfactors: list = [None] * n
    for i in range(n - 1, -1, -1):
        d_moved = jnp.moveaxis(d, 2 + (n - 1), 2)  # t_i back to axis 2
        f = factors[i].astype(jnp.float32)
        if i == 0:
            dfactors[0] = jnp.einsum("bq...,brt...->rqt", zs[0], d_moved,
                                     preferred_element_type=jnp.float32)
            d = jnp.einsum("brt...,rqt->bq...", d_moved, f,
                           preferred_element_type=jnp.float32)
        else:
            dfactors[i] = jnp.einsum("brq...,brt...->rqt", zs[i], d_moved,
                                     preferred_element_type=jnp.float32)
            d = jnp.einsum("brt...,rqt->brq...", d_moved, f,
                           preferred_element_type=jnp.float32)
    dx = d.reshape(b, -1)
    return dx, dfactors


def gather_leaves(
    ids: jax.Array,
    factors_2d: Sequence[jax.Array],
    t_dims: Sequence[int],
    rank: int,
    q_dims: Sequence[int],
) -> tuple[list[jax.Array], list[jax.Array]]:
    """Mixed-radix digits → one-hot gathered leaves.

    ``factors_2d[j]`` is factor j pre-reshaped to ``(t_j, rank·q_j)`` fp32
    (``F.transpose(2, 0, 1).reshape(t, r·q)``). Returns ``(leaves, onehots)``
    with ``leaves[j] (B, rank, q_j)`` and ``onehots[j] (B, t_j)`` — the
    one-hots are reused by the backward scatter (as ``ohᵀ @ dleaf``).
    """
    bsz = ids.shape[0]
    leaves, onehots = [], []
    rem = ids
    for j, f2d in enumerate(factors_2d):
        base = int(math.prod(t_dims[j + 1:]))
        digit = rem // base
        rem = rem % base
        oh = one_hot(digit, t_dims[j])
        g = jnp.dot(oh, f2d, preferred_element_type=jnp.float32)
        leaves.append(g.reshape(bsz, rank, q_dims[j]))
        onehots.append(oh)
    return leaves, onehots


# ---------------------------------------------------------------------------
# Order-2 factor chain in the TPU compiler's layout vocabulary
# ---------------------------------------------------------------------------
#
# The generic chain above reshapes the lane axis ((B, P) -> (B, q_1, q_2, …))
# and moves axes between contractions; Mosaic refuses both. For two factors
# the kron_logits / kron_matmul kernels instead take operands pre-arranged in
# HBM (one XLA relayout per call, outside the kernel):
#
#   x   (B, q_1·q_2)        -> xs  (q_2, B, q_1)        chain2_x
#   F_1 (rank, q_1, t_1)    -> F1i (t_1, rank, q_1)     chain2_factors
#   F_2 (rank, q_2, t_2)    -> F2b (q_2, rank, t_2)
#
# so every in-kernel step is a 2-D matmul on a leading-axis merge
# ((q_2, Bblk, q_1) -> (q_2·Bblk, q_1)) or a leading-batch matmul. One
# first-digit column ``i`` of the output is
#
#   z   = xs @ F1i[i]ᵀ                    (q_2·Bblk, rank) -> (q_2, Bblk, rank)
#   y_i = Σ_b z[b] @ F2b[b]               (Bblk, t_2)
#
# i.e. the same two contractions as ``chain_fused_forward`` (a first, then
# b and the rank together).

def dot(a, b):
    """``a @ b`` at f32 accuracy (the kernels' fp32 contract on the MXU)."""
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def dot_nt(a, b):
    """``a @ bᵀ``."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def dot_tn(a, b):
    """``aᵀ @ b``."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def chain2_factors(f1, f2):
    """``(F1i (t_1, rank, q_1), F2b (q_2, rank, t_2))`` kernel layouts."""
    return jnp.transpose(f1, (2, 0, 1)), jnp.transpose(f2, (1, 0, 2))


def chain2_factor_grads(df1i, df2b):
    """Inverse of :func:`chain2_factors` for the factor cotangents."""
    return jnp.transpose(df1i, (1, 2, 0)), jnp.transpose(df2b, (1, 0, 2))


def chain2_x(x: jax.Array, q1: int, q2: int) -> jax.Array:
    """``(B, q_1·q_2)`` -> ``(q_2, B, q_1)``."""
    return jnp.transpose(x.reshape(x.shape[0], q1, q2), (2, 0, 1))


def chain2_dx(dxs: jax.Array) -> jax.Array:
    """Inverse of :func:`chain2_x`: ``(q_2, B, q_1)`` -> ``(B, q_1·q_2)``."""
    q2, b, q1 = dxs.shape
    return jnp.transpose(dxs, (1, 2, 0)).reshape(b, q1 * q2)


def chain2_tile(xs2, f1i, f2b, scale=None):
    """One first-digit column block: ``(y (Bblk, t_2), z (q_2, Bblk, rank))``.

    ``xs2`` is the ``(q_2·Bblk, q_1)`` activation block, ``f1i`` the
    ``(rank, q_1)`` slice of F1i, ``f2b`` all of F2b; ``scale`` (1, rank)
    folds quantized factors' per-rank scales into ``z``."""
    q2 = f2b.shape[0]
    z = dot_nt(xs2, f1i.astype(jnp.float32))
    z = z.reshape(q2, xs2.shape[0] // q2, z.shape[-1])
    if scale is not None:
        z = z * scale
    y = jnp.einsum("bnr,brk->bnk", z, f2b.astype(jnp.float32), precision=_HI,
                   preferred_element_type=jnp.float32)
    return jnp.sum(y, axis=0), z


def chain2_tile_vjp(xs2, z, f1i, f2b, dy):
    """VJP of :func:`chain2_tile` (unscaled): ``(dxs2, dF1i[i], dF2b)``."""
    q2, bblk, rank = z.shape
    dyb = jnp.broadcast_to(dy[None], (q2,) + dy.shape)
    dz = jnp.einsum("bnk,brk->bnr", dyb, f2b, precision=_HI,
                    preferred_element_type=jnp.float32)
    df2b = jnp.einsum("bnr,bnk->brk", z, dyb, precision=_HI,
                      preferred_element_type=jnp.float32)
    dz2 = dz.reshape(q2 * bblk, rank)
    dxs2 = dot(dz2, f1i)
    df1i = dot_tn(dz2, xs2)
    return dxs2, df1i, df2b

"""Correctness suite for the fused kron_matmul kernel subsystem.

Oracles follow tests/test_kernel_grads.py: the densely materialized
F = Σ_k ⊗_j F_jk (valid at test scale) and the plain XLA factor chain pin
down the kernel op — Pallas-interpret AND host-executor paths — across
orders 2–4 × rank {1, 8} × quant {none, int8, fp8} × the padding edges
(d_in < prod q, out_dim < prod t, batch not divisible by block_b, t1 not
divisible by the requested tile). Gradients are checked against the dense
oracle, the dedicated backward is asserted in use, and REPRO_KRON_BWD=ref
must reproduce the chain VJP exactly.

Also home of the tile-clamp unit tests (the old O(t1) decrement loop in
ketops.apply_matrix_factors is now ``common.largest_divisor_leq``) and the
kron_matmul autotune-family checks (measured-table hit for the bench
shapes, once-per-key miss warning).
"""

import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ketops
from repro.core import quant as Q
from repro.kernels import common as C
from repro.kernels.kron_matmul import ops as mops
from repro.kernels.kron_matmul.kron_matmul import (
    CHAIN_BUDGET_BYTES,
    chain_t1_tile,
    kron_matmul_bwd_host,
    kron_matmul_bwd_pallas,
    kron_matmul_pallas,
    kron_matmul_xla,
)
from repro.kernels.kron_matmul.ref import kron_matmul_dense_ref, kron_matmul_ref

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

SHAPES = {  # order -> (q_dims, t_dims); products overcover the logical dims
    2: ((4, 3), (5, 6)),
    3: ((3, 2, 2), (4, 3, 3)),
    4: ((2, 2, 2, 2), (3, 3, 2, 3)),
}


def _mk_factors(key, rank, q_dims, t_dims, scale=0.3):
    return [
        (jax.random.normal(jax.random.fold_in(key, j), (rank, q, t)) * scale)
        for j, (q, t) in enumerate(zip(q_dims, t_dims))
    ]


def _allclose_trees(a, b, tol=1e-4):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# forward: kernel (host + Pallas interpret) vs dense / chain oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", [2, 3, 4])
@pytest.mark.parametrize("rank", [1, 8])
@pytest.mark.parametrize("quant", ["none", "int8", "fp8"])
def test_forward_matches_oracles(order, rank, quant):
    q, t = SHAPES[order]
    key = jax.random.PRNGKey(order * 10 + rank)
    factors = _mk_factors(key, rank, q, t)
    d_in = math.prod(q) - 1   # x zero-pad edge
    out_dim = math.prod(t) - 2  # column-slice edge
    B = 13                    # not divisible by block_b=8
    x = jax.random.normal(jax.random.fold_in(key, 9), (B, d_in))

    if quant == "none":
        ref = kron_matmul_dense_ref(factors, x, out_dim)
        got_op = mops.kron_matmul(factors, x, out_dim, 2, 8)
        got_pallas = kron_matmul_pallas(
            factors, x, t1_block=2, block_b=8)[:, :out_dim]
        got_chain = kron_matmul_ref(factors, x, out_dim, tile=2)
    else:
        qf = [Q.quantize(f, quant) for f in factors]
        payloads = [f["q"] for f in qf]
        scales = [f["scale"] for f in qf]
        ref = kron_matmul_dense_ref([Q.as_f32(f) for f in qf], x, out_dim)
        got_op = mops.kron_matmul_quant(payloads, scales, x, out_dim, 2, 8)
        got_pallas = kron_matmul_pallas(
            payloads, x, t1_block=2, block_b=8, scales=scales)[:, :out_dim]
        got_chain = kron_matmul_ref(
            [(p, s) for p, s in zip(payloads, scales)], x, out_dim, tile=2)
    for got in (got_op, got_pallas, got_chain):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("order", [2, 3, 4])
def test_rank_folded_chain_equals_plain_chain(order):
    """chain_fused_forward == chain_forward (the rank fold is exact)."""
    q, t = SHAPES[order]
    factors = _mk_factors(jax.random.PRNGKey(order), 5, q, t)
    x = jax.random.normal(jax.random.PRNGKey(order + 50), (9, math.prod(q)))
    np.testing.assert_allclose(
        np.asarray(C.chain_fused_forward(x, factors)),
        np.asarray(C.chain_forward(x, factors)), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# backward: dense-oracle grads, kernel-bwd-in-use, pallas ≡ host, ref exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", [2, 3, 4])
@pytest.mark.parametrize("rank", [1, 8])
def test_grad_vs_dense_oracle(order, rank):
    q, t = SHAPES[order]
    key = jax.random.PRNGKey(order * 100 + rank)
    factors = _mk_factors(key, rank, q, t)
    d_in, out_dim, B = math.prod(q) - 1, math.prod(t) - 2, 13
    x = jax.random.normal(jax.random.fold_in(key, 9), (B, d_in))
    w = jax.random.normal(jax.random.fold_in(key, 11), (B, out_dim))

    g_op = jax.grad(
        lambda fs, xx: jnp.sum(w * mops.kron_matmul(fs, xx, out_dim, 2, 8)),
        argnums=(0, 1))(factors, x)
    g_ref = jax.grad(
        lambda fs, xx: jnp.sum(w * kron_matmul_dense_ref(fs, xx, out_dim)),
        argnums=(0, 1))(factors, x)
    _allclose_trees(g_op, g_ref)


def test_grad_uses_dedicated_backward(monkeypatch):
    """On CPU the host executor runs; on TPU the compiled XLA route."""
    if mops.get_backward_impl() == "ref":
        pytest.skip("REPRO_KRON_BWD=ref oracle leg: dedicated bwd disabled by design")
    target = ("kron_matmul_bwd_xla" if jax.default_backend() == "tpu"
              else "kron_matmul_bwd_host")
    calls = []
    orig = getattr(mops, target)
    monkeypatch.setattr(
        mops, target,
        lambda *a, **k: (calls.append(1), orig(*a, **k))[1])
    factors = _mk_factors(jax.random.PRNGKey(0), 2, (4, 3), (5, 6))
    x = jax.random.normal(jax.random.PRNGKey(1), (9, 11))
    jax.grad(lambda fs: jnp.sum(mops.kron_matmul(fs, x, 28, 2, 8)))(factors)
    assert calls, "gradient took the reference VJP, not the dedicated backward"


@pytest.mark.parametrize("order", [2, 3, 4])
def test_bwd_pallas_matches_host(order):
    """The Pallas bwd kernel (interpret) and the host executor are the same
    algorithm — they must agree on identical inputs."""
    q, t = SHAPES[order]
    factors = _mk_factors(jax.random.PRNGKey(12), 3, q, t)
    B, T = 13, math.prod(t)
    x = jax.random.normal(jax.random.PRNGKey(13), (B, math.prod(q) - 1))
    g = jax.random.normal(jax.random.PRNGKey(14), (B, T))
    dx_p, df_p = kron_matmul_bwd_pallas(factors, x, g, t1_block=2, block_b=8)
    dx_h, df_h = kron_matmul_bwd_host(factors, x, g, t1_block=2)
    _allclose_trees([dx_p, *df_p], [dx_h, *df_h], tol=1e-5)


def test_ref_fallback_is_chain_vjp(monkeypatch):
    """REPRO_KRON_BWD=ref must fall back to the chain VJP exactly — the
    gradient of the op equals jax.grad through the plain tiled chain."""
    factors = _mk_factors(jax.random.PRNGKey(3), 4, (4, 4), (7, 5))
    x = jax.random.normal(jax.random.PRNGKey(4), (10, 16))
    f_op = lambda fs: jnp.sum(jnp.cos(mops.kron_matmul(fs, x, 33, 2, 8)))
    g_kernel = jax.grad(f_op)(factors)
    monkeypatch.setattr(mops, "_backward_impl", "ref")
    g_ref_impl = jax.grad(f_op)(factors)
    g_chain = jax.grad(
        lambda fs: jnp.sum(jnp.cos(kron_matmul_ref(fs, x, 33, tile=2))))(factors)
    _allclose_trees(g_ref_impl, g_chain, tol=2e-5)  # same chain VJP graph
    _allclose_trees(g_kernel, g_chain, tol=1e-4)    # same math, fused exec


# ---------------------------------------------------------------------------
# the compiled TPU route (kron_matmul_xla / _bwd_xla) at the benchmark's
# shapes: the ket linears and the Kronecker head of qwen3-1.7b-ketlin
# (chipbench/configs/qwen3-1.7b-ketlin.json), at few tokens
# ---------------------------------------------------------------------------

KET_SHAPES = {  # name -> (rank, q_dims, t_dims, tokens)
    "wq": (8, (64, 32), (64, 32), 8),
    "wk": (8, (64, 32), (32, 32), 8),
    "wo": (8, (64, 32), (64, 32), 8),
    "wi": (8, (64, 32), (96, 64), 8),
    "ffn_wo": (8, (96, 64), (64, 32), 8),
    "head": (32, (64, 32), (390, 390), 16),
}
# the head's dense operator (2048 x 152,100 per rank) is too large for a
# test: its dense oracle covers every 39th first-digit column
HEAD_COLS = slice(0, 390, 39)


def _ket_case(name):
    rank, q, t, B = KET_SHAPES[name]
    key = jax.random.PRNGKey(sum(map(ord, name)))
    # entries of |y| ~ 1: each factor scaled by 1/sqrt(q_j * sqrt(rank))
    fs = [jax.random.normal(jax.random.fold_in(key, j), (rank, qj, tj))
          / math.sqrt(qj * math.sqrt(rank))
          for j, (qj, tj) in enumerate(zip(q, t))]
    x = jax.random.normal(jax.random.fold_in(key, 7), (B, math.prod(q)))
    return fs, x, math.prod(t)


def _close(got, want, tol=1e-5):
    """max |got - want| within ``tol`` of max |want| (f32 rounding over
    contractions thousands long)."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    err, scale = np.abs(got - want).max(), np.abs(want).max()
    assert err <= tol * scale, (err, scale)


def _head_dense_cols(fs, x):
    """Dense oracle of the head's ``HEAD_COLS`` first-digit columns."""
    sub = [C.slice_factor_t(fs[0], HEAD_COLS), fs[1]]
    return kron_matmul_dense_ref(sub, x, sub[0].shape[2] * fs[1].shape[2])


@pytest.mark.parametrize("name", sorted(KET_SHAPES))
def test_xla_route_forward_matches_oracles(name):
    fs, x, T = _ket_case(name)
    got = kron_matmul_xla(fs, x)
    _close(got, kron_matmul_pallas(fs, x, t1_block=16, block_b=8))
    if name == "head":
        t1, t2 = fs[0].shape[2], fs[1].shape[2]
        cols = got.reshape(-1, t1, t2)[:, HEAD_COLS].reshape(x.shape[0], -1)
        _close(cols, _head_dense_cols(fs, x))
    else:
        _close(got, kron_matmul_dense_ref(fs, x, T))


@pytest.mark.parametrize("name", sorted(KET_SHAPES))
def test_xla_route_grads_match_oracles(name, monkeypatch):
    """The op on its TPU route (``_on_tpu`` forced): forward and custom-VJP
    gradients through kron_matmul_xla / _bwd_xla, against the
    interpret-mode Pallas backward and the dense oracle's VJP."""
    if mops.get_backward_impl() == "ref":
        pytest.skip("REPRO_KRON_BWD=ref oracle leg: dedicated bwd disabled by design")
    fs, x, T = _ket_case(name)
    w = jax.random.normal(jax.random.PRNGKey(5), (x.shape[0], T))
    calls = []
    for fn in ("kron_matmul_xla", "kron_matmul_bwd_xla"):
        orig = getattr(mops, fn)
        monkeypatch.setattr(mops, fn, lambda *a, _o=orig, _n=fn, **k:
                            (calls.append(_n), _o(*a, **k))[1])
    monkeypatch.setattr(mops, "_on_tpu", lambda: True)
    # a t1 tile of 1 is ignored on this route (the tile comes from the budget)
    loss, (dfs, dx) = jax.value_and_grad(
        lambda f, xx: jnp.sum(w * mops.kron_matmul(f, xx, T, 1, 8)),
        argnums=(0, 1))(fs, x)
    assert calls == ["kron_matmul_xla", "kron_matmul_bwd_xla"], calls
    np.testing.assert_allclose(float(loss), float(jnp.sum(w * kron_matmul_xla(fs, x))),
                               rtol=1e-6)
    dx_p, dfs_p = kron_matmul_bwd_pallas(fs, x, w, t1_block=16, block_b=8)
    for got, want in zip([dx, *dfs], [dx_p, *dfs_p]):
        _close(got, want)
    if name == "head":
        return  # the dense oracle's VJP needs the whole head operator
    _, vjp = jax.vjp(lambda f, xx: kron_matmul_dense_ref(f, xx, T), fs, x)
    dfs_d, dx_d = vjp(w)
    for got, want in zip([dx, *dfs], [dx_d, *dfs_d]):
        _close(got, want)


@pytest.mark.parametrize("rank,q,t,tokens,want", [
    # the 8x1024 train step's calls, 4096 tokens
    (8, (64, 32), (64, 32), 4096, 64),  # wq/wo: 256 MiB, fits exactly
    (8, (64, 32), (32, 32), 4096, 32),  # wk/wv
    (8, (64, 32), (96, 64), 4096, 48),  # wi/wg: 384 MiB untiled
    (8, (96, 64), (64, 32), 4096, 32),  # ffn wo: 512 MiB untiled
    # serving: the head at 16 rows runs untiled (26 MB), not 130 tiles of 3
    (32, (64, 32), (390, 390), 16, 390),
    # the head at 4096 rows: 16 MiB a column, widest divisor of 390 <= 16
    (32, (64, 32), (390, 390), 4096, 15),
    # one column over budget still runs, one column at a time
    (8, (64, 32), (64, 32), 4096 * 128, 1),
    # order 3: the widest step is (B, r, t1, t2, q3), not (B, r, t1, q2·q3)
    (2, (2, 2, 8), (4, 8, 2), 1 << 18, 2),
])
def test_chain_t1_tile_rule(rank, q, t, tokens, want):
    """Untiled when the f32 chain intermediate fits the budget; otherwise
    the widest divisor of t1 whose tile fits."""
    tile = chain_t1_tile(rank, q, t, tokens)
    assert tile == want
    assert t[0] % tile == 0
    col = 4 * tokens * rank * max(
        math.prod(t[1:i]) * math.prod(q[i:]) for i in range(1, len(q)))
    wider = [d for d in range(tile + 1, t[0] + 1) if t[0] % d == 0]
    assert tile == 1 or tile * col <= CHAIN_BUDGET_BYTES
    assert all(d * col > CHAIN_BUDGET_BYTES for d in wider)


# ---------------------------------------------------------------------------
# ketops routing + chain-fallback behavior
# ---------------------------------------------------------------------------

def test_apply_matrix_factors_kernel_routing(monkeypatch):
    """use_kernel=True routes apply_matrix_factors through the fused op
    (host executor off-TPU) with identical results; quantized params take
    the dequant-fused leg."""
    factors = _mk_factors(jax.random.PRNGKey(5), 3, (4, 3), (5, 6))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 3, 11))  # lead dims
    chain = ketops.apply_matrix_factors(factors, x, 28, use_kernel=False)
    calls = []
    orig = mops.kron_matmul

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(mops, "kron_matmul", spy)
    routed = ketops.apply_matrix_factors(
        factors, x, 28, tile=2, use_kernel=True, block_b=8)
    assert calls, "use_kernel=True did not route through the kron_matmul op"
    np.testing.assert_allclose(np.asarray(routed), np.asarray(chain),
                               rtol=1e-4, atol=1e-4)

    qparams = [Q.quantize(f, "int8") for f in factors]
    qcalls = []
    orig_q = mops.kron_matmul_quant
    monkeypatch.setattr(mops, "kron_matmul_quant",
                        lambda *a, **k: (qcalls.append(1), orig_q(*a, **k))[1])
    routed_q = ketops.apply_matrix_factors(
        qparams, x, 28, tile=2, use_kernel=True, block_b=8)
    assert qcalls, "quantized params did not take the dequant-fused leg"
    chain_q = ketops.apply_matrix_factors(qparams, x, 28, use_kernel=False)
    np.testing.assert_allclose(np.asarray(routed_q), np.asarray(chain_q),
                               rtol=1e-4, atol=1e-4)


def test_quant_error_within_analytic_bound():
    """Fused int8/fp8 output error vs the fp32 operator stays within the
    PR 3 entrywise bound weighted by the activation L1 norm."""
    factors = _mk_factors(jax.random.PRNGKey(7), 4, (4, 4), (6, 5))
    x = jax.random.normal(jax.random.PRNGKey(8), (17, 16))
    ref = mops.kron_matmul(factors, x, 30, 2, 8)
    for mode in ("int8", "fp8"):
        qf = [Q.quantize(f, mode) for f in factors]
        got = mops.kron_matmul_quant([f["q"] for f in qf],
                                     [f["scale"] for f in qf], x, 30, 2, 8)
        bound = float(jnp.max(jnp.sum(jnp.abs(x), axis=-1))) * \
            Q.materialize_error_bound({"factors": factors}, mode)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err <= bound, (mode, err, bound)


def test_bf16_activations_stay_bf16_into_the_chain():
    """The chain fallback no longer up-casts activations: a bf16 x produces
    a bf16 output with fp32 accumulation, close to the fp32 result."""
    factors = _mk_factors(jax.random.PRNGKey(9), 2, (4, 3), (5, 6))
    x32 = jax.random.normal(jax.random.PRNGKey(10), (7, 11))
    y32 = ketops.apply_matrix_factors(factors, x32, 28)
    y16 = ketops.apply_matrix_factors(factors, x32.astype(jnp.bfloat16), 28)
    assert y16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y16.astype(jnp.float32)),
                               np.asarray(y32), rtol=2e-2, atol=2e-2)


def test_quantized_chain_dequants_per_factor(monkeypatch):
    """The chain fallback never expands all quantized stacks up front — it
    hands (payload, scale) pairs to the chain, one dequant per use point."""
    factors = [Q.quantize(f, "int8")
               for f in _mk_factors(jax.random.PRNGKey(11), 2, (4, 3), (5, 6))]
    x = jax.random.normal(jax.random.PRNGKey(12), (5, 11))
    calls = []
    orig = C.as_f32_factor
    monkeypatch.setattr(C, "as_f32_factor",
                        lambda f: (calls.append(isinstance(f, tuple)),
                                   orig(f))[1])
    ketops.apply_matrix_factors(factors, x, 28, use_kernel=False)
    assert calls and all(calls), \
        "quantized factors were expanded before the chain, not at use"


# ---------------------------------------------------------------------------
# tile clamping (the fixed divisor loop)
# ---------------------------------------------------------------------------

def test_largest_divisor_leq():
    assert C.largest_divisor_leq(96, 32) == 32
    assert C.largest_divisor_leq(96, 31) == 24
    assert C.largest_divisor_leq(7, 3) == 1      # prime: clamps to 1
    assert C.largest_divisor_leq(30, 30) == 30
    assert C.largest_divisor_leq(30, 1000) == 30  # k > n -> n
    assert C.largest_divisor_leq(1, 5) == 1
    with pytest.raises(ValueError):
        C.largest_divisor_leq(30, 0)
    with pytest.raises(ValueError):
        C.largest_divisor_leq(30, -4)
    # agrees with the old decrement loop everywhere it was defined
    for n in (6, 30, 96, 97, 128):
        for k in range(1, n + 1):
            tile = k
            while n % tile != 0:
                tile -= 1
            assert C.largest_divisor_leq(n, k) == tile, (n, k)


def test_kernel_op_accepts_untiled_tile_contract():
    """tile<=0 means 'untiled' on the chain (kron_head_logits passes 0); the
    kernel op must treat it as 'autotune the tile', not crash or tile at 0."""
    factors = _mk_factors(jax.random.PRNGKey(20), 2, (4, 3), (6, 5))
    x = jax.random.normal(jax.random.PRNGKey(21), (9, 11))
    base = mops.kron_matmul(factors, x, 28, 2, 8)
    for tile in (0, -1):
        got = mops.kron_matmul(factors, x, 28, tile, 8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   rtol=1e-5, atol=1e-5)
    routed = ketops.apply_matrix_factors(
        factors, x, 28, tile=0, use_kernel=True, block_b=8)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(base),
                               rtol=1e-5, atol=1e-5)


def test_mixed_quantized_factors_fall_back_to_chain(monkeypatch):
    """A partially quantized stack can't take either kernel leg — the route
    must fall back to the per-factor-dequant chain, not crash."""
    fs = _mk_factors(jax.random.PRNGKey(22), 2, (4, 3), (6, 5))
    mixed = [Q.quantize(fs[0], "int8"), fs[1]]
    base = ketops.apply_matrix_factors(mixed, jnp.ones((3, 11)), 28,
                                       use_kernel=False)
    for name in ("kron_matmul", "kron_matmul_quant"):
        monkeypatch.setattr(mops, name,
                            lambda *a, **k: pytest.fail("kernel leg taken"))
    got = ketops.apply_matrix_factors(mixed, jnp.ones((3, 11)), 28,
                                      use_kernel=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tile", [0, -3, 5, 6, 7, 100])
def test_apply_matrix_factors_tile_edges(tile):
    """tile=0/negative/>=t1 fall back to untiled; a non-divisor clamps to
    the largest divisor — all produce the untiled result exactly."""
    factors = _mk_factors(jax.random.PRNGKey(13), 2, (4, 3), (6, 5))
    x = jax.random.normal(jax.random.PRNGKey(14), (9, 11))
    base = ketops.apply_matrix_factors(factors, x, 28)
    got = ketops.apply_matrix_factors(factors, x, 28, tile=tile)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# autotune: kron_matmul family
# ---------------------------------------------------------------------------

def test_autotune_kron_matmul_heuristic_and_miss_warning(caplog):
    from repro.kernels import autotune
    # a shape nobody measured: heuristic result + exactly one warning
    shape = dict(op="kron_matmul", rank=3, q_dims=(9, 7), t_dims=(13, 11))
    key = autotune.table_key(shape["op"], jax.default_backend(), shape["rank"],
                             shape["q_dims"], shape["t_dims"])
    autotune._warned_misses.discard(key)
    with caplog.at_level(logging.WARNING, logger="repro.kernels.autotune"):
        bc = autotune.get_block_config(
            shape["op"], shape["rank"], shape["q_dims"], shape["t_dims"])
        n_first = sum("autotune table miss" in r.getMessage()
                      for r in caplog.records)
        bc2 = autotune.get_block_config(
            shape["op"], shape["rank"], shape["q_dims"], shape["t_dims"])
        n_second = sum("autotune table miss" in r.getMessage()
                       for r in caplog.records)
    assert bc.block_b > 0 and bc.t1_block > 0 and bc == bc2
    assert bc.t1_block <= 13 and 13 % bc.t1_block == 0
    assert n_first == 1 and n_second == 1  # once per key, not per call


def test_autotune_kron_matmul_measured_entries_present():
    """The bench shapes carry measured winners in the checked-in table (the
    CI runner's backend is cpu — same as the measurement container)."""
    import os

    from repro.kernels import autotune
    if os.environ.get("REPRO_AUTOTUNE_TABLE"):
        pytest.skip("custom autotune table in effect")
    table = autotune.load_table()
    keys = [k for k in table if k.startswith("kron_matmul|cpu|")]
    assert keys, "no measured kron_matmul entries in autotune_table.json"
    # and the resolver actually serves one without warning
    q, t = (64, 32), (128, 64)  # granite-3-2b ffn_wi, the bench arch
    bc = autotune.get_block_config("kron_matmul", 8, q, t, backend="cpu")
    entry = table.get(autotune.table_key("kron_matmul", "cpu", 8, q, t))
    assert entry is not None and bc.t1_block == entry["t1_block"]


# ---------------------------------------------------------------------------
# hypothesis fuzz (when installed)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @st.composite
    def cases(draw):
        order = draw(st.integers(2, 4))
        rank = draw(st.integers(1, 8))
        q_dims = tuple(draw(st.integers(2, 4)) for _ in range(order))
        t_dims = tuple(draw(st.integers(2, 4)) for _ in range(order))
        d_in = draw(st.integers(max(2, math.prod(q_dims) // 2),
                                math.prod(q_dims)))
        out_dim = draw(st.integers(max(2, math.prod(t_dims) // 2),
                                   math.prod(t_dims)))
        tile = draw(st.integers(1, max(1, t_dims[0])))
        B = draw(st.integers(1, 9))
        return order, rank, q_dims, t_dims, d_in, out_dim, tile, B

    @settings(max_examples=25, deadline=None)
    @given(cases(), st.integers(0, 2 ** 31 - 1))
    def test_fuzz_kernel_vs_dense(case, seed):
        order, rank, q_dims, t_dims, d_in, out_dim, tile, B = case
        key = jax.random.PRNGKey(seed)
        factors = _mk_factors(key, rank, q_dims, t_dims)
        x = jax.random.normal(jax.random.fold_in(key, 1), (B, d_in))
        ref = kron_matmul_dense_ref(factors, x, out_dim)
        got = mops.kron_matmul(factors, x, out_dim, tile, 4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_dense_ref_matches_materialize_dense():
    """The two independent dense oracles agree (cross-check of the test
    harness itself, via the ketops spec path)."""
    spec = ketops.KronSpec(in_dim=11, out_dim=28, order=2, rank=3,
                           q_dims=(4, 3), t_dims=(6, 5), use_layernorm=False)
    params = ketops.init(jax.random.PRNGKey(15), spec)
    x = jax.random.normal(jax.random.PRNGKey(16), (5, 11))
    F = ketops.materialize_dense(spec, params)  # (out_dim, in_dim)
    got = kron_matmul_dense_ref(params["factors"], x, 28)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ F.T),
                               rtol=1e-4, atol=1e-4)

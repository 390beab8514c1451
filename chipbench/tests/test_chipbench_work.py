"""Each work function against hand counts at small shapes."""

import numpy as np
import pytest

import small  # noqa: F401
import bench


def test_kron_matmul_counts():
    km = bench.work("kron_matmul")
    # B=2, r=3, q=(2,4), t=(5,6): chain 2·2·3·(2·4·5 + 5·4·6) = 12·(40+120) = 1920
    f, b = km.fwd(2, 3, (2, 4), (5, 6))
    assert f == 1920
    # x 2·8·2 B, factors 3·(2·5+4·6)·4 B, out 2·30·2 B
    assert b == 32 + 408 + 120
    fb, bb = km.bwd(2, 3, (2, 4), (5, 6))
    assert fb == 3840
    assert bb == 32 + 120 + 32 + 2 * 408


def test_kron_matmul_flops_are_the_chain_of_two_contractions():
    """The two contractions compute x @ Σ_k A_k ⊗ B_k; their multiply-adds
    (outputs × summed terms) are what ``fwd`` counts."""
    B, r, q1, q2, t1, t2 = 3, 2, 4, 5, 6, 7
    rng = np.random.default_rng(0)
    x = rng.normal(size=(B, q1 * q2))
    a, bm = rng.normal(size=(r, q1, t1)), rng.normal(size=(r, q2, t2))
    y = np.einsum("bac,kai->bkic", x.reshape(B, q1, q2), a)      # sums q1
    z = np.einsum("bkic,kcj->bij", y, bm).reshape(B, t1 * t2)     # sums r·q2
    dense = sum(np.kron(a[k], bm[k]) for k in range(r))
    np.testing.assert_allclose(z, x @ dense, rtol=1e-10)
    macs = y.size * q1 + z.size * r * q2
    assert bench.work("kron_matmul").fwd(B, r, (q1, q2), (t1, t2))[0] == 2 * macs


def test_fused_ce_counts():
    ce = bench.work("fused_kron_ce")
    f, b = ce.fwd(2, 3, (2, 4), (5, 6))
    assert f == 1920 + 4 * 2 * 30
    assert b == 2 * 8 * 4 + 8 + 3 * (10 + 24) * 4 + 24
    fb, bb = ce.bwd(2, 3, (2, 4), (5, 6))
    assert fb == 2 * 1920 + 3 * 2 * 30
    assert bb == 2 * 64 + 40 + 2 * 408


def test_kron_logits_counts():
    f, b = bench.work("kron_logits").fwd(2, 3, (2, 4), (5, 6))
    assert f == 1920
    assert b == 2 * 8 * 4 + 408 + 2 * 30 * 4


def test_paged_attn_counts():
    f, b = bench.work("paged_attn").fwd([3, 5], H=4, KVH=2, Dh=8)
    assert f == 4 * 4 * 8 * 8
    assert b == 2 * 2 * 8 * 2 * 8 + 2 * 2 * 4 * 8 * 2


@pytest.fixture
def tiny():
    return {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 1,
            "head_dim": 4, "d_ff": 12, "vocab_size": 20, "linear_kind": "dense",
            "factors": {"embed": {"rank": 2, "q": [4, 2], "t": [5, 4]},
                        "head": {"rank": 2, "q": [4, 2], "t": [5, 4]}}}


def test_model_counts(tiny):
    m = bench.work("model")
    # dense projections: 2·(8·8 + 2·8·4 + 8·8 + 3·8·12) = 2·480 per layer
    assert m.projection_flops(tiny) == 2 * 2 * 480
    assert m.head_flops(tiny) == 2 * 2 * (4 * 2 * 5 + 5 * 2 * 4)
    assert m.embed_flops(tiny) == 2 * 8
    assert m.attention_flops(tiny, 3) == 4 * 2 * 4 * 2 * 3
    per_tok = m.train_flops_per_token(tiny, 3)   # mean of 2 positions attended
    assert per_tok == 3 * (1920 + 4 * 2 * 4 * 2 * 2 + 320 + 16)
    # params: 2·(480 + 2·8 + 2·4) + 8 + 2·2·(2·(4·5 + 2·4))
    assert m.param_count(tiny) == 2 * (480 + 16 + 8) + 8 + 2 * 2 * (20 + 8)
    assert m.tick_flops(tiny, 3, 6, 2) == 3 * (1920 + 16) + 4 * 2 * 4 * 2 * 6 + 2 * 320
    assert m.tick_bytes(tiny, 10) == m.param_count(tiny) * 4 + 10 * 2 * 2 * 1 * 4 * 2


def test_model_counts_ket(tiny):
    m = bench.work("model")
    f = {"rank": 1, "q": [4, 2], "t": [4, 2]}
    ket = dict(tiny, linear_kind="ket", factors=dict(
        tiny["factors"], wq=f, wk={"rank": 1, "q": [4, 2], "t": [2, 2]},
        wv={"rank": 1, "q": [4, 2], "t": [2, 2]}, wo=f,
        wi={"rank": 1, "q": [4, 2], "t": [4, 3]}, ffn_wo={"rank": 1, "q": [4, 3], "t": [4, 2]}))
    chain = lambda q, t: 2 * (q[0] * q[1] * t[0] + t[0] * q[1] * t[1])
    per = (2 * chain([4, 2], [4, 2]) + 2 * chain([4, 2], [2, 2])
           + 2 * chain([4, 2], [4, 3]) + chain([4, 3], [4, 2]))
    assert m.projection_flops(ket) == 2 * per

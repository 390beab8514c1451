"""The DeepSeek-V2 serving cell at small sizes on the CPU: the plain
reference against the program, the shares of the experts, ``correct`` on
sound runs, a broken timed path and the float8 control, and the new work
count."""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import longdoc_small as LS
import small  # noqa: F401  (puts chipbench/ and src/ on the path)
import bench
import run

SEED = 6_000_000_007


def _run(cell, o, fault=None, seconds=3.0, tmp=None):
    args = argparse.Namespace(workload=cell, seed=SEED, seconds=seconds, trace=0)
    return run.run_cell(args, require_tpu=False, t_start=time.perf_counter(),
                        overrides=o, fault=fault, out_dir=str(tmp), compile_cache=False)


def _small_deepseek():
    cell = bench.resolve(LS.LONGDOC)
    o = LS.deepseek()
    return cell, {**cell.config, **o["config"]}, o


def test_reference_matches_prefill_then_decode():
    """The program (float32) prefills in chunks and decodes through its paged
    latent cache; every position's logits match the reference's full
    forward on the same weights."""
    from program import program_config, seed_key
    from repro.models import model as MD
    from repro.serve.cache import identity_ptab
    cell, conf, o = _small_deepseek()
    cfg = program_config(cell, {**o["program"], "dtype": jnp.float32})
    ref = cell.reference
    params = ref.init(conf, seed_key(SEED))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 14), 0, conf["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.logits(conf, ref.head_matrix(conf, params),
                                     ref.hidden(conf, params, t)) for t in toks])
    P, C = 9, 4
    cache = identity_ptab(MD.init_cache(cfg, 2, 32, paged=True, page_size=4), 2)
    for s in range(0, P, C):
        n = min(C, P - s)
        chunk = jnp.zeros((2, C), jnp.int32).at[:, :n].set(toks[:, s:s + n])
        logits, cache = MD.prefill_chunk_fn(params, cfg, cache, chunk,
                                            jnp.full((2,), n, jnp.int32))
    got = [logits]
    for t in range(P, 14):
        logits, cache = MD.serve_step_fn(params, cfg, cache, toks[:, t])
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], 1)
    # both float32; the program's absorbed, blocked attention and grouped
    # experts sum in another order: 1e-4 of logits of O(1). A routing flip
    # between near-tied experts would show as an O(0.1) gap.
    np.testing.assert_allclose(got, np.asarray(want[:, P - 1:]), rtol=1e-4, atol=1e-4)


def test_reference_shares_add_up():
    """The reference's held experts over every share, plus the shared experts
    counted once, equal the reference's uncut layer (all 4 experts held)."""
    _, conf, _ = _small_deepseek()
    ref = bench.resolve(LS.LONGDOC).reference
    uncut_conf = {**conf, "n_routed_experts": 4}
    key = jax.random.PRNGKey(0)
    p = ref.init(uncut_conf, key)["groups"][0]["moe"]
    m = jax.tree_util.tree_map(lambda x: x[0], p)
    h = jax.random.normal(jax.random.PRNGKey(1), (6, conf["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe(uncut_conf, m, h, None)
        shared = ref.swiglu(m["shared"], h, None)
        parts = []
        for first in (0, 2):
            # the share holding experts first, first + 1: their weights, the
            # router's columns rolled so that they come first
            share = {**m, "router": jnp.roll(m["router"], -first, axis=1),
                     **{k: m[k][first:first + 2] for k in ("wi", "wg", "wo")}}
            parts.append(ref.moe(conf, share, h, None) - shared)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(uncut), rtol=1e-5, atol=1e-5)  # f32 sum order


def test_longdoc_sound_run_is_correct(tmp_path):
    res = _run(LS.LONGDOC, LS.deepseek(), tmp=tmp_path)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # too few requests a window for a first-token tail or a completed rate
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_longdoc_altered_token_is_caught(tmp_path):
    res = _run(LS.LONGDOC, LS.deepseek(), fault="token_altered", tmp=tmp_path)
    assert not res["correct"], res["compared"]


def test_longdoc_control_fails():
    """Teacher-forced, the float8 reference's first choice lies below the
    float32 reference's best by more than the small cells' limit."""
    from drivers import serve_open as D
    cell, conf, o = _small_deepseek()
    rng = np.random.default_rng(0)
    sample = [(rng.integers(0, conf["vocab_size"], 8).tolist(),
               rng.integers(0, conf["vocab_size"], 40).tolist()) for _ in range(4)]
    gap, n = D.served_gap(cell.reference, conf, D.seed_key(SEED), sample,
                          o["workload"]["max_len"], quant="fp8", control=True)
    assert n == 160
    assert gap > o["workload"]["limits"]["serve_gap"], gap


def test_deepseek_work_counts():
    """At the small size the parameter count is the program's; at the
    published widths a (query, position) pair costs 34,816 FLOPs a layer."""
    from program import program_config
    from repro.models import model as MD
    cell, conf, o = _small_deepseek()
    w = bench.work("deepseek_v2")
    shapes = jax.eval_shape(lambda: MD.init_params(jax.random.PRNGKey(0),
                                                   program_config(cell, o["program"])))
    assert w.param_count(conf) == sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    full = cell.config
    assert w.attention_flops(full, 1) == 34816 * 14
    assert w.param_count(full) == 1_388_250_112
    # tiny by hand: d 64, H 4, Dn 16, R 8, Dv 16, Lr 16
    mla = 2 * (64 * 4 * 24 + 64 * 16 + 64 * 8 + 4 * 16 * 16 + 4 * 16 * 16 + 4 * 16 * 64)
    assert w.mla_flops_per_token(conf) == mla
    assert conf["factors"]["embed"]["q"] == [8, 8]
    per_tok = 3 * mla + 6 * 64 * 96 + 2 * (2 * 64 * 4 + 6 * 64 * 32) + 2 * 8 * 8
    assert w.token_flops(conf) == per_tok
    head = bench.work("model").head_flops(conf)
    assert w.tick_flops(conf, 5, 7, 2, 11) == (5 * per_tok + (2 * 4 * 24 + 2 * 4 * 16) * 3 * 7
                                               + 11 * 6 * 64 * 32 + 2 * head)
    assert w.tick_bytes(conf, 10) == w.param_count(conf) * 4 + 10 * 3 * 24 * 2


@pytest.mark.parametrize("held", [2, 4])
def test_reference_routes_over_all_experts(held):
    """Whatever the share, the router has every routed expert's output."""
    _, conf, _ = _small_deepseek()
    ref = bench.resolve(LS.LONGDOC).reference
    shapes = ref.shapes({**conf, "n_routed_experts": held})
    assert shapes["groups"][0]["moe"]["router"] == (2, 64, 4)
    assert shapes["groups"][0]["moe"]["wi"] == (2, held, 64, 32)
    assert shapes["lead"][0]["ffn"]["wi"] == (64, 96)

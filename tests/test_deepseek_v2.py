"""DeepSeek-V2-Lite's mechanisms in the program: YaRN against the published
formulas, the held-expert dropless MoE (shares add up, no token depends on
its batch), the leading dense layer through prefill and decode, the
serving engine's paths and its per-expert row counters.

Comparisons run in float32 on the CPU; each tolerance says why."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, get_smoke
from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import model as MD
from repro.models import moe as M
from repro.models.common import rope_angles, yarn_of
from repro.models.transformer import forward, lm_logits_last
from repro.serve.cache import identity_ptab
from repro.serve.engine import Request, ServingEngine


def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale, mscale_all):
    """DeepseekV2YarnRotaryEmbedding's inv_freq and cos/sin scale, in float64."""
    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    def get_mscale(s, m):
        return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (inter * (1 - mask) + extra * mask,
            get_mscale(factor, mscale) / get_mscale(factor, mscale_all), (low, high))


def test_yarn_matches_published_formulas_at_factor_40():
    cfg = get_config("deepseek-v2-lite-16b")
    assert yarn_of(cfg) == (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    inv, scale, (low, high) = _published_yarn(64, 10000.0, 40.0, 4096, 32, 1, 0.707, 0.707)
    assert (low, high) == (10, 23) and scale == 1.0
    pos = np.array([0, 1, 7, 4095, 16383])
    cos, sin = rope_angles(jnp.asarray(pos), cfg.rope_head_dim, cfg.rope_theta, yarn_of(cfg))
    ang = pos[:, None] * inv[None]
    # f32 angles of positions up to 16383: |error| ≈ 16383 · 2^-24 ≈ 1e-3 rad
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-3)
    # the high-frequency dims are plain RoPE, the low ones stretched 40x
    np.testing.assert_allclose(inv[:10], 10000.0 ** (-np.arange(10) * 2 / 64), rtol=1e-12)
    np.testing.assert_allclose(inv[23:], 10000.0 ** (-np.arange(23, 32) * 2 / 64) / 40,
                               rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert abs(m - 1.2608) < 1e-4
    assert math.isclose(A.mla_softmax_scale(cfg), 192 ** -0.5 * m * m, rel_tol=1e-12)


def test_rope_without_yarn_is_unchanged():
    cfg = get_config("qwen3-1.7b")
    assert yarn_of(cfg) is None
    pos = jnp.arange(5)
    cos, _ = rope_angles(pos, 128, cfg.rope_theta)
    half = np.arange(64) / 64
    np.testing.assert_allclose(np.asarray(cos), np.cos(np.arange(5)[:, None]
                               * cfg.rope_theta ** -half), atol=1e-6)
    assert A.mla_softmax_scale(get_config("deepseek-v2-lite-16b", rope_scaling="none")) \
        == 192 ** -0.5


def _moe_cfg(**kw):
    base = dict(name="t", family="moe", num_layers=1, d_model=16, num_heads=2,
                num_kv_heads=2, head_dim=8, d_ff=24, vocab_size=64, n_experts=8,
                top_k=3, n_shared_experts=1, dtype=jnp.float32)
    base.update(kw)
    return ModelConfig(**base)


def _share(params, first, n):
    """The layer of the device holding experts first .. first + n - 1: their
    weights, and the router's columns rolled so that they come first (the
    top-k is the same set of experts, relabelled)."""
    return {**params, "router": jnp.roll(params["router"], -first, axis=1),
            **{k: params[k][first:first + n] for k in ("wi", "wg", "wo")}}


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_moe_shares_add_up_to_the_uncut_layer(norm_topk_prob):
    """Every share of the experts computes its held experts' part; those
    parts plus the shared experts, counted once, are the uncut layer."""
    cfg = _moe_cfg(norm_topk_prob=norm_topk_prob, routed_scaling_factor=1.5)
    params = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, 16))
    uncut, met = M.moe_block(params, cfg, x)
    shared = M.ffn(params["shared"], x, "swiglu", cfg.dtype)
    parts, rows = [], []
    for first in range(0, 8, 2):
        c = _moe_cfg(norm_topk_prob=norm_topk_prob, routed_scaling_factor=1.5,
                     n_experts_held=2)
        out, m = M.moe_block(_share(params, first, 2), c, x)
        parts.append(out - shared)
        rows.append(np.asarray(m["moe_rows"]))
    # f32 sums of the same products in another order: a few ulps of O(1)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.concatenate(rows), np.asarray(met["moe_rows"]))
    assert int(np.concatenate(rows).sum()) == 21 * cfg.top_k


def test_moe_gates_follow_deepseek_v2():
    cfg = _moe_cfg(norm_topk_prob=False, routed_scaling_factor=2.0)
    params = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16))
    _, gates, _ = M._route(params, cfg, x)
    probs = jax.nn.softmax(x @ params["router"], -1)
    top = -np.sort(-np.asarray(probs), -1)[:, :3]
    np.testing.assert_allclose(np.asarray(gates), 2.0 * top, rtol=1e-6)
    _, gates, _ = M._route(params, _moe_cfg(), x)
    np.testing.assert_allclose(np.asarray(gates), top / top.sum(-1, keepdims=True), rtol=1e-6)


@pytest.mark.parametrize("held", [0, 3])
def test_moe_token_does_not_depend_on_its_batch(held):
    """Dropless: a token's output is the same alone as among any others."""
    cfg = _moe_cfg(n_experts_held=held)
    params = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 16))
    batch, _ = M.moe_block(params, cfg, x)
    for i in (0, 17, 39):
        alone, _ = M.moe_block(params, cfg, x[:, i:i + 1])
        # the same products on the same row: equal to rounding only
        np.testing.assert_allclose(np.asarray(alone[0, 0]), np.asarray(batch[0, i]),
                                   rtol=1e-6, atol=1e-6)
    # padding rows leave the others untouched and send no rows
    valid = jnp.arange(40)[None] < 25
    masked, met = M.moe_block(params, cfg, x, valid)
    np.testing.assert_allclose(np.asarray(masked[0, :25]), np.asarray(batch[0, :25]),
                               rtol=1e-6, atol=1e-6)
    assert int(met["moe_rows"].sum()) <= 25 * cfg.top_k


def _smoke():
    return get_smoke("deepseek-v2-lite-16b", dtype=jnp.float32)


def test_smoke_has_the_published_mechanisms():
    cfg = _smoke()
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    assert len(params["lead"]) == 1 and "ffn" in params["lead"][0]
    assert params["lead"][0]["ffn"]["wi"].shape == (64, 96)
    moe = params["groups"][0]["moe"]
    assert moe["router"].shape[-1] == 4 and moe["wi"].shape[1] == 2  # 2 of 4 held
    assert len(jax.tree_util.tree_leaves(params["groups"])) and cfg.num_layers == 3
    assert "lead" not in MD.init_params(jax.random.PRNGKey(0), get_smoke("qwen3-1.7b"))


@pytest.mark.parametrize("paged", [True, False])
def test_prefill_then_decode_matches_full_forward(paged):
    """Chunked prefill and then decode, through the latent cache, against the
    full forward at every position (leading dense layer, YaRN, held experts)."""
    cfg = _smoke()
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    B, T, P, C = 2, 12, 7, 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    x, _, _ = forward(params, cfg, toks)
    full = jax.vmap(lambda h: lm_logits_last(params, cfg, h), in_axes=1, out_axes=1)(x)
    if paged:
        cache = identity_ptab(MD.init_cache(cfg, B, 32, paged=True, page_size=4), B)
    else:
        cache = MD.init_cache(cfg, B, 32)
    for s in range(0, P, C):
        n = min(C, P - s)
        chunk = jnp.zeros((B, C), jnp.int32).at[:, :n].set(toks[:, s:s + n])
        logits, cache = MD.prefill_chunk_fn(params, cfg, cache, chunk,
                                            jnp.full((B,), n, jnp.int32))
    got = [logits]
    for t in range(P, T):
        logits, cache = MD.serve_step_fn(params, cfg, cache, toks[:, t])
        got.append(logits)
    # f32 throughout; absorbed vs up-projected attention and blocked softmax
    # reorder sums: agreement to ~1e-5 of logits of O(1)
    np.testing.assert_allclose(np.stack([np.asarray(g) for g in got], 1),
                               np.asarray(full[:, P - 1:]), rtol=1e-4, atol=1e-4)


def test_paged_blocked_read_matches_one_block(monkeypatch):
    """The paged MLA read blocked over many small blocks equals one block."""
    from repro.serve import decode as D
    cfg = _smoke()
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size)

    def run():
        cache = identity_ptab(MD.init_cache(cfg, 2, 32, paged=True, page_size=4), 2)
        lg, cache = MD.prefill_chunk_fn(params, cfg, cache, toks[:, :8],
                                        jnp.array([8, 5], jnp.int32))
        lg2, _ = MD.serve_step_fn(params, cfg, cache, toks[:, 8])
        return np.asarray(lg), np.asarray(lg2)

    one = run()
    monkeypatch.setattr(D, "MLA_SCORE_BUDGET", 1)  # one page a block
    many = run()
    for a, b in zip(one, many):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)  # f32 online softmax


_ENGINE_PATHS = """
import json
import jax, jax.numpy as jnp
from repro import obs
from repro.configs import get_smoke
from repro.models import model as MD
from repro.serve.engine import Request, ServingEngine
cfg = get_smoke("deepseek-v2-lite-16b", dtype=jnp.float32)
params = MD.init_params(jax.random.PRNGKey(0), cfg)
prompts = [[5, 17, 33, 2, 9, 40, 11], [7, 3], [1, 2, 3, 4, 5]]
out = {}
for name, kw in [("chunked", {}), ("stepwise", {"prefill_mode": "stepwise"}),
                 ("dense", {"cache_mode": "dense"})]:
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=32, prefill_chunk=3, **kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=4) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    obs.reset()
    eng.run_until_drained()
    out[name] = {"tokens": [r.output for r in reqs],
                 "syncs": [x.attrs for x in obs.records() if x.name == "engine.sync"]}
print(json.dumps(out))
"""


def test_engine_paths_agree_and_count_expert_rows():
    """The chunked+paged engine, the stepwise engine and the dense-cache
    engine emit the same greedy tokens; the MoE engine records its held
    experts' rows on ``engine.sync`` and the rows add up. Run in a fresh
    process: a CPU process that has compiled many programs before can emit
    other tokens on any engine path (the failure conftest.py describes)."""
    import json
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", _ENGINE_PATHS], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["chunked"]["tokens"] == out["stepwise"]["tokens"] == out["dense"]["tokens"]
    syncs = out["chunked"]["syncs"]
    assert syncs and all("moe_rows" in a and "moe_rows_max" in a for a in syncs)
    assert all(a["moe_rows_max"] <= a["moe_rows"] for a in syncs)
    # each valid token sends at most top_k rows to the held experts in each
    # of its 2 MoE layers: prompts and fed-back outputs, every token once
    cfg = _smoke()
    n_tok = sum(len(p) + 3 for p in ([5, 17, 33, 2, 9, 40, 11], [7, 3], [1, 2, 3, 4, 5]))
    assert 0 < sum(a["moe_rows"] for a in syncs) <= n_tok * cfg.top_k * 2


def test_dense_config_records_no_expert_rows():
    cfg = get_smoke("qwen3-1.7b", dtype=jnp.float32)
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=32, prefill_chunk=4)
    eng.submit(Request(uid=0, prompt=[1, 2, 3, 4, 5], max_new_tokens=3))
    obs.reset()
    eng.run_until_drained()
    syncs = [x.attrs for x in obs.records() if x.name == "engine.sync"]
    assert syncs and not any("moe_rows" in a for a in syncs)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "qwen3-1.7b"])
def test_steps_add_expert_rows_for_held_expert_configs(arch):
    """serve/decode's steps add the rows each held expert received for a
    MoE config, and nothing for a dense one; models/model's public steps
    give (logits, cache) for both. Idle slots and padding send no rows."""
    from repro.serve import decode as D
    cfg = get_smoke(arch, dtype=jnp.float32)
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    cache = MD.init_cache(cfg, 2, 32, paged=True, page_size=4)
    cache = {**cache, "ptab": cache["ptab"].at[0].set(jnp.arange(1, 9))}  # slot 1 idle
    toks = jnp.ones((2, 4), jnp.int32)
    lens = jnp.array([3, 0], jnp.int32)
    pre = D.prefill_step(params, cfg, cache, toks, lens)
    dec = D.serve_step(params, cfg, pre[1], toks[:, 0])
    assert len(MD.prefill_chunk_fn(params, cfg, cache, toks, lens)) == 2
    if arch == "qwen3-1.7b":
        assert len(pre) == 2 and len(dec) == 2
        return
    rows_p, rows_d = (np.asarray(out[2]) for out in (pre, dec))
    assert rows_p.shape == (cfg.experts_held,) and rows_p.dtype == np.int32
    # 3 valid positions, then 1 live decode slot: at most top_k rows each in
    # each of the 2 MoE layers
    assert 0 < rows_p.sum() <= 3 * cfg.top_k * 2
    assert 0 < rows_d.sum() <= 1 * cfg.top_k * 2

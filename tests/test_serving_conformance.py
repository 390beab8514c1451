"""Serving numerical conformance: paged-vs-dense caches and chunked-prefill
vs token-by-token vs full-forward differentials over the
linear_kind {dense, ket} × quant {none, int8} × cache-kind
{attn, local_attn, mla, ssm} matrix — including cells that pin the
kron_matmul-kernel-routed ket linear path (linear_use_kernel=True: the host
executor off-TPU, the Pallas kernel on TPU) — plus engine-level
equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import model as MD
from repro.models.transformer import forward, lm_logits_last
from repro.serve.cache import identity_ptab as _alloc_identity_ptab
from repro.serve.engine import Request, ServingEngine

# cache kinds: attn (dense GQA), local_attn (ring buffer), mla (latent
# cache over a dropless MoE, so no token's output depends on the batch),
# ssm (O(1) recurrent state — paged mode keeps it dense by design)
KINDS = {
    "attn": dict(family="dense", num_heads=4, num_kv_heads=2, qk_norm=True),
    "local_attn": dict(family="dense", layer_pattern=("local_attn",),
                       num_heads=4, num_kv_heads=2, local_window=5),
    "mla": dict(family="moe", mla=True, num_heads=4, num_kv_heads=4,
                n_experts=4, top_k=2, kv_lora_rank=16, rope_head_dim=8),
    "ssm": dict(family="ssm", num_heads=4, num_kv_heads=4),
}

# (linear_kind, quant, linear_use_kernel): the kernel=True cells route every
# ket projection through the fused kron_matmul op (custom-VJP host executor
# off-TPU — the same tiled algorithm as the TPU kernel), so paged/chunked
# conformance pins the kernel-routed path, not just the chain
CELLS = [("dense", "none", None), ("ket", "none", None),
         ("dense", "int8", None), ("ket", "int8", None),
         ("ket", "none", True), ("ket", "int8", True)]


def _cfg(kind: str, linear_kind: str, quant: str,
         use_kernel=None) -> ModelConfig:
    base = dict(
        name=f"conf-{kind}", num_layers=2, d_model=32, d_ff=96, vocab_size=64,
        head_dim=8, embedding_kind="word2ketxs", embedding_rank=4,
        head_kind="kron", head_rank=4, dtype=jnp.float32,
        param_dtype=jnp.float32, remat="none", linear_kind=linear_kind,
        linear_rank=4, quant=quant, linear_use_kernel=use_kernel,
        linear_tile=2, linear_block_b=8)
    base.update(KINDS[kind])
    return ModelConfig(**base)




def _stepwise(cfg, params, cache, toks):
    out = []
    for t in range(toks.shape[1]):
        logits, cache = MD.serve_step_fn(params, cfg, cache, toks[:, t])
        out.append(logits)
    return jnp.stack(out, axis=1), cache


def _chunked_prefill(cfg, params, cache, toks, C):
    B, T = toks.shape
    off, logits = 0, None
    ticks = 0
    while off < T:
        n = min(C, T - off)
        chunk = jnp.zeros((B, C), jnp.int32).at[:, :n].set(toks[:, off:off + n])
        logits, cache = MD.prefill_chunk_fn(params, cfg, cache, chunk,
                                            jnp.full((B,), n, jnp.int32))
        off += n
        ticks += 1
    return logits, cache, ticks


@pytest.mark.parametrize("linear_kind,quant,use_kernel", CELLS)
@pytest.mark.parametrize("kind", list(KINDS))
def test_conformance_matrix(kind, linear_kind, quant, use_kernel):
    """One cell of the serving conformance matrix:
    (a) dense token-by-token decode == full forward at every position;
    (b) paged decode == dense decode;
    (c) chunked prefill (paged, ragged last chunk) reaches the same
        last-position logits in ⌈P/C⌉ calls, and the post-prefill decode
        continuation matches the stepwise continuation."""
    cfg = _cfg(kind, linear_kind, quant, use_kernel)
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    B, T, C = 2, 7, 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    max_len = 16

    x, _, _ = forward(params, cfg, toks)
    full_logits = jax.vmap(lambda h: lm_logits_last(params, cfg, h),
                           in_axes=1, out_axes=1)(x)

    # (a) dense stepwise vs full forward
    dense_logits, dense_cache = _stepwise(
        cfg, params, MD.init_cache(cfg, B, max_len), toks)
    np.testing.assert_allclose(np.asarray(dense_logits), np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)

    # (b) paged stepwise vs dense stepwise
    pcache = _alloc_identity_ptab(
        MD.init_cache(cfg, B, max_len, paged=True, page_size=4), B)
    paged_logits, pcache = _stepwise(cfg, params, pcache, toks)
    np.testing.assert_allclose(np.asarray(paged_logits), np.asarray(dense_logits),
                               rtol=2e-3, atol=2e-3)

    # (c) chunked prefill in ⌈P/C⌉ calls + decode continuation
    ccache = _alloc_identity_ptab(
        MD.init_cache(cfg, B, max_len, paged=True, page_size=4), B)
    chunk_logits, ccache, ticks = _chunked_prefill(cfg, params, ccache, toks, C)
    assert ticks == -(-T // C)
    np.testing.assert_allclose(np.asarray(chunk_logits),
                               np.asarray(full_logits[:, -1]),
                               rtol=2e-3, atol=2e-3)
    nxt = jnp.argmax(chunk_logits, axis=-1).astype(jnp.int32)
    nxt_ref = jnp.argmax(dense_logits[:, -1], axis=-1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(nxt_ref))
    cont, _ = _stepwise(cfg, params, ccache,
                        jnp.broadcast_to(nxt[:, None], (B, 1)))
    cont_ref, _ = _stepwise(cfg, params, dense_cache, nxt_ref[:, None])
    np.testing.assert_allclose(np.asarray(cont), np.asarray(cont_ref),
                               rtol=2e-3, atol=2e-3)


def test_moe_gqa_qknorm_decode_matches_full_forward():
    """Regression: the non-MLA moe_attn decode branch must apply qk-norm
    exactly like training/prefill (it used to skip it, so a chunked prefill
    left normed prompt K next to un-normed decode K in the same cache)."""
    cfg = ModelConfig(
        name="conf-moe-qknorm", family="moe", num_layers=2, d_model=32,
        d_ff=96, vocab_size=64, head_dim=8, num_heads=4, num_kv_heads=2,
        qk_norm=True, n_experts=4, top_k=2, embedding_kind="word2ketxs", embedding_rank=4, head_kind="kron",
        head_rank=4, dtype=jnp.float32, param_dtype=jnp.float32, remat="none")
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    B, T, C = 2, 6, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    x, _, _ = forward(params, cfg, toks)
    full_logits = jax.vmap(lambda h: lm_logits_last(params, cfg, h),
                           in_axes=1, out_axes=1)(x)
    step_logits, _ = _stepwise(cfg, params, MD.init_cache(cfg, B, 16), toks)
    np.testing.assert_allclose(np.asarray(step_logits), np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)
    ccache = _alloc_identity_ptab(
        MD.init_cache(cfg, B, 16, paged=True, page_size=4), B)
    chunk_logits, ccache, _ = _chunked_prefill(cfg, params, ccache, toks, C)
    np.testing.assert_allclose(np.asarray(chunk_logits),
                               np.asarray(full_logits[:, -1]),
                               rtol=2e-3, atol=2e-3)
    # post-prefill decode writes through the same (normed) K path
    nxt = jnp.argmax(chunk_logits, axis=-1).astype(jnp.int32)
    cont, _ = _stepwise(cfg, params, ccache, nxt[:, None])
    cont_ref, _ = _stepwise(
        cfg, params, MD.init_cache(cfg, B, 16),
        jnp.concatenate([toks, nxt[:, None]], axis=1))
    np.testing.assert_allclose(np.asarray(cont[:, 0]),
                               np.asarray(cont_ref[:, -1]),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kind", list(KINDS))
def test_engine_chunked_equals_stepwise_and_direct(kind):
    """Engine-level conformance: the chunked+paged engine, the legacy
    stepwise engine, and a 1-slot dense reference produce identical greedy
    outputs for a mixed batch of prompts."""
    cfg = _cfg(kind, "dense", "none")
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 17, 33, 2, 9, 40, 11], [7, 3], [1, 2, 3, 4, 5]]

    def run(**kw):
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=32,
                            prefill_chunk=3, **kw)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        return [r.output for r in reqs], eng

    out_chunked, eng_c = run()
    out_stepwise, _ = run(prefill_mode="stepwise")
    out_dense, _ = run(cache_mode="dense")
    assert out_chunked == out_stepwise == out_dense
    for p, o in zip(prompts, out_chunked):
        ref = ServingEngine(cfg, params, batch_slots=1, max_len=32,
                            cache_mode="dense", prefill_mode="stepwise")
        r = Request(uid=0, prompt=p, max_new_tokens=4)
        ref.submit(r)
        ref.run_until_drained()
        assert o == r.output
    # the chunked engine actually ran chunked: ⌈7/3⌉+⌈2/3⌉+⌈5/3⌉ prefill ticks
    assert eng_c.stats()["prefill_ticks"] >= 3

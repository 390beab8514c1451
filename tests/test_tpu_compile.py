"""Compile every main-path kernel for a TPU v5e chip, at qwen3-1.7b widths.

Also the ket linears' compiled XLA route (no kernel) with the memory it
takes.

Nothing runs: each kernel is lowered with ``interpret=False`` and compiled
ahead of time for a v5e chip that is described, not attached, so the TPU
compiler's refusals (block tiling, unsupported in-kernel relayouts, VMEM)
surface here instead of on the chip. Block sizes are the ones
``autotune.get_block_config(..., backend="tpu")`` picks for these shapes.

The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around the compiles: an entry
written for a described chip cannot be read back without one.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import embedding_for, head_for
from repro.core import kron as K
from repro.kernels import autotune
from repro.kernels.flash_attn.paged import (
    combine_splits_pallas,
    paged_attention_split_pallas,
)
from repro.kernels.kron_gather.kron_gather import (
    kron_gather_bwd_pallas,
    kron_gather_fwd_pallas,
    kron_gather_pallas,
)
from repro.kernels.kron_logits.kron_logits import kron_ce_bwd_pallas, kron_ce_pallas
from repro.kernels.kron_matmul import ops as mops
from repro.kernels.kron_matmul.kron_matmul import (
    CHAIN_BUDGET_BYTES,
    kron_matmul_bwd_pallas,
    kron_matmul_pallas,
)

CFG = get_config("qwen3-1.7b")
TOKENS = 1024  # tokens per train-path kernel call (grid extent only)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_persistent_cache):
    """Compile ``fn`` for the described chip; every Mosaic kernel in the
    program must carry the name ``kernel`` (the ``pallas_call``'s ``name=``),
    which is what a device trace shows as the operation's name."""
    def run(fn, *shapes, kernel):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        names = re.findall(r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"',
                           text)
        assert names, "no Mosaic kernel in the program"
        assert all(re.fullmatch(rf"{kernel}(\.\d+)?", n) for n in names), names
    return run


@pytest.fixture
def compile_tpu_xla(one_chip, no_persistent_cache):
    """Compile ``fn`` for the described chip where it must hold no Mosaic
    kernel at all; returns the compiled program."""
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert 'custom_call_target="tpu_custom_call"' not in compiled.as_text()
        return compiled
    return run


def _f32(*shape):
    return (shape, jnp.float32)


def _kron_shapes(ecfg):
    q, t = ecfg.resolved_q(), ecfg.resolved_t()
    return [(ecfg.rank, qj, tj) for qj, tj in zip(q, t)]


EMBED = _kron_shapes(embedding_for(CFG))
HEAD = _kron_shapes(head_for(CFG).as_embedding_config())


def _blocks(op, factors):
    return autotune.get_block_config(
        op, factors[0][0], tuple(f[1] for f in factors),
        tuple(f[2] for f in factors), backend="tpu")


def _ket_linear(d_in, d_out):
    q = K.choose_factorization(d_in, CFG.linear_order)
    t = K.choose_factorization(d_out, CFG.linear_order)
    return [(CFG.linear_rank, qj, tj) for qj, tj in zip(q, t)]


# the ket linears of qwen3-1.7b under linear_kind="ket": FFN up (wi/wg) and
# down (wo) projections, the widest and the transposed shape
LINEARS = {
    "ffn_up": _ket_linear(CFG.d_model, CFG.d_ff),
    "ffn_down": _ket_linear(CFG.d_ff, CFG.d_model),
}


def test_kron_gather_fwd(compile_tpu):
    bb = _blocks("kron_gather", EMBED).block_b
    assert bb % 8 == 0
    compile_tpu(lambda f0, f1, ids: kron_gather_pallas(
        [f0, f1], ids, block_b=bb, interpret=False),
        _f32(*EMBED[0]), _f32(*EMBED[1]), ((TOKENS,), jnp.int32),
        kernel="kron_gather_fwd")


def test_kron_gather_fwd_with_stats(compile_tpu):
    bb = _blocks("kron_gather", EMBED).block_b
    compile_tpu(lambda f0, f1, ids: kron_gather_fwd_pallas(
        [f0, f1], ids, block_b=bb, interpret=False),
        _f32(*EMBED[0]), _f32(*EMBED[1]), ((TOKENS,), jnp.int32),
        kernel="kron_gather_fwd")


def test_kron_gather_bwd(compile_tpu):
    bb = _blocks("kron_gather", EMBED).block_b
    rank = EMBED[0][0]
    compile_tpu(lambda f0, f1, ids, g, st: kron_gather_bwd_pallas(
        [f0, f1], ids, g, st, block_b=bb, interpret=False),
        _f32(*EMBED[0]), _f32(*EMBED[1]), ((TOKENS,), jnp.int32),
        _f32(TOKENS, CFG.d_model), _f32(TOKENS, 2, rank),
        kernel="kron_gather_bwd")


def test_fused_kron_ce_fwd(compile_tpu):
    bc = _blocks("kron_logits", HEAD)
    assert bc.block_b % 8 == 0 and HEAD[0][2] % bc.t1_block == 0
    compile_tpu(lambda f0, f1, h, y: kron_ce_pallas(
        [f0, f1], h, y, CFG.vocab_size, t1_block=bc.t1_block,
        block_b=bc.block_b, interpret=False, return_stats=True),
        _f32(*HEAD[0]), _f32(*HEAD[1]), _f32(TOKENS, CFG.d_model),
        ((TOKENS,), jnp.int32), kernel="fused_kron_ce_fwd")


def test_fused_kron_ce_bwd(compile_tpu):
    bc = _blocks("kron_logits", HEAD)
    compile_tpu(lambda f0, f1, h, y, m, l, g: kron_ce_bwd_pallas(
        [f0, f1], h, y, m, l, g, CFG.vocab_size, t1_block=bc.t1_block,
        block_b=bc.block_b, interpret=False),
        _f32(*HEAD[0]), _f32(*HEAD[1]), _f32(TOKENS, CFG.d_model),
        ((TOKENS,), jnp.int32), _f32(TOKENS), _f32(TOKENS), _f32(TOKENS),
        kernel="fused_kron_ce_bwd")


def test_kron_matmul_fwd_head_logits(compile_tpu):
    """Serving: the kron head's logits at decode batch (8 slots)."""
    bc = _blocks("kron_matmul", HEAD)
    compile_tpu(lambda f0, f1, x: kron_matmul_pallas(
        [f0, f1], x, t1_block=CFG.head_vocab_tile, block_b=bc.block_b,
        interpret=False),
        _f32(*HEAD[0]), _f32(*HEAD[1]), _f32(8, CFG.d_model), kernel="kron_matmul_fwd")


@pytest.mark.parametrize("name", sorted(LINEARS))
def test_kron_matmul_fwd(compile_tpu, name):
    fs = LINEARS[name]
    bc = _blocks("kron_matmul", fs)
    assert bc.block_b % 8 == 0 and fs[0][2] % bc.t1_block == 0
    d_in = math.prod(f[1] for f in fs)
    compile_tpu(lambda f0, f1, x: kron_matmul_pallas(
        [f0, f1], x, t1_block=bc.t1_block, block_b=bc.block_b,
        interpret=False),
        _f32(*fs[0]), _f32(*fs[1]), _f32(TOKENS, d_in), kernel="kron_matmul_fwd")


@pytest.mark.parametrize("name", sorted(LINEARS))
def test_kron_matmul_bwd(compile_tpu, name):
    fs = LINEARS[name]
    bc = _blocks("kron_matmul", fs)
    d_in = math.prod(f[1] for f in fs)
    d_out = math.prod(f[2] for f in fs)
    compile_tpu(lambda f0, f1, x, g: kron_matmul_bwd_pallas(
        [f0, f1], x, g, t1_block=bc.t1_block, block_b=bc.block_b,
        interpret=False),
        _f32(*fs[0]), _f32(*fs[1]), _f32(TOKENS, d_in), _f32(TOKENS, d_out),
        kernel="kron_matmul_bwd")


# the ket linears' route on TPU (ops.kron_matmul on a TPU backend: the
# rank-folded chain compiled by XLA) at a train microbatch of 4096 tokens,
# with the config's compute dtype for the activations
ROUTE_TOKENS = 4096


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "value_and_grad"])
@pytest.mark.parametrize("name", sorted(LINEARS))
def test_kron_matmul_xla_route(compile_tpu_xla, monkeypatch, name, grad):
    """No Mosaic kernel; the fusions carry the layer's scope names; the
    temporaries stay within the chain's byte budget per live tile."""
    monkeypatch.setattr(mops, "_on_tpu", lambda: True)
    fs = LINEARS[name]
    d_in = math.prod(f[1] for f in fs)
    d_out = math.prod(f[2] for f in fs)

    def fwd(f0, f1, x):
        return mops.kron_matmul([f0, f1], x, d_out)

    def loss(f0, f1, x):
        return jnp.sum(fwd(f0, f1, x).astype(jnp.float32) ** 2)

    fn = jax.value_and_grad(loss, argnums=(0, 1, 2)) if grad else fwd
    compiled = compile_tpu_xla(fn, _f32(*fs[0]), _f32(*fs[1]),
                               ((ROUTE_TOKENS, d_in), CFG.dtype))
    text = compiled.as_text()
    assert "kron_matmul_fwd" in text
    assert ("kron_matmul_bwd" in text) == grad
    temp = compiled.memory_analysis().temp_size_in_bytes
    # one chain tile forward; the backward adds the tile's cotangent
    assert temp <= (2 if grad else 1) * CHAIN_BUDGET_BYTES + (1 << 20), temp



# serving decode read: 8 slots at max_len 1056 (66 pages of 16 tokens),
# bf16 pools (the config's compute dtype)
SLOTS, PAGES_PER_SLOT, PS = 8, 66, CFG.page_size
KVH, H, DH = CFG.num_kv_heads, CFG.num_heads, CFG.head_dim
KV_SPLITS = autotune.heuristic_kv_splits(PS, H // KVH, DH, PAGES_PER_SLOT,
                                         batch=SLOTS, backend="tpu")


def test_paged_split(compile_tpu):
    pool = ((SLOTS * PAGES_PER_SLOT + 1, PS, KVH, DH), CFG.dtype)
    compile_tpu(lambda q, k, v, t, n: paged_attention_split_pallas(
        q, k, v, t, n, kv_splits=KV_SPLITS, interpret=False),
        ((SLOTS, H, DH), CFG.dtype), pool, pool,
        ((SLOTS, PAGES_PER_SLOT), jnp.int32), ((SLOTS,), jnp.int32),
        kernel="paged_attn_split")


def test_paged_combine(compile_tpu):
    part = (SLOTS, KVH, KV_SPLITS, H // KVH)
    compile_tpu(lambda o, m, l: combine_splits_pallas(o, m, l, interpret=False),
                _f32(*part, DH), _f32(*part, 1), _f32(*part, 1),
                kernel="paged_attn_combine")


# DeepSeek-V2-Lite's serving steps as the longdoc cell runs them: one
# chip's share (14 layers, 8 of 64 routed experts held), 12 slots × 16384
# positions of paged latent cache, the cell's prefill chunk of 64
DS_CFG = get_config("deepseek-v2-lite-16b", num_layers=14, n_experts_held=8,
                    prefill_chunk=64)
DS_SLOTS, DS_MAX_LEN = 12, 16384
# the cell's HBM: 5.17 GiB of f32 weights and 2.96 GiB of latent pool in,
# 2.96 GiB of new pool out, of v5e's ~15.75 GiB; the temporaries get the rest
DS_TEMP_BUDGET = 5 << 30


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_deepseek_longdoc_steps(one_chip, no_persistent_cache, monkeypatch, step):
    """The decode and chunk steps compile for the chip with their kernels
    (no Mosaic kernel of this repo but the embedding's gather), the grouped
    expert product, and temporaries within what the cell leaves free."""
    from repro.models import model as MD
    from repro.serve import decode as D
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, B = DS_CFG, DS_SLOTS

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: MD.init_params(jax.random.PRNGKey(0), cfg)))
    cache = placed(jax.eval_shape(lambda: MD.init_cache(cfg, B, DS_MAX_LEN, paged=True)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if step == "decode":
        fn = lambda p, c, t: D.serve_step(p, cfg, c, t)
        args = (params, cache, i32(B))
    else:
        fn = lambda p, c, t, n: D.prefill_step(p, cfg, c, t, n)
        args = (params, cache, i32(B, cfg.prefill_chunk), i32(B))
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r'%([\w.-]+?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
                             text))
    # XLA lowers lax.ragged_dot to its own Mosaic calls ("ragged-dot-*")
    ours = {k for k in kernels if not k.startswith("ragged-dot")}
    assert ours == {"kron_gather_fwd"}, kernels
    assert "moe_experts" in text and kernels != ours
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < DS_TEMP_BUDGET, ma.temp_size_in_bytes
    assert ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes \
        < int(15.75 * (1 << 30))

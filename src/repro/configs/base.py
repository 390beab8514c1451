"""Config dataclasses for models, embeddings, meshes, and input shapes."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax.numpy as jnp

from repro.core.embedding import EmbeddingConfig
from repro.core.logits import HeadConfig

__all__ = ["ModelConfig", "ShapeSpec", "LM_SHAPES", "embedding_for", "head_for"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config per assigned architecture (exact published dims)."""

    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128

    # attention flavor
    attn_kind: str = "full"  # full | local
    local_window: int = 2048
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # rotary scaling (DeepSeek-V2's ``rope_scaling``): "none" | "yarn". YaRN
    # blends θ^(-2i/d) with θ^(-2i/d)/rope_factor over the correction range
    # of beta_fast/beta_slow at original_max_position_embeddings; MLA's
    # softmax scale gains yarn_get_mscale(rope_factor, mscale_all_dim)².
    rope_scaling: str = "none"
    rope_factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    # MLP flavor
    mlp_type: str = "swiglu"  # swiglu | gelu | geglu

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25  # expert-parallel (mesh all-to-all) path only
    router_dtype: Any = jnp.float32
    # DeepSeek-V2 routing (its config.json keys): renormalise the top-k
    # gates to sum to one, else scale them by ``routed_scaling_factor``
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # the routed experts this device holds: the first ``n_experts_held``
    # (0 = all ``n_experts``). The router keeps all ``n_experts`` outputs;
    # the layer computes its held experts' part.
    n_experts_held: int = 0
    # leading layers whose FFN is a dense SwiGLU of width
    # ``intermediate_size`` instead of the MoE (DeepSeek-V2's
    # ``first_k_dense_replace``); they run unrolled before the scanned groups
    first_k_dense_replace: int = 0
    intermediate_size: int = 0

    # MLA (DeepSeek-style latent attention)
    mla: bool = False
    kv_lora_rank: int = 512
    rope_head_dim: int = 64

    # SSM (mamba-1)
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2

    # hybrid layer pattern, e.g. ("rglru", "rglru", "local_attn"); empty =>
    # uniform pattern derived from family
    layer_pattern: tuple[str, ...] = ()

    # enc-dec (whisper)
    enc_layers: int = 0
    enc_seq: int = 1500  # precomputed audio-frame embeddings (conv frontend STUB)

    # VLM (phi-3-vision): precomputed patch embeddings (CLIP frontend STUB)
    vision_prefix: int = 0

    # embedding & head representation (the paper's technique)
    embedding_kind: str = "word2ketxs"  # regular | word2ket | word2ketxs
    embedding_order: int = 2
    embedding_rank: int = 32
    embedding_layernorm: bool = True
    head_kind: str = "kron"  # dense | kron
    head_order: int = 2
    head_rank: int = 32
    # CE streaming tile (t1 digits) — perf knob; None = autotuned
    head_vocab_tile: Optional[int] = 4
    # fused Pallas kernels for lookup/CE (fwd + dedicated bwd): None = auto
    # (TPU only); token-block sizes: None = autotuned per shape/backend
    use_kernels: Optional[bool] = None
    embedding_block_b: Optional[int] = None
    head_block_b: Optional[int] = None
    # token sharding for the streamed CE loss: "data" replicates head compute
    # across the model axis; "data_model" (§Perf winner: −44% flops on the
    # 256k-vocab cell) splits tokens over it — sequence-parallel CE.
    ce_token_shard: str = "data_model"

    # ket-ified linear layers (beyond-paper: the ketops operator applied to
    # the layers that dominate LM parameter count). "ket" stores FFN wi/wg/wo
    # and attention qkv/out projections as rank-r Kronecker factor stacks and
    # applies them with the chain matmul (core/ketops.apply_matrix).
    linear_kind: str = "dense"  # dense | ket
    linear_order: int = 2
    linear_rank: int = 8
    # t1 column tile for the chain apply / kron_matmul kernel (bounds the
    # (B, r, t1, Πq_rest) intermediate); None = resolved once by
    # train.step.pin_kernel_blocks from the "kron_matmul" autotune family
    linear_tile: Optional[int] = None
    # route ket linear projections through the fused kron_matmul kernel
    # (Pallas on TPU, host executor elsewhere). Tri-state like use_kernels,
    # but independent of it so the embedding/head kernels can stay on their
    # default while the linears opt in (or vice versa). None = auto.
    linear_use_kernel: Optional[bool] = None
    # token-block size of the kron_matmul grid; None = autotuned
    linear_block_b: Optional[int] = None
    # shard the ket factor stacks' rank axis over "model" (rank-parallel
    # operator with one psum at the rank fold; factors are otherwise
    # replicated like embedding factors). Tri-state: None = auto — resolved
    # at build time by train/step.pin_kernel_blocks from the measured
    # compute-vs-collective rule (kernels/autotune.choose_shard_rank, fed by
    # the "comms" interconnect profile); an unpinned None behaves like False
    # (replicate). The kron_matmul kernel honors the decision under an
    # ambient mesh via its shard_map route (kernels/shard.py).
    ket_shard_rank: Optional[bool] = None
    # mesh signature (sorted (axis, size) pairs) stamped by pin_kernel_blocks
    # at step/engine build time. Carrying it in the frozen config makes the
    # mesh part of every jit static key, so a function traced without a mesh
    # can never serve a stale single-device kernel route under one (and vice
    # versa). None = built with no multi-device mesh ambient.
    kernel_mesh: Optional[tuple] = None

    # low-bit ket factor storage (serving): "none" | "int8" | "fp8".
    # Applies to the word2ket(XS) embedding, the kron head, and ket linears;
    # regular tables / dense projections are untouched. init_params then
    # emits {"q", "scale"} wire-format factors (core/quant) — a serving
    # knob: quantized payloads are not differentiable, so train with "none"
    # and quantize post-training (serve/engine.quantize_params).
    quant: str = "none"

    # numerics / training
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # §Perf winner: "dots" saves matmul outputs (−22% step FLOPs vs "full");
    # paired with microbatches=16 it stays under the 16 GB v5e budget.
    remat: str = "dots"
    logit_softcap: float = 0.0

    # perf knobs (hillclimbed in EXPERIMENTS.md §Perf)
    scan_chunk: int = 256     # SSM/RG-LRU time-chunk size
    attn_chunk: int = 1024    # flash-attention KV-chunk size
    ssm_fused_chunks: bool = False  # compute decay/drive per chunk (not whole-S)

    # serving substrate (serve/engine.py + serve/cache.py). ``page_size`` is
    # the token granularity of the paged KV-cache pools; ``prefill_chunk`` is
    # how many prompt tokens one engine tick ingests through the chunked
    # prefill path (⌈P/prefill_chunk⌉ ticks per P-token prompt). Both are
    # serving-time knobs: training/init paths never read them.
    page_size: int = 16
    prefill_chunk: int = 16
    # parallel KV splits of the flash-decoding paged read (split-KV decode):
    # each sequence's pages partition across this many grid splits, merged by
    # an LSE-corrected combine. None = resolved from the "paged_attn"
    # autotune family — the engine pins it at build time
    # (train/step.pin_kernel_blocks) so every decode trace shares one value.
    decode_kv_splits: Optional[int] = None

    def __post_init__(self):
        if not self.layer_pattern:
            pattern = {
                "dense": ("attn",),
                "moe": ("moe_attn",),
                "ssm": ("ssm",),
                "vlm": ("attn",),
                "encdec": ("attn",),
                "hybrid": ("rglru", "rglru", "local_attn"),
            }[self.family]
            object.__setattr__(self, "layer_pattern", pattern)

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.n_experts_held or self.n_experts

    @property
    def dense_ffn_width(self) -> int:
        """FFN width of the leading dense layers."""
        return self.intermediate_size or self.d_ff

    @property
    def q_heads_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def d_inner(self) -> int:  # mamba inner width
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return math.ceil(self.d_model / 16)

    @property
    def is_subquadratic(self) -> bool:
        """Eligible for the long_500k shape (SSM / hybrid-with-local-attn)."""
        kinds = set(self.layer_pattern)
        return kinds <= {"ssm", "rglru", "local_attn"}


def embedding_for(cfg: ModelConfig) -> EmbeddingConfig:
    return EmbeddingConfig(
        vocab_size=cfg.vocab_size,
        embed_dim=cfg.d_model,
        kind=cfg.embedding_kind,
        order=cfg.embedding_order,
        rank=cfg.embedding_rank,
        use_layernorm=cfg.embedding_layernorm,
        dtype=cfg.param_dtype,
        quant=cfg.quant,
        use_kernel=cfg.use_kernels,
        block_b=cfg.embedding_block_b,
    )


def head_for(cfg: ModelConfig) -> HeadConfig:
    return HeadConfig(
        vocab_size=cfg.vocab_size,
        embed_dim=cfg.d_model,
        kind=cfg.head_kind,
        order=cfg.head_order,
        rank=cfg.head_rank,
        vocab_tile=cfg.head_vocab_tile,
        dtype=cfg.param_dtype,
        quant=cfg.quant,
        use_kernel=cfg.use_kernels,
        block_b=cfg.head_block_b,
    )


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"


LM_SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

"""Pallas TPU kernels: flash-decoding split-KV paged-attention read.

Single-query (decode-step) attention over the paged KV pools of
serve/cache.py: K/V live as ``(num_pages, page_size, kv_heads, head_dim)``
pools and each sequence's pages are scattered — the page table is a
**scalar-prefetch** argument, so the K/V BlockSpec index maps dereference
``ptab[b, ·]`` to DMA exactly the pages a sequence owns. No gathered
``(B, S, KVH, Dh)`` intermediate is ever materialized (the XLA reference in
ref.py does exactly that gather and serves as the oracle).

**Split-KV (flash-decoding).** The pre-split kernel walked a slot's pages on
one sequential innermost grid axis, so decode latency grew linearly with
context and the ``(B, KVH, NP)`` grid under-occupied the chip at the small
batch sizes of latency-sensitive traffic. Here the logical pages are
partitioned across a ``kv_splits`` grid axis instead:

* ``_split_kernel`` — grid ``(B, kv_splits, pages_per_split)``, pages
  innermost (sequential per split); each step reads one whole page (all KV
  heads). Each split runs the usual online-softmax accumulation over *its*
  pages only and emits **unnormalized partials** ``mid_o (B, KVH, S, G, Dv)``
  with running stats ``m, l (B, KVH, S, G, 1)`` — the per-(b, split) output
  tile and stats live in revisited VMEM blocks across page steps. Splits
  with no valid page keep their init values ``(0, NEG, 0)``.
* ``_combine_kernel`` — grid ``(B,)``: a log-sum-exp-corrected merge of
  the ``kv_splits`` partials, ``m* = max_s m_s``,
  ``l* = Σ_s l_s·e^{m_s−m*}``, ``o = Σ_s o_s·e^{m_s−m*} / l*`` — the same
  3-scalar combine as the dense flash-decoding leg in serve/decode.py,
  numerically safe for arbitrary ``m`` spread because only non-positive
  exponents are ever taken.

``kv_splits=1`` degenerates to the old sequential-page walk (bit-identical
accumulation order), which the partition-invariance tests pin against every
split count.

Pages past a sequence's length are skipped via ``pl.when`` AND their K/V
index maps clamp to the sequence's last valid page — a revisited block index
elides the DMA, so tail steps neither compute nor copy (the pre-split kernel
DMA'd the trash page for every skipped step).

``interpret=None`` (the default) resolves from the backend — compiled on
TPU, interpret-mode elsewhere. Off-TPU, ``ops.paged_attention`` does not
grid-emulate: ``paged_attention_host`` runs the identical split/partial/
combine algorithm as fused XLA (the kron_matmul host-executor pattern),
walking each split's pages ``page_chunk`` at a time under a ``lax.scan``
online-softmax carry, and ``paged_attention_seq_host`` is the host analogue
of the pre-split sequential-page walk (the benchmark baseline).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30  # plain float: jnp constants would be captured by the kernel
_HI = jax.lax.Precision.HIGHEST


def _default_interpret(interpret):
    """None = backend-detected: compiled on TPU, interpret-mode elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _last_valid_page(length, ps):
    """Index of the last logical page holding a valid token (0 if none)."""
    return jnp.maximum((length + ps - 1) // ps - 1, 0)


def _kv_page_row(p, b, tab, lens, *, ps):
    """Pool row for logical page ``p`` of slot ``b``, with the tail clamp:
    pages past the sequence's length re-map to its last valid page, so the
    (compute-skipped) tail steps revisit an already-resident block and the
    DMA is elided instead of copying the trash page."""
    return tab[b, jnp.minimum(p, _last_valid_page(lens[b], ps))]


# ---------------------------------------------------------------------------
# split kernel: per-split online-softmax partials
# ---------------------------------------------------------------------------
#
# One grid step reads one whole page — every KV head of it — so the K/V
# block is ``(1, page_size, kv_heads, head_dim)``, whose last two dims equal
# the pool's (the TPU compiler's block rule; a one-head ``(…, 1, head_dim)``
# block is refused). In the kernel the page flattens to
# ``(page_size·kv_heads, head_dim)`` rows ``p·KVH + kvh`` and all H query
# heads score against it in one matmul; ``head_mask`` keeps each query head
# on the rows of its own KV head (GQA) and ``row_pos`` maps a row to its
# token offset in the page. Partials use the kernel layout
# ``(B, S, H, D)``; :func:`paged_attention_split_pallas` returns the
# documented ``(B, KVH, S, G, D)`` view.

def _split_kernel(ptab_ref, lens_ref, q_ref, k_ref, v_ref, mask_ref, pos_ref,
                  o_ref, m_ref, l_ref, *, ps, pps, scale):
    b = pl.program_id(0)
    s = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lens_ref[b]
    p = s * pps + j  # logical page this split-step owns

    @pl.when(p * ps < length)
    def _page():
        _, _, kvh, dh = k_ref.shape
        q = q_ref[0].astype(jnp.float32) * scale  # (H, Dh)
        k = k_ref[0].astype(jnp.float32).reshape(ps * kvh, dh)
        v = v_ref[0].astype(jnp.float32).reshape(ps * kvh, v_ref.shape[3])
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 precision=_HI,
                                 preferred_element_type=jnp.float32)
        valid = (mask_ref[...] > 0) & (p * ps + pos_ref[...] < length)
        sc = jnp.where(valid, sc, NEG)  # (H, ps·KVH)

        m_old = m_ref[0, 0]  # (H, 1)
        l_old = l_ref[0, 0]
        m_new = jnp.maximum(m_old, jnp.max(sc, axis=-1, keepdims=True))
        # masked (not just NEG-shifted): another head's rows must add 0 even
        # while this head has seen nothing yet (exp(NEG - NEG) == 1)
        pr = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m_old - m_new)
        l_new = l_old * corr + jnp.sum(pr, axis=-1, keepdims=True)
        pv = jnp.dot(pr, v, precision=_HI,
                     preferred_element_type=jnp.float32)  # (H, Dv)
        o_ref[0, 0] = o_ref[0, 0] * corr + pv
        m_ref[0, 0] = m_new
        l_ref[0, 0] = l_new


def _split_call(q, k_pages, v_pages, ptab, lens, kv_splits, interpret):
    """Split partials in the kernel layout: (B, S, H, Dv), (B, S, H, 1) x2."""
    B, H, Dh = q.shape
    _, ps, KVH, Dv = v_pages.shape
    NP = ptab.shape[1]
    S = max(1, min(int(kv_splits), NP))
    pps = -(-NP // S)  # pages per split (last split may run past NP: clamped)
    G = H // KVH
    rows = jnp.arange(ps * KVH)
    head_mask = (rows[None, :] % KVH == jnp.arange(H)[:, None] // G
                 ).astype(jnp.int32)  # (H, ps·KVH)
    row_pos = (rows // KVH).astype(jnp.int32)[None, :]  # (1, ps·KVH)

    def kv_index(b, s, j, tab, lens_):
        return (_kv_page_row(s * pps + j, b, tab, lens_, ps=ps), 0, 0, 0)

    def out_index(b, s, j, tab, lens_):
        return (b, s, 0, 0)

    def whole(b, s, j, tab, lens_):
        return (0, 0)

    kernel = functools.partial(_split_kernel, ps=ps, pps=pps, scale=Dh ** -0.5)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S, pps),
            in_specs=[
                pl.BlockSpec((1, H, Dh), lambda b, s, j, tab, lens_: (b, 0, 0)),
                pl.BlockSpec((1, ps, KVH, Dh), kv_index),
                pl.BlockSpec((1, ps, KVH, Dv), kv_index),
                pl.BlockSpec(head_mask.shape, whole),
                pl.BlockSpec(row_pos.shape, whole),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, H, Dv), out_index),
                pl.BlockSpec((1, 1, H, 1), out_index),
                pl.BlockSpec((1, 1, H, 1), out_index),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, Dv), jnp.float32),
            jax.ShapeDtypeStruct((B, S, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, S, H, 1), jnp.float32),
        ],
        name="paged_attn_split",
        interpret=_default_interpret(interpret),
    )(ptab.astype(jnp.int32), lens.astype(jnp.int32), q, k_pages, v_pages,
      head_mask, row_pos)


def _to_split_view(x, kvh):
    """(B, S, H, D) kernel layout -> the documented (B, KVH, S, G, D)."""
    B, S, H, D = x.shape
    return jnp.transpose(x.reshape(B, S, kvh, H // kvh, D), (0, 2, 1, 3, 4))


def _from_split_view(x):
    """(B, KVH, S, G, D) -> the kernel layout (B, S, H, D)."""
    B, KVH, S, G, D = x.shape
    return jnp.transpose(x, (0, 2, 1, 3, 4)).reshape(B, S, KVH * G, D)


def paged_attention_split_pallas(q, k_pages, v_pages, ptab, lens, *,
                                 kv_splits, interpret=None):
    """Per-split partials: q (B, H, Dh); pools (P, ps, KVH, Dh/Dv);
    ptab (B, NP); lens (B,) -> mid_o (B, KVH, S, G, Dv) f32 (unnormalized),
    m, l (B, KVH, S, G, 1). Empty splits carry (0, NEG, 0)."""
    KVH = k_pages.shape[2]
    parts = _split_call(q, k_pages, v_pages, ptab, lens, kv_splits, interpret)
    return tuple(_to_split_view(x, KVH) for x in parts)


# ---------------------------------------------------------------------------
# combine kernel: LSE-corrected merge of the split partials
# ---------------------------------------------------------------------------

def _combine_kernel(o_ref, m_ref, l_ref, out_ref):
    o = o_ref[0]  # (S, H, Dv)
    m = m_ref[0]  # (S, H, 1)
    l = l_ref[0]
    m_max = jnp.max(m, axis=0)  # (H, 1)
    # only non-positive exponents: exp never overflows, empty splits
    # (m = NEG) decay to 0 against any split that saw data
    corr = jnp.exp(m - m_max[None])
    l_tot = jnp.sum(l * corr, axis=0)  # (H, 1)
    o_tot = jnp.sum(o * corr, axis=0)  # (H, Dv)
    # all-empty (lens == 0): l_tot == 0 and o_tot == 0 -> output 0
    out_ref[0] = o_tot / jnp.maximum(l_tot, 1e-30)


def _combine_call(mid_o, m, l, interpret):
    """Kernel-layout merge: (B, S, H, Dv) partials -> (B, H, Dv)."""
    B, S, H, Dv = mid_o.shape

    def in_index(b):
        return (b, 0, 0, 0)

    return pl.pallas_call(
        _combine_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, S, H, Dv), in_index),
            pl.BlockSpec((1, S, H, 1), in_index),
            pl.BlockSpec((1, S, H, 1), in_index),
        ],
        out_specs=pl.BlockSpec((1, H, Dv), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Dv), jnp.float32),
        name="paged_attn_combine",
        interpret=_default_interpret(interpret),
    )(mid_o, m, l)


def combine_splits_pallas(mid_o, m, l, *, interpret=None):
    """LSE merge of per-split partials -> (B, KVH, G, Dv) f32 (normalized)."""
    B, KVH, S, G, Dv = mid_o.shape
    out = _combine_call(_from_split_view(mid_o), _from_split_view(m),
                        _from_split_view(l), interpret)
    return out.reshape(B, KVH, G, Dv)


def paged_attention_pallas(q, k_pages, v_pages, ptab, lens, *, kv_splits=1,
                           interpret=None):
    """q (B, H, Dh); k/v pools (P, ps, KVH, Dh/Dv); ptab (B, NP) page table;
    lens (B,) valid tokens per sequence -> (B, H, Dv). Split kernel +
    combine kernel; kv_splits=1 is the sequential-page walk."""
    mid_o, m, l = _split_call(q, k_pages, v_pages, ptab, lens, kv_splits,
                              interpret)
    return _combine_call(mid_o, m, l, interpret).astype(q.dtype)


# ---------------------------------------------------------------------------
# host executors: the identical algorithm as fused XLA (no grid emulation)
# ---------------------------------------------------------------------------

def paged_attention_split_host(q, k_pages, v_pages, ptab, lens, *, kv_splits,
                               page_chunk=32):
    """Host executor of the split kernel: same page partitioning
    (``pps = ceil(NP/S)`` pages per split), same partial format
    (unnormalized mid_o + (m, l); empty splits (0, NEG, 0)).

    Each split's pages are walked ``page_chunk`` at a time by a
    ``lax.scan`` carrying the online-softmax state — the host shape of the
    kernel's sequential page axis, vectorized across (B, S, KVH) per step.
    Chunking keeps the gathered K/V intermediate cache-resident: a one-shot
    whole-table gather materializes several pool-sized copies and loses
    most of the split win at 32k context (measured ~1.6x vs ~3x chunked on
    CPU), while per-page steps pay thousands of tiny-dispatch iterations
    (the seq baseline below)."""
    B, H, Dh = q.shape
    _, ps, KVH, Dv = v_pages.shape
    NP = ptab.shape[1]
    S = max(1, min(int(kv_splits), NP))
    pps = -(-NP // S)
    PC = max(1, min(int(page_chunk), pps))
    n_steps = -(-pps // PC)
    G = H // KVH
    # pad to S splits of pps pages, then each split to n_steps*PC entries;
    # every pad points at the trash page and is masked out below
    tab = jnp.pad(ptab.astype(jnp.int32), ((0, 0), (0, S * pps - NP)))
    tab = jnp.pad(tab.reshape(B, S, pps),
                  ((0, 0), (0, 0), (0, n_steps * PC - pps)))
    # scan steps leading: (T, B, S, PC)
    tab = tab.reshape(B, S, n_steps, PC).transpose(2, 0, 1, 3)
    qf = q.astype(jnp.float32).reshape(B, KVH, G, Dh) * (Dh ** -0.5)
    C = PC * ps  # tokens per scan step per split

    def body(carry, xs):
        o, m, l = carry
        tab_t, t = xs
        gk = k_pages[tab_t].reshape(B, S, C, KVH, Dh)
        gv = v_pages[tab_t].reshape(B, S, C, KVH, Dv)
        sc = jnp.einsum("bkgd,bsckd->bskgc", qf, gk.astype(jnp.float32))
        local = t * C + jnp.arange(C)[None]  # (1, C) position within split
        kpos = (jnp.arange(S) * (pps * ps))[:, None] + local  # (S, C) logical
        # in-split pad entries alias the NEXT split's logical positions, so
        # the length test alone would wrongly admit them
        valid = (local < pps * ps) & (kpos[None] < lens[:, None, None])
        sc = jnp.where(valid[:, :, None, None], sc, NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        # mask (not just NEG-shift) the invalid lanes: in an all-empty split
        # exp(NEG - NEG) would be 1, not 0
        pr = jnp.where(valid[:, :, None, None], jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(pr, axis=-1, keepdims=True)
        o_new = o * corr + jnp.einsum("bskgc,bsckd->bskgd", pr,
                                      gv.astype(jnp.float32))
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((B, S, KVH, G, Dv), jnp.float32)
    m0 = jnp.full((B, S, KVH, G, 1), NEG, jnp.float32)
    l0 = jnp.zeros((B, S, KVH, G, 1), jnp.float32)
    (mid_o, m, l), _ = jax.lax.scan(body, (o0, m0, l0),
                                    (tab, jnp.arange(n_steps)))
    to = lambda x: jnp.moveaxis(x, 1, 2)  # (B, S, KVH, ...) -> (B, KVH, S, ...)
    return to(mid_o), to(m), to(l)


def paged_attention_host(q, k_pages, v_pages, ptab, lens, *, kv_splits,
                         page_chunk=32):
    """Split-KV paged read as fused XLA (the off-TPU serving path): split
    partials + the same LSE-corrected combine as the Pallas pair."""
    from repro.kernels.flash_attn.ref import combine_splits_ref
    B, H, _ = q.shape
    Dv = v_pages.shape[-1]
    mid_o, m, l = paged_attention_split_host(
        q, k_pages, v_pages, ptab, lens, kv_splits=kv_splits,
        page_chunk=page_chunk)
    out = combine_splits_ref(mid_o, m, l)
    return out.reshape(B, H, Dv).astype(q.dtype)


def paged_attention_seq_host(q, k_pages, v_pages, ptab, lens):
    """Host analogue of the PRE-SPLIT kernel: one sequential online-softmax
    walk over the logical pages (fori_loop == the old innermost grid axis).
    The long-context benchmark baseline — split-KV is measured against it."""
    B, H, Dh = q.shape
    _, ps, KVH, Dv = v_pages.shape
    NP = ptab.shape[1]
    G = H // KVH
    qf = q.astype(jnp.float32).reshape(B, KVH, G, Dh) * (Dh ** -0.5)
    tab = ptab.astype(jnp.int32)

    def body(j, carry):
        o, m, l = carry
        pid = tab[:, j]  # (B,)
        k = k_pages[pid].astype(jnp.float32)  # (B, ps, KVH, Dh)
        v = v_pages[pid].astype(jnp.float32)
        sc = jnp.einsum("bkgd,bpkd->bkgp", qf, k)
        kpos = j * ps + jnp.arange(ps)
        sc = jnp.where((kpos[None] < lens[:, None])[:, None, None], sc, NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        pr = jnp.exp(sc - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(pr, axis=-1, keepdims=True)
        o_new = o * corr + jnp.einsum("bkgp,bpkd->bkgd", pr, v)
        # the kernel's pl.when page skip: inactive slots keep their carry
        # (an all-NEG page would otherwise produce exp(NEG - NEG) == 1 rows)
        act = (j * ps < lens)[:, None, None, None]
        return (jnp.where(act, o_new, o), jnp.where(act, m_new, m),
                jnp.where(act, l_new, l))

    o0 = jnp.zeros((B, KVH, G, Dv), jnp.float32)
    m0 = jnp.full((B, KVH, G, 1), NEG, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, 1), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, NP, body, (o0, m0, l0))
    out = o / jnp.maximum(l, 1e-30)
    return out.reshape(B, H, Dv).astype(q.dtype)

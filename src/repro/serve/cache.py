"""Serving cache substrate: dense slot caches + paged KV-cache pools.

Two cache layouts share one cache-kind interface (serve/decode.py consumes
either, branching on the presence of the ``"ptab"`` leaf):

* **dense** — the training/prefill layout: every attention layer holds
  ``(batch_slots, max_len, kv_heads, head_dim)`` K/V tensors, so memory is
  ``batch_slots × max_len`` regardless of how many tokens are actually live.

* **paged** — unbounded-attention layers hold ``(num_pages, page_size,
  kv_heads, head_dim)`` *pools* plus a device-side page table ``ptab``
  ``(batch_slots, ⌈max_len/page_size⌉)`` mapping each slot's logical page to
  a physical pool row. Memory scales with live tokens: a host-side free-list
  :class:`PageAllocator` hands pages out at admission and takes them back at
  retirement. Pool row 0 is a reserved **trash page**: retired/idle slots
  keep all-zero ptab rows, so their (masked, never-read) writes land there
  instead of clobbering live pages. Stale data in a recycled page is never
  read — reads mask by each slot's own position, and every position below it
  was rewritten during the slot's prefill.

Bounded-state kinds (SSM, RG-LRU conv/recurrent state, and the local-window
attention ring buffer) stay dense under both layouts — their footprint is
already O(state) or O(window) per slot, so paging buys nothing.

Slot isolation is driven by an **explicit axis-tag pytree**
(:func:`slot_axes`): each cache leaf is tagged with the axis that indexes
batch slots (or NO_SLOT_AXIS for shared pool leaves), matched by leaf *path*
like parallel/sharding.py — never by guessing which axis happens to equal
``batch_slots``.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

__all__ = [
    "init_cache", "init_layer_cache", "init_paged_cache", "logical_pages",
    "pages_needed", "gather_pages", "identity_ptab", "slot_axes", "reset_slot",
    "copy_page", "PageAllocator", "PrefixCache", "NO_SLOT_AXIS",
    "PAGED_KINDS", "TRASH_PAGE",
]

# attention kinds whose KV/latent history grows with sequence length; only
# these get paged pools ("local_attn" is a bounded ring buffer)
PAGED_KINDS = ("attn", "moe_attn")
# pool row 0 is never allocated: it absorbs the masked writes of idle slots
TRASH_PAGE = 0
# slot_axes tag for leaves with no per-slot axis (paged pools)
NO_SLOT_AXIS = -1


# ---------------------------------------------------------------------------
# Dense layout (training/prefill layout; the pre-paging serving layout)
# ---------------------------------------------------------------------------

def _kv_len(cfg: ModelConfig, kind: str, max_len: int) -> int:
    if kind == "local_attn":
        return min(cfg.local_window, max_len)
    return max_len


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int) -> dict:
    from repro.models import rglru as R
    from repro.models import ssm as S

    dt = cfg.dtype
    S_ = _kv_len(cfg, kind, max_len)
    if kind in ("attn", "local_attn"):
        shp = (batch, S_, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}
    if kind == "moe_attn":
        if cfg.mla:
            return {
                "c": jnp.zeros((batch, S_, cfg.kv_lora_rank), dt),
                "krope": jnp.zeros((batch, S_, cfg.rope_head_dim), dt),
            }
        shp = (batch, S_, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}
    if kind == "ssm":
        return S.ssm_init_cache(cfg, batch, dt)
    if kind == "rglru":
        return R.rglru_init_cache(cfg, batch, dt)
    raise ValueError(kind)


def _assemble(cfg: ModelConfig, batch: int, layer_fn) -> dict:
    pattern = cfg.layer_pattern
    n_lead = cfg.first_k_dense_replace
    n_groups = (cfg.num_layers - n_lead) // len(pattern)
    rem = (cfg.num_layers - n_lead) % len(pattern)

    def stacked(kind):
        one = layer_fn(kind)
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape), one)

    cache = {
        "groups": [stacked(kind) for kind in pattern] if n_groups else [],
        "rem": [layer_fn(pattern[i % len(pattern)]) for i in range(rem)],
        # PER-SLOT positions: each batch slot decodes at its own offset, so a
        # continuous-batching engine can admit a new request into a recycled
        # slot without disturbing its neighbours (serve/engine.py).
        "step": jnp.zeros((batch,), jnp.int32),
    }
    if n_lead:  # leading dense layers: the same attention cache as moe_attn
        cache["lead"] = [layer_fn("moe_attn") for _ in range(n_lead)]
    return cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    return _assemble(cfg, batch,
                     lambda kind: init_layer_cache(cfg, kind, batch, max_len))


# ---------------------------------------------------------------------------
# Paged layout
# ---------------------------------------------------------------------------

def logical_pages(max_len: int, page_size: int) -> int:
    """Page-table width: logical pages covering one slot's max_len tokens."""
    return -(-max_len // page_size)


def pages_needed(n_tokens: int, page_size: int) -> int:
    """Physical pages a request of n_tokens total (prompt + budget) needs."""
    return -(-n_tokens // page_size)


def init_paged_layer_cache(cfg: ModelConfig, kind: str, batch: int,
                           max_len: int, num_pages: int, page_size: int) -> dict:
    dt = cfg.dtype
    if kind in PAGED_KINDS:
        if kind == "moe_attn" and cfg.mla:
            return {
                "c_pages": jnp.zeros((num_pages, page_size, cfg.kv_lora_rank), dt),
                "krope_pages": jnp.zeros((num_pages, page_size, cfg.rope_head_dim), dt),
            }
        shp = (num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
        return {"k_pages": jnp.zeros(shp, dt), "v_pages": jnp.zeros(shp, dt)}
    return init_layer_cache(cfg, kind, batch, max_len)


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     num_pages: int, page_size: int | None = None) -> dict:
    """Paged cache pytree: pools for unbounded-attention kinds, dense state
    for bounded kinds, plus the shared slot→page table ``ptab``.

    ``ptab[b, j]`` is the pool row backing slot b's logical page j (tokens
    ``j·page_size .. (j+1)·page_size``); 0 (TRASH_PAGE) marks unmapped.
    The same table indexes every layer's pool — each layer owns pool row i
    for the same logical page.
    """
    ps = page_size or cfg.page_size
    cache = _assemble(
        cfg, batch,
        lambda kind: init_paged_layer_cache(cfg, kind, batch, max_len,
                                            num_pages, ps))
    cache["ptab"] = jnp.zeros((batch, logical_pages(max_len, ps)), jnp.int32)
    return cache


def identity_ptab(cache: dict, batch: int) -> dict:
    """Allocator-bypassing page table for direct-step harnesses (launchers,
    conformance oracles): slot b owns pool rows b·NP+1 .. (b+1)·NP, row 0
    stays the trash page. The engine's PageAllocator produces the same
    layout class, just with arbitrary row permutations."""
    NP = cache["ptab"].shape[1]
    rows = 1 + jnp.arange(batch * NP, dtype=jnp.int32).reshape(batch, NP)
    cache["ptab"] = rows
    return cache


def gather_pages(pool: jax.Array, ptab: jax.Array) -> jax.Array:
    """Materialize the logical per-slot view of a pool.

    pool (P, ps, ...), ptab (B, NP) -> (B, NP·ps, ...). Logical position t of
    slot b lands at index t; unmapped pages gather the trash page (masked by
    the callers' valid-length masks).
    """
    g = pool[ptab]  # (B, NP, ps, ...)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


# ---------------------------------------------------------------------------
# Slot isolation: explicit axis tags (no shape guessing)
# ---------------------------------------------------------------------------

def _leaf_name(path) -> str:
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    return ""


def _in_groups(path) -> bool:
    return any(hasattr(p, "key") and str(p.key) == "groups" for p in path)


def slot_axes(cache) -> dict:
    """Parallel pytree of per-leaf slot-axis tags.

    Pool leaves (``*_pages``) carry NO_SLOT_AXIS — they are shared across
    slots and isolated via ``ptab`` instead. Dense leaves carry the explicit
    batch axis: 0 at the top level / "rem", 1 under the stacked "groups"
    (whose leading axis is the layer-group stack — the axis the old
    shape-matching reset confused with batch whenever n_groups happened to
    equal batch_slots).
    """
    def tag(path, leaf):
        name = _leaf_name(path)
        if name.endswith("_pages"):
            return NO_SLOT_AXIS
        if name in ("step", "ptab"):
            return 0
        if leaf.ndim == 0:
            return NO_SLOT_AXIS
        return 1 if _in_groups(path) else 0

    return jax.tree_util.tree_map_with_path(tag, cache)


def reset_slot(cache, axes, s: int):
    """Zero slot ``s`` in every dense leaf; pool leaves are left alone (their
    isolation is the page table, which IS zeroed via its axis-0 tag)."""
    def reset(x, ax):
        if ax == NO_SLOT_AXIS:
            return x
        idx = (slice(None),) * ax + (s,)
        return x.at[idx].set(0)

    return jax.tree_util.tree_map(reset, cache, axes)


def copy_page(cache, src: int, dst: int):
    """Copy pool row ``src`` -> ``dst`` in every paged pool leaf (all layers
    at once — the slot→page table indexes every layer's pool with the same
    row id). This is the device half of copy-on-write: the engine allocates
    a private row, copies the shared row's content here, then repoints the
    slot's ptab entry (serve/engine.py::_grow)."""
    def cp(path, leaf):
        if not _leaf_name(path).endswith("_pages"):
            return leaf
        if _in_groups(path):  # stacked pools: (n_groups, P, ps, ...)
            return leaf.at[:, dst].set(leaf[:, src])
        return leaf.at[dst].set(leaf[src])

    return jax.tree_util.tree_map_with_path(cp, cache)


# ---------------------------------------------------------------------------
# Host-side page allocator
# ---------------------------------------------------------------------------

class PageAllocator:
    """Refcounted free-list allocator over pool rows 1..num_pages-1 (row 0 =
    trash).

    Pages come out of ``alloc`` with refcount 1. Sharing a page — a prefix
    cache entry, a second slot mapping the same physical prefix page —
    takes an extra reference via :meth:`acquire`; :meth:`release` drops one
    reference per page and only returns the page to the free list when its
    count reaches zero (``free`` is the same release-to-zero operation,
    kept as the historical name for sole-owner call sites).

    Self-checking: releasing a page that isn't outstanding raises, so
    double-free / leak bugs in the scheduler surface as exceptions rather
    than silent cache corruption.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (row 0 is the trash page)")
        self.capacity = num_pages - 1
        self._free = list(range(num_pages - 1, 0, -1))  # pop() -> low ids first
        self._outstanding: set[int] = set()
        self._refs: dict[int, int] = {}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def outstanding_count(self) -> int:
        return len(self._outstanding)

    @property
    def outstanding(self) -> frozenset[int]:
        """Snapshot of the allocated page ids (engine.check() reconciles
        this against per-slot ownership + externally held pages)."""
        return frozenset(self._outstanding)

    def refcount(self, page: int) -> int:
        """Live references on ``page`` (0 for free/foreign pages)."""
        return self._refs.get(page, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._outstanding.update(pages)
        for p in pages:
            self._refs[p] = 1
        return pages

    def acquire(self, page: int) -> None:
        """Take one more reference on an already-outstanding page."""
        if page not in self._outstanding:
            raise ValueError(f"acquire on non-outstanding page {page}")
        self._refs[page] += 1

    def release(self, pages: list[int]) -> None:
        """Drop one reference per page; a page whose count reaches zero
        returns to the free list."""
        for p in pages:
            if p not in self._outstanding:
                raise ValueError(f"double-free / foreign page {p}")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._outstanding.remove(p)
                self._free.append(p)

    # release-to-zero under the pre-refcount name: sole-owner call sites
    # (held pages, dense-mode bookkeeping) read as plain frees
    free = release

    def check(self) -> None:
        """Invariant: every page is exactly one of {free, outstanding}, and
        every outstanding page carries a positive refcount."""
        assert len(self._free) + len(self._outstanding) == self.capacity, \
            (len(self._free), len(self._outstanding), self.capacity)
        assert not (set(self._free) & self._outstanding)
        assert set(self._refs) == self._outstanding, \
            (set(self._refs), self._outstanding)
        assert all(c >= 1 for c in self._refs.values()), self._refs


# ---------------------------------------------------------------------------
# Content-addressed prefix cache
# ---------------------------------------------------------------------------

class PrefixCache:
    """Content-addressed map from chained page hashes to physical pool rows.

    A prompt is hashed one *full page* at a time: page j's key chains page
    j-1's key with page j's token ids (:meth:`chain_key`), so a hit on page
    j implies every earlier page hit too — matching is a single walk down
    the key list and always yields a leading run. Keys are blake2b over the
    raw token ids, so two prompts share a cached page iff they share the
    entire page-aligned token prefix (position-exact: the chain starts at
    position 0, and KV content depends only on token ids + absolute
    positions).

    The cache holds one allocator reference per cached page (so a cached
    page survives its producer slot's retirement); every slot that maps a
    cached page holds its own reference on top. :meth:`evict` drops LRU
    entries whose page nobody else references — a page shared by any live
    slot is never evicted from under it.
    """

    def __init__(self, allocator: PageAllocator, page_size: int):
        self._alloc = allocator
        self.page_size = page_size
        self._map: OrderedDict[bytes, int] = OrderedDict()  # key -> page, LRU
        self._by_page: dict[int, bytes] = {}
        self.hits = 0
        self.misses = 0
        self.inserts = 0
        self.evictions = 0
        self.invalidations = 0

    def __len__(self) -> int:
        return len(self._map)

    @property
    def pages(self) -> frozenset[int]:
        """Pages the cache itself holds a reference on."""
        return frozenset(self._by_page)

    @staticmethod
    def chain_key(prev: bytes | None, tokens) -> bytes:
        h = hashlib.blake2b(digest_size=16)
        h.update(prev if prev is not None else b"\x00root")
        h.update(np.asarray(list(tokens), np.int64).tobytes())
        return h.digest()

    def page_keys(self, tokens) -> list[bytes]:
        """Chained keys for every full page of ``tokens`` (the ragged tail
        is never cached — partial pages are still being written)."""
        keys: list[bytes] = []
        prev = None
        ps = self.page_size
        for j in range(len(tokens) // ps):
            prev = self.chain_key(prev, tokens[j * ps:(j + 1) * ps])
            keys.append(prev)
        return keys

    def lookup(self, keys: list[bytes]) -> list[int]:
        """Longest leading run of cached pages for ``keys``; acquires one
        reference per returned page (the caller owns them until release)."""
        out: list[int] = []
        for k in keys:
            p = self._map.get(k)
            if p is None:
                break
            self._map.move_to_end(k)
            self._alloc.acquire(p)
            out.append(p)
        self.hits += len(out)
        self.misses += len(keys) - len(out)
        return out

    def insert(self, key: bytes, page: int) -> bool:
        """Cache ``page`` under ``key`` (acquiring a reference). No-op if
        the key is already cached — first producer wins."""
        if key in self._map:
            return False
        self._alloc.acquire(page)
        self._map[key] = page
        self._by_page[page] = key
        self.inserts += 1
        return True

    def invalidate(self, key: bytes) -> bool:
        """Drop one entry (e.g. a page produced by a quarantined slot whose
        model state went non-finite — its content cannot be trusted by
        other requests). Releases the cache's reference; sharers keep
        theirs."""
        p = self._map.pop(key, None)
        if p is None:
            return False
        del self._by_page[p]
        self._alloc.release([p])
        self.invalidations += 1
        return True

    def evict(self, n: int) -> int:
        """Release up to ``n`` LRU pages referenced *only* by the cache.
        Returns how many pages actually went back to the free list."""
        freed = 0
        for k, p in list(self._map.items()):
            if freed >= n:
                break
            if self._alloc.refcount(p) == 1:  # nobody else: safe to drop
                del self._map[k]
                del self._by_page[p]
                self._alloc.release([p])
                self.evictions += 1
                freed += 1
        return freed

    def stats(self) -> dict:
        return {"prefix_cache_pages": len(self._map),
                "prefix_hits": self.hits, "prefix_misses": self.misses,
                "prefix_inserts": self.inserts,
                "prefix_evictions": self.evictions,
                "prefix_invalidations": self.invalidations}

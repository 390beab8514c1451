"""Continuous-batching serving engine: chunked prefill + paged KV cache,
with fault-tolerant scheduling.

The scheduler keeps a fixed decode batch full over two jitted step
functions (never retraced — admissions only touch host bookkeeping, the
page table, and slot resets):

* **prefill (mixed) ticks** — while any slot holds unconsumed prompt
  tokens, one tick pushes a chunk of up to ``prefill_chunk`` tokens *per
  prefilling slot* through ``serve/decode.prefill_step`` (full
  chunk-parallel forward: flash attention over [cache ∪ chunk],
  chunk-parallel SSM/RG-LRU scans), while slots already decoding ride the
  same tick as length-1 chunks — prefill never starves in-flight decodes.
  A P-token prompt warms its cache in ⌈P/prefill_chunk⌉ ticks; the last
  chunk's final-position logits seed the first sampled token.
* **decode ticks** — one token for every decoding slot through the
  (cheaper, chunk-free) decode step, as before.

Memory is governed by a **page budget** (serve/cache.py pools) under one of
two admission policies:

* ``admission="optimistic"`` (default with chunked prefill) — a request
  admits as soon as the free list covers its *first chunk*; pages are then
  allocated incrementally, right before each tick writes into them. On pool
  exhaustion the engine **preempts the youngest slot**: its pages return to
  the free list and the request requeues at the *front* of the queue with
  its already-generated tokens as a resumable prefix (greedy decode replays
  the prefix exactly, so a preempted-then-resumed request emits the same
  stream as an uninterrupted run). Only strictly-younger slots are ever
  preempted on behalf of an older one, so FIFO completion order is
  preserved and the oldest request always progresses; if even preempting
  every younger slot cannot cover a slot's next write (external pressure,
  ``hold_pages``), the slot **stalls** for the tick (lens 0 through the
  mixed tick — its state does not advance).
* ``admission="reserve"`` — the worst case ⌈(prompt+max_new)/page_size⌉ is
  reserved up front and admission blocks FIFO until it fits: no preemption
  machinery, the pre-fault-tolerance behavior (and the only policy for
  ``prefill_mode="stepwise"``, whose batched decode tick cannot express a
  per-slot stall).

**Prefix caching** (``prefix_cache=True``, paged + chunked + fully-paged
layer patterns only): full pages of each slot's written token stream are
published to a content-addressed :class:`~repro.serve.cache.PrefixCache`
under chained blake2b keys; admission maps the longest cached run straight
into the new slot's page table (skipping those prefill ticks) and holds one
allocator reference per mapped page. Writes never target a shared page —
``_grow`` copy-on-writes the one reachable case (a fully-covered prompt
replaying its final token) before the tick. Under page pressure the engine
sheds cold cache entries before preempting anyone. Streaming rides on top:
``Request.on_token`` fires synchronously per emitted token, and per-request
SLO stats (``ttft_s``, ``emit_tps``, ``prefix_hit_pages``) surface through
``Request`` and ``stats()``. See docs/serving.md "Prefix caching".

Request lifecycle robustness (see docs/serving.md "Fault model"):

* **deadlines** — ``Request.deadline_s`` is a TTL from submission; expired
  requests fail with reason ``"deadline"`` whether queued or mid-decode.
  ``cancel(uid)`` fails one request on demand.
* **step failures** — every jitted model call runs under bounded
  retry-with-backoff; when retries exhaust, the engine *degrades*: the
  op-layer kernel switch flips to the reference paths
  (``repro.kernels.set_kernels_forced_off``, the ``REPRO_KERNELS=off``
  switch) and the config is swapped to a kernel-free clone (forcing a
  retrace), then the call retries on the degraded rung. If even the ref
  path fails, every in-flight and queued request fails with a recorded
  reason — never silently lost.
* **non-finite logits** — an emitting slot whose logits are not finite is
  **quarantined**: requeued once (replaying its prefix), failed with reason
  ``"nonfinite_logits"`` on the second strike. The garbage token is never
  emitted.
* **drain** — SIGTERM/SIGINT (opt-in ``handle_signals=True``, shared
  ``repro.fault.PreemptionHandler``) or ``request_drain()`` stops
  admissions; ``run_until_drained`` finishes in-flight requests and fails
  whatever is still queued with reason ``"drained"``.

``check()`` audits the allocator free list, per-slot page ownership, and
the device page table against each other after any tick; the chaos suite
(tests/test_serving_fault.py + serve/faultinject.py) drives all of the
above on seeded schedules.

Serving-grade quantization: ``quantize_params`` / ``dequantize_params``
(re-exported from core/quant) are the post-training calibration roundtrip;
construct with ``quant="int8"|"fp8"`` to calibrate fp params at admission.
``prefill_mode="stepwise"`` keeps the legacy prefill-by-decode path (one
prompt token per tick through the decode step) — the benchmark baseline
and a conformance differential.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import Counter, deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.quant import dequantize_params, quantize_params
from repro.fault import PreemptionHandler, StragglerWatchdog
from repro.models import model as MD
from repro.serve import decode as D
from repro.serve.cache import (PAGED_KINDS, TRASH_PAGE, PageAllocator,
                               PrefixCache, copy_page, logical_pages,
                               pages_needed, reset_slot, slot_axes)

__all__ = ["Request", "ServingEngine", "DrainResult", "EngineStepError",
           "quantize_params", "dequantize_params"]


# module-level jitted entry points (cfg is a hashable frozen dataclass):
# every engine over the same config shares one compilation cache instead of
# re-tracing per instance
# held-expert MoE configs' steps also return the rows each expert received
@functools.partial(jax.jit, static_argnums=(0,))
def _jit_step(cfg, params, cache, tokens):
    return D.serve_step(params, cfg, cache, tokens)


@functools.partial(jax.jit, static_argnums=(0,))
def _jit_prefill(cfg, params, cache, tokens, lens):
    return D.prefill_step(params, cfg, cache, tokens, lens)


class EngineStepError(RuntimeError):
    """A model call failed beyond the retry budget AND the degraded rung."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    deadline_s: Optional[float] = None  # TTL from submission; None = none
    # filled by the engine:
    output: list[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    finished_at: Optional[float] = None
    status: str = "new"  # new | queued | running | done | failed
    fail_reason: Optional[str] = None
    preemptions: int = 0
    # quarantine strikes: one requeue is forgiven, the second failure is
    # attributed to the request (persistently non-finite model state)
    nonfinite_strikes: int = 0
    # streaming: fired synchronously with each emitted token id (replayed
    # tokens after a preemption are NOT re-fired — emission is exactly-once);
    # a raising callback fails the request with reason "callback_error: ..."
    on_token: Optional[Callable[[int], None]] = None
    # SLO stats, filled by the engine
    first_token_at: Optional[float] = None
    prefix_hit_pages: int = 0  # cached pages mapped at (re)admission
    # bounds of the request's spans (repro.obs clock), kept by the engine:
    # when it last entered the queue, and when it was first admitted
    queued_t: float = dataclasses.field(default=0.0, init=False, repr=False)
    admitted_t: Optional[float] = dataclasses.field(default=None, init=False,
                                                   repr=False)

    @property
    def ttft_s(self) -> Optional[float]:
        """Time to first emitted token (None until one is emitted)."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def emit_tps(self) -> Optional[float]:
        """Emitted tokens/sec from first token to finish."""
        if self.first_token_at is None or self.finished_at is None:
            return None
        dt = self.finished_at - self.first_token_at
        return len(self.output) / dt if dt > 0 else None


@dataclasses.dataclass(frozen=True)
class DrainResult:
    """Outcome of ``run_until_drained`` — never silently truncated: if the
    tick budget ran out with work still in flight, ``drained`` is False and
    ``stranded`` names the requests left behind (also surfaced by
    ``stats()["stranded"]``)."""

    ticks: int
    drained: bool
    stranded: tuple[int, ...] = ()  # uids still queued or in-flight


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 8,
                 max_len: int = 512, greedy: bool = True, seed: int = 0,
                 quant: str = "none", cache_mode: str = "paged",
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_mode: str = "chunked",
                 admission: str = "optimistic",
                 prefix_cache: bool = False,
                 max_step_retries: int = 2,
                 retry_backoff_s: float = 0.02,
                 injector=None,
                 clock: Optional[Callable[[], float]] = None,
                 handle_signals: bool = False,
                 watchdog_factor: float = 10.0):
        if cache_mode not in ("paged", "dense"):
            raise ValueError(cache_mode)
        if prefill_mode not in ("chunked", "stepwise"):
            raise ValueError(prefill_mode)
        if admission not in ("optimistic", "reserve"):
            raise ValueError(admission)
        self.cfg = cfg
        # post-training calibration: quantize ket factors to the wire format
        # once at admission; no-op for already-quantized or "none"
        self.params = quantize_params(params, quant)
        self.B = batch_slots
        self.max_len = max_len
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        self.cache_mode = cache_mode
        self.prefill_mode = prefill_mode
        self.page_size = page_size or cfg.page_size

        chunk = prefill_chunk or cfg.prefill_chunk
        if "local_attn" in cfg.layer_pattern:
            # chunk scatter into a ring of RS slots must be collision-free
            chunk = min(chunk, min(cfg.local_window, max_len))
        self.prefill_chunk = max(1, chunk)

        if cache_mode == "paged":
            if num_pages is None:  # full capacity: every slot can reach max_len
                num_pages = batch_slots * logical_pages(max_len, self.page_size) + 1
            self.allocator: Optional[PageAllocator] = PageAllocator(num_pages)
            self.cache = MD.init_cache(cfg, batch_slots, max_len, paged=True,
                                       num_pages=num_pages,
                                       page_size=self.page_size)
        else:
            self.allocator = None
            self.cache = MD.init_cache(cfg, batch_slots, max_len)
        self._axes = slot_axes(self.cache)
        self._needs_pages = (self.allocator is not None
                             and any(k in PAGED_KINDS for k in cfg.layer_pattern))
        # the batched decode tick cannot stall a single slot (its step
        # counter advances for the whole batch), so optimistic admission —
        # whose exhaustion handling needs per-slot stalls — requires the
        # ragged mixed tick. Without pages there is nothing to run out of.
        if prefill_mode == "stepwise" or not self._needs_pages:
            admission = "reserve"
        self.admission = admission

        # content-addressed prefix caching: only sound when every layer's
        # per-token state lives in the shared pools — dense per-slot state
        # (SSM / RG-LRU / local-attn rings) cannot be reused by mapping
        # pages, and the stepwise tick cannot skip prefill positions.
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            if (not self._needs_pages or prefill_mode != "chunked"
                    or any(k not in PAGED_KINDS for k in cfg.layer_pattern)):
                raise ValueError(
                    "prefix_cache requires paged cache_mode, chunked prefill, "
                    f"and a fully-paged layer pattern (got {cfg.layer_pattern})")
            self.prefix_cache = PrefixCache(self.allocator, self.page_size)

        # Build-time pinning: resolve every autotuned tile, the split-KV
        # decode's split count (from the engine's actual read shape — pages
        # at max_len, slot count), the mesh-native kernel-route signature
        # (cfg.kernel_mesh) and the ket_shard_rank decision ONCE, so every
        # engine trace shares one static config and the degraded-mode clone
        # in _degrade carries the pinned values along. Stamping the ambient
        # mesh here is what keys the jit cache per mesh — an engine built
        # under a mesh can never reuse a stale single-device trace.
        from repro.train.step import pin_kernel_blocks
        decode_pages = (logical_pages(max_len, self.page_size)
                        if self._needs_pages and cfg.decode_kv_splits is None
                        else None)
        cfg = pin_kernel_blocks(
            cfg, decode_pages=decode_pages, decode_batch=batch_slots,
            decode_page_size=self.page_size, tokens_hint=batch_slots)
        self.cfg = cfg

        self._step = functools.partial(_jit_step, cfg)
        self._prefill = functools.partial(_jit_prefill, cfg)

        # slot bookkeeping (host side)
        self.slot_req: list[Optional[Request]] = [None] * batch_slots
        self.slot_pending: list[deque] = [deque() for _ in range(batch_slots)]
        self.slot_pages: list[list[int]] = [[] for _ in range(batch_slots)]
        # tokens written into the slot's cache so far (mirrors cache["step"])
        self.slot_pos: list[int] = [0] * batch_slots
        # prefix-cache bookkeeping: how many leading pages of the slot are
        # shared (read-only until copy-on-write), the chained page keys
        # covering the slot's written stream, and the keys this slot itself
        # published (quarantine must pull those back)
        self.slot_shared_n: list[int] = [0] * batch_slots
        self.slot_keys: list[list[bytes]] = [[] for _ in range(batch_slots)]
        self.slot_inserted: list[list[bytes]] = [[] for _ in range(batch_slots)]
        # admission sequence number: smallest = oldest (preemption victims
        # are always the youngest)
        self.slot_seq: list[int] = [0] * batch_slots
        self._admit_seq = 0
        self.queue: deque[Request] = deque()
        self.done: list[Request] = []
        self.failed: list[Request] = []
        self._cur_tokens = np.zeros((batch_slots,), np.int32)
        self.prefill_ticks = 0
        self.decode_ticks = 0
        self.stalled_ticks = 0
        self._busy_s = 0.0
        self._tick = 0

        # fault tolerance
        self.max_step_retries = max_step_retries
        self.retry_backoff_s = retry_backoff_s
        self._injector = injector
        self._clock = clock or time.time
        self.watchdog = StragglerWatchdog(factor=watchdog_factor)
        self._preempt_handler = PreemptionHandler() if handle_signals else None
        self._draining = False
        self._held_pages: list[int] = []
        self._last_drain: Optional[DrainResult] = None
        self.degraded = False
        self.degrade_reason: Optional[str] = None
        self.preemptions = 0
        self.retries = 0
        self.quarantines = 0
        self.cow_copies = 0
        self.prefix_hit_pages_total = 0
        # immutable failure record: (uid, reason) per _fail call. Request
        # objects can be resubmitted (submit() resets their lifecycle
        # fields), so stats() must not rebuild failure history from them.
        self._fail_log: list[tuple[int, str]] = []

    # ------------------------------------------------------------------
    # submission + lifecycle
    # ------------------------------------------------------------------
    def submit(self, req: Request):
        if not req.prompt:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            # a 0-budget request admits a slot that can never retire under
            # chunked prefill (no emission ever happens)
            raise ValueError(f"max_new_tokens must be >= 1, got {req.max_new_tokens}")
        if req.eos_id is not None and req.eos_id < 0:
            raise ValueError(f"eos_id must be a token id (>= 0), got {req.eos_id}")
        if req.deadline_s is not None and req.deadline_s <= 0:
            raise ValueError(f"deadline_s must be positive, got {req.deadline_s}")
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({len(req.prompt)}) + max_new({req.max_new_tokens}) "
                f"exceeds max_len={self.max_len}")
        if self._needs_pages and self._pages_worst_case(req) > self.allocator.capacity:
            raise ValueError(
                f"request needs {self._pages_worst_case(req)} pages but the pool "
                f"only has {self.allocator.capacity}: it could never admit")
        if (any(r.uid == req.uid for r in self.queue)
                or any(r is not None and r.uid == req.uid for r in self.slot_req)):
            # uids key cancel() and per-request accounting: a duplicate live
            # uid would make cancel() stop at the first match and conflate
            # the two requests' stats
            raise ValueError(f"uid {req.uid} is already live (queued or in-flight)")
        # a resubmitted Request object (same prompt after a cancel/deadline
        # that caught it mid-preemption) must not carry stale lifecycle
        # state into the new attempt: partial output would be replayed as a
        # resumable prefix, and strike/preemption counts would fail it early
        req.output = []
        req.status = "new"
        req.fail_reason = None
        req.finished_at = None
        req.preemptions = 0
        req.nonfinite_strikes = 0
        req.first_token_at = None
        req.prefix_hit_pages = 0
        req.submitted_at = self._clock()
        req.queued_t = time.perf_counter()
        req.admitted_t = None
        req.status = "queued"
        self.queue.append(req)

    def cancel(self, uid: int) -> bool:
        """Fail one request (queued or in-flight) with reason "cancelled"."""
        for req in self.queue:
            if req.uid == uid:
                self.queue.remove(req)
                self._fail(req, "cancelled")
                return True
        for s in range(self.B):
            req = self.slot_req[s]
            if req is not None and req.uid == uid:
                self._fail(req, "cancelled", slot=s)
                return True
        return False

    def request_drain(self):
        """Stop admitting; ``run_until_drained`` finishes in-flight work and
        fails the rest with reason "drained" (the SIGTERM path)."""
        self._draining = True

    def _pages_worst_case(self, req: Request) -> int:
        return pages_needed(len(req.prompt) + req.max_new_tokens, self.page_size)

    def _resume_prompt(self, req: Request) -> list[int]:
        """The prefix a (re)admitted request must prefill: its prompt plus
        everything already generated. Greedy decode replays the generated
        tokens bit-exactly, so resumption is invisible in the output."""
        return list(req.prompt) + list(req.output)

    # ------------------------------------------------------------------
    # admission + page growth + preemption
    # ------------------------------------------------------------------
    def _admit(self):
        if self._draining:
            return
        ps = self.page_size
        for s in range(self.B):
            if self.slot_req[s] is not None or not self.queue:
                continue
            req = self.queue[0]
            prefix = self._resume_prompt(req)
            # content-addressed reuse: map the longest run of cached pages
            # covering the page-aligned prefix and skip their prefill ticks
            hits: list[int] = []
            keys: list[bytes] = []
            if self.prefix_cache is not None:
                keys = self.prefix_cache.page_keys(prefix)
                hits = self.prefix_cache.lookup(keys)  # acquires one ref each
                if self.admission == "reserve" and hits:
                    # reserve mode has no COW machinery (its _grow is a
                    # no-op): keep the prefix's last token out of shared
                    # pages so writes never land in one
                    cap = (len(prefix) - 1) // ps
                    if len(hits) > cap:
                        self.allocator.release(hits[cap:])
                        hits = hits[:cap]
            h = len(hits)
            # fully-covered prompt: replay only its last token — that write
            # copy-on-writes the final shared page in _grow before the tick
            start = min(h * ps, len(prefix) - 1)
            pages: list[int] = list(hits)
            if self._needs_pages:
                if self.admission == "reserve":
                    want = self._pages_worst_case(req) - h
                else:
                    first = min(self.prefill_chunk, len(prefix) - start)
                    want = pages_needed(start + first, ps) - h
                want = max(0, want)
                got = self.allocator.alloc(want)
                if got is None and self.prefix_cache is not None:
                    # shed cold cache entries before blocking admission
                    self.prefix_cache.evict(want - self.allocator.free_count)
                    got = self.allocator.alloc(want)
                if got is None:
                    if hits:
                        self.allocator.release(hits)  # undo the lookup refs
                    return  # page budget exhausted: block FIFO (no skipping)
                pages += got
            self.queue.popleft()
            now = time.perf_counter()
            obs.record("request.queue", req.queued_t, now, uid=req.uid,
                       requeues=req.preemptions)
            if req.admitted_t is None:
                req.admitted_t = now
            self._admit_seq += 1
            self.slot_req[s] = req
            self.slot_seq[s] = self._admit_seq
            self.slot_pages[s] = pages
            self.slot_pos[s] = start
            self.slot_shared_n[s] = h
            self.slot_keys[s] = keys[:h]
            self.slot_inserted[s] = []
            req.status = "running"
            req.prefix_hit_pages = h
            self.prefix_hit_pages_total += h
            # engine-level cache isolation: zero the slot along the tagged
            # axes (clears dense state, the step counter, and the ptab row)
            self.cache = reset_slot(self.cache, self._axes, s)
            if "ptab" in self.cache and pages:
                row = np.zeros((self.cache["ptab"].shape[1],), np.int32)
                row[:len(pages)] = pages
                self.cache["ptab"] = self.cache["ptab"].at[s].set(jnp.asarray(row))
            if start:
                # skipped prefill: reads/writes resume past the shared pages
                self.cache["step"] = self.cache["step"].at[s].set(start)
            if self.prefill_mode == "chunked":
                self.slot_pending[s] = deque(prefix[start:])
                self._cur_tokens[s] = 0
            else:  # stepwise: first prompt token feeds the next decode tick
                self.slot_pending[s] = deque(prefix)
                self._cur_tokens[s] = self.slot_pending[s].popleft()

    def _tokens_this_tick(self, s: int) -> int:
        if self.slot_pending[s]:
            n = len(self.slot_pending[s])
            return min(self.prefill_chunk, n) if self.prefill_mode == "chunked" else 1
        return 1  # decoding: one token

    def _acquire_pages(self, s: int, need: int) -> Optional[list[int]]:
        """Allocate under pressure on behalf of slot ``s``: shed cold
        prefix-cache entries first (pages nothing live references), then
        preempt strictly-younger slots, else give up (caller stalls)."""
        while not self.allocator.can_alloc(need):
            if (self.prefix_cache is not None and
                    self.prefix_cache.evict(need - self.allocator.free_count)):
                continue
            victim = self._youngest_live_slot(younger_than=self.slot_seq[s])
            if victim is None:
                break
            self._preempt(victim, "page_pressure")
        return self.allocator.alloc(need)

    def _grow(self) -> set[int]:
        """Optimistic mode: make sure every live slot owns — exclusively —
        the pages its next tick will write into: copy-on-write any shared
        page in the write path, then grow, preempting strictly-younger
        slots on exhaustion. Returns the slots that must stall this tick."""
        stalled: set[int] = set()
        if self.admission != "optimistic":
            return stalled
        order = sorted((s for s in range(self.B) if self.slot_req[s] is not None),
                       key=lambda s: self.slot_seq[s])
        for s in order:
            if self.slot_req[s] is None:
                continue  # preempted by an older slot earlier in this pass
            wp = self.slot_pos[s] // self.page_size
            if wp < self.slot_shared_n[s]:
                # the next write lands in a shared page (a fully-covered
                # prefix replaying its last token): allocate a private page,
                # copy the pool rows, repoint the ptab entry. Only the LAST
                # shared page can ever be in the write path — earlier pages
                # are fully covered by the matched prefix.
                got = self._acquire_pages(s, 1)
                if got is None:
                    stalled.add(s)
                    continue
                new = got[0]
                old = self.slot_pages[s][wp]
                self.cache = copy_page(self.cache, old, new)
                self.slot_pages[s][wp] = new
                self.cache["ptab"] = self.cache["ptab"].at[s, wp].set(new)
                self.allocator.release([old])  # drop this slot's shared ref
                self.slot_shared_n[s] = wp
                self.cow_copies += 1
            need = pages_needed(self.slot_pos[s] + self._tokens_this_tick(s),
                                self.page_size) - len(self.slot_pages[s])
            if need <= 0:
                continue
            got = self._acquire_pages(s, need)
            if got is None:
                stalled.add(s)  # external pressure: wait, don't corrupt
                continue
            base = len(self.slot_pages[s])
            self.slot_pages[s].extend(got)
            ptab = self.cache["ptab"]
            for j, p in enumerate(got):
                ptab = ptab.at[s, base + j].set(p)
            self.cache["ptab"] = ptab
        return stalled

    def _youngest_live_slot(self, younger_than: int) -> Optional[int]:
        cands = [s for s in range(self.B)
                 if self.slot_req[s] is not None and self.slot_seq[s] > younger_than]
        return max(cands, key=lambda s: self.slot_seq[s]) if cands else None

    def _release_slot(self, s: int):
        self.slot_req[s] = None
        self.slot_pending[s].clear()
        self.slot_pos[s] = 0
        self._cur_tokens[s] = 0
        self.slot_shared_n[s] = 0
        self.slot_keys[s] = []
        self.slot_inserted[s] = []
        if self.slot_pages[s]:
            # drop one reference per page: pages the prefix cache (or
            # another sharing slot) still references stay outstanding
            self.allocator.release(self.slot_pages[s])
            self.slot_pages[s] = []
        if "ptab" in self.cache:
            # re-point the idle slot at the trash page NOW: its masked decode
            # writes must not land in pages a future request may own
            self.cache["ptab"] = self.cache["ptab"].at[s].set(TRASH_PAGE)

    def _preempt(self, s: int, reason: str):
        """Evict slot ``s`` and requeue its request at the FRONT of the
        queue with its generated tokens as a resumable prefix. Preempted
        requests were admitted before anything still queued, so the front
        slot preserves FIFO completion order."""
        req = self.slot_req[s]
        assert req is not None
        req.preemptions += 1
        req.status = "queued"
        req.queued_t = time.perf_counter()
        self.preemptions += 1
        self._release_slot(s)
        self.queue.appendleft(req)

    def _retire(self, s: int, req: Request):
        req.finished_at = self._clock()
        req.status = "done"
        self.done.append(req)
        self._release_slot(s)

    def _fail(self, req: Request, reason: str, slot: Optional[int] = None):
        req.status = "failed"
        req.fail_reason = reason
        req.finished_at = self._clock()
        self.failed.append(req)
        self._fail_log.append((req.uid, reason))
        if slot is not None:
            self._release_slot(slot)

    def _quarantine(self, s: int):
        """Non-finite logits for an emitting slot: requeue once (the prefix
        replays through a reset cache), fail on the second strike. The
        garbage token is never emitted."""
        req = self.slot_req[s]
        self.quarantines += 1
        if self.prefix_cache is not None and self.slot_inserted[s]:
            # the slot's model state went non-finite: every page it
            # published this tenure may hold garbage K/V — pull them from
            # the cache before another request can map them
            for k in self.slot_inserted[s]:
                self.prefix_cache.invalidate(k)
            self.slot_inserted[s] = []
        if req.nonfinite_strikes >= 1:
            self._fail(req, "nonfinite_logits", slot=s)
            return
        req.nonfinite_strikes += 1
        req.preemptions += 1
        req.status = "queued"
        req.queued_t = time.perf_counter()
        self._release_slot(s)
        self.queue.appendleft(req)

    def _expire(self):
        now = self._clock()

        def expired(req: Request) -> bool:
            return (req.deadline_s is not None
                    and now - req.submitted_at > req.deadline_s)

        for req in [r for r in self.queue if expired(r)]:
            self.queue.remove(req)
            self._fail(req, "deadline")
        for s in range(self.B):
            req = self.slot_req[s]
            if req is not None and expired(req):
                self._fail(req, "deadline", slot=s)

    # ------------------------------------------------------------------
    # page pressure hooks (fault injection / benchmarks)
    # ------------------------------------------------------------------
    def hold_pages(self, n: int) -> int:
        """Steal up to ``n`` pages from the free list (external pressure:
        a co-tenant, a shrinking pool). Returns how many were taken."""
        if self.allocator is None or n <= 0:
            return 0
        got = self.allocator.alloc(min(n, self.allocator.free_count))
        if not got:
            return 0
        self._held_pages.extend(got)
        return len(got)

    def release_held(self) -> int:
        """Return every held page to the free list."""
        n = len(self._held_pages)
        if n:
            self.allocator.free(self._held_pages)
            self._held_pages = []
        return n

    # ------------------------------------------------------------------
    # model-call fault envelope
    # ------------------------------------------------------------------
    def _model_call(self, thunk):
        """Run one jitted model call under the degradation ladder: bounded
        retry-with-backoff, then kernel degradation (ref paths + retraced
        config), then fail-everything. ``thunk`` re-reads ``self._step`` /
        ``self._prefill`` so a degraded config takes effect on retry."""
        attempts = 0
        while True:
            try:
                if self._injector is not None:
                    self._injector.before_model_call(self)
                return thunk()
            except Exception as e:  # noqa: BLE001 — every failure is handled
                attempts += 1
                if attempts <= self.max_step_retries:
                    self.retries += 1
                    time.sleep(self.retry_backoff_s * (2 ** (attempts - 1)))
                    continue
                if not self.degraded:
                    self._degrade(f"step failure: {e!r}")
                    attempts = 0
                    continue
                raise EngineStepError(
                    f"model call failed beyond retries and degraded mode: {e!r}"
                ) from e

    def _degrade(self, reason: str):
        """Drop to the reference kernel paths: flip the op-layer switch so
        anything traced from here on avoids Pallas, and swap in a
        kernel-free config clone (a new static jit key — the poisoned
        compiled executable is never reused)."""
        from repro import kernels as KR
        KR.set_kernels_forced_off(True)
        self.cfg = dataclasses.replace(self.cfg, use_kernels=False,
                                       linear_use_kernel=False)
        self._step = functools.partial(_jit_step, self.cfg)
        self._prefill = functools.partial(_jit_prefill, self.cfg)
        self.degraded = True
        self.degrade_reason = reason

    def _fail_all_in_flight(self, reason: str):
        for s in range(self.B):
            req = self.slot_req[s]
            if req is not None:
                self._fail(req, reason, slot=s)
        while self.queue:
            self._fail(self.queue.popleft(), reason)

    # ------------------------------------------------------------------
    # ticks
    # ------------------------------------------------------------------
    def _emit(self, s: int, req: Request, tok: int):
        """Record one sampled token; retire on EOS / max-new. The finish
        check counts the request's TOTAL output (it may have accumulated
        across preemptions), not tokens since the last admission."""
        if req.first_token_at is None:
            req.first_token_at = self._clock()
            obs.record("request.prefill", req.admitted_t, time.perf_counter(),
                       uid=req.uid)
        req.output.append(tok)
        if req.on_token is not None:
            try:
                req.on_token(tok)
            except Exception as e:  # noqa: BLE001 — user code, never fatal
                # the consumer is gone: fail the request rather than keep
                # generating tokens nobody will see
                self._fail(req, f"callback_error: {e!r}", slot=s)
                return
        finished = (len(req.output) >= req.max_new_tokens
                    or (req.eos_id is not None and tok == req.eos_id))
        if finished:
            self._retire(s, req)
        else:
            self._cur_tokens[s] = tok

    def _sample(self, logits) -> jax.Array:
        if self.greedy:
            return jnp.argmax(logits, axis=-1)
        self.key, k = jax.random.split(self.key)
        return jax.random.categorical(k, logits)

    def _guarded_emit(self, logits, emitting: list[int], rows=None):
        """Sample and emit for ``emitting`` slots, quarantining any slot
        whose logits row is not finite (max over the vocab catches both NaN
        and ±inf in one cheap (B,) transfer). ``rows`` (MoE configs: rows
        each held expert received this tick) comes back in the sampled
        tokens' transfer and is recorded on the ``engine.sync`` span as
        ``moe_rows`` (their sum) and ``moe_rows_max`` (the most any held
        expert received)."""
        with obs.span("engine.sync") as sp:
            if self._injector is not None:
                logits = self._injector.corrupt_logits(self, logits, emitting)
            nxt = self._sample(logits)
            if rows is not None:
                nxt, rows = jax.device_get((nxt, rows))
                sp.set(moe_rows=int(rows.sum()), moe_rows_max=int(rows.max()))
            nxt = np.asarray(nxt, np.int32)
            finite = np.isfinite(np.asarray(jnp.max(logits, axis=-1)))
        with obs.span("engine.emit"):
            for s in emitting:
                req = self.slot_req[s]
                if req is None:
                    continue
                if not finite[s]:
                    self._quarantine(s)
                else:
                    self._emit(s, req, int(nxt[s]))

    def _prefill_tick(self, stalled: set[int] = frozenset()):
        """Mixed tick: prefilling slots consume up to C prompt tokens; slots
        already decoding ride along as length-1 chunks (prefill_step is the
        stepwise decode for C==1), so prefill pressure never stalls them.
        Stalled slots keep lens 0 — their cache state does not advance."""
        if all(self.slot_req[s] is None or s in stalled for s in range(self.B)):
            self.stalled_ticks += 1  # every live slot stalled: no model call
            return
        C = self.prefill_chunk
        with obs.span("engine.dispatch"):
            toks = np.zeros((self.B, C), np.int32)
            lens = np.zeros((self.B,), np.int32)
            was_decoding = [False] * self.B
            for s in range(self.B):
                if self.slot_req[s] is None or s in stalled:
                    continue
                if self.slot_pending[s]:
                    n = min(C, len(self.slot_pending[s]))
                    for i in range(n):
                        toks[s, i] = self.slot_pending[s].popleft()
                    lens[s] = n
                else:
                    was_decoding[s] = True
                    toks[s, 0] = self._cur_tokens[s]
                    lens[s] = 1
            logits, self.cache, *rows = self._model_call(
                lambda: self._prefill(self.params, self.cache,
                                      jnp.asarray(toks), jnp.asarray(lens)))
        self.prefill_ticks += 1
        emitting = []
        for s in range(self.B):
            req = self.slot_req[s]
            if req is None or lens[s] == 0:
                continue  # idle or stalled slot
            self.slot_pos[s] += int(lens[s])
            if not was_decoding[s] and self.slot_pending[s]:
                continue  # still mid-prompt: logits row not meaningful yet
            # piggybacked decode, or prompt done (first token samples here)
            emitting.append(s)
        self._guarded_emit(logits, emitting, *rows)

    def _decode_tick(self):
        with obs.span("engine.dispatch"):
            # a host copy: on the CPU the step's input may alias the numpy
            # buffer it came from (``jnp.array`` too), and stepwise prefill
            # rewrites ``_cur_tokens`` below while the step may still read it
            toks = jnp.asarray(self._cur_tokens.copy())
            logits, self.cache, *rows = self._model_call(
                lambda: self._step(self.params, self.cache, toks))
        self.decode_ticks += 1
        emitting = []
        for s in range(self.B):
            req = self.slot_req[s]
            if req is None:
                continue
            self.slot_pos[s] += 1
            if self.slot_pending[s]:
                # stepwise prefill: feed the next prompt token, ignore sample
                self._cur_tokens[s] = self.slot_pending[s].popleft()
                continue
            emitting.append(s)
        self._guarded_emit(logits, emitting, *rows)

    def step(self):
        """One engine tick: one jitted model call for the whole batch (or a
        pure bookkeeping tick when everything live is stalled). The tick
        and its phases are ``repro.obs`` spans; the tick's span times it."""
        tick = self._tick
        self._tick += 1
        ran = (self.prefill_ticks, self.decode_ticks)
        with obs.span("engine.step") as sp:
            try:
                if self._injector is not None:
                    self._injector.on_tick(self, tick)
                if self._preempt_handler is not None and self._preempt_handler.preempted:
                    self._draining = True
                with obs.span("engine.admit"):
                    self._expire()
                    self._admit()
                with obs.span("engine.grow"):
                    stalled = self._grow()
                live = [s for s in range(self.B) if self.slot_req[s] is not None]
                if not live:
                    self.stalled_ticks += 1  # queue blocked on pages, or empty
                else:
                    prefilling = any(self.slot_pending[s] for s in live)
                    if self.prefill_mode == "chunked" and (prefilling or stalled):
                        self._prefill_tick(stalled)
                    else:
                        self._decode_tick()
                    if self.prefix_cache is not None:
                        with obs.span("engine.publish"):
                            self._publish_full_pages()
            except EngineStepError as e:
                # the model cannot run even on the degraded rung: account for
                # every request rather than losing them
                self._fail_all_in_flight(f"step_failed: {e}")
            sp.set(kind="prefill" if self.prefill_ticks > ran[0] else
                   "decode" if self.decode_ticks > ran[1] else "stalled")
        dt = sp.t1 - sp.t0
        self._busy_s += dt
        self.watchdog.observe(tick, dt)

    def _publish_full_pages(self):
        """Post-tick: hash every newly completed page of each live slot
        into the prefix cache. The tokens written at positions
        ``[0, slot_pos)`` are exactly ``(prompt + output)[:slot_pos]`` —
        prompt tokens via prefill, emitted tokens fed back through the
        decode tick — so the chained keys are derived from the request
        itself, no separate written-token log needed. A page is published
        only once full (the ragged tail is still being written); full pages
        are never written again (writes are strictly sequential), so cached
        content is frozen."""
        ps = self.page_size
        for s in range(self.B):
            req = self.slot_req[s]
            if req is None:
                continue
            full = min(self.slot_pos[s] // ps, len(self.slot_pages[s]))
            if len(self.slot_keys[s]) >= full:
                continue
            stream = list(req.prompt) + list(req.output)
            while len(self.slot_keys[s]) < full:
                j = len(self.slot_keys[s])
                prev = self.slot_keys[s][-1] if j else None
                key = PrefixCache.chain_key(prev, stream[j * ps:(j + 1) * ps])
                self.slot_keys[s].append(key)
                if self.prefix_cache.insert(key, self.slot_pages[s][j]):
                    self.slot_inserted[s].append(key)

    def _has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slot_req)

    def run_until_drained(self, max_ticks: int = 10_000) -> DrainResult:
        ticks = 0
        while self._has_work() and ticks < max_ticks:
            if self._draining and not any(r is not None for r in self.slot_req):
                break  # drained: only queued (never-admitted) work remains
            self.step()
            ticks += 1
        if self._draining:
            while self.queue:
                self._fail(self.queue.popleft(), "drained")
        stranded = tuple(r.uid for r in self.queue) + tuple(
            r.uid for r in self.slot_req if r is not None)
        res = DrainResult(ticks=ticks, drained=not self._has_work(),
                          stranded=stranded)
        self._last_drain = res
        return res

    # ------------------------------------------------------------------
    # invariants + stats
    # ------------------------------------------------------------------
    def check(self):
        """Invariant audit (chaos suite runs this after every tick):

        * allocator: free ∪ outstanding partitions the pool, refcounts ≥ 1;
        * reference reconciliation: summing one reference per (slot, page)
          mapping, per held page, and per prefix-cache entry reproduces the
          allocator's per-page refcounts exactly (no leaked or phantom refs);
        * slot page lists never contain the trash page or intra-slot dups;
          any page a slot may still WRITE (not fully written, not shared)
          has exactly one reference — no writer ever aliases shared data;
        * the device page table mirrors the host lists exactly — live rows
          are their slot's pages then trash, idle rows all trash (pinned);
        * every live slot owns the pages its written tokens occupy.
        """
        if self.allocator is not None:
            self.allocator.check()
            refs: Counter[int] = Counter()
            writable: set[int] = set()
            for s in range(self.B):
                pages = self.slot_pages[s]
                assert TRASH_PAGE not in pages, f"slot {s} owns the trash page"
                assert len(set(pages)) == len(pages), \
                    f"slot {s} maps a page twice: {pages}"
                refs.update(pages)
                if self.slot_req[s] is None:
                    assert not pages, f"idle slot {s} still holds pages"
                else:
                    assert len(pages) >= pages_needed(self.slot_pos[s],
                                                      self.page_size), \
                        (s, self.slot_pos[s], pages)
                    for j, p in enumerate(pages):
                        if (j >= self.slot_shared_n[s]
                                and (j + 1) * self.page_size > self.slot_pos[s]):
                            writable.add(p)
            refs.update(self._held_pages)
            cache_pages: frozenset[int] = frozenset()
            if self.prefix_cache is not None:
                cache_pages = self.prefix_cache.pages
                refs.update(cache_pages)
            outstanding = self.allocator.outstanding
            assert set(refs) == set(outstanding), \
                (set(refs) ^ set(outstanding))
            for p, n in refs.items():
                assert self.allocator.refcount(p) == n, \
                    (p, n, self.allocator.refcount(p))
            for p in writable:
                assert refs[p] == 1 and p not in cache_pages, \
                    f"writable page {p} is shared (refs={refs[p]})"
        if "ptab" in self.cache:
            ptab = np.asarray(self.cache["ptab"])
            for s in range(self.B):
                k = len(self.slot_pages[s])
                assert list(ptab[s, :k]) == self.slot_pages[s], \
                    (s, ptab[s], self.slot_pages[s])
                assert (ptab[s, k:] == TRASH_PAGE).all(), (s, ptab[s])

    def page_stats(self) -> dict:
        if self.allocator is None:
            return {"free_pages": None, "page_capacity": None, "held_pages": 0}
        return {"free_pages": self.allocator.free_count,
                "page_capacity": self.allocator.capacity,
                "held_pages": len(self._held_pages)}

    def stats(self) -> dict:
        # percentile semantics pinned explicitly: method="higher" returns an
        # OBSERVED sample ≥ the quantile, so p95 == max on tiny n instead of
        # np.percentile's default linear interpolation reporting a latency
        # no request ever saw (with 2 completions the default p95 < max)
        def pct(xs, q):
            return float(np.percentile(xs, q, method="higher")) if xs else None

        lat = [r.finished_at - r.submitted_at for r in self.done if r.finished_at]
        # failed requests reported separately — folding them into the done
        # percentiles would let fast failures mask slow completions
        flat = [r.finished_at - r.submitted_at for r in self.failed
                if r.finished_at is not None]
        ttft = [r.ttft_s for r in self.done if r.ttft_s is not None]
        toks = sum(len(r.output) for r in self.done)
        prompt_toks = sum(len(r.prompt) for r in self.done)
        busy = max(self._busy_s, 1e-9)
        last = self._last_drain
        out = {
            "completed": len(self.done),
            "failed": len(self.failed),
            # uid-keyed convenience view (last failure wins); fail_log is
            # the faithful record when one uid failed more than once across
            # resubmissions
            "fail_reasons": dict(self._fail_log),
            "fail_log": list(self._fail_log),
            "queued": len(self.queue),
            "in_flight": sum(r is not None for r in self.slot_req),
            "stranded": 0 if last is None or last.drained else len(last.stranded),
            "generated_tokens": toks,
            "prompt_tokens": prompt_toks,
            "p50_latency_s": pct(lat, 50),
            "p95_latency_s": pct(lat, 95),
            "failed_p50_latency_s": pct(flat, 50),
            "failed_p95_latency_s": pct(flat, 95),
            "ttft_p50_s": pct(ttft, 50),
            "ttft_p95_s": pct(ttft, 95),
            "tokens_per_sec": toks / busy,
            "prompt_tokens_per_sec": prompt_toks / busy,
            "prefill_ticks": self.prefill_ticks,
            "decode_ticks": self.decode_ticks,
            "stalled_ticks": self.stalled_ticks,
            "ticks": self.prefill_ticks + self.decode_ticks,
            "preemptions": self.preemptions,
            "retries": self.retries,
            "quarantines": self.quarantines,
            "cow_copies": self.cow_copies,
            "prefix_hit_pages": self.prefix_hit_pages_total,
            "degraded": self.degraded,
            "step_p50_s": None,
            "step_p95_s": None,
            "stragglers": 0,
        }
        out.update(self.watchdog.stats())
        out.update(self.page_stats())
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.stats())
        return out

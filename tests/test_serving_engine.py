"""Continuous-batching engine: correctness vs direct decode + scheduler
invariants (tick accounting, page budget, slot isolation, random streams)."""

from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.models import model as MD
from repro.serve.cache import NO_SLOT_AXIS, PageAllocator, slot_axes
from repro.serve.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke("granite-3-2b", dtype=jnp.float32)
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _direct_greedy(cfg, params, prompt, n_new):
    cache = MD.init_cache(cfg, 1, 64)
    for t in prompt:
        logits, cache = MD.serve_step_fn(params, cfg, cache,
                                         jnp.array([t], jnp.int32))
    out = []
    tok = int(jnp.argmax(logits[0]))
    out.append(tok)
    for _ in range(n_new - 1):
        logits, cache = MD.serve_step_fn(params, cfg, cache,
                                         jnp.array([tok], jnp.int32))
        tok = int(jnp.argmax(logits[0]))
        out.append(tok)
    return out


def test_engine_matches_direct_decode(setup):
    cfg, params = setup
    prompt = [5, 17, 333, 42]
    ref = _direct_greedy(cfg, params, prompt, 6)

    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64)
    req = Request(uid=1, prompt=prompt, max_new_tokens=6)
    eng.submit(req)
    eng.run_until_drained()
    assert req.output == ref


# run in a fresh process: late in a long pytest worker XLA:CPU can return
# garbage logits (conftest.py), which would read as the fault checked here
_STEPWISE_ALIGNED = """
import json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke
from repro.models import model as MD
from repro.serve.engine import Request, ServingEngine
from test_serving_engine import _direct_greedy
cfg = get_smoke("granite-3-2b", dtype=jnp.float32)
params = MD.init_params(jax.random.PRNGKey(0), cfg)
prompt = [5, 17, 333, 42, 7, 11, 250, 3]
out = {"ref": _direct_greedy(cfg, params, prompt, 4), "engine": [], "aliased": False}
for _ in range(3):
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=64,
                        cache_mode="dense", prefill_mode="stepwise")
    buf = np.zeros(32, np.int32)
    off = (-buf.ctypes.data % 64) // buf.itemsize
    eng._cur_tokens = buf[off:off + 1]
    assert eng._cur_tokens.ctypes.data % 64 == 0
    step = eng._step
    def watched(p, cache, toks):
        lo = eng._cur_tokens.ctypes.data
        out["aliased"] |= lo <= toks.unsafe_buffer_pointer() < lo + eng._cur_tokens.nbytes
        return step(p, cache, toks)
    eng._step = watched
    req = Request(uid=1, prompt=prompt, max_new_tokens=4)
    eng.submit(req)
    eng.run_until_drained()
    out["engine"].append(req.output)
print(json.dumps(out))
"""


def test_stepwise_prefill_feeds_the_tokens_it_was_given():
    """Stepwise prefill rewrites the host's next-token buffer right after
    dispatching a step. On the CPU an aligned numpy buffer can back the
    step's input without a copy, so the step must get a copy of it: with
    the buffer 64-byte aligned, no step's input lies in the buffer, and
    outputs match direct decode every time."""
    import json
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(os.path.dirname(here), "src"), here]))
    p = subprocess.run([sys.executable, "-c", _STEPWISE_ALIGNED], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert not out["aliased"]
    assert out["engine"] == [out["ref"]] * 3


def test_engine_batches_multiple_requests(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64)
    reqs = [Request(uid=i, prompt=[i + 1, i + 2], max_new_tokens=4)
            for i in range(5)]  # 5 requests through 2 slots
    for r in reqs:
        eng.submit(r)
    res = eng.run_until_drained()
    st = eng.stats()
    assert st["completed"] == 5
    assert st["generated_tokens"] == 20
    assert res.drained and res.ticks < 40
    # batched outputs equal isolated single-request outputs
    for r in reqs:
        assert r.output == _direct_greedy(cfg, params, r.prompt, 4), r.uid


def test_engine_eos_early_stop(setup):
    cfg, params = setup
    ref = _direct_greedy(cfg, params, [9, 9], 8)
    eos = ref[2]  # stop at the 3rd generated token
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=64)
    req = Request(uid=1, prompt=[9, 9], max_new_tokens=8, eos_id=eos)
    eng.submit(req)
    eng.run_until_drained()
    assert req.output == ref[:3]


def test_engine_eos_on_final_step_retires_once(setup):
    """EOS arriving on the same step as max_new_tokens: the request retires
    exactly once, with the EOS token included and no extra tick consumed."""
    cfg, params = setup
    n = 4
    ref = _direct_greedy(cfg, params, [7, 3], n)
    eos = ref[n - 1]  # EOS is exactly the max_new_tokens-th token
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=64)
    req = Request(uid=1, prompt=[7, 3], max_new_tokens=n, eos_id=eos)
    eng.submit(req)
    eng.run_until_drained()
    assert req.output == ref[:n]
    assert req.finished_at is not None
    assert eng.done == [req]  # retired once, not duplicated
    assert eng.slot_req == [None]
    assert eng.stats()["completed"] == 1


def test_engine_admission_queue_longer_than_free_slots(setup):
    """Submitting more requests than slots: exactly batch_slots admit per
    tick-wave, the rest wait FIFO, and nothing is dropped or reordered."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64)
    reqs = [Request(uid=i, prompt=[i + 1], max_new_tokens=3) for i in range(7)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    # one tick: 2 admitted into the 2 slots, 5 still queued
    assert [r is not None for r in eng.slot_req] == [True, True]
    assert [r.uid for r in eng.queue] == [2, 3, 4, 5, 6]
    eng.run_until_drained()
    assert not eng.queue and eng.slot_req == [None, None]
    assert eng.stats()["completed"] == 7
    # FIFO: finish order tracks submission order for equal-length requests
    assert [r.uid for r in eng.done] == [r.uid for r in reqs]
    for r in reqs:
        assert r.output == _direct_greedy(cfg, params, r.prompt, 3), r.uid


def test_engine_slot_reuse_matches_fresh_engine(setup):
    """Retire -> readmit into the same slot: the recycled slot's cache is
    isolated, so the second request decodes exactly like on a fresh engine."""
    cfg, params = setup
    prompt_a, prompt_b = [5, 17, 333], [42, 8]

    eng = ServingEngine(cfg, params, batch_slots=1, max_len=64)
    ra = Request(uid=1, prompt=prompt_a, max_new_tokens=5)
    eng.submit(ra)
    eng.run_until_drained()
    rb = Request(uid=2, prompt=prompt_b, max_new_tokens=5)
    eng.submit(rb)  # reuses the slot request A just vacated
    eng.run_until_drained()

    fresh = ServingEngine(cfg, params, batch_slots=1, max_len=64)
    rb_fresh = Request(uid=3, prompt=prompt_b, max_new_tokens=5)
    fresh.submit(rb_fresh)
    fresh.run_until_drained()

    assert rb.output == rb_fresh.output
    assert ra.output == _direct_greedy(cfg, params, prompt_a, 5)


# ---------------------------------------------------------------------------
# chunked prefill: tick accounting + stats
# ---------------------------------------------------------------------------

def test_prefill_completes_in_ceil_p_over_c_ticks(setup):
    """A P-token prompt warms its cache in exactly ⌈P/prefill_chunk⌉ engine
    ticks (acceptance); the remaining ticks are pure decode."""
    cfg, params = setup
    for P_, C in [(13, 4), (8, 4), (1, 4), (5, 16)]:
        eng = ServingEngine(cfg, params, batch_slots=1, max_len=64,
                            prefill_chunk=C)
        req = Request(uid=1, prompt=list(range(1, P_ + 1)), max_new_tokens=3)
        eng.submit(req)
        res = eng.run_until_drained()
        st = eng.stats()
        expect_prefill = -(-P_ // min(C, eng.prefill_chunk))
        assert st["prefill_ticks"] == expect_prefill, (P_, C, st)
        # first token samples on the last prefill tick
        assert st["decode_ticks"] == 3 - 1, (P_, C, st)
        assert res.ticks == st["ticks"] == expect_prefill + 2
        assert req.output == _direct_greedy(cfg, params, req.prompt, 3)


def test_stats_fields(setup):
    """stats() exposes p95 latency, throughput, and the prefill/decode tick
    split alongside the page-budget gauges."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64, prefill_chunk=4)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=[i + 1, i + 2, i + 3, 7, 9],
                           max_new_tokens=4))
    eng.run_until_drained()
    st = eng.stats()
    assert st["completed"] == 3
    assert st["generated_tokens"] == 12 and st["prompt_tokens"] == 15
    assert st["p50_latency_s"] > 0 and st["p95_latency_s"] >= st["p50_latency_s"]
    assert st["tokens_per_sec"] > 0 and st["prompt_tokens_per_sec"] > 0
    assert st["prefill_ticks"] >= 2 and st["decode_ticks"] >= 3
    assert st["ticks"] == st["prefill_ticks"] + st["decode_ticks"]
    assert st["free_pages"] == st["page_capacity"] > 0  # all pages returned


# ---------------------------------------------------------------------------
# spans (repro.obs): tick phases and each request's queue wait and prefill
# ---------------------------------------------------------------------------

def _children(recs, parent):
    return [r.name for r in recs if r.parent == parent.name
            and parent.t0 <= r.t0 and r.t1 <= parent.t1]


def test_tick_spans_in_phase_order(setup):
    """Each tick is an ``engine.step`` span holding admit, grow, dispatch,
    sync and emit in that order; its ``kind`` says which tick ran, and the
    engine's busy time is the sum of these spans."""
    cfg, params = setup
    obs.reset()
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64, prefill_chunk=4)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=[i + 1, 5, 9, 2, 8], max_new_tokens=3))
    res = eng.run_until_drained()
    recs = obs.records()
    steps = [r for r in recs if r.name == "engine.step"]
    assert len(steps) == res.ticks
    st = eng.stats()
    kinds = Counter(r.attrs["kind"] for r in steps)
    assert kinds == {"prefill": st["prefill_ticks"], "decode": st["decode_ticks"]}
    for r in steps:
        assert r.parent is None
        assert _children(recs, r) == ["engine.admit", "engine.grow", "engine.dispatch",
                                      "engine.sync", "engine.emit"], r
    busy = sum(r.t1 - r.t0 for r in steps)
    assert st["tokens_per_sec"] == pytest.approx(st["generated_tokens"] / busy)


@pytest.mark.parametrize("pool", ["roomy", "tight"])
def test_request_spans_split_ttft(setup, pool):
    """A request's first ``request.queue`` span and its ``request.prefill``
    span add up to its time to first token; the prefill starts where that
    wait ends. A preempted request waits again, in a span of its own."""
    cfg, params = setup
    obs.reset()
    kw = (dict(max_len=64) if pool == "roomy" else
          dict(max_len=32, page_size=4, num_pages=3))  # forces preemption
    eng = ServingEngine(cfg, params, batch_slots=2, prefill_chunk=4, **kw)
    reqs = [Request(uid=i, prompt=[i + 1, 7, 9], max_new_tokens=5) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert len(eng.done) == 3
    recs = obs.records()
    for req in reqs:
        waits = [r for r in recs if r.name == "request.queue" and r.attrs["uid"] == req.uid]
        (prefill,) = [r for r in recs
                      if r.name == "request.prefill" and r.attrs["uid"] == req.uid]
        assert [w.attrs["requeues"] for w in waits] == list(range(req.preemptions + 1))
        assert prefill.t0 == waits[0].t1
        split = (waits[0].t1 - waits[0].t0) + (prefill.t1 - prefill.t0)
        assert split == pytest.approx(req.first_token_at - req.submitted_at, abs=2e-3)
    preempted = sum(r.preemptions for r in reqs)
    assert (preempted > 0) == (pool == "tight")


# ---------------------------------------------------------------------------
# slot isolation: explicit axis tags (regression for the shape-guessing reset)
# ---------------------------------------------------------------------------

def test_slot_axes_tags(setup):
    cfg, params = setup
    paged = MD.init_cache(cfg, 2, 32, paged=True, page_size=4)
    axes = slot_axes(paged)
    assert axes["step"] == 0 and axes["ptab"] == 0
    for g in axes["groups"]:
        for leaf in jax.tree_util.tree_leaves(g):
            assert leaf == NO_SLOT_AXIS  # stacked attn pools: shared
    dense = MD.init_cache(cfg, 2, 32)
    daxes = slot_axes(dense)
    for g in daxes["groups"]:
        for leaf in jax.tree_util.tree_leaves(g):
            assert leaf == 1  # (n_groups, B, ...): batch axis tagged, not guessed


def test_reset_slot_with_batch_slots_equal_to_group_count(setup):
    """Regression: the old reset zeroed the FIRST axis whose size equals
    batch_slots — with batch_slots == n_groups that's the layer-group stack
    axis, wiping one layer's cache for EVERY slot. A mid-decode neighbour
    must survive another slot's admission reset."""
    cfg, params = setup
    n_groups = cfg.num_layers // len(cfg.layer_pattern)
    assert n_groups == 3  # the collision this test exercises
    eng = ServingEngine(cfg, params, batch_slots=3, max_len=64,
                        cache_mode="dense")
    ra = Request(uid=1, prompt=[5, 17, 333], max_new_tokens=8)
    eng.submit(ra)
    for _ in range(4):  # prefill + a few decode ticks; slot 0 mid-request
        eng.step()
    rb = Request(uid=2, prompt=[42, 8], max_new_tokens=2)
    eng.submit(rb)  # admits into slot 1 -> reset_slot(1) while slot 0 lives
    eng.run_until_drained()
    assert ra.output == _direct_greedy(cfg, params, ra.prompt, 8)
    assert rb.output == _direct_greedy(cfg, params, rb.prompt, 2)


def test_decode_rides_prefill_ticks(setup):
    """A slot already decoding is not starved by another slot's long prefill:
    it piggybacks every mixed tick as a length-1 chunk and keeps emitting."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64, prefill_chunk=2)
    ra = Request(uid=1, prompt=[5, 17], max_new_tokens=12)
    eng.submit(ra)
    eng.step()  # ra prefills (one chunk) and samples its first token
    assert len(ra.output) == 1
    rb = Request(uid=2, prompt=list(range(1, 21)), max_new_tokens=2)
    eng.submit(rb)  # 20-token prompt -> 10 prefill ticks at chunk 2
    for i in range(10):
        before = len(ra.output)
        eng.step()
        assert len(ra.output) == before + 1, f"decode starved at prefill tick {i}"
    eng.run_until_drained()
    assert ra.output == _direct_greedy(cfg, params, ra.prompt, 12)
    assert rb.output == _direct_greedy(cfg, params, rb.prompt, 2)


# ---------------------------------------------------------------------------
# page budget: admission blocking + accounting
# ---------------------------------------------------------------------------

def test_page_allocator_invariants():
    al = PageAllocator(6)  # 5 usable pages (row 0 = trash)
    assert al.capacity == 5
    a = al.alloc(3)
    assert a is not None and 0 not in a
    assert al.alloc(3) is None  # insufficient
    b = al.alloc(2)
    assert al.free_count == 0
    al.free(a)
    with pytest.raises(ValueError):
        al.free(a)  # double-free raises
    al.free(b)
    al.check()
    assert al.free_count == al.capacity


def test_admission_blocks_on_page_budget(setup):
    """With pages for only one request in flight, the queue drains strictly
    one-at-a-time (FIFO), every request still completes, and the free list
    returns to capacity (no leak)."""
    cfg, params = setup
    ps = 4
    # budget: exactly one request's worth of pages (3 prompt + 5 new -> 2);
    # reserve admission — optimistic would admit both on first-chunk pages
    # (see test_optimistic_admits_more_than_reserve)
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=32, page_size=ps,
                        num_pages=2 + 1, prefill_chunk=4, admission="reserve")
    reqs = [Request(uid=i, prompt=[i + 1, 7, 9], max_new_tokens=5)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    # only one slot admitted despite 2 free slots: pages cover one request
    assert sum(r is not None for r in eng.slot_req) == 1
    assert eng.allocator.free_count == 0
    eng.run_until_drained()
    assert [r.uid for r in eng.done] == [0, 1, 2]  # FIFO, exactly once
    eng.allocator.check()
    assert eng.allocator.free_count == eng.allocator.capacity
    for r in reqs:
        assert r.output == _direct_greedy(cfg, params, r.prompt, 5), r.uid


def test_submit_rejects_over_capacity(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=1, prompt=[1] * 10, max_new_tokens=10))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=2, prompt=[], max_new_tokens=2))


# ---------------------------------------------------------------------------
# uid lifecycle: duplicate rejection + resubmission
# ---------------------------------------------------------------------------

def test_submit_rejects_duplicate_live_uid(setup):
    """A uid keys cancel() and per-request accounting: submitting it twice
    while the first is queued OR in-flight must be rejected, not silently
    accepted (where cancel() would stop at the first match)."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=64)
    r1 = Request(uid=1, prompt=[5, 17], max_new_tokens=4)
    eng.submit(r1)
    with pytest.raises(ValueError, match="already live"):
        eng.submit(Request(uid=1, prompt=[9, 9], max_new_tokens=2))
    eng.step()  # r1 now in-flight
    with pytest.raises(ValueError, match="already live"):
        eng.submit(Request(uid=1, prompt=[9, 9], max_new_tokens=2))
    # cancel still reaches the one real request after the rejected dupes
    assert eng.cancel(1) and r1.status == "failed"
    assert eng.allocator.free_count == eng.allocator.capacity


def test_resubmit_after_finish_resets_lifecycle_state(setup):
    """A retired uid may be submitted again — including the SAME Request
    object: stale output/strikes/preemptions must not leak into the new
    attempt (a carried-over output would replay as a resumable prefix)."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=64)
    req = Request(uid=1, prompt=[5, 17, 333], max_new_tokens=4)
    eng.submit(req)
    eng.run_until_drained()
    first = list(req.output)
    assert first == _direct_greedy(cfg, params, req.prompt, 4)
    # simulate stale damage a cancelled-mid-preemption request would carry
    req.preemptions, req.nonfinite_strikes = 3, 1
    eng.submit(req)  # same object, uid no longer live
    eng.run_until_drained()
    assert req.output == first  # NOT first + first (no prefix replay)
    assert req.preemptions == 0 and req.nonfinite_strikes == 0
    assert req.status == "done"


# ---------------------------------------------------------------------------
# stats: pinned percentile semantics + failure records
# ---------------------------------------------------------------------------

def test_percentiles_are_observed_samples(setup):
    """method="higher" semantics: with 2 completions p95 == max (the default
    linear interpolation reports a latency no request ever saw), and failed
    requests get their own percentiles instead of vanishing."""
    from repro.serve.faultinject import VirtualClock
    cfg, params = setup
    vc = VirtualClock()
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=64, clock=vc)

    def rec(uid, lat, done=True):
        r = Request(uid=uid, prompt=[1], max_new_tokens=1)
        r.submitted_at, r.finished_at = vc.now(), vc.now() + lat
        (eng.done if done else eng.failed).append(r)
        if not done:
            r.fail_reason = "deadline"
            eng._fail_log.append((uid, "deadline"))
        return r

    rec(1, 1.0)
    rec(2, 3.0)
    rec(3, 10.0, done=False)
    st = eng.stats()
    assert st["p95_latency_s"] == 3.0  # == max, not 2.9
    assert st["p50_latency_s"] == 3.0  # "higher": observed sample >= median
    assert st["failed_p95_latency_s"] == st["failed_p50_latency_s"] == 10.0
    assert st["fail_reasons"] == {3: "deadline"}


def test_fail_log_keeps_distinct_failures_for_one_uid(setup):
    """A uid can legitimately fail twice across resubmissions; the uid-keyed
    fail_reasons view keeps the last, fail_log keeps both (regression: the
    old dict built from Request objects silently conflated them)."""
    from repro.serve.faultinject import VirtualClock
    cfg, params = setup
    vc = VirtualClock()
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=64, clock=vc)
    req = Request(uid=7, prompt=[5, 17], max_new_tokens=30, deadline_s=1.0)
    eng.submit(req)
    vc.advance(5.0)
    eng.step()  # expires in the queue
    assert req.fail_reason == "deadline"
    req.deadline_s = None
    eng.submit(req)  # uid 7 free again: resubmission is legal
    eng.step()
    assert eng.cancel(7)
    st = eng.stats()
    assert st["fail_reasons"] == {7: "cancelled"}  # last wins
    assert st["fail_log"] == [(7, "deadline"), (7, "cancelled")]
    assert st["failed"] == 2
    assert eng.allocator.free_count == eng.allocator.capacity


# ---------------------------------------------------------------------------
# scheduler invariants under random arrival/eos/max-token streams
# ---------------------------------------------------------------------------

def _stream_invariants(cfg, params, cases, batch_slots, num_pages,
                       prefill_chunk):
    eng = ServingEngine(cfg, params, batch_slots=batch_slots, max_len=32,
                        page_size=4, num_pages=num_pages,
                        prefill_chunk=prefill_chunk)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=n, eos_id=e)
            for i, (p, n, e) in enumerate(cases)]
    arrivals = iter(reqs)
    # staggered arrivals: submit one request per tick until exhausted
    pending = next(arrivals, None)
    ticks = 0
    while pending is not None or eng.queue or any(
            r is not None for r in eng.slot_req):
        if pending is not None:
            eng.submit(pending)
            pending = next(arrivals, None)
        eng.step()
        if eng.allocator is not None:
            eng.allocator.check()  # never leaks or double-frees, every tick
        ticks += 1
        assert ticks < 10_000
    # every request retires exactly once
    assert sorted(r.uid for r in eng.done) == sorted(r.uid for r in reqs)
    assert len(eng.done) == len(set(id(r) for r in eng.done))
    if eng.allocator is not None:
        assert eng.allocator.free_count == eng.allocator.capacity
    # outputs equal a 1-slot reference engine run per request
    for r in reqs:
        ref = ServingEngine(cfg, params, batch_slots=1, max_len=32,
                            page_size=4, prefill_chunk=prefill_chunk)
        rr = Request(uid=r.uid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                     eos_id=r.eos_id)
        ref.submit(rr)
        ref.run_until_drained()
        assert r.output == rr.output, r.uid


def test_scheduler_invariants_deterministic(setup):
    """Hand-picked stream: mixed prompt lengths, EOS early stops (including
    an unreachable eos_id), contention on both slots and pages."""
    cfg, params = setup
    first = _direct_greedy(cfg, params, [9, 9], 8)
    # a valid token id the 4th case never samples (eos_id must be >= 0 now)
    ref4 = _direct_greedy(cfg, params, [2, 4, 6, 8], 6)
    never = next(t for t in range(cfg.vocab_size) if t not in ref4)
    cases = [
        ([1, 2, 3, 4, 5, 6, 7], 4, None),
        ([9, 9], 8, first[2]),          # stops at the 3rd token
        ([5], 1, None),                  # single-token everything
        ([2, 4, 6, 8], 6, never),        # eos never sampled
        ([7, 7, 7, 7, 7, 7, 7, 7, 7], 2, None),
    ]
    _stream_invariants(cfg, params, cases, batch_slots=2, num_pages=7,
                       prefill_chunk=4)


def test_scheduler_invariants_fuzzed(setup):
    """Hypothesis-driven random arrival/eos/max-token streams."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    cfg, params = setup

    case = st.tuples(
        st.lists(st.integers(0, cfg.vocab_size - 1), min_size=1, max_size=9),
        st.integers(1, 6),
        st.one_of(st.none(), st.integers(0, cfg.vocab_size - 1)),
    )

    @hyp.settings(max_examples=8, deadline=None,
                  suppress_health_check=[hyp.HealthCheck.too_slow])
    @hyp.given(cases=st.lists(case, min_size=1, max_size=5),
               batch_slots=st.integers(1, 3),
               # ≥5: the largest request (9 prompt + 6 new) needs 4 pages + trash
               pages=st.sampled_from((5, 9, 25)), chunk=st.sampled_from((1, 4)))
    def run(cases, batch_slots, pages, chunk):
        _stream_invariants(cfg, params, cases, batch_slots, pages, chunk)

    run()


def test_scheduler_invariants_fuzzed_faulty(setup):
    """Hypothesis streams under fire: random arrivals over a tight page pool
    with seeded page-pressure / NaN / step-error injection and per-request
    deadlines on a virtual clock. Asserts exactly-once retirement, per-tick
    allocator + page-table consistency (engine.check()), recorded reasons on
    every failure, and preempted-then-resumed output equal to an
    uninterrupted 1-slot reference."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    from repro.serve.faultinject import FaultInjector, VirtualClock
    cfg, params = setup

    case = st.tuples(
        st.lists(st.integers(0, cfg.vocab_size - 1), min_size=1, max_size=9),
        st.integers(1, 6),
        st.one_of(st.none(), st.floats(0.5, 40.0)),  # deadline_s (virtual)
    )

    @hyp.settings(max_examples=6, deadline=None,
                  suppress_health_check=[hyp.HealthCheck.too_slow])
    @hyp.given(cases=st.lists(case, min_size=1, max_size=5),
               batch_slots=st.integers(1, 3),
               pages=st.sampled_from((5, 9)), seed=st.integers(0, 2**16))
    def run(cases, batch_slots, pages, seed):
        vc = VirtualClock()
        inj = FaultInjector.seeded(
            seed, horizon=600, p_nan=0.02, p_step_error=0.04, p_hold=0.06,
            max_hold_pages=2, max_hold_ticks=5, max_consecutive_failures=1)
        eng = ServingEngine(cfg, params, batch_slots=batch_slots, max_len=32,
                            page_size=4, num_pages=pages, prefill_chunk=4,
                            injector=inj, clock=vc, retry_backoff_s=0.0)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=n, deadline_s=d)
                for i, (p, n, d) in enumerate(cases)]
        arrivals = iter(reqs)
        pending = next(arrivals, None)
        ticks = 0
        while pending is not None or eng.queue or any(
                r is not None for r in eng.slot_req):
            if pending is not None:
                eng.submit(pending)
                pending = next(arrivals, None)
            eng.step()
            eng.check()  # allocator + slot pages + ptab reconcile, every tick
            vc.advance(0.25)
            ticks += 1
            assert ticks < 5_000
        eng.release_held()
        # exactly-once: done ⊎ failed == submitted, reasons recorded
        done_uids = sorted(r.uid for r in eng.done)
        failed_uids = sorted(r.uid for r in eng.failed)
        assert sorted(done_uids + failed_uids) == sorted(r.uid for r in reqs)
        assert len(set(done_uids)) == len(done_uids)
        for r in eng.failed:
            assert r.fail_reason in ("deadline", "nonfinite_logits"), \
                (r.uid, r.fail_reason)
        assert eng.allocator.free_count == eng.allocator.capacity
        # fault-free 1-slot reference: resumed == uninterrupted
        for r in eng.done:
            ref = ServingEngine(cfg, params, batch_slots=1, max_len=32,
                                page_size=4, prefill_chunk=4)
            rr = Request(uid=r.uid, prompt=r.prompt,
                         max_new_tokens=r.max_new_tokens)
            ref.submit(rr)
            ref.run_until_drained()
            assert r.output == rr.output, r.uid

    run()

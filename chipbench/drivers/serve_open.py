"""Serving cells under open-loop arrivals: the program's ``ServingEngine``
on its default path (paged KV cache, chunked prefill, optimistic
admission), fed requests at their due times.

Set-up makes the weights from the seed on the device (the reference's
``init``, one jitted call), builds the engine with a ``perf_counter`` clock,
and runs a warm-up that fills every slot through prefill and decode ticks,
so both tick programs at the cell's slot count are compiled before the
window. The requests of the run are generated from the seed in set-up.

The arrivals start ``preroll_s`` (a traffic parameter) before the window,
so the window opens on an engine as loaded as the rate keeps it; the
pre-roll is set-up. The window runs for ``--seconds``. Between ticks the
harness submits every request that has come due (``submit``), steps the
engine when it has work (``step``) and polls the requests' status
(``poll``); with no work it sleeps until the next due time (``idle``).
Requests due before the window are not counted in the latencies, but
their tokens in the window are. After the window closes it keeps
stepping, with no new arrivals, until every request due in the window has
its first token (at most ``grace_s``), so a request served late counts its
wait; one that never gets a first token, or fails, is a failure.

* ``ttft_p95_ms``: first token's time less the request's due time;
* ``itl_p95_ms``: every gap between consecutive output tokens of any
  request that both arrived in the window, from ``on_token`` times;
* ``serve_tokens_per_s``: prompt plus output tokens of the requests that
  completed in the window, over the window.

Correctness: a sample of the completed requests drawn from the seed, the
one with most tokens among them, is run through the reference (each prompt
with its served tokens, padded to ``max_len``). Each served token's logit
lies below the reference's best at its position by a gap; the widest gap is
compared with its limit (greedy decoding only).
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

import bench
import generator as TR
from program import check_layout, program_config, seed_key


class Bookkeeping:
    """Per-request times and per-tick work, kept by the harness."""

    def __init__(self, arrivals):
        self.arrivals = {a.uid: a for a in arrivals}
        self.token_times: dict[int, list[float]] = {a.uid: [] for a in arrivals}
        self.admitted_at: dict[int, float] = {}
        self.lateness: list[float] = []
        self.ticks: list[dict] = []   # n_tokens, ctx_sum, kv_tokens, live, dt


def _faulty(eng, fault):
    """Breaks the timed path for the harness's own tests."""
    if fault == "state_unchanged":
        prefill = eng._prefill

        def stale(params, cache, toks, lens):
            logits, _ = prefill(params, cache, toks, lens)
            return logits, cache
        eng._prefill = stale
    elif fault == "token_altered":
        emit = eng._emit

        def altered(s, req, tok):
            if len(req.output) == 1:
                tok = (tok + 1) % eng.cfg.vocab_size
            return emit(s, req, tok)
        eng._emit = altered
    else:
        raise ValueError(f"unknown fault {fault!r}")


def run(ctx: bench.Context) -> bench.Outcome:
    import jax
    from repro.serve.engine import Request, ServingEngine

    cell, wl = ctx.cell, ctx.cell.workload
    wl = {**wl, **ctx.overrides.get("workload", {})}
    ref = cell.reference
    conf = {**cell.config, **ctx.overrides.get("config", {})}
    tr = {**cell.traffic, **ctx.overrides.get("traffic", {})}
    cfg = program_config(cell, ctx.overrides.get("program", {}))
    key = seed_key(ctx.seed)
    spans = ctx.spans
    slots, max_len = wl["slots"], wl["max_len"]

    # --- set-up ---------------------------------------------------------------
    make_params = jax.jit(lambda k: ref.init(conf, k))
    check_layout(cfg, jax.eval_shape(make_params, key))
    params = make_params(key)
    eng = ServingEngine(cfg, params, batch_slots=slots, max_len=max_len,
                        clock=time.perf_counter)
    if (eng.cache_mode, eng.prefill_mode, eng.admission) != ("paged", "chunked", "optimistic"):
        raise RuntimeError("engine is off its default path")
    for a in TR.warmup_requests(conf["vocab_size"], slots):
        eng.submit(Request(uid=a.uid, prompt=a.prompt, max_new_tokens=a.max_new))
    eng.run_until_drained()
    st0 = eng.stats()
    if st0["failed"] or st0["degraded"] or st0["completed"] != slots:
        raise RuntimeError(f"warm-up failed: {st0['fail_log'][:3]} {eng.degrade_reason}")
    if ctx.fault:
        _faulty(eng, ctx.fault)
    preroll = float(tr.get("preroll_s", 0.0))
    arrivals = TR.open_loop(tr, conf["vocab_size"], ctx.seed, preroll + ctx.seconds)
    in_window = [a for a in arrivals if a.due_s >= preroll]
    reqs = {}
    book = Bookkeeping(arrivals)
    pending = sorted(arrivals, key=lambda a: a.due_s)
    waiting: set[int] = set()         # submitted, not yet admitted

    def tick():
        before = {s: (id(r), eng.slot_pos[s]) for s, r in enumerate(eng.slot_req)
                  if r is not None}
        ts = time.perf_counter()
        with spans.span("step"):
            eng.step()
        dt = time.perf_counter() - ts
        n_tok = ctx_sum = kv = live = 0
        for s in range(slots):
            r = eng.slot_req[s]
            if r is None:
                if s in before:        # retired this tick: one decode token
                    p = before[s][1]
                    n_tok, ctx_sum, kv, live = n_tok + 1, ctx_sum + p + 1, kv + p + 1, live + 1
                continue
            p0 = before[s][1] if s in before and before[s][0] == id(r) else 0
            n = eng.slot_pos[s] - p0
            n_tok += n
            ctx_sum += n * p0 + n * (n + 1) // 2
            kv += eng.slot_pos[s]
            live += 1
        book.ticks.append({"n_tokens": n_tok, "ctx_sum": ctx_sum,
                           "kv_tokens": kv, "live": live, "dt": dt})

    def poll():
        with spans.span("poll"):
            now = time.perf_counter()
            for uid in [u for u in waiting if reqs[u].status != "queued"]:
                book.admitted_at[uid] = now
                waiting.discard(uid)

    def submit_due(now):
        with spans.span("submit"):
            while pending and base + pending[0].due_s <= now:
                a = pending.pop(0)
                times = book.token_times[a.uid]
                r = Request(uid=a.uid, prompt=a.prompt, max_new_tokens=a.max_new,
                            on_token=lambda _tok, times=times: times.append(time.perf_counter()))
                reqs[a.uid] = r
                eng.submit(r)
                waiting.add(a.uid)
                book.lateness.append(time.perf_counter() - (base + a.due_s))

    def drive(until):
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            submit_due(now)
            if eng._has_work():
                tick()
                poll()
            else:
                nxt = base + pending[0].due_s if pending else until
                with spans.span("idle"):
                    time.sleep(max(0.0, min(nxt, until) - time.perf_counter()))

    # the stream starts ``preroll_s`` before the window, so the window sees
    # the engine as loaded as the rate keeps it (set-up, not measured)
    spans.keep = False
    base = time.perf_counter()
    drive(base + preroll)
    pre_ticks = len(book.ticks)
    compiles_setup = ctx.compiles.count

    # --- window ---------------------------------------------------------------
    trace_dir = bench.start_trace(ctx)
    spans.keep = True
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    end = t0 + ctx.seconds
    with spans.span("window"):
        drive(end)
    t1 = time.perf_counter()
    bench.stop_trace(ctx)
    queued_at_close = len(eng.queue)
    window_ticks = book.ticks[pre_ticks:]
    compiles_window = ctx.compiles.count - compiles_setup
    done_in_window = [r for r in reqs.values()
                      if r.status == "done" and t0 <= r.finished_at <= t1]

    # --- after the window: first tokens of every request due in it -------------
    spans.keep = False
    grace_end = time.perf_counter() + wl["grace_s"]
    submit_due(math.inf)
    while (any(not book.token_times[a.uid] for a in in_window)
           and eng._has_work() and time.perf_counter() < grace_end):
        tick()
        poll()
    mem_peak = bench.memory_peak_bytes(cell.entry["chips"])

    ttft, itl = [], []
    missing = 0
    for a in in_window:
        times = book.token_times[a.uid]
        if not times or reqs[a.uid].status == "failed":
            missing += 1
            continue
        ttft.append(times[0] - (base + a.due_s))
    for times in book.token_times.values():
        in_win = [t for t in times if t0 <= t <= t1]
        itl.extend(np.diff(in_win).tolist())
    missing += sum(r.status == "failed" for r in reqs.values()
                   if book.arrivals[r.uid].due_s < preroll)
    served = sum(len(r.prompt) + len(r.output) for r in done_in_window)
    admit_wait = [book.admitted_at[a.uid] - (base + a.due_s)
                  for a in in_window if a.uid in book.admitted_at]
    st = eng.stats()

    # --- correctness: the reference over a sample of the completed requests ---
    finished = sorted((r for r in reqs.values() if r.status == "done"),
                      key=lambda r: r.uid)
    sample = _sample(finished, ctx.seed, wl["reference_requests"])
    sample_io = [(list(r.prompt), list(r.output)) for r in sample]
    del eng, params, reqs, finished, sample
    gc.collect()
    gap, n_tok = served_gap(ref, conf, key, sample_io, max_len)
    upper = {}
    if ctx.overrides.get("control"):
        upper["control"] = served_gap(ref, conf, key, sample_io, max_len,
                                      quant="fp8", control=True)[0]
        v = conf["vocab_size"]
        altered = [(p, o[:1] + [(o[1] + 1) % v] + o[2:]) for p, o in sample_io if len(o) > 1]
        upper["token_altered"] = served_gap(ref, conf, key, altered, max_len)[0]
    checks = [bench.Check("serve_gap", gap, wl["limits"]["serve_gap"])]
    # a retried or degraded model call, or a slot quarantined for non-finite
    # logits, means the window did not run the default path as it stands
    engine_faults = st["retries"] + st["quarantines"] + int(st["degraded"])
    if engine_faults:
        checks.append(bench.Check("engine_faults", float(engine_faults), 0.0))
    if missing:
        checks.append(bench.Check("requests_missing", float(missing), 0.0))
    if not sample_io:
        checks.append(bench.Check("requests_compared", 0.0, -1.0))

    lat = np.asarray(book.lateness or [0.0])
    counters = {
        "ticks": len(window_ticks), "tick_records": window_ticks,
        "queued_at_close": queued_at_close,
        "admit_wait_s": admit_wait, "sent": len(in_window),
        "completed_in_window": len(done_in_window),
        "completed": st["completed"] - st0["completed"],
        "failed": st["failed"], "missing": missing,
        "prefill_ticks": st["prefill_ticks"] - st0["prefill_ticks"],
        "decode_ticks": st["decode_ticks"] - st0["decode_ticks"],
        "retries": st["retries"], "quarantines": st["quarantines"],
        "preemptions": st["preemptions"], "compiles_in_window": compiles_window,
        "generator_late_p50_s": float(np.median(lat)),
        "generator_late_max_s": float(lat.max()),
        "compared_tokens": n_tok, "upper_readings": upper,
    }
    e2e = {"setup_s": setup_s,
           "ttft_p95_ms": 1e3 * bench.quantile(ttft, 0.95) if ttft else float("nan"),
           "itl_p95_ms": 1e3 * bench.quantile(itl, 0.95) if itl else float("nan"),
           "serve_tokens_per_s": served / (t1 - t0)}
    slow = sorted(((times[0] - (base + a.due_s), a) for a in in_window
                   if (times := book.token_times[a.uid])), key=lambda x: -x[0])[:3]
    lines = [
        "slowest first tokens: " + ", ".join(
            f"uid {a.uid} due {a.due_s:.2f} s prompt {len(a.prompt)} ttft {t * 1e3:.1f} ms "
            f"admit wait {1e3 * (book.admitted_at.get(a.uid, float('nan')) - base - a.due_s):.1f} ms"
            for t, a in slow),
        f"requests sent {len(in_window)} in the window ({len(arrivals) - len(in_window)} before it), completed in window {len(done_in_window)}, "
        f"completed by the end {counters['completed']}, failed {st['failed']}, "
        f"without a first token {missing}",
        f"generator lateness p50 {counters['generator_late_p50_s'] * 1e3:.3f} ms, "
        f"max {counters['generator_late_max_s'] * 1e3:.3f} ms",
        f"ticks {len(window_ticks)} in window ({counters['prefill_ticks']} prefill, "
        f"{counters['decode_ticks']} decode overall), compiles in window {compiles_window}",
        f"ttft median {1e3 * float(np.median(ttft)) if ttft else float('nan'):.3f} ms "
        f"over {len(ttft)}, itl median {1e3 * float(np.median(itl)) if itl else float('nan'):.3f} ms "
        f"over {len(itl)} gaps",
    ]
    return bench.Outcome(end_to_end=e2e, checks=checks, attempted=len(in_window),
                         failed=missing, counters=counters, window=(t0, t1),
                         trace_dir=trace_dir, memory_peak_bytes=mem_peak,
                         extra_lines=lines)


def _sample(finished, seed: int, n: int):
    """The request with most tokens, and n - 1 others drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.prompt) + len(r.output), -r.uid))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence(TR._seed_words(seed) + [7]))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gap(ref, conf, key, sample_io, max_len, quant=None, control=False):
    """Widest gap, over every served token of the sample, between the
    reference's best logit and its logit of the served token. With
    ``control``, the token compared at each position is the one that the
    reference computed at ``quant`` puts first (teacher-forced on the same
    prompts and served tokens). Returns (gap, tokens compared)."""
    import jax
    import jax.numpy as jnp

    if not sample_io:
        return 0.0, 0
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: ref.init(conf, k))(key)
        w = jax.jit(lambda p: ref.head_matrix(conf, p))(params)

        @jax.jit
        def gaps_of(p, w, toks, pos, served):
            h = ref.hidden(conf, p, toks)[pos]           # (n, d)
            z = ref.logits(conf, w, h)                    # (n, V)
            if control:
                zc = ref.logits(conf, w, ref.hidden(conf, p, toks, quant)[pos], quant)
                served = jnp.argmax(zc, -1)
            best = jnp.max(z, -1)
            return best - jnp.take_along_axis(z, served[:, None], -1)[:, 0]

        worst, n = 0.0, 0
        for prompt, out in sample_io:
            seq = prompt + out[:-1]
            toks = np.zeros((max_len,), np.int32)
            toks[:len(seq)] = seq
            pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
            pad = np.full((max_len,), pos[-1], np.int64)
            pad[:len(pos)] = pos
            srv = np.zeros((max_len,), np.int32)
            srv[:len(out)] = out
            g = np.asarray(gaps_of(params, w, jnp.asarray(toks), jnp.asarray(pad),
                                   jnp.asarray(srv)))[:len(out)]
            worst = max(worst, float(g.max()))
            n += len(out)
    return worst, n


def calibrate(ctx: bench.Context) -> dict:
    """Readings for setting the limit (``calibrate.py``): a whole run's
    numbers, and with ``overrides["control"]`` the control's and an altered
    token's widest gap on the same sample."""
    o = run(ctx)
    c = o.counters
    keep = ("sent", "completed_in_window", "completed", "failed", "missing", "ticks",
            "prefill_ticks", "decode_ticks", "compiles_in_window", "generator_late_p50_s",
            "generator_late_max_s", "compared_tokens")
    return {"end_to_end": o.end_to_end,
            "program": {ch.name: ch.value for ch in o.checks},
            "counters": {k: c[k] for k in keep},
            "queued_at_close": c.get("queued_at_close"),
            "memory_peak_bytes": o.memory_peak_bytes,
            **{k: {"serve_gap": v} for k, v in c["upper_readings"].items()}}

"""The readers of the program's own spans (``repro.obs``) and of named
Pallas kernels: on made-up records and operations, on a recorded v5e
excerpt with named kernels and program spans, and with a program that has
neither (the readers then give no value). The existing readers read the
same values from the recorded excerpt without names as before."""

import json
import os
import sys

import pytest

import small
import bench
import trace_reduce as T
from repro import obs

SERVE = "qwen3-1.7b.serve-chat"
TRAIN = "qwen3-1.7b-ketlin.train-8x1024"
R = obs.Record


def _read(metric, trace=None, counters=None, spans=(), window=(0.0, 1.0), cell=SERVE):
    reading = bench.Reading(cell=bench.resolve(cell), device={"count": 1}, peak=None,
                            trace=trace, counters=counters or {}, spans=list(spans),
                            window=window)
    return bench.metric_reader(metric).read(reading, {"name": metric})


def _load(name):
    with open(os.path.join(small.HERE, "data", name)) as f:
        return json.load(f)


def _reduced(rec):
    return T.reduce_events([[tuple(e) for e in rec["device"]]],
                           [tuple(e) for e in rec["host"]], 1)


# ---------------------------------------------------------------------------
# kernel_ms on made-up operations
# ---------------------------------------------------------------------------

CUSTOM = ' = f32[8] custom-call(), custom_call_target="tpu_custom_call"'
OPS = [("%while.3 = (s32[]) while(...)", 0, 1000),
       ("%kron_matmul_fwd.1" + CUSTOM, 100, 300),
       ("%kron_matmul_fwd.2" + CUSTOM, 400, 450),
       ("%kron_matmul_bwd.7" + CUSTOM, 500, 800),
       ("%paged_attn_split.4" + CUSTOM, 1100, 1200),
       ("%paged_attn_combine.5" + CUSTOM, 1200, 1230),
       ("%kron_matmul_fwd_fusion.9 = f32[8] fusion()", 1300, 1400),  # not a kernel
       ("%checkpoint.77" + CUSTOM, 1500, 1600),                     # unnamed kernel
       ("%jvp_fused_kron_ce_fwd_.11" + CUSTOM, 1700, 1720),          # under jax.grad
       ("%transpose_jvp_fused_kron_ce_bwd__.11" + CUSTOM, 1720, 1760)]


@pytest.mark.parametrize("metric,ns", [
    ("kernel_ms.kron_matmul_fwd.train", 250), ("kernel_ms.kron_matmul_bwd.train", 300),
    ("kernel_ms.paged_attn.itl", 130), ("kernel_ms.fused_kron_ce_fwd.train", 20),
    ("kernel_ms.fused_kron_ce_bwd.train", 40)])
def test_kernel_ms_sums_the_named_kernels_self_time(metric, ns):
    trace = T.reduce_events([OPS], [("window", 0, 2000)], 1)
    cell, key = (TRAIN, "steps") if metric.endswith(".train") else (SERVE, "ticks")
    got = _read(metric, trace=trace, counters={key: 2}, cell=cell)
    assert got == pytest.approx(1e3 * ns * 1e-9 / 2)


def test_kernel_ms_gives_nothing_without_the_name():
    trace = T.reduce_events([OPS], [("window", 0, 2000)], 1)
    assert _read("kernel_ms.kron_gather_fwd.itl", trace=trace, counters={"ticks": 2}) is None
    assert _read("kernel_ms.kron_matmul_fwd.itl", trace=trace, counters={}) is None


# ---------------------------------------------------------------------------
# span readers on made-up records
# ---------------------------------------------------------------------------

RECORDS = [
    R("request.queue", 0.5, 1.5, None, {"uid": 1, "requeues": 0}),   # starts before
    R("engine.sync", 2.2, 2.5, "engine.step", {}),
    R("engine.step", 2.0, 3.0, None, {"kind": "prefill"}),
    R("engine.step", 3.0, 3.1, None, {"kind": "stalled"}),
    R("request.queue", 2.0, 4.0, None, {"uid": 2, "requeues": 0}),
    R("engine.sync", 4.5, 4.6, "engine.step", {}),
    R("engine.step", 4.0, 5.0, None, {"kind": "decode"}),
    R("request.queue", 3.5, 4.0, None, {"uid": 3, "requeues": 0}),
    R("request.prefill", 4.0, 6.0, None, {"uid": 2}),
    R("request.queue", 6.5, 7.0, None, {"uid": 3, "requeues": 1}),  # after a preemption
    R("request.prefill", 4.0, 8.0, None, {"uid": 3}),
    R("engine.step", 9.5, 10.5, None, {"kind": "decode"}),           # ends after the window
]


@pytest.mark.parametrize("metric,value", [
    ("tick_host_ms.itl", 1e3 * (0.7 + 0.9) / 2),
    ("queue_wait_p95_ms.ttft", 1e3 * bench.quantile([2.0, 0.5], 0.95)),
    ("prefill_p95_ms.ttft", 1e3 * bench.quantile([2.0, 4.0], 0.95))])
def test_span_readers_on_made_up_records(monkeypatch, metric, value):
    monkeypatch.setattr(obs, "records", lambda: list(RECORDS))
    assert _read(metric, window=(1.8, 10.0)) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["tick_host_ms.itl", "queue_wait_p95_ms.ttft",
                                    "prefill_p95_ms.ttft"])
def test_span_readers_without_the_recorder(monkeypatch, metric):
    """A program without ``repro.obs`` (the benchmark laid over an older
    checkout): no value, no error."""
    import repro
    monkeypatch.setattr(obs, "records", lambda: list(RECORDS))
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _read(metric, window=(1.8, 10.0)) is None


# ---------------------------------------------------------------------------
# the recorded excerpt without names: existing readers read what they read
# before, the new ones read nothing
# ---------------------------------------------------------------------------

OLD = "serve_ticks_v5e.json"


@pytest.mark.parametrize("metric,value", [
    ("pallas_ms.itl", 1.453918), ("tick_device_ms.itl", 67.093405),
    ("device_idle_share.itl", 18.742327563429107), ("tick_ms.itl", 82.5003165)])
def test_existing_readers_unchanged_on_the_unnamed_excerpt(metric, value):
    rec = _load(OLD)
    spans = [(n, s * 1e-9, e * 1e-9) for n, s, e in rec["host"]]
    (window,) = [(s, e) for n, s, e in spans if n == "window"]
    got = _read(metric, trace=_reduced(rec), counters={"ticks": 2}, spans=spans,
                window=window)
    assert got == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("kernel", ["kron_matmul_fwd", "paged_attn", "kron_gather_fwd"])
def test_kernel_ms_reads_nothing_from_unnamed_kernels(kernel):
    got = _read(f"kernel_ms.{kernel}.itl", trace=_reduced(_load(OLD)), counters={"ticks": 2})
    assert got is None


# ---------------------------------------------------------------------------
# the recorded excerpt with names and program spans
# ---------------------------------------------------------------------------

NAMED = "serve_ticks_named_v5e.json"
SERVE_KERNELS = ("kron_matmul_fwd", "paged_attn", "kron_gather_fwd")


def _named():
    rec = _load(NAMED)
    return rec, [R(*r) for r in rec["records"]], [R(*r) for r in rec["requests"]]


def test_named_excerpt_every_kernel_has_a_name():
    rec = _load(NAMED)
    trace = _reduced(rec)
    custom = [o.name for chip in trace.ops for o in chip if o.custom]
    assert custom
    assert all(n.lstrip("%").startswith(SERVE_KERNELS) for n in custom), custom
    ticks = {"ticks": 2}
    total = sum(_read(f"kernel_ms.{k}.itl", trace=trace, counters=ticks) or 0.0
                for k in SERVE_KERNELS)
    assert total == pytest.approx(_read("pallas_ms.itl", trace=trace, counters=ticks),
                                  rel=1e-12)


@pytest.mark.parametrize("kernel", SERVE_KERNELS)
def test_named_excerpt_kernel_ms(kernel):
    rec = _load(NAMED)
    ns = sum(v for k, v in rec["expect"]["kernel_ns"].items() if k.startswith(kernel))
    got = _read(f"kernel_ms.{kernel}.itl", trace=_reduced(rec), counters={"ticks": 2})
    assert got == (pytest.approx(ns * 1e-6 / 2) if ns else None)


def test_named_excerpt_tick_host_ms(monkeypatch):
    rec, ticks, _ = _named()
    monkeypatch.setattr(obs, "records", lambda: ticks)
    got = _read("tick_host_ms.itl", window=tuple(rec["ticks_window"]))
    assert got == pytest.approx(rec["expect"]["tick_host_ms"])
    # the tick's phases, in order, inside each tick
    for step in (r for r in ticks if r.name == "engine.step"):
        kids = [r.name for r in ticks if r.parent == "engine.step"
                and step.t0 <= r.t0 and r.t1 <= step.t1]
        assert kids == ["engine.admit", "engine.grow", "engine.dispatch", "engine.sync",
                        "engine.emit"]


@pytest.mark.parametrize("metric,key", [("queue_wait_p95_ms.ttft", "queue_wait_p95_ms"),
                                        ("prefill_p95_ms.ttft", "prefill_p95_ms")])
def test_named_excerpt_request_spans_give_the_chip_runs_values(monkeypatch, metric, key):
    """The whole run's request spans, read again, give the values the traced
    run on the chip reported."""
    rec, _, requests = _named()
    monkeypatch.setattr(obs, "records", lambda: requests)
    got = _read(metric, window=tuple(rec["run_window"]))
    assert got == pytest.approx(rec["expect"][key], rel=1e-12)


def test_named_excerpt_gaps_fall_in_engine_phases():
    """With the program's spans among the host events, the device's idle
    time inside a tick is put down to an engine phase, not the whole tick."""
    rec = _load(NAMED)
    gaps = dict(_reduced(rec).gaps)
    engine = sum(v for k, v in gaps.items() if k.startswith("engine."))
    assert engine >= 0.9 * sum(gaps.values())
    assert "step" not in gaps or gaps["step"] < 0.01 * engine

"""The trace reduction: busy/idle union, self time of nested operations,
Pallas kernel time, and naming idle gaps by the host span around them."""

import json
import os

import pytest

import small
import trace_reduce as T


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (28, 29)]
    assert T.union_ns(iv, 0, 40) == 25
    assert T.union_ns(iv, 8, 25) == 12
    assert T.idle_gaps(iv, 0, 40) == [(15, 20), (30, 40)]
    assert T.idle_gaps(iv, -5, 12) == [(-5, 0)]


def test_self_time_of_nested_ops():
    ops = T.with_self_time([T.Op("%while", 0, 100, False), T.Op("%a", 10, 30, True),
                            T.Op("%b", 40, 90, False), T.Op("%c", 50, 60, True),
                            T.Op("%d", 120, 130, False)])
    self_ns = {o.name: o.self_ns for o in ops}
    assert self_ns == {"%while": 30, "%a": 20, "%b": 40, "%c": 10, "%d": 10}


def test_reduce_events():
    dev = [[("%while.1 = (s32[]) while(...)", 100, 600),
            ("%k = f32[4] custom-call(), custom_call_target=\"tpu_custom_call\"", 150, 250),
            ("%f = f32[4] fusion()", 300, 400),
            ("%g = f32[4] fusion()", 700, 800)]]
    host = [("window", 0, 1000), ("step", 50, 650), ("poll", 650, 700), ("step", 700, 820),
            ("submit", 820, 1000)]
    r = T.reduce_events(dev, host, 1)
    assert r.window_s == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx(600e-9)
    assert r.custom_call_seconds() == pytest.approx(100e-9)
    gaps = dict(r.gaps)
    assert gaps == pytest.approx({"step": 100e-9, "poll": 100e-9, "submit": 200e-9})
    b = r.breakdown()
    assert b["device_ops"][0][0] == "%while.1"
    assert [k for k, _ in b["device_ops"]] == ["%while.1", "%k", "%f", "%g"]


def test_recorded_chip_trace():
    """An excerpt of a trace recorded on a v5e chip (two serving ticks):
    device ops of ``/device:TPU:0`` and the benchmark's host spans."""
    path = os.path.join(small.HERE, "data", "serve_ticks_v5e.json")
    rec = json.load(open(path))
    r = T.reduce_events([[tuple(e) for e in rec["device"]]],
                        [tuple(e) for e in rec["host"]], 1)
    assert 0 < r.busy_s < r.window_s
    assert r.custom_call_seconds() > 0
    assert r.gaps and all(name in {"step", "poll", "submit", "idle", "other"}
                          for name, _ in r.gaps)
    assert sum(s for _, s in r.gaps) == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    assert rec["expect"]["busy_s"] == pytest.approx(r.busy_s)
    assert rec["expect"]["custom_s"] == pytest.approx(r.custom_call_seconds())

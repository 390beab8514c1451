"""The longdoc cell's readers: ``moe_imbalance`` and ``mfu.longdoc`` on
made-up records, without the program's counters (no value), and on a
recorded excerpt of a traced v5e run of the cell."""

import json
import os

import pytest

import longdoc_small as LS
import small
import bench
from repro import obs

R = obs.Record


def _read(metric, cell, counters=None, spans=(), window=(0.0, 10.0), peak=None):
    reading = bench.Reading(cell=bench.resolve(cell), device={"count": 1},
                            peak=peak or bench.peaks("TPU v5 lite"), trace=None,
                            counters=counters or {}, spans=list(spans), window=window)
    return bench.metric_reader(metric).read(reading, {"name": metric})


def _sync(t0, rows, mx):
    return R("engine.sync", t0, t0 + 0.1, "engine.step", {"moe_rows": rows, "moe_rows_max": mx})


def test_moe_imbalance_on_made_up_records(monkeypatch):
    recs = [_sync(1.0, 80, 20), _sync(2.0, 16, 2), _sync(3.0, 0, 0),
            R("engine.sync", 4.0, 4.1, "engine.step", {}),       # a dense config's
            _sync(20.0, 800, 800)]                               # after the window
    monkeypatch.setattr(obs, "records", lambda: recs)
    # (20 + 2 + 0) / ((80 + 16 + 0) / 8 held experts)
    assert _read("moe_imbalance.longdoc", LS.LONGDOC) == pytest.approx(22 / 12)


def test_readers_give_nothing_without_the_counters(monkeypatch):
    monkeypatch.setattr(obs, "records", lambda: [R("engine.sync", 1.0, 1.1, "engine.step", {})])
    ticks = [{"n_tokens": 3, "ctx_sum": 9, "kv_tokens": 9, "live": 1, "dt": 0.05}]
    assert _read("moe_imbalance.longdoc", LS.LONGDOC) is None
    assert _read("mfu.longdoc", LS.LONGDOC, counters={"tick_records": ticks},
                 spans=[("step", 0.9, 1.2)]) is None


def test_mfu_longdoc_on_made_up_ticks(monkeypatch):
    """Each tick's rows are those of the sync record inside its step span;
    a tick is bound by FLOPs or by bytes, whichever takes longer."""
    recs = [_sync(1.05, 600, 100), _sync(2.05, 8, 1)]
    monkeypatch.setattr(obs, "records", lambda: recs)
    ticks = [{"n_tokens": 1000, "ctx_sum": 10 ** 7, "kv_tokens": 90000, "live": 12, "dt": 0.2},
             {"n_tokens": 12, "ctx_sum": 90000, "kv_tokens": 90000, "live": 12, "dt": 0.03}]
    spans = [("step", 1.0, 1.2), ("poll", 1.2, 1.21), ("step", 2.0, 2.03)]
    cell = bench.resolve(LS.LONGDOC)
    w, peak = bench.work("deepseek_v2"), bench.peaks("TPU v5 lite")
    f0 = w.tick_flops(cell.config, 1000, 10 ** 7, 12, 600)
    b1 = w.tick_bytes(cell.config, 90000)
    least = (max(f0 / peak["bf16_flops_per_s"], b1 / peak["hbm_bytes_per_s"])
             + max(w.tick_flops(cell.config, 12, 90000, 12, 8) / peak["bf16_flops_per_s"],
                   b1 / peak["hbm_bytes_per_s"]))
    got = _read("mfu.longdoc", LS.LONGDOC, counters={"tick_records": ticks}, spans=spans)
    assert got == pytest.approx(100 * least / 0.23, rel=1e-12)
    # the first tick is bound by FLOPs, the second by the weights' bytes
    assert f0 / peak["bf16_flops_per_s"] > b1 / peak["hbm_bytes_per_s"]


def test_longdoc_kernel_readers_on_made_up_ops():
    """``kernel_ms.ragged-dot.longdoc`` reads XLA's grouped-product Mosaic
    calls for the held experts, ``kernel_ms.kron_gather_fwd.longdoc`` the
    embedding's kernel, ``pallas_ms.longdoc`` every Mosaic call; each per
    tick of the window."""
    from trace_reduce import Op, Reduced, with_self_time
    ops = with_self_time([Op("%ragged-dot-none.1", 0, 3e6, True),
                          Op("%ragged-dot-metadata", 3e6, 4e6, True),
                          Op("%kron_gather_fwd.2", 4e6, 6e6, True),
                          Op("%fusion.3", 6e6, 16e6, False)])
    trace = Reduced(ops=[ops], busy_s=16e-3, window_s=1.0, gaps=[])
    reading = bench.Reading(cell=bench.resolve(LS.LONGDOC), device={"count": 1},
                            peak=bench.peaks("TPU v5 lite"), trace=trace,
                            counters={"ticks": 2}, spans=[], window=(0.0, 1.0))

    def read(metric):
        return bench.metric_reader(metric).read(reading, {"name": metric})
    assert read("kernel_ms.ragged-dot.longdoc") == pytest.approx(2.0)  # 4 ms over 2 ticks
    assert read("kernel_ms.kron_gather_fwd.longdoc") == pytest.approx(1.0)
    assert read("pallas_ms.longdoc") == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# recorded excerpts of traced v5e runs
# ---------------------------------------------------------------------------

def _excerpt(name):
    path = os.path.join(small.HERE, "data", name)
    with open(path) as f:
        return json.load(f)


EXCERPTS = {"mfu.longdoc": "longdoc_reading_v5e.json",
            "moe_imbalance.longdoc": "longdoc_reading_v5e.json"}


@pytest.mark.parametrize("metric", sorted(EXCERPTS))
def test_recorded_run_gives_the_chip_runs_value(monkeypatch, metric):
    """The traced run's ticks, step spans and engine records, read again,
    give the value that run reported on the chip."""
    rec = _excerpt(EXCERPTS[metric])
    records = [R(n, t0, t1, parent, attrs) for n, t0, t1, parent, attrs in rec["records"]]
    monkeypatch.setattr(obs, "records", lambda: records)
    got = _read(metric, rec["cell"], counters={"tick_records": rec["tick_records"]},
                spans=[tuple(s) for s in rec["steps"]], window=tuple(rec["window"]))
    assert got == pytest.approx(rec["metrics"][metric]["value"], rel=1e-9)

"""The span recorder: records with parent links, attributes set late, the
bound on the log, explicit intervals, ``reset()``, and the profiler's trace."""

import glob

import pytest

from repro import obs


@pytest.fixture(autouse=True)
def empty_log():
    obs.reset()
    yield
    obs.reset()


def test_nesting_and_parent_links():
    with obs.span("engine.step") as outer:
        with obs.span("engine.admit"):
            pass
        with obs.span("engine.sync") as inner:
            pass
    recs = obs.records()
    # appended when each closes: children before their parent
    assert [(r.name, r.parent) for r in recs] == [
        ("engine.admit", "engine.step"), ("engine.sync", "engine.step"),
        ("engine.step", None)]
    assert outer.t0 <= recs[0].t0 <= recs[0].t1 <= inner.t0 <= inner.t1 <= outer.t1
    assert (recs[-1].t0, recs[-1].t1) == (outer.t0, outer.t1)


def test_parent_is_restored_after_an_exception():
    with pytest.raises(RuntimeError):
        with obs.span("engine.step"):
            with obs.span("engine.dispatch"):
                raise RuntimeError("model call failed")
    with obs.span("engine.emit"):
        pass
    assert [(r.name, r.parent) for r in obs.records()] == [
        ("engine.dispatch", "engine.step"), ("engine.step", None), ("engine.emit", None)]


def test_attributes_given_and_set_late():
    with obs.span("engine.step", tick=3) as sp:
        sp.set(kind="decode")
    (rec,) = obs.records()
    assert rec.attrs == {"tick": 3, "kind": "decode"}


def test_record_logs_an_interval_without_parent():
    with obs.span("engine.admit"):
        obs.record("request.queue", 1.0, 2.5, uid=7, requeues=0)
    first = obs.records()[0]
    assert first == obs.Record("request.queue", 1.0, 2.5, None, {"uid": 7, "requeues": 0})


def test_log_is_bounded_and_keeps_the_newest():
    n = obs.MAX_RECORDS + 10
    for i in range(n):
        obs.record("request.prefill", float(i), float(i), uid=i)
    recs = obs.records()
    assert len(recs) == obs.MAX_RECORDS
    assert recs[0].attrs["uid"] == 10 and recs[-1].attrs["uid"] == n - 1


def test_reset_forgets_everything():
    with obs.span("engine.grow"):
        pass
    assert obs.records()
    obs.reset()
    assert obs.records() == []


def test_span_names_are_unique_and_namespaced():
    assert len(set(obs.SPAN_NAMES)) == len(obs.SPAN_NAMES)
    assert all(n.split(".")[0] in ("engine", "request") for n in obs.SPAN_NAMES)


def test_spans_appear_in_a_profiler_trace(tmp_path):
    """In a profiler session a span is a host event under its bare name,
    with its attributes (also those set late) as the event's stats."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("engine.step") as sp:
            with obs.span("engine.sync"):
                pass
            sp.set(kind="prefill")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {e.name: dict(e.stats) for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events if e.name in obs.SPAN_NAMES}
    assert events == {"engine.step": {"kind": "prefill"}, "engine.sync": {}}

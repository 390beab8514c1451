"""The program's own span records (``repro.obs``: ``time.perf_counter``,
the clock of the benchmark's window), for the metrics that read them. A
program without the recorder has none, and those metrics are then not
reported."""


def records():
    """Every record the program keeps, oldest first; None without a recorder."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.records()


def first_waits(recs, lo: float, hi: float) -> dict:
    """Each request's first wait in the engine's queue (``request.queue``
    before any requeue) that starts in [lo, hi], by uid."""
    return {x.attrs["uid"]: x for x in recs
            if x.name == "request.queue" and x.attrs.get("requeues") == 0
            and lo <= x.t0 <= hi}

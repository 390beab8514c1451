"""Load imbalance over the held experts of a MoE layer: over the window's
ticks, Σ ``moe_rows_max`` ÷ (Σ ``moe_rows`` ÷ experts held), where
``moe_rows`` (rows the held experts received in a tick, summed over layers)
and ``moe_rows_max`` (the most any one held expert received) are attrs of
the program's ``engine.sync`` spans. 1 is an even load; the experts held
is the configuration's ``n_routed_experts``. A program without the attrs
gives no value. Source: the program's counters (``repro.obs`` records)."""

import obs_records


def sync_rows(recs, lo: float, hi: float) -> list:
    """(t0, moe_rows, moe_rows_max) of every ``engine.sync`` record in
    [lo, hi] that carries the attrs."""
    return [(x.t0, x.attrs["moe_rows"], x.attrs["moe_rows_max"]) for x in recs
            if x.name == "engine.sync" and "moe_rows" in x.attrs
            and lo <= x.t0 and x.t1 <= hi]


def read(r, metric):
    recs = obs_records.records()
    if not recs:
        return None
    rows = sync_rows(recs, *r.window)
    total = sum(x[1] for x in rows)
    if not total:
        return None
    return sum(x[2] for x in rows) / (total / r.cell.config["n_routed_experts"])

"""Host time of one engine tick while the chip waits: over the program's
``engine.step`` spans in the window that ran the model (``kind`` not
``stalled``), the mean of a span's length less its ``engine.sync`` child
(sampling and the finiteness check, where the host waits for the device),
in ms. Source: the host clock (the program's ``repro.obs`` records)."""

import bisect

import obs_records


def read(r, metric):
    recs = obs_records.records()
    if not recs:
        return None
    lo, hi = r.window
    inside = [x for x in recs if x.t0 >= lo and x.t1 <= hi]
    steps = sorted((x.t0, x.t1) for x in inside
                   if x.name == "engine.step" and x.attrs.get("kind") != "stalled")
    if not steps:
        return None
    starts = [s for s, _ in steps]
    host = [e - s for s, e in steps]
    for x in inside:
        if x.name == "engine.sync" and x.parent == "engine.step":
            i = bisect.bisect_right(starts, x.t0) - 1
            if i >= 0 and x.t1 <= steps[i][1]:
                host[i] -= x.t1 - x.t0
    return 1e3 * sum(host) / len(host)

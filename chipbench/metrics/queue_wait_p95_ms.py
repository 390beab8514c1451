"""95th percentile of a request's first wait in the engine's queue, from
``submit`` to its admission (the program's ``request.queue`` span before
any requeue), over the requests whose wait starts in the window, in ms.
Source: the host clock (the program's ``repro.obs`` records)."""

import bench
import obs_records


def read(r, metric):
    recs = obs_records.records()
    if not recs:
        return None
    waits = obs_records.first_waits(recs, *r.window)
    q = bench.quantile([x.t1 - x.t0 for x in waits.values()], 0.95)
    return None if q is None else 1e3 * q

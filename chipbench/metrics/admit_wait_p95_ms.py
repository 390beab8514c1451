"""95th percentile of the wait from a request's due time until the engine
admits it (its status leaves ``queued``, polled between ticks), in ms.
Source: the host clock."""

import bench


def read(r, metric):
    xs = r.counters.get("admit_wait_s") or []
    q = bench.quantile(xs, 0.95)
    return None if q is None else 1e3 * q

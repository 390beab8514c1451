"""95th percentile of a request's time from its first admission to its
first token (the program's ``request.prefill`` span), over the requests
whose first wait in the queue starts in the window (``queue_wait_p95_ms``'s
requests), in ms. Source: the host clock (the program's ``repro.obs``
records)."""

import bench
import obs_records


def read(r, metric):
    recs = obs_records.records()
    if not recs:
        return None
    waits = obs_records.first_waits(recs, *r.window)
    xs = [x.t1 - x.t0 for x in recs
          if x.name == "request.prefill" and x.attrs["uid"] in waits
          and x.t0 == waits[x.attrs["uid"]].t1]
    q = bench.quantile(xs, 0.95)
    return None if q is None else 1e3 * q

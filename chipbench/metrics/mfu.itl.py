"""The serving tick's share of the chip's roofline, over the window.

For each tick the least time the chip could take is the larger of the
model FLOPs of what the tick computed over the bf16 peak and the bytes it
must move over the HBM bandwidth (``work/model.py``: the tokens it
advanced, their attention over each slot's context and the head for every
live slot; the parameters as stored plus every live slot's cached keys and
values). The sum of those times over the ticks is divided by the sum of the
ticks' host times. Source: the host clock (the benchmark's ``step`` spans)
and the harness's per-tick counts."""

import bench


def read(r, metric):
    ticks = r.counters.get("tick_records") or []
    if not ticks:
        return None
    conf = r.cell.config
    m = bench.work("model")
    peak, bw = r.peak["bf16_flops_per_s"], r.peak["hbm_bytes_per_s"]
    least = sum(max(m.tick_flops(conf, t["n_tokens"], t["ctx_sum"], t["live"]) / peak,
                    m.tick_bytes(conf, t["kv_tokens"]) / bw) for t in ticks)
    wall = sum(t["dt"] for t in ticks)
    return 100.0 * least / wall if wall > 0 else None

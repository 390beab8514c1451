"""The DeepSeek-V2 serving tick's share of the chip's roofline, over the
window.

For each tick the least time the chip could take is the larger of the model
FLOPs of what the tick computed over the bf16 peak and the bytes it must
move over the HBM bandwidth (``work/deepseek_v2.py``: the tokens it
advanced, their absorbed MLA over each slot's context, the dense, shared
and router work per token, the held experts' rows from the program's
``moe_rows`` counter, and the head for every live slot; the parameters as
stored plus every live slot's cached latents). The sum of those times over
the ticks is divided by the sum of the ticks' host times. A tick's rows are
those of the ``engine.sync`` record inside its ``step`` span; a program
without the counter gives no value. Source: the host clock (the benchmark's
``step`` spans) and the harness's per-tick counts."""

import bench
import obs_records

IMB = bench.load_module("metrics", "moe_imbalance.py")


def read(r, metric):
    ticks = r.counters.get("tick_records") or []
    recs = obs_records.records()
    if not ticks or not recs:
        return None
    lo, hi = r.window
    steps = [(s, e) for n, s, e in r.spans if n == "step" and lo <= s and e <= hi]
    syncs = IMB.sync_rows(recs, lo, hi)
    if len(steps) != len(ticks) or not syncs:
        return None
    rows, i = [], 0
    for s, e in steps:
        n = 0
        while i < len(syncs) and syncs[i][0] < e:
            if syncs[i][0] >= s:
                n += syncs[i][1]
            i += 1
        rows.append(n)
    conf = r.cell.config
    w = bench.work("deepseek_v2")
    peak, bw = r.peak["bf16_flops_per_s"], r.peak["hbm_bytes_per_s"]
    least = sum(max(w.tick_flops(conf, t["n_tokens"], t["ctx_sum"], t["live"], m) / peak,
                    w.tick_bytes(conf, t["kv_tokens"]) / bw) for t, m in zip(ticks, rows))
    wall = sum(t["dt"] for t in ticks)
    return 100.0 * least / wall if wall > 0 else None

"""Model FLOP/s utilization of training: the model FLOPs a token needs
(forward and backward, nothing recomputed; ``work/model.py``) times the
tokens trained per second in the traced window, over the chips' bf16 peak.
Source: the host clock (whole steps, each ending in the fetched loss)."""

import bench


def read(r, metric):
    c = r.counters
    lo, hi = r.window
    if not c.get("steps") or hi <= lo:
        return None
    conf = r.cell.config
    per_tok = bench.work("model").train_flops_per_token(conf, r.cell.traffic["seq_len"])
    rate = c["steps"] * c["tokens_per_step"] / (hi - lo)
    return 100.0 * per_tok * rate / (r.peak["bf16_flops_per_s"] * r.device["count"])

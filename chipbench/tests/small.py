"""Small sizes of the benchmark's cells, for its CPU tests."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _factors(d_in, d_out, rank):
    from repro.core import kron as K
    return {"rank": rank, "q": list(K.choose_factorization(d_in, 2)),
            "t": list(K.choose_factorization(d_out, 2))}


def overrides(cell: str, linear_kind: str) -> dict:
    """Sizes of a two-layer model of the cell's family: the program's config
    overrides and the matching reference sizes."""
    d, H, KVH, Dh, ff, V, L = 64, 4, 2, 16, 192, 1024, 2
    program = dict(num_layers=L, d_model=d, num_heads=H, num_kv_heads=KVH,
                   head_dim=Dh, d_ff=ff, vocab_size=V, embedding_rank=2,
                   head_rank=2, linear_rank=2)
    emb = _factors(d, V, 2)
    factors = {"embed": emb, "head": emb}
    if linear_kind == "ket":
        factors.update(wq=_factors(d, H * Dh, 2), wk=_factors(d, KVH * Dh, 2),
                       wv=_factors(d, KVH * Dh, 2), wo=_factors(H * Dh, d, 2),
                       wi=_factors(d, ff, 2), ffn_wo=_factors(ff, d, 2))
    config = dict(num_layers=L, d_model=d, num_heads=H, num_kv_heads=KVH,
                  head_dim=Dh, d_ff=ff, vocab_size=V, factors=factors)
    # Limits at this size, set as the cells' are, from CPU readings here
    # (seeds 7000000001, 6000000007, 12): training, sound runs read gaps up
    # to loss 8.9e-4, grad 2.0e-3, median-leaf grad 5.3e-4, update 1.0e-2,
    # and the float8 control at least 4.9e-3, 1.4e-2, 3.8e-3, 1.6e-2 (the
    # update gap does not separate at this size; the faults read 6e-2 and
    # more); serving, sound runs match the reference's argmax (gap 0) and
    # the float8 control reads 0.17-0.24 on random sequences. The chip's
    # limits are in the workload files.
    if cell.endswith("train-8x1024"):
        return {"program": program, "config": config,
                "traffic": {"batch": 4, "seq_len": 32},
                "workload": {"limits": {"loss_gap": 2.5e-3, "grad_gap": 6e-3,
                                        "grad_gap_median": 1.5e-3,
                                        "update_gap": 3e-2}}}
    return {"program": program, "config": config,
            "traffic": {"rate_per_s": 4.0, "preroll_s": 1.0, "schedule_seed": 5,
                        "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
                        "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}},
            "workload": {"slots": 4, "max_len": 48, "grace_s": 30,
                         "reference_requests": 3, "limits": {"serve_gap": 0.05}}}

#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set, on the chip.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 [--controls 3]
        [--seconds 40] [--rate R] [--out calib.jsonl]

For each seed, in one process, one JSON line with the numbers the cell
compares, read from a sound run of the program (the lower readings), and
for the first ``--controls`` seeds the same numbers read from the control
(the reference computed in float8 in the program's place) and from the
faults a cell can have (the upper readings):

* training: the program's first three steps against the reference; the
  control; the reference on half of each batch (its mean over the rest);
  a state left unchanged reads 1 by the measure and needs no run;
* serving: a whole run (``--seconds`` of open-loop traffic, ``--rate``
  overriding the traffic's rate) against the reference; the control's
  first choice at each position of the same prompts and served tokens; a
  served token altered where it is produced.

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    cell = bench.resolve(args.workload)
    device = bench.device_info(cell.entry["chips"])
    out = open(args.out, "a") if args.out else None
    clock = bench.CompileClock()
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        overrides = {}
        if args.rate is not None:
            overrides["traffic"] = {"rate_per_s": args.rate}
        if n < args.controls:
            overrides["control"] = True
        ctx = bench.Context(cell=cell, seed=seed, seconds=args.seconds, trace=False,
                            t_start=t0, out_dir=os.path.join(HERE, "out", cell.name),
                            compiles=clock, overrides=overrides)
        rec = {"cell": cell.name, "seed": seed, "device": device["kind"]}
        rec.update(cell.driver.calibrate(ctx))
        rec["seconds"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

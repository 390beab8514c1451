#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything it
needs is found by name under ``chipbench/`` (see ``bench.py``). The run
makes its weights and inputs from ``--seed``, warms up every shape the
window uses (set-up), measures for ``--seconds``, then checks what the timed
path produced against the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is recorded by the profiler and the result carries
the cell's per-layer metrics, the device's busy and window seconds, and a
breakdown of device time and idle gaps.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``), then
``compared``: each number compared beside its limit. The same numbers are
the last lines of standard error. Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import bench  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def run_cell(args, *, require_tpu: bool = True, t_start: float = T_START,
             overrides: dict | None = None, fault: str | None = None,
             out_dir: str | None = None, compile_cache: bool = True) -> dict:
    """One run of one cell; returns the result object. ``require_tpu``,
    ``overrides``, ``fault`` and ``compile_cache`` exist for the benchmark's
    own tests."""
    cell = bench.resolve(args.workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise bench.NoChip(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    cache_dir = None
    if compile_cache:
        from repro.launch.compile_cache import configure_compile_cache
        cache_dir = configure_compile_cache()
    device = bench.device_info(cell.entry["chips"], require_tpu=require_tpu)
    bench.log(f"device {device} cell {cell.name} seed {args.seed} "
              f"seconds {args.seconds} trace {args.trace} compile cache {cache_dir}")
    ctx = bench.Context(
        cell=cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=t_start, compiles=bench.CompileClock(),
        out_dir=out_dir or os.path.join(ROOT, "chipbench", "out", cell.name),
        overrides=overrides or {}, fault=fault)
    out = cell.driver.run(ctx)
    for line in out.extra_lines:
        bench.log(line)
    bench.log("end to end " + " ".join(f"{k} {v!r}" for k, v in out.end_to_end.items()))
    result = {"correct": all(c.ok for c in out.checks),
              "attempted": out.attempted, "failed": out.failed}
    if args.trace:
        from trace_reduce import reduce_window
        red = reduce_window(out.trace_dir, ctx.spans.records, cell.entry["chips"])
        reading = bench.Reading(cell=cell, device=device,
                                peak=bench.peaks(device["kind"]),
                                trace=red, counters=out.counters,
                                spans=ctx.spans.records, window=out.window)
        metrics = {}
        for m in cell.per_layer:
            value = bench.metric_reader(m["name"]).read(reading, m)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = {**device, "busy_s": red.busy_s, "window_s": red.window_s}
        result["breakdown"] = red.breakdown()
    else:
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device["memory_peak_bytes"] = out.memory_peak_bytes
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in out.checks}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(args)
    except bench.NoChip as e:
        bench.log(f"no result: {e}")
        return 2
    for name, c in result["compared"].items():
        bench.log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Shared pieces of the on-chip benchmark: the cell's files, the clocks,
the host spans, the trace window and the result line.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) is found by name:

* ``workloads/<cell>.json``: the configuration's name, the driver kind,
  the traffic's name, the system's knobs (slots, ``max_len``, optimizer),
  and the correctness limits;
* ``configs/<config>.json``: the model's sizes as run, its source, what was
  reduced or assumed, and the name of its plain reference
  (``references/<reference>.py``);
* ``traffic/<traffic>.json``: the parameters the one generator
  (``generator.py``) reads;
* ``drivers/<kind>.py``: the runner of that kind of cell;
* ``metrics/<metric>.py``: one reader per per-layer metric (the part of the
  name before the first ``.`` when no file carries the whole name);
* ``work/<kernel>.py``: operations and bytes of one kernel call.

Adding a cell, a traffic mix, a configuration or a metric adds files and
edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import statistics
import sys
import time
from typing import Any, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """No run is possible: JAX found no TPU or fewer chips than the cell
    asks for, or the checkout holds no program."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    path = os.path.join(HERE, *parts)
    name = "chipbench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict        # the cell's entry in BENCHMARK.json
    workload: dict     # workloads/<cell>.json
    config: dict       # configs/<config>.json
    traffic: dict      # traffic/<traffic>.json
    end_to_end: list   # BENCHMARK.json metrics this cell reports
    per_layer: list

    @property
    def driver(self):
        return load_module("drivers", self.workload["driver"] + ".py")

    @property
    def reference(self):
        return load_module("references", self.config["reference"] + ".py")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, spec: Optional[dict] = None) -> Cell:
    spec = spec if spec is not None else benchmark_spec()
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    workload = load_json("workloads", name + ".json")
    if workload["config"] != entry["config"] or workload["traffic"] != entry["traffic"]:
        raise ValueError(f"{name}: workload file disagrees with BENCHMARK.json")
    return Cell(
        name=name, entry=entry, workload=workload,
        config=load_json("configs", entry["config"] + ".json"),
        traffic=load_json("traffic", entry["traffic"] + ".json"),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def metric_reader(metric_name: str):
    """The reader module of a per-layer metric: ``metrics/<name>.py``, else
    ``metrics/<name up to its first dot>.py``."""
    for fname in (metric_name, metric_name.split(".")[0]):
        if os.path.exists(os.path.join(HERE, "metrics", fname + ".py")):
            return load_module("metrics", fname + ".py")
    raise FileNotFoundError(f"no reader for metric {metric_name!r}")


def work(kernel: str):
    return load_module("work", kernel + ".py")


def peaks(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# clocks and spans
# ---------------------------------------------------------------------------

class CompileClock:
    """Counts XLA backend compiles and their seconds (jax.monitoring)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.count = 0
        self.seconds = 0.0
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, duration, **_):
            if name == event:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


class Spans:
    """Host spans of the benchmark's own calls into the program.

    Each span is kept in memory as (name, start, end) on ``perf_counter``
    and, while a trace is recorded, also written into the profiler's trace
    (``TraceAnnotation``) so that the device's idle gaps can be named by
    what the host was doing."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.keep = True

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    __slots__ = ("owner", "name", "t0", "ann")

    def __init__(self, owner: Spans, name: str):
        self.owner, self.name = owner, name

    def __enter__(self):
        import jax
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        if self.owner.keep:
            self.owner.records.append((self.name, self.t0, t1))
        return False


def quantile(values, q: float) -> Optional[float]:
    """The q-quantile (0 < q < 1) of observed values, by the same rule as
    ``statistics.quantiles(..., method="inclusive")``; None when empty."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    k = round(q * 100)
    return float(statistics.quantiles(xs, n=100, method="inclusive")[k - 1])


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                      # process start (perf_counter)
    out_dir: str                        # where a trace is written
    compiles: CompileClock
    spans: Spans = dataclasses.field(default_factory=Spans)
    overrides: dict = dataclasses.field(default_factory=dict)
    # tests plant faults in the timed path through this hook
    fault: Optional[str] = None


@dataclasses.dataclass
class Outcome:
    """What a driver hands back."""
    end_to_end: dict[str, float]
    checks: list[Check]
    attempted: int
    failed: int
    counters: dict[str, Any]
    window: tuple[float, float]          # perf_counter bounds of the window
    trace_dir: Optional[str] = None
    memory_peak_bytes: Optional[int] = None
    extra_lines: list[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader gets: the cell, the device and its
    peaks, the reduced trace of the window, the cell runner's counters and the
    host spans (``perf_counter``) with the window's bounds."""
    cell: Cell
    device: dict
    peak: dict
    trace: Any
    counters: dict
    spans: list
    window: tuple[float, float]


def device_info(n_chips: int, require_tpu: bool = True) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX found {dev.platform} devices, no TPU "
                     "(the benchmark has no CPU fallback)")
    if len(devices) < n_chips:
        raise NoChip(f"the cell asks for {n_chips} chips, JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": n_chips}


def memory_peak_bytes(n_chips: int) -> Optional[int]:
    import jax
    peaks_ = []
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


def start_trace(ctx: Context) -> Optional[str]:
    if not ctx.trace:
        return None
    import shutil

    import jax
    d = os.path.join(ctx.out_dir, "trace")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    jax.profiler.start_trace(d)
    return d


def stop_trace(ctx: Context) -> None:
    if ctx.trace:
        import jax
        jax.profiler.stop_trace()


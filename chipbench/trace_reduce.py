"""Reduce a profiler trace of the measured window to device metrics.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData``. Three things are taken from it:

* device operations: the events of each chip's ``XLA Ops`` line (plane
  ``/device:TPU:<i>``), each with its HLO name, its interval and whether it
  is a Mosaic kernel (``tpu_custom_call``). They nest: a ``while`` holds
  the ops of its body, so per-op times are self times. The kernels carry no
  name of their own (no ``pallas_call`` of the program sets ``name=``), so
  the trace cannot tell one kernel from another;
* the benchmark's host spans (``bench.Spans``), which it wrote into the
  trace with ``TraceAnnotation``; the ``window`` span bounds the window on
  the trace's clock;
* nothing else: metric readers get the result as plain data.

Busy time is the union of the device-operation intervals inside the window,
averaged over the chips used; idle gaps are the stretches of the window
with no operation, each named by the innermost host span that covers its
middle (``other`` when none does).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Op:
    name: str      # the HLO instruction's name, e.g. "%fusion.167"
    start: float   # ns, trace clock
    end: float
    custom: bool   # a Mosaic (Pallas) kernel: custom_call_target="tpu_custom_call"
    self_ns: float = 0.0  # duration less the operations nested inside it


def short_name(event_name: str) -> str:
    """``%fusion.1 = bf16[...] fusion(...)`` -> ``%fusion.1``."""
    return event_name.split(" = ", 1)[0]


def with_self_time(ops: list[Op]) -> list[Op]:
    """Device ops nest (a ``while`` holds its body's ops); give each its
    self time so that nothing is counted twice."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    stack: list[Op] = []
    for o in ops:
        o.self_ns = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)
    return ops


@dataclasses.dataclass
class Reduced:
    ops: list[list[Op]]                 # per chip, inside the window
    busy_s: float
    window_s: float
    gaps: list[tuple[str, float]]        # (host span name, seconds), longest first

    def op_seconds(self) -> dict[str, float]:
        """Device self seconds per operation name, averaged over chips."""
        tot: dict[str, float] = collections.defaultdict(float)
        for chip in self.ops:
            for o in chip:
                tot[o.name] += o.self_ns * 1e-9
        n = max(len(self.ops), 1)
        return {k: v / n for k, v in tot.items()}

    def custom_call_seconds(self) -> float:
        """Device self seconds in Mosaic (Pallas) kernels, averaged over chips."""
        n = max(len(self.ops), 1)
        return sum(o.self_ns for chip in self.ops for o in chip if o.custom) * 1e-9 / n

    def breakdown(self) -> dict:
        top = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def name_gap(gap, spans) -> str:
    """The innermost span (shortest) that covers the gap's middle."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for name, s, e in spans:
        if name != "window" and s <= mid <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "other"


def reduce_events(device_events, host_events, n_chips: int) -> Reduced:
    """``device_events``: per chip, a list of (name, start_ns, end_ns);
    ``host_events``: (name, start_ns, end_ns) of the benchmark's spans."""
    windows = [(s, e) for n, s, e in host_events if n == "window"]
    if not windows:
        raise ValueError("no window span in the trace")
    lo, hi = windows[0]
    ops = [with_self_time([Op(short_name(n), s, e, "tpu_custom_call" in n)
                           for n, s, e in chip if e > lo and s < hi])
           for chip in device_events[:n_chips]]
    busy = [union_ns([(o.start, o.end) for o in chip], lo, hi) for chip in ops]
    spans = [(n, s, e) for n, s, e in host_events if e > lo and s < hi]
    gaps = collections.defaultdict(float)
    for chip in ops:
        for g in idle_gaps([(o.start, o.end) for o in chip], lo, hi):
            gaps[name_gap(g, spans)] += (g[1] - g[0]) * 1e-9 / max(len(ops), 1)
    return Reduced(ops=ops, busy_s=sum(busy) * 1e-9 / max(len(busy), 1),
                   window_s=(hi - lo) * 1e-9,
                   gaps=sorted(gaps.items(), key=lambda kv: -kv[1]))


def read_xplane(trace_dir: str, span_names: set[str]):
    """(device events per chip, host span events) from the newest trace."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: dict[int, list] = {}
    host = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, e.start_ns, e.end_ns) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns) for e in line.events
                            if e.name in span_names)
    return [devices[k] for k in sorted(devices)], host


def reduce_window(trace_dir: str, span_records, n_chips: int) -> Reduced:
    names = {r[0] for r in span_records} | {"window"}
    dev, host = read_xplane(trace_dir, names)
    if not dev:
        raise ValueError("no device operations in the trace")
    return reduce_events(dev, host, n_chips)

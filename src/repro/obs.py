"""The program's span recorder: named intervals of host work.

A span is kept twice:

* in a bounded in-memory log, read by ``records()``, as a :class:`Record`
  ``(name, t0, t1, parent, attrs)`` on ``time.perf_counter``. ``parent`` is
  the name of the span open around it on the same thread, None at the top;
* while a ``jax.profiler`` session records, as a ``TraceAnnotation`` on the
  profiler's host plane: an event named ``name`` with ``attrs`` as its
  stats, on the same clock as the device's operations. With no session the
  annotation is not made.

Recording is always on. A span costs two clock reads, a tuple and an
append. The log keeps the newest ``MAX_RECORDS`` records (a serving run
makes about ten a tick) and drops the oldest.

``record`` logs an interval whose ends were taken at different places, as
a request's wait in the queue, which spans several ticks. Such records have
no parent and are not in the profiler's trace.

``SPAN_NAMES`` lists every name the program records: the contract that
readers of the records and of a trace rely on.

Counters ride on spans as attrs. A MoE config's ``engine.sync`` carries
``moe_rows``, the rows the held routed experts received in the tick
(summed over experts and layers; padding and idle slots send none), and
``moe_rows_max``, the most rows any one held expert received (summed over
layers). They come back in the sampled tokens' device→host transfer; other
configs record neither.
"""

from __future__ import annotations

import collections
import threading
from time import perf_counter
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

SPAN_NAMES = (
    "engine.step",      # one ServingEngine tick; kind = prefill | decode | stalled
    "engine.admit",     # deadlines and admission from the queue
    "engine.grow",      # page growth, copy-on-write and preemption
    "engine.dispatch",  # the tick's device inputs and the model call, up to its return
    "engine.sync",      # sampling and the finiteness check: the host waits for the device;
                        # MoE configs: moe_rows, moe_rows_max
    "engine.emit",      # per-slot emission and on_token callbacks
    "engine.publish",   # full pages hashed into the prefix cache
    "request.queue",    # submit or requeue to admission; uid, requeues
    "request.prefill",  # first admission to first emitted token; uid
)
MAX_RECORDS = 1 << 16


class Record(NamedTuple):
    name: str
    t0: float               # time.perf_counter, s
    t1: float
    parent: Optional[str]   # the enclosing span's name
    attrs: dict


# plain (name, t0, t1, parent, attrs) tuples: records() makes them Records
_log: collections.deque[tuple] = collections.deque(maxlen=MAX_RECORDS)
_append = _log.append
_open = threading.local()   # .names: the open spans' names, innermost last
_tracing = TraceAnnotation.is_enabled


class Span:
    """A context manager; after it exits, ``t0``/``t1`` are its bounds."""

    __slots__ = ("name", "attrs", "parent", "t0", "t1", "_ann", "_open")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def set(self, **attrs) -> None:
        """Add attributes known only once the span is open."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def __enter__(self) -> Span:
        try:
            names = _open.names
        except AttributeError:
            names = _open.names = []
        self._open = names
        self.parent = names[-1] if names else None
        names.append(self.name)
        self._ann = None
        if _tracing():
            self._ann = TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = self.t1 = perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._open.pop()
        _append((self.name, self.t0, t1, self.parent, self.attrs))
        return False


def span(name: str, **attrs) -> Span:
    """``with span("engine.sync"): ...`` records the block as ``name``."""
    return Span(name, attrs)


def record(name: str, t0: float, t1: float, **attrs) -> None:
    """Log an interval whose ends were read from ``perf_counter``
    apart, with no parent; it is not written into a profiler trace."""
    _append((name, t0, t1, None, attrs))


def records() -> list[Record]:
    """The logged spans, oldest first (each appended when it closed)."""
    return [Record._make(r) for r in _log]


def reset() -> None:
    """Forget every logged span."""
    _log.clear()

"""Span tracing on the profiler's clock, with an optional ring
exportable as Chrome trace events (DESIGN.md Sec. 12).

Every span enters a `jax.profiler.TraceAnnotation`, so whenever a
profiler session is on it lands on the trace's `/host:CPU` plane, on
the clock of the device's op events, with its keyword arguments as the
event's stats.  `span_or_null(tracer, name, **args)` is the served
path's span: the annotation alone without a `Tracer` (about a
microsecond to enter and leave with no session on), the annotation
plus a ring record with one.

`Tracer.span(name)` is the repo's ONE wall-clock primitive: it times on
the monotonic `time.perf_counter` (never `time.time()`, which steps
under NTP adjustments), works as a plain stopwatch even when event
recording is disabled, and — when enabled — appends a complete event to
a bounded ring.  `to_chrome_trace()` emits the Chrome trace-event JSON
format (`ph: "X"` complete events, microsecond timestamps), which loads
directly in Perfetto (ui.perfetto.dev) or chrome://tracing; the launch
drivers' `--trace-out` writes it.

Single-threaded by design, like the serving loop it instruments: spans
nest via a plain stack, and nesting shows up in Perfetto through
ts/duration containment on one track.
"""

from __future__ import annotations

import os
import time
from collections import deque

from jax.profiler import TraceAnnotation


class Span:
    """One timed region, also a profiler annotation.  Usable as a bare
    stopwatch: `duration_s` after the `with` block, `elapsed_s` inside it
    (both perf_counter-based).

    Implements the with-statement protocol directly rather than via a
    `@contextmanager` generator: this sits on the serving hot path, and
    the generator machinery costs more than the timing itself."""

    __slots__ = ("name", "cat", "args", "depth", "t0", "t1", "_tracer",
                 "_annot")

    def __init__(self, name: str, cat: str, args: dict, tracer=None):
        self.name = name
        self.cat = cat
        self.args = args
        self.depth = 0
        self._tracer = tracer
        self._annot = TraceAnnotation(name, **args)
        self.t0 = time.perf_counter()
        self.t1: float | None = None

    def __enter__(self) -> "Span":
        tr = self._tracer
        if tr is not None:
            self.depth = len(tr._stack)
            tr._stack.append(self.name)
        self._annot.__enter__()
        self.t0 = time.perf_counter()  # re-arm: timing starts at entry
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = time.perf_counter()
        self._annot.__exit__(exc_type, exc, tb)
        tr = self._tracer
        if tr is not None:
            tr._stack.pop()
            if tr.enabled:
                tr._events.append((
                    "X", self.name, self.cat, (self.t0 - tr._t0) * 1e6,
                    (self.t1 - self.t0) * 1e6, self.depth, self.args,
                ))
        return False

    @property
    def elapsed_s(self) -> float:
        """Seconds since the span opened (live reads mid-span)."""
        return time.perf_counter() - self.t0

    @property
    def duration_s(self) -> float:
        return (self.t1 if self.t1 is not None
                else time.perf_counter()) - self.t0

    @property
    def duration_us(self) -> float:
        return self.duration_s * 1e6


class Tracer:
    """Bounded ring of spans; see the module docstring."""

    def __init__(self, enabled: bool = True, capacity: int = 65536):
        self.enabled = enabled
        self._events: deque = deque(maxlen=capacity)
        self._stack: list[str] = []
        self._t0 = time.perf_counter()  # trace epoch (ts are relative)

    @property
    def depth(self) -> int:
        """Current span-nesting depth (0 outside any span)."""
        return len(self._stack)

    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def span(self, name: str, cat: str = "serve", **args) -> Span:
        """A `with`-able Span; recorded into the ring on exit."""
        return Span(name, cat, args, tracer=self)

    def events(self) -> list:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()

    def to_chrome_trace(self) -> dict:
        pid = os.getpid()
        out = []
        for ph, name, cat, ts, dur, depth, args in self._events:
            out.append(dict(name=name, cat=cat, ph=ph, ts=ts, dur=dur,
                            pid=pid, tid=0, args=dict(args, depth=depth)))
        return {"traceEvents": out, "displayTimeUnit": "ms"}


def span_or_null(tracer: Tracer | None, name: str, **args):
    """The served path's span: `tracer.span(...)` when a tracer is
    present, else the bare profiler annotation — so the span is in any
    profiler trace whether or not observability is installed."""
    if tracer is None:
        return TraceAnnotation(name, **args)
    return tracer.span(name, **args)

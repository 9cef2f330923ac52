"""From a profiler trace to the numbers the per-layer metrics read.

`record` runs a callable under `jax.profiler` (host spans on, Python
tracer off) and `load` turns the `.xplane.pb` it wrote into plain
tuples.  What a TPU trace holds, as read by hand from one:

  * one plane per chip, `/device:TPU:<i>`, with the lines
    `XLA Modules` (one event per program run, named
    `jit_<fn>(<fingerprint>)`), `XLA Ops` (one event per HLO op, named by
    the op's HLO text, `%<op> = <type> <opcode>(...)`) and
    `Async XLA Ops` (copy-start/done pairs, which overlap the others);
  * the Pallas kernel as the op `%fused_query.<n> = ... custom-call(...)`;
  * host planes `/host:CPU`, one line per thread, which carry the
    benchmark's `TraceAnnotation` spans by name.

Times are nanoseconds on one clock for host and device events.  The
reduction below is pure: each function takes event tuples and returns
numbers, so it is tested on hand-built events.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
import tempfile

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_NAME = re.compile(r"^%([\w.\-]+) = ")
_OPCODE = re.compile(r"[\]})] ([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")
WINDOW = "bench/window"   # the span around the whole measured loop


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float   # ns
    dur: float     # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]       # chip -> XLA ops
    modules: dict[int, list[Event]]   # chip -> program runs
    spans: list[Event]                # the benchmark's host spans


def op_name(text: str) -> str:
    """`%fused_query.1 = (...) custom-call(...)` -> `fused_query.1`."""
    m = _NAME.match(text)
    return m.group(1) if m else text.split(" ", 1)[0].lstrip("%")


def op_base(text: str) -> str:
    """The op's name without its numeric suffix: `fused_query`."""
    return re.sub(r"(\.\d+)+$", "", op_name(text))


def op_label(text: str) -> str:
    """A short stable label: `copy.32 f32[4,16384,128,100] copy`."""
    m = _NAME.match(text)
    code = _OPCODE.search(text, m.end()) if m else None
    if not code:
        return text[:100]
    kind = _LAYOUT.sub("", text[m.end():code.start() + 1])
    return f"{m.group(1)} {kind} {code.group(1)}"[:100]


def clip(events, lo: float, hi: float) -> list[Event]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start):
        if cur_e is None or e.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start, e.end
        else:
            cur_e = max(cur_e, e.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals of [lo, hi] not covered by any event."""
    out, t = [], lo
    for e in sorted(clip(events, lo, hi), key=lambda e: e.start):
        if e.start > t:
            out.append((t, e.start))
        t = max(t, e.end)
    if hi > t:
        out.append((t, hi))
    return out


def busy_s(tr: Trace, lo: float, hi: float) -> float:
    """Seconds in which an op ran, averaged over the chips."""
    chips = sorted(tr.ops)
    if not chips:
        return 0.0
    return sum(union_ns(clip(tr.ops[c], lo, hi)) for c in chips) / len(
        chips) / 1e9


def kernel_ns(tr: Trace, base: str, lo: float, hi: float) -> float:
    """Summed device time of the ops named `base` over every chip."""
    return sum(e.dur for c in tr.ops for e in clip(tr.ops[c], lo, hi)
               if op_base(e.name) == base)


def module_runs(tr: Trace, fn: str, lo: float, hi: float) -> list[Event]:
    """Runs, on every chip, of the program jitted from `fn`, that start
    inside the window."""
    want = f"jit_{fn}("
    return [e for c in tr.modules for e in tr.modules[c]
            if e.name.startswith(want) and lo <= e.start < hi]


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10):
    """[[label, seconds per chip]] of the ops that took most time."""
    acc: dict[str, float] = {}
    for c in tr.ops:
        for e in clip(tr.ops[c], lo, hi):
            key = op_label(e.name)
            acc[key] = acc.get(key, 0.0) + e.dur
    chips = max(len(tr.ops), 1)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / chips / 1e9] for k, v in top]


def idle_by_span(tr: Trace, lo: float, hi: float, n: int = 10):
    """[[host span, idle seconds per chip]]: each idle gap of a chip is
    split over the benchmark's host spans that overlap it (innermost
    wins where they nest); time no span covers reads `host:other`."""
    acc: dict[str, float] = {}
    spans = sorted((s for s in tr.spans if s.name != WINDOW),
                   key=lambda e: e.start)
    starts = [s.start for s in spans]
    longest = max((s.dur for s in spans), default=0.0)
    for c in tr.ops:
        for g0, g1 in gaps(tr.ops[c], lo, hi):
            covered = []
            for s in spans[bisect.bisect_left(starts, g0 - longest):
                           bisect.bisect_left(starts, g1)]:
                s0, s1 = max(s.start, g0), min(s.end, g1)
                if s1 > s0:
                    covered.append(Event(s.name, s0, s1 - s0))
            # innermost: shortest spans claim their time first
            claimed: list[Event] = []
            for s in sorted(covered, key=lambda e: e.dur):
                rest = [(s.start, s.end)]
                for k in claimed:
                    rest = [piece for a, b in rest for piece in
                            ((a, min(b, k.start)), (max(a, k.end), b))
                            if piece[1] > piece[0]]
                got = sum(b - a for a, b in rest)
                if got > 0:
                    acc[s.name] = acc.get(s.name, 0.0) + got
                claimed.append(s)
            other = (g1 - g0) - union_ns(covered)
            if other > 0:
                acc["host:other"] = acc.get("host:other", 0.0) + other
    chips = max(len(tr.ops), 1)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / chips / 1e9] for k, v in top]


def window_of(tr: Trace, name: str = WINDOW) -> tuple[float, float]:
    for s in tr.spans:
        if s.name == name:
            return s.start, s.end
    raise ValueError(f"no {name} span in the trace")


# -- recording and loading ----------------------------------------------------


def record(fn, workdir: str | None = None):
    """Run fn() under the profiler; returns (fn's result, Trace)."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-", dir=workdir)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return out, load(files[0])
    finally:
        shutil.rmtree(d, ignore_errors=True)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] = [Event(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[chip] = [Event(e.name, e.start_ns, e.duration_ns)
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith("bench/"))
    return Trace(ops, modules, spans)

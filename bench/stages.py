#!/usr/bin/env python3
"""Where the serving thread's time goes, dispatch by dispatch.

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s>

Runs the cell exactly as `bench/run.py ... --trace 1` does and prints
run.py's lines, then one JSON line more, read from the same profiler
trace and window: the frontend's own stage spans (`serve/*` in
`serve/frontend.py`, each tagged with its dispatch as `batch`), which
run.py's trace loader keeps out of its `Trace`, and the frontend's
`ServeStats.queued_us` over the loop.  Per dispatch of the window:

  * `host_ms`: the serving thread's time inside `serve/*` spans other
    than `serve/compute` (the union of their intervals, so nested spans
    count once), and `host_ms_by_span`, its split by span (self times);
  * `device_wait_ms`: time blocked on the device (`serve/compute`);
  * `inflight_ms`: stage to respond of a batch, weighted by live rows;
  * `queue_wait_ms`: a live row's ring wait, submit to its batch's stage
    (`queued_us / dispatched`, over the loop);
  * `stall`: the longest self time (duration less its child spans) of
    any one `serve/*` span other than `serve/compute`;
  * `idle_gaps`: the device's idle time split over the benchmark's and
    the frontend's spans, innermost first (run.py's `breakdown.idle_gaps`
    with the frontend's spans added), and `long_gaps`, the same for each
    gap of at least 5 ms.

The reductions are pure functions of events and are tested on
hand-built ones (`bench/tests/test_stages.py`).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import tracing

COMPUTE = "serve/compute"
STAGE = "serve/stage"
LONG_GAP_NS = 5e6


@dataclasses.dataclass(frozen=True)
class Span(tracing.Event):
    """A host span of the program with its stats (`batch`, `rows`, ...)."""

    args: dict = dataclasses.field(default_factory=dict, compare=False)


def program_spans(path: str) -> list[Span]:
    """The `serve/*` host events of a profiler trace file, by start."""
    from jax.profiler import ProfileData

    out = [Span(e.name, e.start_ns, e.duration_ns, dict(e.stats))
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("serve/")]
    return sorted(out, key=lambda s: s.start)


def starting_in(spans, lo: float, hi: float) -> list[Span]:
    return [s for s in spans if lo <= s.start < hi]


def batches(spans) -> set:
    return {s.args["batch"] for s in spans if s.name == STAGE}


def host_ms(spans):
    n = len(batches(spans))
    if not n:
        return None
    return tracing.union_ns(
        [s for s in spans if s.name != COMPUTE]) / n / 1e6


def device_wait_ms(spans):
    n = len(batches(spans))
    if not n:
        return None
    return sum(s.dur for s in spans if s.name == COMPUTE) / n / 1e6


def inflight_ms(spans):
    """Stage start to respond end of each batch, weighted by its rows."""
    stage = {s.args["batch"]: s for s in spans if s.name == STAGE}
    done = {s.args["batch"]: s.end for s in spans
            if s.name == "serve/respond"}
    both = [(stage[b].args["rows"], done[b] - stage[b].start)
            for b in stage if b in done]
    rows = sum(r for r, _ in both)
    if not rows:
        return None
    return sum(r * t for r, t in both) / rows / 1e6


def queue_wait_ms(queued_us, dispatched: int):
    if queued_us is None or dispatched <= 0:
        return None
    return queued_us / dispatched / 1e3


def self_ns(spans) -> list[tuple[Span, float]]:
    """(span, its duration less its direct children's) for spans that
    nest properly, as the spans of one thread do."""
    order = sorted(spans, key=lambda s: (s.start, -s.dur))
    child = [0.0] * len(order)
    stack: list[int] = []
    for i, s in enumerate(order):
        while stack and order[stack[-1]].end <= s.start:
            stack.pop()
        if stack:
            child[stack[-1]] += s.dur
        stack.append(i)
    return [(s, s.dur - c) for s, c in zip(order, child)]


def host_ms_by_span(spans) -> dict:
    """{span: self time per dispatch, ms} of the spans other than
    `serve/compute`, largest first; their sum is `host_ms`."""
    n = len(batches(spans))
    acc: dict[str, float] = {}
    for s, t in self_ns(spans):
        if s.name != COMPUTE:
            acc[s.name] = acc.get(s.name, 0.0) + t
    return {k: v / n / 1e6 for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])} if n else {}


def stall(spans):
    """{name, batch, ms, at_ns} of the longest self time of any span
    other than `serve/compute`."""
    best = max(((s, t) for s, t in self_ns(spans) if s.name != COMPUTE),
               key=lambda st: st[1], default=None)
    if best is None:
        return None
    s, t = best
    return {"name": s.name, "batch": s.args.get("batch"), "ms": t / 1e6,
            "at_ns": s.start}


def idle_by_stage(tr: tracing.Trace, program, lo: float, hi: float):
    """run.py's idle-gap split with the frontend's spans beside the
    benchmark's."""
    return tracing.idle_by_span(
        tracing.Trace(tr.ops, tr.modules, tr.spans + list(program)), lo, hi)


def long_gaps(tr: tracing.Trace, program, lo: float, hi: float,
              min_ns: float = LONG_GAP_NS):
    """[[seconds into the window, gap ms, [[span, idle s], ...]], ...]
    for each idle gap of a chip of at least `min_ns`."""
    spans = tr.spans + list(program)
    out = []
    for c in sorted(tr.ops):
        one = tracing.Trace({c: tr.ops[c]}, {}, spans)
        for g0, g1 in tracing.gaps(tr.ops[c], lo, hi):
            if g1 - g0 >= min_ns:
                out.append([(g0 - lo) / 1e9, (g1 - g0) / 1e6,
                            tracing.idle_by_span(one, g0, g1)])
    return out


def reading(tr: tracing.Trace, program, lo: float, seconds: float,
            queued_us, dispatched: int) -> dict:
    hi = lo + seconds * 1e9
    win = starting_in(program, lo, hi)
    return {
        "dispatches": len(batches(win)),
        "host_ms": host_ms(win),
        "host_ms_by_span": host_ms_by_span(win),
        "device_wait_ms": device_wait_ms(win),
        "inflight_ms": inflight_ms(win),
        "queue_wait_ms": queue_wait_ms(queued_us, dispatched),
        "stall": stall(win),
        "idle_gaps": idle_by_stage(tr, program, lo, hi),
        "long_gaps": long_gaps(tr, program, lo, hi),
    }


def main(argv=None) -> int:
    """run.py's traced run, with its frontend, trace file and window
    intercepted where run.py makes them (run.py itself is the
    benchmark's and is left as it is)."""
    import deploy
    import run

    seen: dict = {}
    make_frontend, record, load = deploy.frontend, tracing.record, tracing.load

    def frontend(*args, **kw):
        seen["fe"] = make_frontend(*args, **kw)
        return seen["fe"]

    def load_both(path):
        seen["program"] = program_spans(path)
        return load(path)

    def record_counted(fn, workdir=None):
        stats = seen["fe"].stats
        q0, d0 = getattr(stats, "queued_us", None), stats.dispatched
        out = record(fn, workdir)
        seen["trace"] = out[1]
        seen["dispatched"] = stats.dispatched - d0
        seen["queued_us"] = None if q0 is None else stats.queued_us - q0
        return out

    deploy.frontend, tracing.record, tracing.load = (
        frontend, record_counted, load_both)
    try:
        argv = list(sys.argv[1:] if argv is None else argv)
        rc = run.main(argv + ["--trace", "1"])
    finally:
        deploy.frontend, tracing.record, tracing.load = (
            make_frontend, record, load)
    if rc != 0 or "trace" not in seen:
        return rc
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, required=True)
    seconds = ap.parse_known_args(argv)[0].seconds
    tr = seen["trace"]
    lo, _ = tracing.window_of(tr)
    print(json.dumps({"stages": reading(
        tr, seen["program"], lo, seconds, seen["queued_us"],
        seen["dispatched"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

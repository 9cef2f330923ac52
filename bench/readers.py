"""What the per-layer metric files read, and the helpers they share.

Each file `bench/metrics/<metric>.py` defines `read(r: Reading)` and
returns a number, or None where its run has nothing to read (the harness
then leaves the metric out of the result line).  A reading is taken over
the traced loop: `lo`..`hi` spans the whole loop (every dispatch it made,
the drain included), `lo`..`lo + window_s` the measured window.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import pathlib

import tracing
import workcount

BENCH = pathlib.Path(__file__).resolve().parent
STEP_FN = "_impl"   # the frontend backend's jitted search step


@dataclasses.dataclass
class Reading:
    trace: tracing.Trace
    lo: float            # ns, loop start
    hi: float            # ns, loop end (drain included)
    window_s: float
    dep: object          # deploy.Deployment
    peak: dict           # the chip's peaks (bench/peaks.json)
    dispatched: int      # live rows the frontend dispatched in the loop
    padded: int          # padding rows it added
    lag_ms: float        # how late the load generator sent, at worst


def load(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def idle_pct(r: Reading):
    if not r.trace.ops:
        return None
    busy = tracing.busy_s(r.trace, r.lo, r.lo + r.window_s * 1e9)
    return 100.0 * (1.0 - busy / r.window_s)


def fused_query_roofline(r: Reading):
    """Share of its roofline the fused query kernel ran at, over every
    dispatch of the loop: the rows are the live (query, table) pairs, the
    probes the exact bucket and its k near buckets."""
    ns = tracing.kernel_ns(r.trace, "fused_query", r.lo, r.hi)
    if ns <= 0 or r.dispatched <= 0:
        return None
    d = r.dep
    work = workcount.fused_query(rows=r.dispatched * d.L, probes=1 + d.k,
                                 capacity=d.capacity, d=d.d, m=d.m)
    return workcount.roofline_pct(work, ns / 1e9, r.peak)


def step_device_ms(r: Reading):
    """Device time of one run of the search step, averaged over runs."""
    runs = tracing.module_runs(r.trace, STEP_FN, r.lo, r.hi)
    if not runs:
        return None
    return sum(e.dur for e in runs) / len(runs) / 1e6


def batch_fill_pct(r: Reading):
    total = r.dispatched + r.padded
    if total <= 0:
        return None
    return 100.0 * r.dispatched / total


def lag_ms(r: Reading):
    """The longest the one serving thread kept an arrival from being
    submitted: a blocking reap costs up to one step; more is a stall."""
    return r.lag_ms

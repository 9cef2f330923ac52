#!/usr/bin/env python3
"""The chip benchmark of the served LSH index: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the repository root.  The cell (an entry of BENCHMARK.json's
`workloads`) names a configuration (`bench/configs/<config>.json`), a
traffic mix (`bench/traffic/<mix>.json`) and its own rate or client count
(`bench/workloads/<cell>.json`).  The run builds the deployment on the
chip from the seed, warms the dispatch shapes its traffic uses (set-up),
then drives the served path — `RetrievalFrontend.submit` -> `pump` ->
`take_results` over `RuntimeBackend` — for `--seconds`.  Afterwards it
checks a sample of the answers, drawn from the seed, against the plain
reference (`bench/reference.py`).

With `--trace 0` the result line carries the cell's end-to-end metrics,
with `--trace 1` its per-layer metrics, read from a profiler trace of
the same window by the readers under `bench/metrics/`.  The last line of
standard output is the JSON result; the last lines of standard error
give each compared number beside its limit.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import deploy  # noqa: E402
import loops  # noqa: E402
import readers  # noqa: E402
import reference  # noqa: E402
import tracing  # noqa: E402
import traffic  # noqa: E402
import workcount  # noqa: E402

SAMPLE = 4096   # answers checked against the reference per run


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_spec(name: str) -> tuple[dict, dict]:
    bench = spec()
    for w in bench["workloads"]:
        if w["name"] == name:
            return bench, w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def metrics_for(spec: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in spec[kind]
            if cell in m.get("workloads", [cell])]


def finite(v: float) -> float:
    """JSON has no infinity: an infinite reading (a latency percentile
    that falls on a shed query, an unreachable answer) prints as the
    largest float."""
    return min(v, sys.float_info.max)


def compile_counter() -> collections.Counter:
    """Counts backend compiles (persistent-cache hits included)."""
    from jax import monitoring

    seen = collections.Counter()

    def on_duration(event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell = cell_spec(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU found (platform {devs[0].platform}); the "
              "benchmark runs only on the chip", file=sys.stderr)
        return 3
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, found "
              f"{len(devs)}", file=sys.stderr)
        return 3
    kind = devs[0].device_kind
    peak = workcount.peaks(kind)
    return run(args, spec, cell, devs[:cell["chips"]], peak)


def run(args, spec: dict, cell: dict, devs, peak: dict) -> int:
    import jax

    deploy.enable_compile_cache()
    compiles = compile_counter()

    config = deploy.load_config(cell["config"])
    dep = deploy.deployment(config)
    mix = traffic.load(cell["traffic"], cell["name"])
    seed = args.seed

    vecs, centres = deploy.make_corpus(dep, seed)
    planes = deploy.make_planes(dep, seed)
    rt = deploy.runtime(dep)
    store = deploy.build_index(dep, rt, planes, vecs)
    fe = deploy.frontend(dep, rt, planes, store)
    sizes = ([dep.max_batch] if mix.loop == "closed"
             else [1 << i for i in range(dep.max_batch.bit_length())])
    shapes = deploy.dispatch_shapes(dep, rt, sizes)
    deploy.warm(fe, dep, shapes, deploy.make_queries(
        dep, centres, seed, 2 * sum(shapes), stream="warm-up"))
    queries = mix.draw(dep, centres, seed, mix.query_count(args.seconds))
    arrivals = (mix.arrival_times(args.seconds, seed)
                if mix.loop == "open" else None)
    jax.block_until_ready(store)
    gc.collect()   # set-up's garbage is collected in set-up, not mid-window
    setup_s = time.perf_counter() - T_START

    def window():
        with loops.span(bool(args.trace), tracing.WINDOW):
            if mix.loop == "open":
                return loops.open_loop(fe, queries, arrivals, args.seconds,
                                       dep.m, trace=bool(args.trace))
            return loops.closed_loop(fe, queries, mix.clients, args.seconds,
                                     dep.m, trace=bool(args.trace))

    before = dict(dispatched=fe.stats.dispatched, padded=fe.stats.padded)
    compiled_before = compiles["compiles"]
    if args.trace:
        served, tr = tracing.record(window)
    else:
        served, tr = window(), None
    compiled_in_window = compiles["compiles"] - compiled_before
    counts = {k: getattr(fe.stats, k) - v for k, v in before.items()}
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs)

    # -- free the program's state, then check against the reference -------
    vecs_host = np.asarray(vecs[:dep.n])
    planes_host = np.asarray(planes)
    del fe, store, rt, vecs, centres, planes
    answered = np.flatnonzero(~served.shed & ~np.isnan(served.done))
    rng = np.random.default_rng([seed % 2**64, 2])
    pick = np.sort(rng.choice(answered, min(SAMPLE, answered.size),
                              replace=False))
    ref = reference.ReferenceIndex(
        vecs_host, planes_host, capacity=dep.capacity,
        hash_precision="bf16" if devs[0].platform == "tpu" else "f32")
    gaps = reference.compare(ref, queries[pick], served.ids[pick],
                             served.scores[pick], dep.m)
    lim = config["correct"]
    checks = {
        "unanswered": (loops.unanswered(served), 0),
        "answer_gap": (gaps["answer_gap"], lim["answer_gap"]),
    }
    correct = bool(pick.size > 0 and all(v <= lim_
                                         for v, lim_ in checks.values()))

    lat = loops.latencies_ms(served)
    metrics = {}
    if not args.trace:
        values = {
            "setup_s": lambda: setup_s,
            "p50_ms": lambda: loops.percentile_ms(lat, 50),
            "p99_ms": lambda: loops.percentile_ms(lat, 99),
            "qps": lambda: loops.completed_qps(served),
        }
        for m in metrics_for(spec, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": finite(values[m["name"]]()),
                                  "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    breakdown = None
    if args.trace:
        lo, hi = tracing.window_of(tr)
        reading = readers.Reading(
            trace=tr, lo=lo, hi=hi, window_s=args.seconds, dep=dep,
            peak=peak, lag_ms=served.lag_s * 1e3, **counts)
        for m in metrics_for(spec, cell["name"], "per_layer"):
            v = readers.load(m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        w_hi = lo + args.seconds * 1e9
        device["busy_s"] = tracing.busy_s(tr, lo, w_hi)
        device["window_s"] = float(args.seconds)
        breakdown = {"device_ops": tracing.top_ops(tr, lo, w_hi),
                     "idle_gaps": tracing.idle_by_span(tr, lo, w_hi)}

    result = {"correct": correct,
              "attempted": int(np.sum(served.due < served.window_s)),
              "failed": loops.failed(served),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": finite(v), "limit": lim_}
                        for k, (v, lim_) in checks.items()}
    late = np.argsort(served.due - served.sent_at)[:3]
    print(f"bench: generator lag worst at due times (s) "
          f"{served.due[late].round(3).tolist()}: "
          f"{((served.sent_at - served.due)[late] * 1e3).round(3).tolist()}"
          f" ms; full collections in window (start s, s) "
          f"{[(round(a, 3), round(b, 4)) for a, b in served.gc_pauses]}",
          file=sys.stderr)
    print(f"bench: latency from schedule p50 "
          f"{loops.percentile_ms(lat, 50)!r} ms, p99 "
          f"{loops.percentile_ms(lat, 99)!r} ms, failed "
          f"{loops.failed(served)}", file=sys.stderr)
    print(f"bench: sent {served.sent}, checked {pick.size} answers, "
          f"generator lag {served.lag_s * 1e3:.3f} ms, compiles in window "
          f"{compiled_in_window}, dispatches {counts}, rank_gap "
          f"{gaps['rank_gap']!r}, score_gap {gaps['score_gap']!r}",
          file=sys.stderr)
    for k, (v, lim_) in checks.items():
        print(f"check {k} {v!r} limit {lim_!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

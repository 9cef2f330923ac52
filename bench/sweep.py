#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one process, one rate ladder.

    python3 bench/sweep.py --workload <open cell> --seed <n> --seconds <s> \
        --rates 1000,1500,2000

Builds the cell's deployment once, warms every dispatch shape, then
offers each rate for `--seconds` with the cell's own arrival process and
query draw, and prints one line per rate: p50 and p99 from schedule (a
shed query is infinitely late), the queries shed or left unanswered, how
late the generator ran, and the backlog trend — the median latency of
the last quarter of arrivals over that of the second quarter (a backlog
that grows through the run reads well above 1).  The knee is the highest rate with nothing shed and no growing backlog; it is
written into the cell's file by hand, as a number, once.  The last line
is the curve as JSON.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import deploy  # noqa: E402
import loops  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

GROWTH = 2.0   # last-quarter over second-quarter median latency


def trend(s: loops.Served) -> float:
    n = s.due.size
    lat = (s.done - s.due) * 1e3
    q2 = lat[n // 4:n // 2]
    q4 = lat[3 * n // 4:]
    q2, q4 = q2[~np.isnan(q2)], q4[~np.isnan(q4)]
    if q2.size == 0 or q4.size == 0:
        return float("inf")
    return float(np.median(q4) / np.median(q2))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    _, cell = run.cell_spec(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print("sweep: needs a TPU with the cell's chips", file=sys.stderr)
        return 3
    deploy.enable_compile_cache()
    dep = deploy.deployment(deploy.load_config(cell["config"]))
    mix = traffic.load(cell["traffic"], cell["name"])
    if mix.loop != "open":
        raise SystemExit("sweep: the knee is for open-loop cells")
    vecs, centres = deploy.make_corpus(dep, args.seed)
    planes = deploy.make_planes(dep, args.seed)
    rt = deploy.runtime(dep)
    store = deploy.build_index(dep, rt, planes, vecs)
    fe = deploy.frontend(dep, rt, planes, store)
    shapes = deploy.dispatch_shapes(
        dep, rt, [1 << i for i in range(dep.max_batch.bit_length())])
    deploy.warm(fe, dep, shapes, deploy.make_queries(
        dep, centres, args.seed, 2 * sum(shapes), stream="warm-up"))

    curve = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t = dataclasses.replace(mix, rate_qps=rate)
        arrivals = t.arrival_times(args.seconds, args.seed + i)
        queries = t.draw(dep, centres, args.seed + i, arrivals.size)
        s = loops.open_loop(fe, queries, arrivals, args.seconds, dep.m)
        lat = loops.latencies_ms(s)
        row = dict(rate_qps=rate, p50_ms=loops.percentile_ms(lat, 50),
                   p99_ms=loops.percentile_ms(lat, 99),
                   failed=loops.failed(s), lag_ms=s.lag_s * 1e3,
                   trend=trend(s))
        curve.append(row)
        print(" ".join(f"{k}={v:.6g}" if isinstance(v, float) else
                       f"{k}={v}" for k, v in row.items()), flush=True)
    ok = [r["rate_qps"] for r in curve
          if r["failed"] == 0 and r["trend"] < GROWTH]
    print(json.dumps({"knee_qps": max(ok) if ok else None, "curve": curve}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  `--full` switches in the larger
LiveJournal/Friendster-scale synthetic datasets (slower); default exercises
every benchmark at CPU-friendly scale.  A suite that raises is reported
on stderr and makes the run exit non-zero once the other suites are done.  `--json PATH` additionally writes
the same rows as machine-readable JSON (a list of
``{"name", "us_per_call", "derived", "suite"}`` objects, e.g.
``BENCH_serve.json``), so perf trajectories can be tracked across commits.
Rows whose benchmark published ``bench_dropped_probes`` /
``bench_nodes_contacted`` gauges into the obs metrics registry
(bench_serve does) additionally carry those as JSON columns.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="use all three OSN-scale datasets")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark prefixes to run")
    ap.add_argument("--json", default="",
                    help="also write rows as JSON to this path")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes / fewer reps where supported "
                         "(kernels, roofline)")
    args = ap.parse_args(argv)

    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import (
        bench_churn, bench_distributed, bench_kernels, bench_serve,
        fig1_sp_vs_buckets, fig2_sp_vs_L, fig3_sp_vs_cost, fig4_sp_empirical,
        fig5_quality, table1_costs,
    )
    from benchmarks import roofline
    from repro.obs.registry import REGISTRY

    suites = [
        ("fig1", lambda: fig1_sp_vs_buckets.rows()),
        ("fig2", lambda: fig2_sp_vs_L.rows()),
        ("fig3", lambda: fig3_sp_vs_cost.rows()),
        ("table1", lambda: table1_costs.rows()),
        ("fig4", lambda: fig4_sp_empirical.rows(full=args.full)),
        ("fig5", lambda: fig5_quality.rows(full=args.full)),
        ("churn", lambda: bench_churn.rows()),
        ("kernels", lambda: bench_kernels.rows(smoke=args.smoke)),
        ("dist", lambda: bench_distributed.rows()),
        ("serve", lambda: bench_serve.rows()),
        ("roofline", lambda: roofline.rows(smoke=args.smoke)),
    ]
    wanted = [w for w in args.only.split(",") if w]
    collected: list[dict] = []
    failed: list[str] = []
    print("name,us_per_call,derived")
    for name, fn in suites:
        if wanted and not any(name.startswith(w) for w in wanted):
            continue
        t0 = time.time()
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.2f},{derived}")
                row = dict(
                    name=row_name, us_per_call=round(float(us), 2),
                    derived=str(derived), suite=name,
                )
                dp = REGISTRY.value("bench_dropped_probes", row=row_name)
                if dp is not None:
                    row["dropped_probes"] = int(dp)
                nc = REGISTRY.value("bench_nodes_contacted", row=row_name)
                if nc is not None:
                    row["nodes_contacted"] = round(float(nc), 2)
                collected.append(row)
            print(f"# suite {name} done in {time.time()-t0:.1f}s",
                  file=sys.stderr)
        except Exception:  # run the other suites, then fail the run
            failed.append(name)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(collected, f, indent=1)
        print(f"# wrote {len(collected)} rows to {args.json}",
              file=sys.stderr)
    sys.stdout.flush()
    if failed:
        sys.exit(f"benchmark suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()

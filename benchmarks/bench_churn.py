"""Churn/soft-state benchmark (paper Sec. 4.1 dynamics, beyond-paper
quantification): CNB recall vs refresh period under profile updates and
node churn — single-host, plus the same trajectory on a 2-shard mesh
(recall + estimated wire bytes/epoch vs refresh period).

The single-host cells run in this process, on the default backend.  The
mesh cells (dist, nodes, failure) need several devices of one kind, which
a one-chip host has only as forced host (CPU) devices: each runs in a
child pinned to `JAX_PLATFORMS=cpu` with XLA_FLAGS set before its first
jax import (the host device count is fixed at backend init).  Those
children never open the accelerator, so they cannot contend with this
process for it.  Their columns are recall and modelled wire bytes; their
`us` is CPU time.  A failed cell raises."""

import dataclasses
import json
import os
import subprocess
import sys
import time

from repro.core.churn import ChurnConfig, run_churn

N_SHARDS = 2

_DIST_SCRIPT = r"""
import json, sys, time
import dataclasses
import numpy as np
from repro.core.churn import ChurnConfig, run_churn_distributed
from repro.core import distributed as dist
from repro.core.hashing import LshParams

base = ChurnConfig(**json.loads(sys.argv[1]))
n_shards = int(sys.argv[2])
out = []
for period in (1, 2, 4, 8):
    cfg = dataclasses.replace(base, refresh_every=period)
    t0 = time.time()
    r = run_churn_distributed(cfg, n_shards=n_shards)
    us = (time.time() - t0) / cfg.epochs * 1e6
    params = LshParams(d=cfg.dim, k=cfg.k, L=cfg.L, seed=cfg.seed + 1)
    dcfg = dist.DistConfig(params=params, n_shards=n_shards, variant="cnb",
                           m=cfg.m + 1, cap_factor=float(n_shards))
    qbytes = dist.estimate_query_bytes(
        dcfg, batch=cfg.num_queries, d=cfg.dim, n_total=n_shards)["total"]
    rbytes = dist.estimate_refresh_bytes(dcfg, cfg.capacity, cfg.dim)
    bytes_per_epoch = qbytes + rbytes / period  # refresh amortized
    out.append(dict(period=period, us=us,
                    mean_recall=r["mean_recall"],
                    final_recall=r["final_recall"],
                    dropped=int(r["dropped_probes"].sum()),
                    max_stale=int(r["cache_staleness"].max()),
                    bytes_per_epoch=bytes_per_epoch))
print("RESULT " + json.dumps(out))
"""


_NODE_SCRIPT = r"""
import json, sys, time
import numpy as np
from repro.core.churn import ChurnConfig, NodeChurnConfig, run_node_churn

base = ChurnConfig(**json.loads(sys.argv[1]))
out = []
for name, sched in (
    ("static", (1,)),
    ("join2", (1, 2)),
    ("sawtooth", (1, 2, 4, 2, 1, 2, 1)),
):
    t0 = time.time()
    r = run_node_churn(NodeChurnConfig(churn=base, schedule=sched))
    us = (time.time() - t0) / base.epochs * 1e6
    out.append(dict(
        name=name, us=us,
        mean_recall=r["mean_recall"],
        rounds=len(r["reshard_events"]),
        handoff=int(r["total_handoff_bytes"]),
        refresh=int(r["total_refresh_bytes"]),
        dropped=int(r["dropped_probes"].sum())))
print("RESULT " + json.dumps(out))
"""

_FAILURE_SCRIPT = r"""
import json, sys, time
import numpy as np
from repro.core.churn import (
    ChurnConfig, FailureChurnConfig, run_failure_churn,
)

base = ChurnConfig(**json.loads(sys.argv[1]))
out = []
for read_mode in ("first", "quorum"):
    t0 = time.time()
    r = run_failure_churn(FailureChurnConfig(
        churn=base, n_nodes=4, replication=2, read_mode=read_mode,
        kills=((base.epochs // 2, 1),),
    ))
    us = (time.time() - t0) / base.epochs * 1e6
    out.append(dict(
        name=read_mode, us=us,
        mean_recall=float(np.mean(r["recalls"])),
        degraded_gap=r["degraded_gap"],
        recovered_gap=r["recovered_gap"],
        recovery_epochs=r["recovery_epochs"],
        replication=int(r["total_replication_bytes"]),
        recovery=int(r["total_recovery_bytes"]),
        dropped=int(r["dropped_probes"].sum())))
print("RESULT " + json.dumps(out))
"""

N_NODES_MAX = 4


def _subprocess_rows(script: str, base: ChurnConfig, devices: int,
                     extra_args=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", script,
         json.dumps(dataclasses.asdict(base)), *extra_args],
        capture_output=True, text=True, timeout=1200, env=env,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"churn subprocess failed:\n{proc.stderr}")
    payload = next(ln for ln in proc.stdout.splitlines()
                   if ln.startswith("RESULT "))
    return json.loads(payload[len("RESULT "):])


def _dist_rows(base: ChurnConfig):
    out = []
    for r in _subprocess_rows(_DIST_SCRIPT, base, N_SHARDS,
                              (str(N_SHARDS),)):
        out.append((
            f"churn/dist{N_SHARDS}shard/refresh_every={r['period']}",
            r["us"],
            f"mean_recall={r['mean_recall']:.3f};"
            f"final_recall={r['final_recall']:.3f};"
            f"bytes_per_epoch={r['bytes_per_epoch']:.3e};"
            f"dropped={r['dropped']};max_cache_stale={r['max_stale']}"))
    return out


def _node_rows(base: ChurnConfig):
    """Elastic-membership cell: recall + handoff/refresh bytes as node
    join/leave rounds interleave with content churn (vs the static
    schedule on the same trajectory — the recall columns should match)."""
    out = []
    for r in _subprocess_rows(_NODE_SCRIPT, base, N_NODES_MAX):
        out.append((
            f"churn/nodes/{r['name']}", r["us"],
            f"mean_recall={r['mean_recall']:.3f};rounds={r['rounds']};"
            f"handoff_bytes={r['handoff']};refresh_bytes={r['refresh']};"
            f"dropped={r['dropped']}"))
    return out


def _failure_rows(base: ChurnConfig):
    """Fail-stop cell (DESIGN.md Sec. 10): kill 1 of 4 replicated nodes
    mid-run with NO handoff, serve through first-responder vs quorum
    reads — recall gap while degraded, epochs to parity, and the
    replication/recovery byte bill next to the Table-1 query costs."""
    out = []
    for r in _subprocess_rows(_FAILURE_SCRIPT, base, N_NODES_MAX):
        out.append((
            f"churn/failure/R2/{r['name']}", r["us"],
            f"mean_recall={r['mean_recall']:.3f};"
            f"degraded_gap={r['degraded_gap']:.3f};"
            f"recovered_gap={r['recovered_gap']:.3f};"
            f"recovery_epochs={r['recovery_epochs']};"
            f"replication_bytes={r['replication']};"
            f"recovery_bytes={r['recovery']};dropped={r['dropped']}"))
    return out


def rows():
    base = ChurnConfig(num_users=2000, epochs=8, num_queries=96,
                       update_rate=0.1, churn_rate=0.03, seed=1)
    out = []
    for period in (1, 2, 4, 8):
        t0 = time.time()
        r = run_churn(dataclasses.replace(base, refresh_every=period))
        us = (time.time() - t0) / base.epochs * 1e6
        out.append((f"churn/refresh_every={period}", us,
                    f"mean_recall={r['mean_recall']:.3f};"
                    f"final_recall={r['final_recall']:.3f}"))
    out.extend(_dist_rows(base))
    out.extend(_node_rows(base))
    out.extend(_failure_rows(base))
    return out

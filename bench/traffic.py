"""The one traffic generator: a mix file of parameters, read by name.

`bench/traffic/<mix>.json` states the loop, the arrival process and the
query draw; the cell's own file, `bench/workloads/<cell>.json`, states its
rate or its client count and may override any key of the mix.  Keys:

  loop      "open": arrivals on a schedule fixed before the run, whether
            or not earlier queries have finished (independent users);
            "closed": `clients` callers, each sending its next query as
            soon as its last one is answered.
  arrivals  (open loop) {"kind": <name>, ...parameters}: the schedule.
  queries   {"kind": <name>, ...parameters}: what each query asks.

A kind is a file of its own, found by name like the per-layer metric
readers: `bench/traffic/arrivals.<kind>.py` defines
`times(params, rate_qps, seconds, rng)` -> sorted arrival times, and
`bench/traffic/queries.<kind>.py` defines
`draw(params, dep, centres, seed, count)` -> [count, d] float32 queries.
A new mix of existing kinds is a data file alone; a new kind adds its
file and edits none.  Every kind offers the same number of queries for
every seed (`query_count`), so a seed changes which queries come and
when, never how many.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
MIXES = BENCH / "traffic"


@functools.cache
def kind(group: str, name: str):
    """The module of one arrival process or query draw."""
    path = MIXES / f"{group}.{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown {group} kind {name!r} (no {path.name} "
                         f"in bench/traffic)")
    spec = importlib.util.spec_from_file_location(
        f"traffic_{group}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Traffic:
    loop: str
    queries: dict
    arrivals: dict | None = None
    rate_qps: float | None = None   # open loop
    clients: int | None = None      # closed loop
    max_qps: float | None = None    # closed loop: query pool sizing

    def query_count(self, seconds: float) -> int:
        """Queries a run needs (a closed loop draws a pool big enough for
        `max_qps` over the window, plus its clients' tail)."""
        if self.loop == "open":
            return int(round(self.rate_qps * seconds))
        return int(self.max_qps * seconds) + 2 * self.clients

    def arrival_times(self, seconds: float, seed: int) -> np.ndarray:
        """Scheduled arrival times, seconds from the window's start."""
        if self.loop != "open":
            raise ValueError("a closed loop has no schedule")
        rng = np.random.default_rng([int(seed) % 2**64, 1])
        t = kind("arrivals", self.arrivals["kind"]).times(
            self.arrivals, self.rate_qps, seconds, rng)
        if t.size != self.query_count(seconds):
            raise ValueError(f"arrivals {self.arrivals['kind']!r} made "
                             f"{t.size} of {self.query_count(seconds)}")
        return t

    def draw(self, dep, centres, seed: int, count: int) -> np.ndarray:
        """`count` queries [count, d] float32 on the host."""
        return kind("queries", self.queries["kind"]).draw(
            self.queries, dep, centres, seed, count)


def from_spec(spec: dict) -> Traffic:
    t = Traffic(loop=spec["loop"], queries=spec["queries"],
                arrivals=spec.get("arrivals"), rate_qps=spec.get("rate_qps"),
                clients=spec.get("clients"), max_qps=spec.get("max_qps"))
    if t.loop == "open" and not (t.rate_qps and t.arrivals):
        raise ValueError("an open loop needs arrivals and rate_qps")
    if t.loop == "closed" and not (t.clients and t.max_qps):
        raise ValueError("a closed loop needs clients and max_qps")
    if t.loop not in ("open", "closed"):
        raise ValueError(f"unknown loop {t.loop!r}")
    kind("queries", t.queries["kind"])
    if t.arrivals:
        kind("arrivals", t.arrivals["kind"])
    return t


def load(mix: str, cell: str) -> Traffic:
    spec = json.loads((MIXES / f"{mix}.json").read_text())
    spec.update(json.loads((BENCH / "workloads" / f"{cell}.json").read_text()))
    return from_spec(spec)

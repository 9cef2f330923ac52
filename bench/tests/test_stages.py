"""The frontend-stage reduction (`bench/stages.py`), on hand-built events,
and one traced run of it on the CPU."""

from __future__ import annotations

import dataclasses
import json
import types

import pytest

import stages
import tracing
from stages import Span
from tracing import Event, Trace


def _dispatch(b: int, t: float, rows: int = 3, retrace: float = 0.0,
              compute: float = 20.0) -> list[Span]:
    """One dispatch's spans from t (ns): stage 2 (a retrace nested in it
    when given), compute, fetch 1, reap 1, respond 1."""
    out = [Span("serve/intake", t, 1, {"batch": b}),
           Span("serve/enqueue", t + 1, 1, {"batch": b}),
           Span("serve/stage", t + 2, 2 + retrace,
                {"batch": b, "rows": rows, "pad": 4})]
    if retrace:
        out.append(Span("serve/retrace", t + 3, retrace, {"pad": 4}))
    t += 4 + retrace
    return out + [Span("serve/compute", t, compute, {"batch": b}),
                  Span("serve/fetch", t + compute, 1, {"batch": b}),
                  Span("serve/reap", t + compute + 1, 1, {"batch": b}),
                  Span("serve/respond", t + compute + 2, 1, {"batch": b})]


def test_per_dispatch_host_and_device_wait():
    # two dispatches: host spans 7 ns each, the second with a 10 ns
    # retrace nested in its stage (counted once); compute 20 and 40
    spans = _dispatch(0, 0) + _dispatch(1, 100, rows=1, retrace=10,
                                        compute=40)
    assert stages.batches(spans) == {0, 1}
    assert stages.host_ms(spans) == pytest.approx((7 + 17) / 2 / 1e6)
    split = stages.host_ms_by_span(spans)
    assert list(split)[0] == "serve/retrace"
    assert split["serve/retrace"] == pytest.approx(10 / 2 / 1e6)
    assert split["serve/stage"] == pytest.approx(4 / 2 / 1e6)
    assert sum(split.values()) == pytest.approx(stages.host_ms(spans))
    assert stages.device_wait_ms(spans) == pytest.approx(30 / 1e6)
    # stage start to respond end: 25 ns (3 rows) and 55 ns (1 row)
    assert stages.inflight_ms(spans) == pytest.approx(
        (3 * 25 + 1 * 55) / 4 / 1e6)
    assert stages.starting_in(spans, 100, 200) == spans[7:]
    for read in (stages.host_ms, stages.device_wait_ms, stages.inflight_ms):
        assert read([]) is None
    assert stages.host_ms_by_span([]) == {}


def test_queue_wait_is_mean_ring_wait_of_a_live_row():
    assert stages.queue_wait_ms(3_000.0, 2) == pytest.approx(1.5)
    assert stages.queue_wait_ms(None, 2) is None   # a program without it
    assert stages.queue_wait_ms(0.0, 0) is None


def test_stall_is_the_longest_self_time_outside_compute():
    spans = _dispatch(0, 0, compute=500) + _dispatch(1, 600, retrace=9)
    selfs = {(s.name, s.args.get("batch")): t
             for s, t in stages.self_ns(spans)}
    assert selfs[("serve/stage", 1)] == 2        # 11 less its retrace
    assert selfs[("serve/retrace", None)] == 9
    got = stages.stall(spans)
    assert got == {"name": "serve/retrace", "batch": None, "ms": 9e-6,
                   "at_ns": 603}
    assert stages.stall([Span("serve/compute", 0, 9, {"batch": 0})]) is None


def _traced():
    # one chip busy [0, 40) and [60, 100); idle [40, 60) while the host is
    # in bench/pump 38-62, inside it serve/compute 42-50 and serve/reap
    # 50-54, the pump's own Python the rest
    ops = {0: [Event("%fusion.1 = f32[8]{0} fusion(x)", 0, 40),
               Event("%fusion.2 = f32[8]{0} fusion(x)", 60, 40)]}
    spans = [Event(tracing.WINDOW, 0, 100), Event("bench/pump", 38, 24)]
    program = [Span("serve/compute", 42, 8, {"batch": 0}),
               Span("serve/reap", 50, 4, {"batch": 0})]
    return Trace(ops, {}, spans), program


def test_frontend_span_nested_in_pump_claims_its_share_of_a_gap():
    tr, program = _traced()
    got = dict(stages.idle_by_stage(tr, program, 0, 100))
    assert got == pytest.approx({"serve/compute": 8e-9, "serve/reap": 4e-9,
                                 "bench/pump": 8e-9})
    # run.py's own split, from the same trace, still says bench/pump
    assert dict(tracing.idle_by_span(tr, 0, 100)) == pytest.approx(
        {"bench/pump": 20e-9})
    gaps = stages.long_gaps(tr, program, 0, 100, min_ns=20)
    assert len(gaps) == 1
    at_s, ms, split = gaps[0]
    assert at_s == pytest.approx(40e-9) and ms == pytest.approx(20e-6)
    assert dict(split) == got
    assert stages.long_gaps(tr, program, 0, 100, min_ns=21) == []


def test_reading_over_the_window():
    tr, program = _traced()
    program = program + [Span("serve/stage", 30, 5,
                              {"batch": 0, "rows": 2, "pad": 2})]
    got = stages.reading(tr, program, 0, 100e-9, queued_us=10.0,
                         dispatched=2)
    assert got["dispatches"] == 1
    assert got["host_ms"] == pytest.approx(9e-6)
    assert got["device_wait_ms"] == pytest.approx(8e-6)
    assert got["queue_wait_ms"] == pytest.approx(5e-3)
    assert got["stall"]["name"] == "serve/stage"
    json.dumps(got)


def test_traced_cpu_run_reads_the_frontend_spans(tiny, monkeypatch, capsys):
    """The whole tool on the CPU: run.py's traced run (its chip check
    stepped over, as the harness's own tests do), then the stage line."""
    import jax

    import run
    import traffic

    real = traffic.load
    monkeypatch.setattr(traffic, "load", lambda mix, cell: dataclasses.replace(
        real(mix, cell), rate_qps=150.0))

    def main(argv):
        ap = run.argparse.ArgumentParser()
        for flag in ("--workload", "--seed", "--seconds", "--trace"):
            ap.add_argument(flag)
        a = ap.parse_args(argv)
        args = types.SimpleNamespace(workload=a.workload, seed=int(a.seed),
                                     seconds=float(a.seconds),
                                     trace=int(a.trace))
        spec, cell = run.cell_spec(args.workload)
        return run.run(args, spec, cell, jax.devices()[:1],
                       {"flops_per_s": 1e12, "bytes_per_s": 1e11})

    monkeypatch.setattr(run, "main", main)
    assert stages.main(["--workload", "glove100-dot.fresh-open",
                        "--seed", str(2**33 + 1), "--seconds", "2"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-2])["correct"] is True
    got = json.loads(lines[-1])["stages"]
    assert got["dispatches"] > 0
    for key in ("host_ms", "device_wait_ms", "inflight_ms", "queue_wait_ms"):
        assert got[key] > 0, key
    assert got["stall"]["name"].startswith("serve/")
    assert got["idle_gaps"] == [] and got["long_gaps"] == []  # no chip

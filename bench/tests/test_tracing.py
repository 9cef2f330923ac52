"""The trace reduction, checked on hand-built events."""

from __future__ import annotations

import pytest

import readers
import tracing
from tracing import Event, Trace

KERNEL = ("%fused_query.1 = (s32[256,10]{1,0:T(8,128)}, f32[256,10]{1,0}) "
          "custom-call(s32[3840]{0} %a, f32[1]{0} %b), custom_call_target=x")
PAD = ("%pad.0 = f32[65536,128,128]{2,1,0:T(8,128)} pad(f32[65536,128,100]"
       "{2,1,0:T(8,128)} %bitcast.5, f32[]{:T(128)} %c), padding=0_0x0_0x0_28")
COPY = "%copy.32 = f32[4,16384,128,100]{3,2,1,0} copy(f32[4,16384,128,100]{2,3,1,0} %x)"


def test_op_names():
    assert tracing.op_name(KERNEL) == "fused_query.1"
    assert tracing.op_base(KERNEL) == "fused_query"
    assert tracing.op_label(KERNEL) == (
        "fused_query.1 (s32[256,10], f32[256,10]) custom-call")
    assert tracing.op_label(PAD) == "pad.0 f32[65536,128,128] pad"
    assert tracing.op_label(COPY) == "copy.32 f32[4,16384,128,100] copy"


def test_union_and_gaps():
    ev = [Event("a", 0, 10), Event("b", 5, 10), Event("c", 30, 5)]
    assert tracing.union_ns(ev) == 20
    assert tracing.gaps(ev, 0, 40) == [(15, 30), (35, 40)]
    assert tracing.gaps(ev, 8, 32) == [(15, 30)]
    assert tracing.union_ns(tracing.clip(ev, 8, 32)) == 9


def _trace():
    # two chips, window [0, 100): chip 0 busy 60, chip 1 busy 30
    ops = {
        0: [Event(PAD, 0, 40), Event(KERNEL, 40, 20)],
        1: [Event(KERNEL, 10, 10), Event(COPY, 50, 20)],
    }
    modules = {
        0: [Event("jit__impl(123)", 0, 60)],
        1: [Event("jit__impl(123)", 10, 60), Event("jit_other(9)", 90, 5)],
    }
    spans = [
        Event(tracing.WINDOW, 0, 100),
        Event("bench/pump", 60, 30),
        Event("bench/submit", 70, 10),   # nested in pump: claims its own
        Event("bench/sleep", 90, 5),
    ]
    return Trace(ops, modules, spans)


def test_busy_kernel_modules():
    tr = _trace()
    assert tracing.busy_s(tr, 0, 100) == pytest.approx(45e-9)
    assert tracing.kernel_ns(tr, "fused_query", 0, 100) == 30
    assert tracing.kernel_ns(tr, "fused_query", 0, 15) == 5
    runs = tracing.module_runs(tr, "_impl", 0, 100)
    assert [e.dur for e in runs] == [60, 60]
    assert tracing.window_of(tr) == (0, 100)


def test_top_ops_per_chip():
    top = tracing.top_ops(_trace(), 0, 100)
    assert top[0] == ["pad.0 f32[65536,128,128] pad", pytest.approx(20e-9)]
    assert top[1][1] == pytest.approx(15e-9)


def test_idle_gap_attribution():
    # chip 0 idles [60, 100): pump 60-70 and 80-90 (20), submit 70-80
    # (10), sleep 90-95 (5), nothing 95-100 (5).  Chip 1 idles [0, 10),
    # [20, 50), [70, 100): other 45, pump 10, submit 10, sleep 5.
    got = dict(tracing.idle_by_span(_trace(), 0, 100))
    assert got["bench/pump"] == pytest.approx((20 + 10) / 2 * 1e-9)
    assert got["bench/submit"] == pytest.approx((10 + 10) / 2 * 1e-9)
    assert got["bench/sleep"] == pytest.approx((5 + 5) / 2 * 1e-9)
    assert got["host:other"] == pytest.approx((5 + 45) / 2 * 1e-9)
    assert tracing.WINDOW not in got
    idle = sum(got.values())
    assert idle == pytest.approx(100e-9 - tracing.busy_s(_trace(), 0, 100))


def test_readers_on_events():
    import deploy
    from conftest import tiny_config

    dep = deploy.deployment(tiny_config())
    r = readers.Reading(trace=_trace(), lo=0, hi=100, window_s=100e-9,
                        dep=dep, peak={"flops_per_s": 1e12,
                                       "bytes_per_s": 1e9},
                        dispatched=8, padded=8, lag_ms=1.0)
    assert readers.idle_pct(r) == pytest.approx(55.0)
    assert readers.step_device_ms(r) == pytest.approx(60e-6)
    assert readers.batch_fill_pct(r) == pytest.approx(50.0)
    assert readers.fused_query_roofline(r) > 0
    empty = readers.Reading(trace=Trace({}, {}, []), lo=0, hi=100,
                            window_s=1.0, dep=dep, peak=r.peak,
                            dispatched=0, padded=0, lag_ms=0.0)
    for read in (readers.idle_pct, readers.step_device_ms,
                 readers.batch_fill_pct, readers.fused_query_roofline):
        assert read(empty) is None


def test_every_metric_file_loads():
    import json

    spec = json.loads((readers.BENCH.parent / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(readers.load(m["name"]))

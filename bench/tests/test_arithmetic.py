"""Latency and qps arithmetic, the work count, and the traffic's
determinism."""

from __future__ import annotations

import numpy as np
import pytest

import loops
import traffic
import workcount


def _served(due, done, shed=None, window=10.0):
    due = np.asarray(due, float)
    done = np.asarray(done, float)
    shed = np.zeros(due.size, bool) if shed is None else np.asarray(shed)
    n = due.size
    return loops.Served(due, done, shed, np.zeros((n, 1), np.int32),
                        np.zeros((n, 1), np.float32), window, due.copy(), [])


def test_latency_from_schedule_over_all_requests():
    # a stall delays the late arrivals: each is timed from its schedule,
    # not from when it was submitted
    s = _served(due=[0.0, 1.0, 2.0, 3.0], done=[0.01, 2.5, 2.5, 3.002])
    np.testing.assert_allclose(loops.latencies_ms(s),
                               [10.0, 1500.0, 500.0, 2.0])
    lat = loops.latencies_ms(s)
    assert loops.percentile_ms(lat, 50) == pytest.approx(10.0)
    assert loops.percentile_ms(lat, 99) == pytest.approx(1500.0)


def test_generator_lag_is_the_latest_send():
    s = _served(due=[0.0, 1.0, 2.0], done=[0.1, 1.1, 2.1])
    s.sent_at = np.array([0.0, 1.25, 2.001])
    assert s.lag_s == pytest.approx(0.25)


def test_shed_and_unanswered_count_as_failed():
    s = _served(due=[0.0, 1.0, 2.0, 3.0, 11.0],
                done=[0.1, np.nan, np.nan, 3.2, 11.1],
                shed=[False, True, False, False, False])
    lat = loops.latencies_ms(s)
    # every query due in the window; shed and unanswered are infinite
    np.testing.assert_allclose(lat, [100.0, np.inf, np.inf, 200.0])
    assert loops.failed(s) == 2
    assert loops.unanswered(s) == 1
    assert loops.percentile_ms(lat, 50) == pytest.approx(200.0)
    assert loops.percentile_ms(lat, 75) == np.inf


def test_shedding_never_lowers_a_percentile():
    due = np.linspace(0.0, 9.0, 100)
    done = due + np.linspace(0.001, 0.1, 100)        # 1..100 ms
    full = loops.latencies_ms(_served(due, done))
    shed = np.zeros(100, bool)
    shed[50:60] = True                          # ten mid-rank queries shed
    part = loops.latencies_ms(_served(due, done, shed))
    for p in (50, 90, 99):
        assert loops.percentile_ms(part, p) >= loops.percentile_ms(full, p)


def test_percentile_counts_every_request():
    lat = np.arange(1, 1001, dtype=float)     # 1..1000 ms
    assert loops.percentile_ms(lat, 50) == 500.0
    assert loops.percentile_ms(lat, 99) == 990.0


def test_qps_over_the_whole_window():
    done = np.concatenate([np.linspace(0.1, 9.9, 500), [10.5, np.nan]])
    s = _served(due=np.zeros(done.size), done=done, window=10.0)
    assert loops.completed_qps(s) == pytest.approx(50.0)


def test_work_count_is_the_algorithms():
    w = workcount.fused_query(rows=256, probes=15, capacity=128, d=100,
                              m=10)
    slots = 256 * 15 * 128
    assert w.flops == 2 * slots * 100
    assert w.bytes == 4 * (slots * 101 + 256 * 100 + 256 * 20)


@pytest.mark.parametrize("padded", [0, 7, 64])
@pytest.mark.parametrize("tb,kc", [(8, 128), (16, 128), (8, 256)])
def test_work_count_ignores_block_shape_and_padding(padded, tb, kc):
    """The yardstick reads only live rows and valid probes: the kernel's
    block shape (TB, KC) and the frontend's padding rows never enter."""
    import deploy
    import readers
    import tracing
    from conftest import tiny_config

    dep = deploy.deployment(tiny_config())
    kernel = "%fused_query.3 = (s32[64,10]{1,0}) custom-call(s32[1]{0} %a)"
    ops = {0: [tracing.Event(kernel, 0, 1000)]}
    r = readers.Reading(trace=tracing.Trace(ops, {}, []), lo=0, hi=1000,
                        window_s=1e-6, dep=dep,
                        peak={"flops_per_s": 1e12, "bytes_per_s": 1e11},
                        dispatched=40, padded=padded, lag_ms=0.0)
    want = workcount.fused_query(rows=40 * dep.L, probes=1 + dep.k,
                                 capacity=dep.capacity, d=dep.d, m=dep.m)
    least = max(want.flops / 1e12, want.bytes / 1e11)
    assert readers.fused_query_roofline(r) == pytest.approx(
        100 * least / 1e-6)


def test_unknown_device_kind_is_an_error():
    assert workcount.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        workcount.peaks("cpu")


@pytest.mark.parametrize("arrivals", [
    {"kind": "poisson"},
    {"kind": "onoff", "on_s": 0.5, "off_s": 1.5},
])
def test_traffic_is_deterministic_per_seed(arrivals):
    t = traffic.from_spec({"loop": "open", "arrivals": arrivals,
                           "queries": {"kind": "fresh"}, "rate_qps": 2000.0})
    seed = 2**31 + 12345
    a, b = t.arrival_times(15, seed), t.arrival_times(15, seed)
    np.testing.assert_array_equal(a, b)
    c = t.arrival_times(15, seed + 1)
    assert a.size == c.size == 30000       # every seed offers the same load
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 15


def test_onoff_arrivals_fall_in_bursts():
    t = traffic.from_spec({"loop": "open", "queries": {"kind": "fresh"},
                           "arrivals": {"kind": "onoff", "on_s": 0.5,
                                        "off_s": 1.5},
                           "rate_qps": 1000.0})
    a = t.arrival_times(7.0, 3)
    phase = np.mod(a, 2.0)
    assert np.all(phase < 0.5)                 # none in an off period
    per_burst = np.bincount((a // 2.0).astype(int))
    # 7 s: bursts at 0, 2, 4 and 6 s, each of 0.5 s, so 7,000 over 2 s of
    # bursts; each burst gets about a quarter
    assert per_burst.sum() == 7000
    assert np.all(np.abs(per_burst - 1750) < 200)


def test_zipf_pool_repeats_its_hot_queries():
    import deploy
    from conftest import tiny_config

    dep = deploy.deployment(tiny_config())
    seed = 2**32 + 17
    _, centres = deploy.make_corpus(dep, seed)
    t = traffic.from_spec({"loop": "closed", "clients": 8, "max_qps": 10,
                           "queries": {"kind": "zipf-pool", "pool": 50,
                                       "s": 1.2}})
    q = t.draw(dep, centres, seed, 2000)
    np.testing.assert_array_equal(q, t.draw(dep, centres, seed, 2000))
    distinct, counts = np.unique(q, axis=0, return_counts=True)
    assert len(distinct) <= 50
    assert counts.max() > 2000 * 0.2           # rank 1 carries ~30%


def test_unknown_kind_is_an_error():
    with pytest.raises(ValueError, match="unknown queries kind"):
        traffic.from_spec({"loop": "closed", "clients": 1, "max_qps": 1,
                           "queries": {"kind": "nope"}})


def test_queries_are_deterministic_per_seed():
    import deploy
    from conftest import tiny_config

    dep = deploy.deployment(tiny_config())
    seed = 2**33 + 7
    _, centres = deploy.make_corpus(dep, seed)
    q1 = deploy.make_queries(dep, centres, seed, 64)
    q2 = deploy.make_queries(dep, deploy.make_corpus(dep, seed)[1], seed, 64)
    np.testing.assert_array_equal(q1, q2)
    q3 = deploy.make_queries(dep, centres, seed + 1, 64)
    assert not np.array_equal(q1, q3)
    np.testing.assert_allclose(np.linalg.norm(q1, axis=1), 1.0, atol=1e-5)
    v1, _ = deploy.make_corpus(dep, seed)
    v2, _ = deploy.make_corpus(dep, seed)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_mix_files_load():
    t = traffic.load("fresh-open", "glove100-dot.fresh-open")
    assert t.loop == "open" and t.rate_qps > 0
    c = traffic.load("fresh-closed", "glove100-dot.fresh-closed")
    assert c.loop == "closed" and c.clients == 256
    assert c.query_count(15) >= c.max_qps * 15

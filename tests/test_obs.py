"""Observability layer (DESIGN.md Sec. 12).

Pins the obs contracts:
  * registry: label-set aggregation, idempotent registration (kind
    mismatch is a TypeError), JSON snapshot schema, bucket-resolution
    histogram quantiles;
  * tracer: span nesting via plain stack, stopwatch semantics with
    recording disabled, Chrome-trace JSON that round-trips and carries
    the required event keys;
  * profiler spans: with obs off, every dispatch leaves its stage,
    compute, fetch, reap and respond spans, tagged with one `batch`, on
    the profiler trace's host plane;
  * flight recorder: bounded ring, drop-spike auto dump (dispatch/epoch
    records only), anomaly snapshots preserving the ring, `total()`
    accounting over direct fields and `extra` entries;
  * frontend integration: per-query + per-dispatch records agree with
    `ServeStats`, all six pipeline-stage spans appear, the sampled
    recall probe lands in the registry;
  * ZERO-RETRACE: obs-on serves the SAME compiled executables as
    obs-off (trace counters), with bit-identical results;
  * churn: flight epoch records sum to the driver's aggregate arrays
    bit-for-bit;
  * `core.metrics` edge cases (empty ideal sets, duplicates, m >
    candidates) that the recall probe leans on.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    DenseCorpus, EngineConfig, LshEngine, LshParams, make_hyperplanes,
    metrics,
)
from repro.core.churn import ChurnConfig, run_churn_distributed
from repro.core.hashing import sketch_codes_batched
from repro.core.store import build_store_host
from repro.obs import ObsConfig, Observability
from repro.obs.flight import FlightRecorder, QueryRecord
from repro.obs.registry import Registry
from repro.obs.trace import Span, Tracer, span_or_null
from repro.serve import FrontendConfig, RetrievalFrontend, RuntimeBackend

K, L, D, M = 5, 3, 16, 8


def _make_engine(n=400, seed=0, capacity=32):
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, D)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    params = LshParams(d=D, k=K, L=L, seed=seed + 1)
    h = make_hyperplanes(params)
    codes = sketch_codes_batched(jnp.asarray(emb), h)
    store = build_store_host(codes, params.num_buckets, capacity=capacity)
    engine = LshEngine(params, h, store, DenseCorpus(jnp.asarray(emb)), None,
                       EngineConfig(variant="cnb"))
    return emb, engine


# -----------------------------------------------------------------------------
# registry
# -----------------------------------------------------------------------------


def test_counter_aggregates_per_label_set():
    reg = Registry()
    c = reg.counter("msgs_total", "messages")
    c.inc(3, node="a")
    c.inc(2, node="a")
    c.inc(7, node="b")
    c.inc()  # unlabeled series is its own label set
    assert c.value(node="a") == 5
    assert c.value(node="b") == 7
    assert c.value() == 1
    assert c.value(node="never") == 0
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registration_is_idempotent_and_kind_checked():
    reg = Registry()
    a = reg.counter("x")
    assert reg.counter("x") is a
    with pytest.raises(TypeError):
        reg.gauge("x")
    assert reg.value("missing", default=-1) == -1
    g = reg.gauge("y")
    assert g.value(k="v") is None  # never set
    assert reg.value("y", default=0.0, k="v") == 0.0


def test_histogram_counts_and_quantile():
    reg = Registry()
    h = reg.histogram("lat_us", buckets=(10.0, 100.0, 1000.0))
    for v in (5, 5, 50, 500, 5000):
        h.observe(v, stage="dispatch")
    assert h.value(stage="dispatch") == 5
    assert h.value(stage="other") == 0
    assert h.quantile(0.2, stage="dispatch") == 10.0
    assert h.quantile(0.6, stage="dispatch") == 100.0
    assert h.quantile(1.0, stage="dispatch") == float("inf")  # 5000 > top edge
    assert h.quantile(0.5, stage="other") == 0.0


def test_histogram_observe_many_matches_observe():
    reg = Registry()
    one = reg.histogram("a", buckets=(10.0, 100.0, 1000.0))
    many = reg.histogram("b", buckets=(10.0, 100.0, 1000.0))
    vals = [5.0, 10.0, 99.0, 100.0, 5000.0, 0.0]  # edges land identically
    for v in vals:
        one.observe(v, stage="s")
    many.observe_many(vals, stage="s")
    assert one._series == many._series
    many.observe_many([], stage="s")  # no-op, no series mutation
    assert one._series == many._series
    assert many.value(stage="s") == len(vals)


def test_snapshot_schema_and_prometheus_text():
    reg = Registry()
    reg.counter("c_total", "help text").inc(4, node="0")
    reg.gauge("g").set(1.5)
    reg.histogram("h", buckets=(10.0,)).observe(3.0)
    snap = reg.snapshot()
    assert set(snap) == {"c_total", "g", "h"}
    assert snap["c_total"]["type"] == "counter"
    assert snap["c_total"]["help"] == "help text"
    assert snap["c_total"]["samples"] == [
        dict(labels={"node": "0"}, value=4)]
    hs = snap["h"]["samples"][0]
    assert hs["count"] == 1 and hs["sum"] == 3.0
    assert hs["buckets"] == {"10": 1, "+Inf": 1}  # cumulative
    assert json.loads(json.dumps(snap)) == snap  # JSON-able end to end

    # the snapshot is the one export: label sets come out sorted, each
    # histogram series with its own cumulative buckets
    reg.histogram("h", buckets=(10.0,)).observe(30.0, node="1")
    reg.counter("c_total").inc(1, node="-1")
    snap = reg.snapshot()
    assert [s["labels"] for s in snap["c_total"]["samples"]] == [
        {"node": "-1"}, {"node": "0"}]
    assert [s["buckets"] for s in snap["h"]["samples"]] == [
        {"10": 1, "+Inf": 1}, {"10": 0, "+Inf": 1}]


# -----------------------------------------------------------------------------
# tracer
# -----------------------------------------------------------------------------


def test_span_nesting_depth_and_stopwatch():
    tr = Tracer()
    assert tr.depth == 0
    with tr.span("outer") as outer:
        assert tr.depth == 1
        with tr.span("inner") as inner:
            assert tr.depth == 2
            assert inner.elapsed_s >= 0.0
    assert tr.depth == 0
    assert outer.duration_s >= inner.duration_s >= 0.0
    assert outer.duration_us == pytest.approx(outer.duration_s * 1e6)
    by_name = {e[1]: e for e in tr.events()}
    assert by_name["outer"][5] == 0 and by_name["inner"][5] == 1  # depths


def test_disabled_tracer_still_times_but_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("work") as sp:
        pass
    assert sp.duration_s >= 0.0
    assert tr.events() == []
    with tr.span("outer"), tr.span("inner"):
        pass
    assert tr.events() == [] and tr.depth == 0


def test_span_or_null_without_tracer_yields_null_context():
    # without a tracer the span is the bare profiler annotation: nothing
    # is timed on the host or recorded into any ring
    with span_or_null(None, "anything", batch=3) as sp:
        assert not isinstance(sp, Span)
    tr = Tracer()
    with span_or_null(tr, "named", n=3) as sp:
        assert isinstance(sp, Span)
    assert tr.events()[0][1] == "named"


def test_span_records_even_when_body_raises():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("doomed"):
            raise RuntimeError("boom")
    assert tr.depth == 0  # stack unwound
    assert [e[1] for e in tr.events()] == ["doomed"]


def test_tracer_ring_is_bounded():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    ev = tr.events()
    assert len(ev) == 4
    assert [e[1] for e in ev] == ["s6", "s7", "s8", "s9"]


def test_chrome_trace_round_trips_with_required_keys(tmp_path):
    tr = Tracer()
    with tr.span("stage", cat="serve", rows=7):
        with tr.span("inner"):
            pass
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(tr.to_chrome_trace()))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(ev)
        assert ev["ph"] == "X" and ev["dur"] >= 0.0
    stage = next(e for e in evs if e["name"] == "stage")
    assert stage["args"] == {"rows": 7, "depth": 0}
    inner = next(e for e in evs if e["name"] == "inner")
    assert inner["args"]["depth"] == 1
    assert stage["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= stage["ts"] + stage["dur"]


# -----------------------------------------------------------------------------
# flight recorder
# -----------------------------------------------------------------------------


def test_flight_ring_is_bounded():
    fl = FlightRecorder(capacity=3, drop_spike=0)
    for i in range(7):
        fl.record(QueryRecord(qid=i))
    assert len(fl) == 3
    assert [r.qid for r in fl.records()] == [4, 5, 6]
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_drop_spike_dumps_on_dispatch_not_query_records():
    fl = FlightRecorder(capacity=16, drop_spike=2)
    fl.record(QueryRecord(qid=0, kind="query", dropped_probes=99))
    assert fl.dumps == []  # query records never trigger the dump
    fl.record(QueryRecord(qid=1, kind="dispatch", dropped_probes=1))
    assert fl.dumps == []  # below the spike threshold
    fl.record(QueryRecord(qid=2, kind="dispatch", dropped_probes=2))
    assert len(fl.dumps) == 1
    d = fl.dumps[0]
    assert d["reason"] == "drop_spike"
    assert d["detail"]["dropped_probes"] == 2
    assert d["n_records"] == 3 == len(d["records"])
    fl2 = FlightRecorder(capacity=16, drop_spike=0)  # <=0 disables
    fl2.record(QueryRecord(kind="dispatch", dropped_probes=100))
    assert fl2.dumps == []


def test_note_anomaly_snapshots_the_ring():
    fl = FlightRecorder(capacity=2, drop_spike=0)
    for i in range(4):
        fl.record(QueryRecord(qid=i))
    dump = fl.note_anomaly("kill_node", node=3)
    assert dump["reason"] == "kill_node" and dump["detail"] == {"node": 3}
    # only the surviving (ring) records are in the snapshot ...
    assert [r["qid"] for r in dump["records"]] == [2, 3]
    # ... and they survive the ring wrapping past them afterwards
    for i in range(10, 14):
        fl.record(QueryRecord(qid=i))
    assert [r["qid"] for r in fl.dumps[0]["records"]] == [2, 3]


def test_total_sums_direct_fields_and_extra_entries():
    fl = FlightRecorder(drop_spike=0)
    fl.record(QueryRecord(kind="epoch", dropped_probes=2,
                          extra=dict(replication_bytes=100)))
    fl.record(QueryRecord(kind="epoch", dropped_probes=3,
                          extra=dict(replication_bytes=50)))
    fl.record(QueryRecord(kind="query", dropped_probes=999))  # other kind
    assert fl.total("dropped_probes") == 5
    assert fl.total("replication_bytes") == 150
    assert fl.total("dropped_probes", kind="query") == 999
    assert fl.total("never_charged") == 0


def test_prestamped_t_us_is_preserved():
    fl = FlightRecorder(drop_spike=0)
    r1 = fl.record(QueryRecord(qid=0, t_us=fl.to_us(0.0)))
    assert r1.t_us < 0  # recorder started after perf_counter epoch 0
    r2 = fl.record(QueryRecord(qid=1))
    assert r2.t_us > 0  # stamped by record()


def test_flight_export_and_chrome_events(tmp_path):
    fl = FlightRecorder(drop_spike=0)
    fl.record(QueryRecord(qid=7, kind="query", t_us=500.0, latency_us=120.0))
    fl.record(QueryRecord(qid=0, kind="dispatch", dropped_probes=1))
    fl.note_anomaly("reshard", old_n=2, new_n=4)
    doc = fl.to_chrome_trace()
    q = next(e for e in doc["traceEvents"] if e["name"] == "query:7")
    assert q["ph"] == "X" and q["ts"] == 380.0 and q["dur"] == 120.0
    d = next(e for e in doc["traceEvents"] if e["name"] == "dispatch:0")
    assert d["ph"] == "i" and d["s"] == "t"
    a = next(e for e in doc["traceEvents"] if e["name"] == "anomaly:reshard")
    assert a["ph"] == "i" and a["s"] == "p" and a["args"]["old_n"] == 2

    path = tmp_path / "flight.json"
    fl.export(str(path))
    blob = json.loads(path.read_text())
    assert [r["qid"] for r in blob["records"]] == [7, 0]
    assert blob["dumps"][0]["reason"] == "reshard"
    assert blob["capacity"] == fl.capacity


def test_obs_bundle_merges_and_exports(tmp_path):
    obs = Observability()
    with obs.tracer.span("stage"):
        pass
    obs.flight.record(QueryRecord(qid=1, kind="query", latency_us=10.0))
    obs.registry.counter("c").inc(2)
    doc = obs.chrome_trace()
    names = [e["name"] for e in doc["traceEvents"]]
    assert "stage" in names and "query:1" in names
    tp, mp = tmp_path / "t.json", tmp_path / "m.json"
    obs.export_trace(str(tp))
    obs.export_metrics(str(mp))
    assert len(json.loads(tp.read_text())["traceEvents"]) == 2
    assert json.loads(mp.read_text())["c"]["samples"][0]["value"] == 2
    with pytest.raises(ValueError):
        ObsConfig(flight_capacity=0)


# -----------------------------------------------------------------------------
# frontend integration
# -----------------------------------------------------------------------------


def test_frontend_obs_records_agree_with_stats():
    emb, engine = _make_engine()
    obs = Observability()
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=16, queue_capacity=64, cache=False),
        obs=obs,
    )
    q, ex = emb[:24], np.arange(24)
    fe.search(q, exclude=ex)
    s = fe.stats.summary()
    queries = obs.flight.records(kind="query")
    assert len(queries) == s["completed"] == 24
    assert all(r.latency_us > 0 and r.cache_hit is False for r in queries)
    dispatches = obs.flight.records(kind="dispatch")
    assert len(dispatches) == s["batches"]
    assert (obs.flight.total("dropped_probes", kind="dispatch")
            == s["dropped_probes"])
    # every query record points at a real dispatch and carries its share
    by_seq = {d.qid: d for d in dispatches}
    for r in queries:
        d = by_seq[r.batch]
        assert r.batch_size == d.batch_size
        assert r.probes_issued == d.probes_issued // d.batch_size
    span_names = {e[1] for e in obs.tracer.events()}
    assert {"serve/intake", "serve/enqueue", "serve/stage", "serve/compute",
            "serve/fetch", "serve/reap", "serve/respond"} <= span_names


def _profiled_serve_spans(tmp_path, fn):
    """Run fn() under the jax profiler; the `serve/*` events of the trace's
    host planes as (name, start_ns, stats), by start."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = [(e.name, e.start_ns, dict(e.stats))
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("serve/")]
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("depth", [1, 2])
def test_stage_spans_reach_the_profiler_without_obs(tmp_path, depth):
    """With obs off (how the chip benchmark builds its frontend) every
    dispatch still leaves its stage spans on the profiler's host plane,
    each tagged with the dispatch seq as `batch`, in pipeline order."""
    emb, engine = _make_engine()
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=8, queue_capacity=64, cache=False,
                       pipeline_depth=depth))
    for n in (1, 2, 4, 8):   # compile every shape outside the trace
        fe.search(emb[:n])
    warm = fe.stats.batches

    def serve():
        for q in emb[:29]:
            fe.submit(q)
            fe.pump()
        fe.flush()

    spans = _profiled_serve_spans(tmp_path, serve)
    by_batch: dict[int, list] = {}
    for name, _, stats in spans:
        by_batch.setdefault(stats["batch"], []).append((name, stats))
    assert sorted(by_batch) == list(range(warm, fe.stats.batches))
    for seq, evs in by_batch.items():
        assert [n for n, _ in evs] == [
            "serve/intake", "serve/enqueue", "serve/stage", "serve/compute",
            "serve/fetch", "serve/reap", "serve/respond"], seq
        stage = dict(evs)["serve/stage"]
        assert 1 <= stage["rows"] <= stage["pad"] <= 8
        assert stage["pad"] & (stage["pad"] - 1) == 0   # the pow-2 grid
    rows = sum(dict(evs)["serve/stage"]["rows"] for evs in by_batch.values())
    assert rows == 29


def test_cache_hits_become_hit_records():
    emb, engine = _make_engine()
    obs = Observability()
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=16, queue_capacity=64, cache=True),
        obs=obs,
    )
    q, ex = emb[:8], np.arange(8)
    fe.search(q, exclude=ex)
    fe.search(q, exclude=ex)  # identical -> all hits
    hits = [r for r in obs.flight.records(kind="query") if r.cache_hit]
    assert len(hits) == 8
    assert all(r.batch == -1 for r in hits)  # hits ride no dispatch


def test_obs_on_is_zero_retrace_and_bit_identical():
    emb, engine = _make_engine()
    backend = RuntimeBackend(engine)
    fe_off = RetrievalFrontend(
        backend, FrontendConfig(m=M, max_batch=16, queue_capacity=64,
                                cache=False))
    q, ex = emb[:24], np.arange(24)
    ids_off, sc_off = fe_off.search(q, exclude=ex)
    traces = (backend.traces, backend.sketch_traces)

    fe_on = RetrievalFrontend(
        backend, FrontendConfig(m=M, max_batch=16, queue_capacity=64,
                                cache=False),
        obs=Observability())
    ids_on, sc_on = fe_on.search(q, exclude=ex)
    # the SAME executables served both frontends: not one extra retrace
    assert (backend.traces, backend.sketch_traces) == traces
    np.testing.assert_array_equal(ids_on, ids_off)
    np.testing.assert_array_equal(sc_on, sc_off)


def test_recall_probe_publishes_registry_gauge():
    emb, engine = _make_engine()
    obs = Observability(ObsConfig(recall_probe_every=1))  # probe every miss
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=16, queue_capacity=64, cache=False),
        obs=obs,
    )
    fe.search(emb[:16], exclude=np.arange(16))
    reg = obs.registry
    assert reg.value("serve_recall_probes_total") == 16
    last = reg.value("serve_recall_probe", window="last")
    mean = reg.value("serve_recall_probe", window="mean")
    assert last is not None and 0.0 <= last <= 1.0
    assert mean is not None and 0.0 <= mean <= 1.0


# -----------------------------------------------------------------------------
# churn accounting
# -----------------------------------------------------------------------------


def test_churn_epoch_records_sum_to_aggregates_exactly():
    cfg = ChurnConfig(num_users=300, dim=16, k=4, L=2, capacity=32,
                      epochs=4, num_queries=32, refresh_every=2, seed=11)
    obs = Observability()
    out = run_churn_distributed(cfg, n_shards=1, obs=obs)
    eps = obs.flight.records(kind="epoch")
    # one record per loop epoch: the epoch-0 announce plus every read epoch
    assert len(eps) == len(out["recalls"]) + 1
    fl = obs.flight
    assert fl.total("dropped_probes") == int(out["dropped_probes"].sum())
    assert fl.total("replication_bytes") == out["total_replication_bytes"]
    assert fl.total("recovery_bytes") == out["total_recovery_bytes"]
    assert fl.total("handoff_bytes") == out["total_handoff_bytes"]
    assert fl.total("refresh_bytes") == out["total_refresh_bytes"]
    # per-epoch reconstruction, not just totals (eps[0] is the announce)
    assert ([r.extra["refresh_bytes"] for r in eps[1:]]
            == out["refresh_bytes"].tolist())
    assert ([r.extra["recall"] for r in eps[1:]]
            == out["recalls"].tolist())
    reg = obs.registry
    assert (reg.value("churn_dropped_probes_total")
            == int(out["dropped_probes"].sum()))
    assert (reg.value("churn_replication_bytes_total")
            == out["total_replication_bytes"])
    assert (reg.value("churn_recall", window="last")
            == pytest.approx(out["final_recall"]))
    assert (reg.value("churn_recall", window="mean")
            == pytest.approx(out["mean_recall"]))


# -----------------------------------------------------------------------------
# core.metrics edge cases (the recall probe's foundation)
# -----------------------------------------------------------------------------


def test_recall_empty_ideal_set_counts_as_perfect():
    approx = np.array([[1, 2, -1]], np.int32)
    ideal = np.full((1, 3), -1, np.int32)  # nothing to find
    assert metrics.recall_at_m(approx, ideal) == 1.0


def test_recall_duplicate_ids_count_once():
    approx = np.array([[5, 5, 5, -1]], np.int32)
    ideal = np.array([[5, 6, -1, -1]], np.int32)
    assert metrics.recall_at_m(approx, ideal) == pytest.approx(0.5)
    # duplicates in the ideal collapse too: {5} fully covered
    assert metrics.recall_at_m(
        np.array([[5, -1]], np.int32), np.array([[5, 5]], np.int32)) == 1.0


def test_recall_fewer_candidates_than_m():
    approx = np.array([[3, -1, -1, -1]], np.int32)  # 1 found, m=4 asked
    ideal = np.array([[3, 7, 9, 11]], np.int32)
    assert metrics.recall_at_m(approx, ideal) == pytest.approx(0.25)
    # and per-query averaging over a mixed batch
    approx2 = np.array([[3, -1], [7, 8]], np.int32)
    ideal2 = np.array([[3, 4], [7, 8]], np.int32)
    assert metrics.recall_at_m(approx2, ideal2) == pytest.approx(0.75)


def test_ncs_zero_and_missing_scores():
    approx = np.array([[0.5, 0.0]], np.float64)
    ideal = np.array([[1.0, 1.0]], np.float64)
    assert metrics.ncs_at_m(approx, ideal) == pytest.approx(0.25)
    # all-zero ideal: guarded denominator, no division blow-up
    z = np.zeros((1, 2))
    assert metrics.ncs_at_m(z, z) == 0.0
    # -inf padding (missing results) contributes nothing
    pad = np.array([[0.5, -np.inf]], np.float64)
    assert metrics.ncs_at_m(pad, ideal) == pytest.approx(0.25)
    # negative similarities clamp to 0 on both sides
    neg = np.array([[-0.5, -0.5]], np.float64)
    assert metrics.ncs_at_m(neg, ideal) == 0.0


def test_query_record_asdict_schema_stable():
    # the flight export feeds external tooling: pin the field set
    fields = set(QueryRecord.__dataclass_fields__)
    assert {"qid", "kind", "t_us", "latency_us", "cache_hit", "generation",
            "batch", "batch_size", "probes_issued", "probes_routed",
            "dropped_probes", "dropped_by_dest", "nodes_contacted",
            "replica_fanout", "stage_us", "extra"} == fields
    d = dataclasses.asdict(QueryRecord(qid=3, extra=dict(x=1)))
    assert d["qid"] == 3 and d["extra"] == {"x": 1}

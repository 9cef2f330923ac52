"""Online retrieval serving subsystem (DESIGN.md Sec. 7).

Pins the serving contracts:
  * frontend results are BIT-IDENTICAL to direct `engine.search`, cache
    on and off (the no-serving-only-query-path rule);
  * pow-2 batch padding bounds the set of compiled shapes (trace count);
  * admission control rejects over-capacity arrivals, counted;
  * the sketch-keyed cache never serves a stale-generation entry across
    insert/expire churn;
  * telemetry aggregates QueryCost and dropped_probes at the summary;
  * the mesh-step backend (1-shard, single device) matches the engine;
  * read/write-epoch serving tracks the churn reference trajectory
    exactly.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    DenseCorpus, EngineConfig, LshEngine, LshParams, make_hyperplanes,
)
from repro.core import costmodel
from repro.core.churn import ChurnConfig, run_churn
from repro.core.hashing import sketch_codes_batched
from repro.core.store import build_store_host, expire, insert_batch, make_store
from repro.serve import (
    ADMIT_REJECT, RING_FULL, FrontendConfig, QueryCache, RetrievalFrontend,
    RuntimeBackend, ServeStats, ServeChurnConfig, SubmitReject, dispatch_pad,
    pow2_pad, run_serve_churn,
)

K, L, D, M = 5, 3, 16, 8


def _make_engine(n=400, seed=0, capacity=32, variant="cnb", payload=False):
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, D)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    params = LshParams(d=D, k=K, L=L, seed=seed + 1)
    h = make_hyperplanes(params)
    codes = sketch_codes_batched(jnp.asarray(emb), h)
    store = build_store_host(
        codes, params.num_buckets, capacity=capacity,
        payload=emb if payload else None,
    )
    engine = LshEngine(params, h, store, DenseCorpus(jnp.asarray(emb)), None,
                       EngineConfig(variant=variant))
    return emb, engine, codes


# -----------------------------------------------------------------------------
# bit-identity with the reference engine
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("cache", [False, True])
def test_frontend_matches_engine_search(cache):
    emb, engine, _ = _make_engine()
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=16, queue_capacity=64, cache=cache),
    )
    q = emb[:50]
    ex = np.arange(50)
    ids, scores = fe.search(q, exclude=ex)
    ref = engine.search(jnp.asarray(q), m=M, exclude=ex)
    np.testing.assert_array_equal(ids, ref.ids)
    np.testing.assert_allclose(scores, ref.scores)


def test_repeat_queries_hit_cache_and_stay_identical():
    emb, engine, _ = _make_engine()
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=16, queue_capacity=64, cache=True),
    )
    q = emb[:24]
    ids1, sc1 = fe.search(q)
    ids2, sc2 = fe.search(q)
    np.testing.assert_array_equal(ids1, ids2)
    np.testing.assert_array_equal(sc1, sc2)
    assert fe.stats.cache_hits == 24
    assert fe.stats.completed == 48
    # a cache hit costs zero overlay messages: the measured average halves
    full = fe.backend.cost().messages
    assert fe.stats.messages_per_query == pytest.approx(full / 2)


# -----------------------------------------------------------------------------
# pow-2 padding: bounded compiled-shape set
# -----------------------------------------------------------------------------


def test_pow2_pad():
    assert [pow2_pad(n) for n in (1, 2, 3, 5, 8, 9, 64)] == [
        1, 2, 4, 8, 8, 16, 64]
    assert pow2_pad(3, floor=8) == 8


def test_dispatch_pad_divides_over_non_pow2_meshes():
    # a sharded backend's batch must divide over the device count: the
    # pow-2 grid rounds UP to a multiple (3 devices: 2 live rows -> 3)
    assert dispatch_pad(2, multiple=3) == 3
    assert [dispatch_pad(n, 3) for n in (1, 3, 4, 7)] == [3, 6, 6, 9]
    for n in range(1, 70):
        assert dispatch_pad(n, 3) % 3 == 0 and dispatch_pad(n, 3) >= n
    # degenerate multiples keep the plain pow-2 grid
    assert [dispatch_pad(n, 1) for n in (1, 5, 9)] == [1, 8, 16]
    # the shape set stays bounded: one padded size per pow-2 value
    assert len({dispatch_pad(n, 3) for n in range(1, 65)}) <= 7


def test_pow2_padding_bounds_trace_count():
    emb, engine, _ = _make_engine()
    backend = RuntimeBackend(engine)
    fe = RetrievalFrontend(
        backend,
        FrontendConfig(m=M, max_batch=64, queue_capacity=128, cache=True),
    )
    rng = np.random.default_rng(3)
    sizes = [1, 2, 3, 5, 7, 11, 13, 17, 23, 31, 43, 57, 64, 6, 29]
    for n in sizes:
        rows = rng.integers(0, emb.shape[0], size=n)
        fe.search(emb[rows])
    # every dispatch shape is a power of two <= 64: at most 7 distinct
    # shapes regardless of the arrival-size mix (and of cache hit layout)
    assert backend.traces <= 7
    assert backend.sketch_traces <= 7
    assert fe.stats.batches >= 1


# -----------------------------------------------------------------------------
# admission control
# -----------------------------------------------------------------------------


def test_ring_full_pushback_is_retryable():
    """A full ring pushes back with the RETRYABLE `RING_FULL` sentinel —
    counted in `stats.ring_full`, NOT in `rejected` (an admission shed):
    the two failure modes used to collapse into one None + reject count.
    A retry after one `step` (which drains a batch) must succeed."""
    emb, engine, _ = _make_engine()
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=4, queue_capacity=8, cache=False),
    )
    tickets = [fe.submit(emb[i]) for i in range(12)]
    ok = [t for t in tickets if not isinstance(t, SubmitReject)]
    assert len(ok) == 8
    assert all(t is RING_FULL and t.retryable for t in tickets[8:])
    assert not any(tickets[8:])  # falsy, so `if not ticket` still works
    assert fe.stats.ring_full == 4
    assert fe.stats.rejected == 0 and fe.stats.accepted == 8
    # transient: one step drains max_batch=4 rows, the retry is admitted
    fe.step()
    t = fe.submit(emb[8])
    assert not isinstance(t, SubmitReject)
    fe.flush()
    assert fe.stats.completed == 9
    got = [fe.poll(k) for k in ok + [t]]
    assert all(g is not None for g in got)


def test_admission_limit_sheds_with_admit_reject():
    """`admit_limit` counts ring + in-flight rows; beyond it `submit`
    sheds with the NON-retryable `ADMIT_REJECT` sentinel, counted in
    `stats.rejected` (kept apart from ring_full pushback)."""
    emb, engine, _ = _make_engine()
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=4, queue_capacity=16, cache=False,
                       admit_limit=6),
    )
    tickets = [fe.submit(emb[i]) for i in range(9)]
    ok = [t for t in tickets if not isinstance(t, SubmitReject)]
    assert len(ok) == 6
    assert all(t is ADMIT_REJECT and not t.retryable for t in tickets[6:])
    assert fe.stats.rejected == 3 and fe.stats.ring_full == 0
    fe.flush()
    assert fe.stats.completed == 6
    assert all(fe.poll(t) is not None for t in ok)


def test_cache_hit_bypasses_full_ring():
    """Intake-time cache lookup: a hit during a FULL ring still completes
    immediately — it never occupies a ring or dispatch-queue slot, so
    queued misses cannot backpressure hits (no priority inversion)."""
    emb, engine, _ = _make_engine()
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=4, queue_capacity=4, cache=True),
    )
    # prime the cache with one served query
    ids0, sc0 = fe.search(emb[:1])
    # fill the ring to capacity with distinct misses
    fillers = [fe.submit(emb[10 + i]) for i in range(4)]
    assert all(not isinstance(t, SubmitReject) for t in fillers)
    assert isinstance(fe.submit(emb[30]), SubmitReject)  # ring really full
    # the primed query again: full ring, but it must be served NOW
    t_hit = fe.submit(emb[0])
    assert not isinstance(t_hit, SubmitReject)
    got = fe.poll(t_hit)
    assert got is not None
    np.testing.assert_array_equal(got[0], ids0[0])
    assert fe.stats.cache_hits == 1
    assert fe.pending == 4  # the queued misses are all still waiting
    fe.flush()


# -----------------------------------------------------------------------------
# the query cache: keying, LRU, generation invalidation
# -----------------------------------------------------------------------------


def test_qcache_lru_and_generation():
    c = QueryCache(capacity=2)
    q = np.ones((4,), np.float32)
    k1 = c.key([1, 2, 3], -2, q)
    k2 = c.key([1, 2, 4], -2, q)
    k3 = c.key([9, 9, 9], -2, q)
    ids = np.arange(3)
    c.put(k1, ids, ids, generation=5)
    c.put(k2, ids, ids, generation=5)
    assert c.get(k1, 5) is not None          # hit refreshes recency
    c.put(k3, ids, ids, generation=5)        # evicts k2 (LRU)
    assert c.get(k2, 5) is None and c.lru_evictions == 1
    # same key, older generation: evicted, never served
    assert c.get(k1, 6) is None
    assert c.stale_evictions == 1
    assert c.get(k1, 5) is None              # really gone
    # exclusion id and query bytes are part of the exact-mode key
    assert c.key([1, 2, 3], -2, q) != c.key([1, 2, 3], 7, q)
    q2 = q.copy(); q2[0] = 0.5
    assert c.key([1, 2, 3], -2, q) != c.key([1, 2, 3], -2, q2)
    # sketch-only mode shares entries across same-sketch queries
    c_approx = QueryCache(capacity=2, sketch_only=True)
    assert c_approx.key([1, 2, 3], -2, q) == c_approx.key([1, 2, 3], -2, q2)


def test_cache_key_includes_m():
    """Regression: the cache key used to omit the requested top-m, so an
    entry computed at a small m could serve a larger-m request TRUNCATED
    (correct prefix, silently missing tail).  m is now part of the key."""
    c = QueryCache()
    q = np.ones((4,), np.float32)
    assert c.key([1, 2, 3], -2, q, m=4) != c.key([1, 2, 3], -2, q, m=8)
    c_approx = QueryCache(sketch_only=True)
    assert c_approx.key([1, 2, 3], -2, m=4) != c_approx.key([1, 2, 3], -2,
                                                           m=8)
    # end-to-end: one shared cache behind two serving depths — the m=8
    # frontend must recompute, never serve the m=4 entry's prefix
    emb, engine, _ = _make_engine()
    backend = RuntimeBackend(engine)
    fe4 = RetrievalFrontend(
        backend, FrontendConfig(m=4, max_batch=16, queue_capacity=64,
                                cache=True),
    )
    q = emb[:8]
    ids4, _ = fe4.search(q)
    assert ids4.shape[1] == 4
    fe8 = RetrievalFrontend(
        backend, FrontendConfig(m=M, max_batch=16, queue_capacity=64,
                                cache=True),
    )
    fe8.cache = fe4.cache  # the two depths share one result cache
    ids8, _ = fe8.search(q)
    ref = engine.search(jnp.asarray(q), m=M)
    np.testing.assert_array_equal(ids8, ref.ids)
    # and the m=4 entries are still served at m=4 (distinct key spaces)
    ids4b, _ = fe4.search(q)
    np.testing.assert_array_equal(ids4b, ids4)


def test_store_generation_bumps():
    store = make_store(L, 1 << K, 8)
    assert int(store.generation) == 0
    ids = jnp.arange(4, dtype=jnp.int32)
    codes = jnp.zeros((4, L), jnp.uint32)
    store2 = insert_batch(store, ids, codes, jnp.int32(1))
    assert int(store2.generation) == L  # one bump per table insert
    store3 = expire(store2, jnp.int32(10), ttl=2)
    assert int(store3.generation) == L + 1


def test_cache_never_serves_stale_after_churn():
    emb, engine, codes = _make_engine(n=200)
    backend = RuntimeBackend(engine)
    fe = RetrievalFrontend(
        backend,
        FrontendConfig(m=M, max_batch=16, queue_capacity=64, cache=True),
    )
    q = emb[:16]
    ids1, _ = fe.search(q)
    assert fe.search(q)[0] is not None and fe.stats.cache_hits == 16

    # write epoch: insert near-duplicates of the queries under new ids
    n = emb.shape[0]
    store = engine.store
    dup = emb[:16]
    dup_codes = sketch_codes_batched(jnp.asarray(dup), engine.hyperplanes)
    new_ids = jnp.arange(n, n + 16, dtype=jnp.int32)
    store = insert_batch(store, new_ids, jnp.asarray(dup_codes), jnp.int32(1))
    corpus = DenseCorpus(jnp.asarray(np.concatenate([emb, dup])))
    backend.update(store, corpus)

    ids3, _ = fe.search(q)
    # the near-duplicate (cosine 1.0) MUST now appear in every result row
    # (right after the query's own id, which wins the equal-score tie by
    # lower id): a stale cache entry could not contain ids >= n
    assert np.all(ids3[:, 0] == np.arange(16))
    assert np.all(ids3[:, 1] == np.arange(n, n + 16))
    assert fe.cache.stale_evictions == 16

    # expire everything: served results must reflect the empty store
    store = expire(store, jnp.int32(100), ttl=1)
    backend.update(store)
    ids4, _ = fe.search(q)
    assert np.all(ids4 == -1)


def test_corpus_only_update_invalidates_cache():
    """A corpus swap changes scores even with the store untouched: the
    backend generation must bump on EVERY update, not only store bumps."""
    emb, engine, _ = _make_engine(n=100)
    backend = RuntimeBackend(engine)
    fe = RetrievalFrontend(
        backend,
        FrontendConfig(m=M, max_batch=16, queue_capacity=64, cache=True),
    )
    q = emb[:4]
    ids1, sc1 = fe.search(q)
    gen0 = backend.generation
    # same store object, new corpus: every indexed vector now equals
    # query 0, so all scores against q[0] become exactly 1.0
    emb2 = np.tile(emb[0], (emb.shape[0], 1)).astype(np.float32)
    backend.update(engine.store, DenseCorpus(jnp.asarray(emb2)))
    assert backend.generation > gen0
    ids2, sc2 = fe.search(q)
    assert fe.cache.stale_evictions == 4  # old entries died, none served
    live = ids2[0] >= 0
    np.testing.assert_allclose(sc2[0][live], 1.0, atol=1e-6)


# -----------------------------------------------------------------------------
# telemetry
# -----------------------------------------------------------------------------


def test_telemetry_aggregates_cost_and_drops():
    s = ServeStats()
    cost = costmodel.table1("cnb", k=6, L=4, bucket_size=2.0)
    s.record_submit(True)
    s.record_submit(True)
    s.record_submit(False)
    s.record_batch(2, 6, dropped_probes=3, cost=cost)
    s.record_done(100.0, hit=False)
    s.record_done(300.0, hit=False)
    out = s.summary()
    assert out["accepted"] == 2 and out["rejected"] == 1
    assert out["dropped_probes"] == 3
    assert out["padded"] == 6
    assert out["messages_per_query"] == pytest.approx(cost.messages)
    assert out["vectors_searched_per_query"] == pytest.approx(
        cost.vectors_searched)
    assert out["p50_us"] == pytest.approx(200.0)
    assert out["p99_us"] <= 300.0
    # format_summary is the driver's human surface — must not raise
    assert "dropped_probes=3" in s.format_summary()


def test_telemetry_empty_summary_is_finite():
    """Regression: before anything completes, qps/percentiles must be
    well-defined zeros, not nan (np.percentile of an empty array) — a
    crashed run's partial summary still has to print and aggregate."""
    s = ServeStats()
    out = s.summary()
    for key, v in out.items():
        if isinstance(v, float):
            assert np.isfinite(v), key
    assert out["qps"] == 0.0
    assert out["p50_us"] == 0.0 and out["p99_us"] == 0.0
    assert "nodes/query=" in s.format_summary()  # must not raise either


def test_telemetry_surfaces_nodes_contacted():
    """`nodes_contacted` was accumulated but never read out: Table 1's
    FIRST column (nodes contacted per query) now rides summary() and
    format_summary(), hit-rate discounted like messages_per_query."""
    s = ServeStats()
    cost = costmodel.table1("cnb", k=6, L=4, bucket_size=2.0)
    s.record_batch(2, 0, dropped_probes=0, cost=cost)
    s.record_done(10.0, hit=False)
    s.record_done(10.0, hit=False)
    s.record_done(5.0, hit=True)  # a cache hit contacts no node
    assert s.summary()["nodes_contacted_per_query"] == pytest.approx(
        cost.nodes_contacted * 2 / 3)
    assert f"nodes/query={cost.nodes_contacted * 2 / 3:.1f}" \
        in s.format_summary()


def test_telemetry_latency_window_is_bounded():
    s = ServeStats(latency_window=4)
    for i in range(10):
        s.record_done(float(i), hit=False)
    # only the last `latency_window` samples are retained (ring)
    assert s.latencies_us.size == 4
    assert sorted(s.latencies_us) == [6.0, 7.0, 8.0, 9.0]
    assert s.completed == 10
    assert s.percentile(50) == pytest.approx(7.5)


def test_queue_times_match_per_row_ring():
    """One `record_queue_times` call per batch keeps exactly the ring a
    per-row loop keeps, a batch wider than the ring included, and sums
    every row into `queued_us`."""
    window = 5
    s = ServeStats(latency_window=window)
    ring = np.empty((window,), np.float64)
    staged = 0
    for batch in ([1.0, 2.0, 3.0], [4.0, 5.0, 6.0, 7.0], list(range(8, 20))):
        s.record_queue_times(np.asarray(batch, np.float64))
        for us in batch:   # the per-row reference
            ring[staged % window] = us
            staged += 1
        np.testing.assert_array_equal(s._queue, ring)
    assert s.staged == staged == 19
    assert s.queued_us == sum(range(1, 20))
    assert s.queue_percentile(50) == np.percentile(ring, 50)


def test_queued_us_sums_ring_wait_of_live_rows(monkeypatch):
    """`ServeStats.queued_us` is the sum over dispatched rows of stage
    time minus submit time, on a hand-driven frontend and clock."""
    import time

    emb, engine, _ = _make_engine()
    fe = RetrievalFrontend(
        RuntimeBackend(engine),
        FrontendConfig(m=M, max_batch=4, queue_capacity=16, cache=False))
    clock = [100.0]
    monkeypatch.setattr(time, "perf_counter", lambda: clock[0])
    expect = 0.0
    for submits, stage_at in (([100.0, 100.5, 101.0], 102.0),
                              ([103.0, 103.25, 103.5, 103.75, 104.0], 106.0)):
        for t, q in zip(submits, emb):
            clock[0] = t
            fe.submit(q)
        clock[0] = stage_at
        while fe.pending:
            live = min(fe.pending, fe.cfg.max_batch)
            expect += sum(stage_at - t for t in submits[-fe.pending:]
                          [:live]) * 1e6
            fe.step()
    fe.flush()
    assert fe.stats.dispatched == 8
    assert fe.stats.queued_us == pytest.approx(expect)
    assert expect == pytest.approx((2.0 + 1.5 + 1.0 + 3.0 + 2.75 + 2.5
                                    + 2.25 + 2.0) * 1e6)


# -----------------------------------------------------------------------------
# mesh-step backend (single device, 1-shard — tier-1)
# -----------------------------------------------------------------------------


def test_mesh_backend_matches_engine(single_mesh):
    from repro.core import distributed as dist
    from repro.core.runtime import IndexRuntime

    emb, engine, codes = _make_engine(payload=True)
    store = dist.shard_store(single_mesh, engine.store)
    dcfg = dist.DistConfig(
        params=engine.params, n_shards=1, variant="cnb", m=M + 1,
        routing="alltoall", cap_factor=2.0,
    )
    backend = RuntimeBackend(
        IndexRuntime(dcfg, mesh=single_mesh),
        hyperplanes=engine.hyperplanes, store=store,
    )
    fe = RetrievalFrontend(
        backend, FrontendConfig(m=M, max_batch=16, queue_capacity=64,
                                cache=True),
    )
    q = emb[:20]
    ex = np.arange(20)
    ids, _ = fe.search(q, exclude=ex)
    ref = engine.search(jnp.asarray(q), m=M, exclude=ex)
    np.testing.assert_array_equal(ids, ref.ids)
    # repeats hit the cache and stay identical
    ids2, _ = fe.search(q, exclude=ex)
    np.testing.assert_array_equal(ids2, ids)
    assert fe.stats.cache_hits == 20
    assert fe.stats.dropped_probes == 0


def test_mesh_backend_surfaces_dropped_probes(single_mesh):
    from repro.core import distributed as dist
    from repro.core.runtime import IndexRuntime

    emb, engine, codes = _make_engine(payload=True)
    store = dist.shard_store(single_mesh, engine.store)
    # cap_factor < 1 under-provisions the send buffers on purpose: the
    # router MUST count the overflow, and the frontend MUST surface it
    dcfg = dist.DistConfig(
        params=engine.params, n_shards=1, variant="cnb", m=M + 1,
        routing="alltoall", cap_factor=0.25,
    )
    backend = RuntimeBackend(
        IndexRuntime(dcfg, mesh=single_mesh),
        hyperplanes=engine.hyperplanes, store=store,
    )
    fe = RetrievalFrontend(
        backend, FrontendConfig(m=M, max_batch=16, queue_capacity=64,
                                cache=False),
    )
    fe.search(emb[:16])
    assert fe.stats.dropped_probes > 0
    assert fe.stats.summary()["dropped_probes"] == fe.stats.dropped_probes


def test_backend_update_enforces_topology_guards(single_mesh):
    """update() keeps __init__'s topology rules: a corpus on a mesh
    backend (or a neighbor cache on a 1-node backend) must raise, never
    be silently ignored."""
    from repro.core import distributed as dist
    from repro.core.runtime import IndexRuntime

    emb, engine, _ = _make_engine(payload=True)
    store = dist.shard_store(single_mesh, engine.store)
    dcfg = dist.DistConfig(
        params=engine.params, n_shards=1, variant="cnb", m=M + 1,
        routing="alltoall", cap_factor=2.0,
    )
    mesh_backend = RuntimeBackend(
        IndexRuntime(dcfg, mesh=single_mesh),
        hyperplanes=engine.hyperplanes, store=store,
    )
    with pytest.raises(ValueError, match="1-node only"):
        mesh_backend.update(store, corpus=engine.corpus)
    local_backend = RuntimeBackend(engine)
    with pytest.raises(ValueError, match="mesh runtimes"):
        local_backend.update(engine.store, cache=(None, None))


# -----------------------------------------------------------------------------
# live topology swaps (elastic membership on the serving path, Sec. 9)
# -----------------------------------------------------------------------------


def _payload_backend(single_mesh):
    """1-node slot-payload backend + the two runtimes a single device can
    host (mesh-free and 1-shard shard_map), sharing one RuntimeConfig."""
    from repro.core.runtime import IndexRuntime, RuntimeConfig

    emb, engine, _ = _make_engine(payload=True)
    store = engine.store  # payload-carrying store
    rcfg = RuntimeConfig(params=engine.params, variant="cnb", m=M + 1,
                         cap_factor=2.0)
    rt_local = IndexRuntime(rcfg)
    rt_mesh = IndexRuntime(rcfg, mesh=single_mesh)
    backend = RuntimeBackend(rt_local, hyperplanes=engine.hyperplanes,
                             store=store)
    return emb, engine, store, backend, rt_local, rt_mesh


def test_topology_swap_bumps_generation_never_serves_stale(single_mesh):
    """Any topology swap through RuntimeBackend.update() bumps the
    backend generation, so no sketch-keyed cache entry computed on the
    old topology is ever served after a reshard — and the recomputed
    results are bit-identical (the reshard contract, live)."""
    from repro.core.runtime import reshard

    emb, engine, store, backend, rt_local, rt_mesh = _payload_backend(
        single_mesh)
    fe = RetrievalFrontend(
        backend, FrontendConfig(m=M, max_batch=16, queue_capacity=64,
                                cache=True),
    )
    q = emb[:20]
    ex = np.arange(20)
    ids_pre, sc_pre = fe.search(q, exclude=ex)
    ids_rep, _ = fe.search(q, exclude=ex)
    np.testing.assert_array_equal(ids_rep, ids_pre)
    assert fe.stats.cache_hits == 20  # warm within the generation
    gen0 = backend.generation

    # -- the membership round: 1-node -> 1-shard mesh ----------------------
    rt2, store2, _ = reshard(rt_local, store, runtime=rt_mesh)
    fe.update_backend(runtime=rt2, store=store2)
    assert backend.generation > gen0  # every swap bumps
    hits_before = fe.stats.cache_hits
    ids_post, _ = fe.search(q, exclude=ex)
    # nothing was served from the pre-swap cache...
    assert fe.stats.cache_hits == hits_before
    assert fe.cache.stale_evictions >= 20
    # ...and the new topology recomputed the SAME results
    np.testing.assert_array_equal(ids_post, ids_pre)
    # post-swap repeats hit again (the cache works within the new gen)
    ids_post2, _ = fe.search(q, exclude=ex)
    np.testing.assert_array_equal(ids_post2, ids_pre)
    assert fe.stats.cache_hits == hits_before + 20

    # -- and back: mesh -> 1-node (the cache dies again) -------------------
    gen1 = backend.generation
    rt3, store3, _ = reshard(rt2, store2, runtime=rt_local)
    fe.update_backend(runtime=rt3, store=store3)
    assert backend.generation > gen1
    ids_back, _ = fe.search(q, exclude=ex)
    np.testing.assert_array_equal(ids_back, ids_pre)


def test_topology_swap_argument_guards(single_mesh):
    """A swap without the migrated store, hyperplanes outside a swap, or
    a serving m over the new runtime's wire headroom must all raise."""
    from repro.core.runtime import IndexRuntime, RuntimeConfig

    emb, engine, store, backend, rt_local, rt_mesh = _payload_backend(
        single_mesh)
    with pytest.raises(ValueError, match="migrated store"):
        backend.update(runtime=rt_mesh)
    with pytest.raises(ValueError, match="runtime swap"):
        backend.update(store, hyperplanes=engine.hyperplanes)
    # an ids-only store cannot back a mesh dispatch (slot-payload scoring)
    # — must fail validation, not blow up at trace time half-mutated
    from repro.core import distributed as dist0
    ids_store = _make_engine(payload=False)[1].store
    with pytest.raises(ValueError, match="payload-carrying"):
        backend.update(runtime=rt_mesh,
                       store=dist0.shard_store(single_mesh, ids_store))

    fe = RetrievalFrontend(
        backend, FrontendConfig(m=M, max_batch=8, queue_capacity=32,
                                cache=True),
    )
    # a mesh runtime with NO headroom for host-side self-exclusion
    tight = IndexRuntime(
        RuntimeConfig(params=engine.params, variant="cnb", m=M,
                      cap_factor=2.0),
        mesh=single_mesh,
    )
    from repro.core import distributed as dist
    with pytest.raises(ValueError, match="headroom"):
        fe.update_backend(runtime=tight,
                          store=dist.shard_store(single_mesh, store))
    # the failed swap installed nothing: the backend still serves
    ids, _ = fe.search(emb[:4], exclude=np.arange(4))
    assert ids.shape == (4, M)


def test_serve_reshard_tracks_reference(single_mesh):
    """The lifecycle driver: live swaps at every read epoch track the
    run_churn reference exactly, repeats across swaps stay identical,
    and the swap count / stale evictions prove the cache died each
    time."""
    from repro.serve.lifecycle import run_serve_reshard

    churn = ChurnConfig(
        num_users=400, dim=D, k=K, L=L, capacity=32, epochs=4,
        num_queries=32, m=M, refresh_every=2, ttl_epochs=3, seed=5,
    )
    ref = run_churn(churn)
    out = run_serve_reshard(
        ServeChurnConfig(churn=churn, max_batch=16, queue_capacity=64),
        mesh=single_mesh,
    )
    np.testing.assert_allclose(out["recalls"], ref["recalls"])
    assert out["repeat_mismatches"] == 0
    assert out["swaps"] == 4  # one per read epoch
    # every swap invalidated that epoch's freshly-cached batch
    assert out["stale_evictions"] >= 4 * 32
    # the third serve of each epoch hit the post-swap cache
    assert out["cache_hits"] >= 4 * 32
    # degenerate 1 <-> 1-shard rounds move no zone state
    assert out["total_handoff_bytes"] == 0


# -----------------------------------------------------------------------------
# read/write epochs: serving under live churn
# -----------------------------------------------------------------------------


def test_serve_churn_tracks_reference_trajectory():
    churn = ChurnConfig(
        num_users=400, dim=D, k=K, L=L, capacity=32, epochs=4,
        num_queries=32, m=M, refresh_every=2, ttl_epochs=3, seed=5,
    )
    ref = run_churn(churn)
    out = run_serve_churn(ServeChurnConfig(
        churn=churn, query_repeats=2, max_batch=16, queue_capacity=64,
    ))
    # same trajectory, same store ops, same engine semantics -> recall
    # matches the fresh-engine-per-epoch reference EXACTLY
    np.testing.assert_allclose(out["recalls"], ref["recalls"])
    assert out["repeat_mismatches"] == 0
    # the repeats were served from the cache within each generation
    assert out["summary"]["hit_rate"] > 0.3
    # write epochs bumped the generation monotonically
    gens = out["generations"]
    assert np.all(np.diff(gens) >= 0) and gens[-1] > gens[0]
    assert out["store_generation"] == gens[-1]


def test_serve_churn_config_fields():
    cfg = ServeChurnConfig()
    assert dataclasses.is_dataclass(cfg) and cfg.query_repeats >= 1


@pytest.mark.slow
def test_mesh_backend_on_non_pow2_mesh():
    """Non-pow-2 DEVICE count (data=3 — the model axis must stay a power
    of two for the CAN geometry): dispatch sizes must round up to
    multiples of the device count, since a bare pow-2 pad would fail
    NamedSharding placement."""
    from conftest import run_in_subprocess

    out = run_in_subprocess(
        """
        import numpy as np, jax.numpy as jnp
        from repro.core import (
            DenseCorpus, EngineConfig, LshEngine, LshParams,
            make_hyperplanes,
        )
        from repro.core import distributed as dist
        from repro.core.hashing import sketch_codes_batched
        from repro.core.runtime import IndexRuntime
        from repro.core.store import build_store_host
        from repro.launch.mesh import make_host_mesh
        from repro.serve import FrontendConfig, RetrievalFrontend, RuntimeBackend

        M = 8
        rng = np.random.default_rng(0)
        emb = rng.standard_normal((300, 16)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=1, keepdims=True)
        params = LshParams(d=16, k=5, L=3, seed=1)
        h = make_hyperplanes(params)
        codes = sketch_codes_batched(jnp.asarray(emb), h)
        store_host = build_store_host(
            codes, params.num_buckets, capacity=32, payload=emb)
        engine = LshEngine(
            params, h, store_host, DenseCorpus(jnp.asarray(emb)), None,
            EngineConfig(variant="cnb"))

        mesh = make_host_mesh(data=3, model=1)
        store = dist.shard_store(mesh, store_host)
        dcfg = dist.DistConfig(
            params=params, n_shards=1, variant="cnb", m=M + 1,
            routing="alltoall", cap_factor=3.0)
        backend = RuntimeBackend(IndexRuntime(dcfg, mesh=mesh),
                                 hyperplanes=h, store=store)
        fe = RetrievalFrontend(backend, FrontendConfig(
            m=M, max_batch=16, queue_capacity=64, cache=True))
        # 2 pending rows on 3 devices: pad must be 6, not pow2(2)=4
        q, ex = emb[:2], np.arange(2)
        ids, _ = fe.search(q, exclude=ex)
        ref = engine.search(jnp.asarray(q), m=M, exclude=ex)
        assert np.array_equal(ids, ref.ids), (ids, ref.ids)
        ids20, _ = fe.search(emb[:20], exclude=np.arange(20))
        ref20 = engine.search(
            jnp.asarray(emb[:20]), m=M, exclude=np.arange(20))
        assert np.array_equal(ids20, ref20.ids)
        assert fe.stats.dropped_probes == 0
        print("OK", fe.stats.completed)
        """,
        devices=3,
    )
    assert "OK 22" in out

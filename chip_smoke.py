#!/usr/bin/env python3
"""Bring-up smoke: the served LSH index on a TPU, at 1M-vector scale.

    python chip_smoke.py             # one chip: the served 1-node index
    python chip_smoke.py --chips 4   # four chips: the sharded CAN zones only

One chip.  Builds one deployment from `--seed`: N = 1,000,000 unit
vectors (d = 128) in a cnb index with k = 14, L = 4, capacity 128, m = 10.
The dot store carries its own f32 payload (~4.3 GB of HBM); a second
store carries the packed sketch words (W = 2) for `score="hamming"`.
Both are served through `RuntimeBackend` + `RetrievalFrontend`
(pipeline depth 2, max batch 64, every pow-2 dispatch shape warmed
first).  Phases: 2,048 zipf-skewed queries in dot and in hamming mode;
`contains` on 4,096 stored ids; one write epoch (2% of the corpus
re-announced with drift, then TTL expiry) followed by 512 more queries.
Every phase is checked against the same `IndexRuntime` under
`fused="off"` on the same device store — the tests' oracle: dot ids
bit-identical and scores within 1e-5, hamming ids and scores
bit-identical, every `contains` target found.  The search step must
lower to a Mosaic kernel (`tpu_custom_call`).

Four chips.  The same corpus on `n_nodes=4` CAN zones over one 4-device
mesh (cnb, replication 2, first-responder reads, the default router
capacity): the store's shards must sit on 4 devices, pre-kill ids must
equal the 1-node `fused="off"` reference with no dropped probe, and after
`kill_node(1)` every query is still answered with recall within 0.05 of
the pre-kill recall at the failure harness's router capacity (cap_factor
n; the default capacity's post-kill drops are printed as a reading).

Lines starting "smoke reading:" are bring-up readings (one run, host
clock), not benchmark numbers.  The last line is the JSON verdict.
Without a TPU the script exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


@dataclasses.dataclass(frozen=True)
class Deployment:
    n: int = 1_000_000        # corpus vectors
    d: int = 128
    k: int = 14               # 2^14 buckets per table
    L: int = 4
    capacity: int = 128
    m: int = 10
    max_batch: int = 64
    chunk: int = 50_000       # insert batch; chunk 0 departs (announced
    #                           at t=0, collected by the write epoch's GC)
    n_queries: int = 2048
    n_contains: int = 4096
    n_after: int = 512        # queries after the write epoch
    cluster: int = 16         # vectors per cluster: a query's true
    spread: float = 0.35      # neighbours are its cluster mates (cosine
    #                           ~1/(1+spread^2) to each other)
    churn: float = 0.02       # share of the corpus re-announced
    drift: float = 0.3        # re-announced vectors move by ~this norm
    seed: int = 0


def expect(ok, msg: str) -> None:
    """A smoke check (kept under `python -O`, unlike assert)."""
    if not ok:
        raise AssertionError(msg)


def reading(msg: str) -> None:
    print(f"smoke reading: {msg}", flush=True)


def unit(x):
    import jax.numpy as jnp

    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def make_corpus(dep: Deployment):
    """Clustered unit vectors, made on the device: ids c*cluster ..
    (c+1)*cluster - 1 scatter around one random centre, as embeddings of
    related items do."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        kc, kx = jax.random.split(key)
        centres = unit(jax.random.normal(
            kc, (dep.n // dep.cluster, 1, dep.d), jnp.float32))
        noise = jax.random.normal(
            kx, (dep.n // dep.cluster, dep.cluster, dep.d), jnp.float32)
        x = unit(centres + dep.spread / dep.d**0.5 * noise)
        return x.reshape(dep.n, dep.d)

    return gen(jax.random.key(dep.seed))


def build_store(dep: Deployment, rt, h, vecs):
    """Announce the corpus through the runtime's insert step, in chunks."""
    import jax.numpy as jnp

    from repro.core import make_store, packed

    hamming = rt.cfg.score == "hamming"
    store = make_store(
        dep.L, 2**dep.k, dep.capacity,
        payload_dim=packed.num_words(dep.k, dep.L) if hamming else dep.d,
        dtype=jnp.uint32 if hamming else jnp.float32,
    )
    for c0 in range(0, dep.n, dep.chunk):
        ids = np.arange(c0, c0 + dep.chunk, dtype=np.int32)
        store = rt.insert(h, store, vecs[c0:c0 + dep.chunk], ids,
                          0 if c0 == 0 else 1)
    return store


def zipf_ids(rng, n: int, size: int) -> np.ndarray:
    """Zipf(1.2)-skewed ids over a random hot set."""
    hot = rng.permutation(n)
    return hot[np.minimum(rng.zipf(1.2, size), n) - 1].astype(np.int32)


def reference_search(rt_ref, h, store, vecs, ids, batch, *, exclude=True):
    out_i, out_s = [], []
    for b0 in range(0, len(ids), batch):
        b = ids[b0:b0 + batch]
        i, s, _ = rt_ref.search(h, store, vecs[b],
                                exclude=b if exclude else None)
        out_i.append(np.asarray(i))
        out_s.append(np.asarray(s))
    return np.concatenate(out_i), np.concatenate(out_s)


def check_same(name, got, want, *, exact: bool) -> None:
    gi, gs = got
    wi, ws = want
    bad = np.flatnonzero(np.any(gi != wi, axis=1))
    if bad.size:
        j = bad[0]
        raise AssertionError(
            f"{name}: ids differ in {bad.size}/{len(gi)} queries; first "
            f"q{j}: {gi[j].tolist()} {gs[j].tolist()} vs "
            f"{wi[j].tolist()} {ws[j].tolist()}")
    if exact:
        np.testing.assert_array_equal(gs, ws, err_msg=name)
    else:
        np.testing.assert_allclose(gs, ws, rtol=0, atol=1e-5, err_msg=name)
    print(f"{name}: {len(gi)} queries match the fused='off' reference",
          flush=True)


def exact_topm(vecs, q_ids, m: int, *, exclude=True, batch: int = 128):
    """Brute-force top-m ids over the whole corpus (recall ground truth)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def top(vecs, q, ex):
        sims = jnp.dot(q, vecs.T, precision=jax.lax.Precision.HIGHEST)
        col = jnp.arange(vecs.shape[0])[None, :]
        sims = jnp.where(col == ex[:, None], -jnp.inf, sims)
        return jax.lax.top_k(sims, m)[1]

    out = []
    for b0 in range(0, len(q_ids), batch):
        b = q_ids[b0:b0 + batch]
        ex = b if exclude else np.full_like(b, -1)
        out.append(np.asarray(top(vecs, vecs[b], jnp.asarray(ex))))
    return np.concatenate(out)


def recall(got: np.ndarray, ideal: np.ndarray) -> float:
    from repro.core import metrics

    return float(metrics.recall_at_m(got, ideal))


def compile_clock() -> collections.Counter:
    """Seconds jax spends tracing, lowering and compiling (backend compile
    includes persistent-cache reads), and the persistent-cache hits."""
    from jax import monitoring

    clock = collections.Counter()

    def on_duration(event: str, duration_secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            clock[event.rsplit("/", 1)[1]] += duration_secs

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            clock["persistent_cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return clock


def lowered_text(step, *args) -> str:
    return step.lower(*args).as_text()


def store_bytes(store) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(store))


def warm(backend, dep: Deployment) -> None:
    """Compile (and run once) every pow-2 dispatch shape up to max_batch."""
    from repro.serve.frontend import NO_EXCLUDE

    s = 1
    while s <= dep.max_batch:
        backend.dispatch(np.zeros((s, dep.d), np.float32),
                         np.full((s,), NO_EXCLUDE, np.int32), dep.m)
        s *= 2


def median_seconds(fn, reps: int = 5) -> float:
    """Median wall time of `fn()`, waited for with block_until_ready."""
    import jax

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def one_chip(dep: Deployment, cfg) -> None:
    """The served 1-node index, dot and hamming, against fused='off'."""
    import jax

    from repro.core import IndexRuntime, make_hyperplanes
    from repro.serve import FrontendConfig, RetrievalFrontend, RuntimeBackend

    rng = np.random.default_rng(dep.seed)
    h = make_hyperplanes(cfg.params)
    t0 = time.perf_counter()
    vecs = make_corpus(dep)
    modes = {}
    for score in ("dot", "hamming"):
        c = dataclasses.replace(cfg, score=score)
        rt = IndexRuntime(c)
        ref = IndexRuntime(dataclasses.replace(c, fused="off"))
        store = build_store(dep, rt, h, vecs)
        modes[score] = dict(rt=rt, ref=ref, store=store)
    jax.block_until_ready([v["store"] for v in modes.values()])
    reading(f"corpus + store build seconds {time.perf_counter() - t0:.3f}")
    for score, v in modes.items():
        reading(f"{score} store bytes {store_bytes(v['store'])}")

    t0 = time.perf_counter()
    for score, v in modes.items():
        v["backend"] = RuntimeBackend(v["rt"], hyperplanes=h,
                                      store=v["store"])
        v["fe"] = RetrievalFrontend(v["backend"], FrontendConfig(
            m=dep.m, max_batch=dep.max_batch, pipeline_depth=2))
        warm(v["backend"], dep)
    reading(f"warm-up (compile + first run of {2 * dep.max_batch.bit_length()}"
            f" dispatch shapes) seconds {time.perf_counter() - t0:.3f}")

    q64 = np.asarray(vecs[:dep.max_batch])
    ex64 = np.arange(dep.max_batch, dtype=np.int32)
    for score, v in modes.items():
        st = v["store"]
        text = lowered_text(v["rt"].make_search_step(), h, st.ids,
                            st.payload, q64, ex64, dep.m)
        expect("tpu_custom_call" in text, f"{score} search has no kernel")
    ctext = lowered_text(modes["dot"]["rt"].make_contains_step(), h,
                         modes["dot"]["store"].ids, q64, ex64)
    expect("tpu_custom_call" in ctext, "contains has no kernel")
    print("search (dot, hamming) and contains steps lower to "
          "tpu_custom_call", flush=True)

    # -- phase 1: zipf-skewed queries through the frontend ---------------
    qids = zipf_ids(rng, dep.n, dep.n_queries)
    ideal = exact_topm(vecs, qids, dep.m)
    for score, v in modes.items():
        got = v["fe"].search(np.asarray(vecs[qids]), exclude=qids)
        want = reference_search(v["ref"], h, v["store"], vecs, qids,
                                dep.max_batch)
        check_same(f"phase 1 {score}", got, want, exact=score == "hamming")
        reading(f"phase 1 {score} recall@{dep.m} {recall(got[0], ideal)}")

    # -- phase 2: contains on stored ids ----------------------------------
    targets = rng.choice(np.arange(dep.chunk, dep.n), dep.n_contains,
                         replace=False).astype(np.int32)
    rt, ref, st = (modes["dot"][k] for k in ("rt", "ref", "store"))
    for b0 in range(0, dep.n_contains, dep.max_batch):
        tb = targets[b0:b0 + dep.max_batch]
        hit, _ = rt.contains(h, st, vecs[tb], tb)
        hit_ref, _ = ref.contains(h, st, vecs[tb], tb)
        np.testing.assert_array_equal(np.asarray(hit), np.asarray(hit_ref))
        expect(np.all(np.asarray(hit)), f"contains missed targets of {b0}")
    print(f"phase 2: all {dep.n_contains} contains targets found", flush=True)

    for score, v in modes.items():
        sec = median_seconds(
            lambda: v["backend"].dispatch(q64, ex64, dep.m))
        reading(f"{score} served (fused) batch of {dep.max_batch} seconds "
                f"{sec:.6f}")
        sec = median_seconds(
            lambda: v["ref"].search(h, v["store"], q64, exclude=ex64))
        reading(f"{score} fused='off' batch of {dep.max_batch} seconds "
                f"{sec:.6f}")

    # -- phase 3: one write epoch, then more queries ----------------------
    movers = rng.choice(dep.n, int(dep.churn * dep.n),
                        replace=False).astype(np.int32)
    drift = jax.random.normal(jax.random.key(dep.seed + 1),
                              (movers.size, dep.d))
    moved = unit(vecs[movers] + dep.drift / dep.d**0.5 * drift)
    vecs = vecs.at[movers].set(moved)
    for score, v in modes.items():
        st = v["rt"].insert(h, v["store"], moved, movers, 2)
        st = v["rt"].expire(st, 2, ttl=1)
        v["store"] = st
        v["backend"].update(store=st)
    qids = zipf_ids(rng, dep.n, dep.n_after)
    for score, v in modes.items():
        got = v["fe"].search(np.asarray(vecs[qids]), exclude=qids)
        want = reference_search(v["ref"], h, v["store"], vecs, qids,
                                dep.max_batch)
        check_same(f"phase 3 {score}", got, want, exact=score == "hamming")
    live = int(np.sum(np.asarray(modes["dot"]["store"].ids) >= 0))
    reading(f"live slots after the write epoch {live}")


def four_chips(dep: Deployment, cfg) -> None:
    """Sharded CAN zones (cnb, R = 2) vs the 1-node fused='off' run."""
    import jax

    from repro.core import IndexRuntime, make_hyperplanes
    from repro.core.runtime import kill_node
    from repro.launch.mesh import make_zone_mesh

    rng = np.random.default_rng(dep.seed)
    h = make_hyperplanes(cfg.params)
    vecs = make_corpus(dep)
    ref = IndexRuntime(dataclasses.replace(cfg, fused="off"))
    store1 = build_store(dep, ref, h, vecs)
    n = 4
    # served at the default router capacity (cap_factor 2.0): a zipf-hot
    # batch can overflow a destination's buffer, and a drop fails the
    # pre-kill check
    mesh = make_zone_mesh(n)
    rt = IndexRuntime(dataclasses.replace(cfg, n_nodes=n, replication=2,
                                          read_mode="first"), mesh=mesh)
    t0 = time.perf_counter()
    st = rt.shard_store(store1)
    devices = {s.device for s in st.payload.addressable_shards}
    expect(len(devices) == n, f"store shards on {len(devices)} devices")
    cache = rt.refresh_cache(st)
    reps = rt.replicate_store(st)
    jax.block_until_ready((st, cache, reps))
    reading(f"shard + cache + replicas seconds {time.perf_counter() - t0:.3f}")
    print(f"store shards on {len(devices)} devices", flush=True)

    qids = zipf_ids(rng, dep.n, dep.n_queries)
    want = reference_search(ref, h, store1, vecs, qids, dep.max_batch,
                            exclude=False)
    del store1
    ideal = exact_topm(vecs, qids, dep.m, exclude=False)

    def serve(rt, store, replicas, live):
        ids, dropped = [], 0
        for b0 in range(0, len(qids), dep.max_batch):
            b = qids[b0:b0 + dep.max_batch]
            i, _, stats = rt.search(h, store, vecs[b], cache=cache,
                                    replicas=replicas, live=live)
            ids.append(np.asarray(i))
            dropped += int(stats)
        return np.concatenate(ids), dropped

    q64 = np.asarray(vecs[:dep.max_batch])
    text = lowered_text(rt.make_search_step(), h, st.ids, st.payload,
                        *cache, *reps, np.ones(n, np.int32), q64)
    expect("tpu_custom_call" in text, "mesh search has no kernel")
    t0 = time.perf_counter()
    pre, dropped = serve(rt, st, reps, np.ones(n, np.int32))
    reading(f"pre-kill serve seconds (incl. compile) "
            f"{time.perf_counter() - t0:.3f}")
    expect(dropped == 0, f"{dropped} probes dropped at cap_factor "
                         f"{rt.cfg.cap_factor}")
    bad = np.flatnonzero(np.any(pre != want[0], axis=1))
    expect(bad.size == 0, f"pre-kill ids differ from the 1-node reference "
                          f"in {bad.size}/{len(qids)} queries")
    r_pre = recall(pre, ideal)
    print(f"pre-kill: {len(qids)} queries equal the 1-node reference, "
          f"0 dropped probes at cap_factor {rt.cfg.cap_factor}", flush=True)

    st_k, reps_k = kill_node(rt, st, reps, 1)
    live = np.ones(n, np.int32)
    live[1] = 0
    # node 1's rows now go to its replica holder, which takes twice its
    # share: at cap_factor 2.0 that buffer overflows, so the default-
    # capacity run is a reading, and the check runs at cap_factor n, the
    # capacity the failure harness (churn.make_churn_runtime) serves with
    post, dropped = serve(rt, st_k, reps_k, live)
    reading(f"post-kill at cap_factor {rt.cfg.cap_factor}: dropped probes "
            f"{dropped}, unanswered queries {int(np.sum(post[:, 0] < 0))}, "
            f"recall@{dep.m} {recall(post, ideal)}")
    rt_n = IndexRuntime(dataclasses.replace(rt.cfg, cap_factor=float(n)),
                        mesh=mesh)
    post, dropped = serve(rt_n, st_k, reps_k, live)
    expect(np.all(post[:, 0] >= 0), "a query went unanswered after the kill")
    r_post = recall(post, ideal)
    reading(f"recall@{dep.m} pre-kill {r_pre} post-kill at cap_factor "
            f"{rt_n.cfg.cap_factor} {r_post} (dropped probes {dropped})")
    expect(r_post >= r_pre - 0.05, f"recall fell {r_pre} -> {r_post}")
    print("post-kill: every query answered, recall within 0.05", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    print(f"jax {jax.__version__} platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", flush=True)
    if devs[0].platform != "tpu":
        sys.exit("chip_smoke: no TPU found; this smoke never runs on "
                 f"{devs[0].platform}")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, found {len(devs)}")

    from repro import compile_cache
    from repro.core import LshParams, RuntimeConfig

    print(f"compile cache {compile_cache.enable()}", flush=True)
    dep = Deployment(seed=args.seed)
    cfg = RuntimeConfig(
        params=LshParams(d=dep.d, k=dep.k, L=dep.L, seed=dep.seed),
        variant="cnb", m=dep.m,
    )
    clock = compile_clock()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(dep, cfg)
    reading(f"total seconds {time.perf_counter() - t0:.3f}")
    reading("compile seconds " + " ".join(
        f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in sorted(clock.items())))
    stats = devs[0].memory_stats() or {}
    reading(f"device 0 peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Dynamic-OSN churn simulation (paper Sec. 2.2 + Sec. 4.1 soft state).

The paper's data model: users join/leave and update their interest
profiles; bucket nodes hold *soft state* that users re-announce
periodically, and entries older than a TTL are garbage-collected.  The
paper asserts this keeps the index fresh at negligible cost (update rate
<< query rate) but runs no churn experiment — this module does:

  epoch loop:
    1. a fraction `update_rate` of users mutate their interest vectors
       (their true buckets move);
    2. a fraction `churn_rate` of users leave and are replaced by fresh
       users (new ids, new vectors);
    3. every `refresh_every` epochs, all live users re-announce and the
       store expires entries older than `ttl`;
    4. CNB-LSH recall@m is measured against the *current* ground truth.

Output: recall trajectory vs refresh period — the freshness/cost trade the
paper's design argues about, quantified.

ONE driver (`run_churn_runtime`) over ONE trajectory generator and ONE
execution layer (`repro.core.runtime.IndexRuntime`): the scenario loop is
topology-blind by construction — announces go through the runtime's
insert step, GC through its expire step, payload freshness through its
payload-sync step, the CNB neighbor cache (when the topology has node
bits) through its refresh step, and queries through its search step.

  * `run_churn(cfg)`             — the 1-node topology (the reference);
  * `run_churn_distributed(cfg)` — the same loop on a >= 2-shard host
    mesh (the paper's actual P2P scenario on the production code path).
    The two trajectories share the RNG stream and match EXACTLY
    (tests/test_churn.py asserts <= 0.02; in practice maxdiff 0.0).

Scoring uses the ANNOUNCED snapshot of each vector, not the live one:
the paper's LocalSimSearch runs at the bucket node against the copies
users last announced (Alg. 1), so between refreshes both the buckets AND
the scores are stale — recall is measured against the current ground
truth, which is exactly the freshness cost being quantified.  The
payload-sync step keeps re-announce semantics id-keyed (an entry left in
a mover's OLD bucket scores with its LATEST announced vector).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import costmodel, hashing, metrics
from repro.core.hashing import LshParams
from repro.obs.flight import QueryRecord
from repro.core.runtime import IndexRuntime, RuntimeConfig, kill_node, reshard
from repro.core.store import make_store


@dataclasses.dataclass(frozen=True)
class ChurnConfig:
    num_users: int = 4000
    dim: int = 64
    k: int = 6
    L: int = 4
    capacity: int = 128
    epochs: int = 12
    update_rate: float = 0.05     # users mutating their vector per epoch
    churn_rate: float = 0.02      # users replaced per epoch
    refresh_every: int = 2        # re-announce period (epochs)
    ttl_epochs: int = 4           # GC horizon
    mutation: float = 0.5         # vector drift magnitude on update
    num_queries: int = 128
    m: int = 10
    seed: int = 0


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _lsh_setup(cfg: ChurnConfig):
    params = LshParams(d=cfg.dim, k=cfg.k, L=cfg.L, seed=cfg.seed + 1)
    return params, hashing.make_hyperplanes(params)


def _trajectory(cfg: ChurnConfig):
    """Yield the per-epoch world state — one RNG stream shared by every
    driver, so 1-node and distributed runs see identical vectors, churn
    events, and query draws.

    Yields (epoch, vecs, do_refresh, qidx, ideal); epoch 0 is the initial
    announce (qidx/ideal None).  Every epoch's `vecs` is a fresh array
    that is never written again: the CPU backend may alias host arrays
    without copying, so mutating a yielded array in place would race the
    device steps still reading it.
    """
    rng = np.random.default_rng(cfg.seed)
    vecs = _unit(rng.standard_normal((cfg.num_users, cfg.dim))).astype(
        np.float32
    )
    yield 0, vecs, True, None, None

    for epoch in range(1, cfg.epochs + 1):
        vecs = vecs.copy()
        # 1. profile updates (vector drift)
        n_upd = int(cfg.update_rate * cfg.num_users)
        upd = rng.choice(cfg.num_users, n_upd, replace=False)
        vecs[upd] = _unit(
            vecs[upd] + cfg.mutation * rng.standard_normal((n_upd, cfg.dim))
        ).astype(np.float32)
        # 2. churn: replace users (id reused; semantics = leave + join)
        n_churn = int(cfg.churn_rate * cfg.num_users)
        rep = rng.choice(cfg.num_users, n_churn, replace=False)
        vecs[rep] = _unit(
            rng.standard_normal((n_churn, cfg.dim))
        ).astype(np.float32)

        # 4. current ground truth for this epoch's query draw
        qidx = rng.choice(cfg.num_users, cfg.num_queries, replace=False)
        sims = vecs[qidx] @ vecs.T
        sims[np.arange(cfg.num_queries), qidx] = -np.inf
        ideal = np.argsort(-sims, axis=1)[:, : cfg.m].astype(np.int32)

        yield epoch, vecs, epoch % cfg.refresh_every == 0, qidx, ideal


def _pad_to(x: np.ndarray, n: int, fill) -> np.ndarray:
    if x.shape[0] == n:
        return x
    pad = np.full((n - x.shape[0],) + x.shape[1:], fill, x.dtype)
    return np.concatenate([x, pad], axis=0)


def make_churn_runtime(
    cfg: ChurnConfig,
    n_shards: int = 1,
    mesh=None,
    cap_factor: float | None = None,
    replication: int = 1,
    read_mode: str = "first",
) -> IndexRuntime:
    """The runtime a churn trajectory executes on.

    `m` carries one result of wire headroom: the routed search path has no
    exclusion support (the id is not secret, paper Sec. 6), so the driver
    filters the query's own id host-side — the same convention on every
    topology, which is what keeps the trajectories comparable.
    cap_factor = n_shards guarantees zero drops (worst case routes every
    probe of a device to one owner shard); callers may lower it to trade
    buffer bytes for reported drops.
    """
    params, _ = _lsh_setup(cfg)
    rcfg = RuntimeConfig(
        params=params, n_nodes=n_shards, variant="cnb",
        m=cfg.m + 1,
        routing="alltoall",
        cap_factor=float(n_shards if cap_factor is None else cap_factor),
        replication=replication,
        read_mode=read_mode,
    )
    return IndexRuntime(rcfg, mesh=mesh)


def _expand_schedule(schedule, epochs: int) -> list[int]:
    """Per-epoch node counts (length epochs + 1, epoch 0 included): a
    short schedule holds its last value; a long one is clipped.  Every
    entry must be a power of two >= 1 (the `can.py` join/leave rounds)."""
    sched = [int(n) for n in schedule]
    if not sched:
        raise ValueError("empty membership schedule")
    for n in sched:
        if n < 1 or (n & (n - 1)):
            raise ValueError(f"schedule entries must be powers of two, "
                             f"got {n}")
    sched = (sched + [sched[-1]] * (epochs + 1))[: epochs + 1]
    return sched


def _zone_mesh(n: int):
    from repro.launch.mesh import make_zone_mesh

    return make_zone_mesh(n)


def _expand_kills(kills, epochs: int, n_nodes: int) -> dict[int, list[int]]:
    """Normalize a failure schedule ((epoch, node), ...) to epoch -> nodes.
    Kills fire at epoch START, before the epoch's announces and queries."""
    by_epoch: dict[int, list[int]] = {}
    for epoch, node in kills:
        epoch, node = int(epoch), int(node)
        if not (0 <= epoch <= epochs):
            raise ValueError(f"kill epoch {epoch} outside [0, {epochs}]")
        if not (0 <= node < n_nodes):
            raise ValueError(f"kill node {node} outside [0, {n_nodes})")
        by_epoch.setdefault(epoch, []).append(node)
    return by_epoch


def run_churn_runtime(
    cfg: ChurnConfig,
    rt: IndexRuntime,
    *,
    schedule=None,
    mesh_for=None,
    kills=None,
    obs=None,
) -> dict:
    """Drive the churn trajectory on ANY topology (the one driver).

    Announce epochs: runtime insert + expire + payload sync (+ CNB cache
    refresh when the topology has node bits — between refreshes that
    cache is STALE, the freshness/cost trade of the paper's periodic
    bucket exchange).  Read epochs: runtime search + host-side
    self-exclusion, recall against the current ground truth.

    With `schedule` (per-epoch node counts, see `_expand_schedule`) the
    topology itself churns: whenever the scheduled count differs from the
    current runtime's, a membership round fires FIRST (`runtime.reshard`
    — zone split/merge + bucket-state handoff + NB-cache rewarm), then
    the epoch's content churn and queries run on the new topology.
    Handoff and refresh bytes are charged per epoch (never silently);
    the world trajectory shares the static run's RNG stream, so recalls
    are directly comparable (in practice identical — the global bucket
    array is invariant under a round).  `mesh_for(n)` supplies the mesh
    for n-node topologies (default: a host-device-prefix zone mesh);
    runtimes are cached per node count so revisited topologies reuse
    their compiled steps.

    With `kills` (a failure schedule, ((epoch, node), ...)) nodes suffer
    FAIL-STOP losses with NO handoff (`runtime.kill_node` at epoch start,
    contrast the graceful `schedule` path — the two are mutually
    exclusive): the zone is gone, the node's liveness bit drops to 0, and
    queries read through the R-way replicas until the next announce epoch
    revives the node and repopulates its zone (recovery bytes charged per
    revival, `costmodel.estimate_recovery_bytes`).  Requires
    `rt.cfg.replication > 1`; each announce's R-1-way fan-out is charged
    via `costmodel.estimate_replication_bytes`, never silently.

    With `obs` (an `repro.obs.Observability`) the run feeds the flight
    recorder and metrics registry (DESIGN.md Sec. 12): one ``epoch``
    record per epoch whose stats and byte charges sum EXACTLY to the
    aggregate arrays returned here (the smoke drivers assert it), an
    anomaly dump on every `kill_node` and reshard, and the drop/byte
    totals as registry counters.
    """
    from repro.core import distributed as dist_mod

    params, hp = _lsh_setup(cfg)
    sched = (
        None if schedule is None
        else _expand_schedule(schedule, cfg.epochs)
    )
    if sched is not None and sched[0] != rt.cfg.n_nodes:
        raise ValueError(
            f"schedule[0]={sched[0]} != initial runtime n_nodes="
            f"{rt.cfg.n_nodes}"
        )
    kills_by_epoch = _expand_kills(kills or (), cfg.epochs, rt.cfg.n_nodes)
    if kills_by_epoch:
        if sched is not None:
            raise ValueError(
                "kills and schedule are mutually exclusive (a membership "
                "round re-keys zones; a fail-stop loss must not)"
            )
        if rt.cfg.replication < 2:
            raise ValueError(
                "a failure schedule needs replication >= 2 (a killed zone "
                "with no replicas is simply gone until the next announce)"
            )
    replication = rt.cfg.replication
    if sched is not None and replication > 1:
        raise ValueError(
            "membership schedules do not compose with replication > 1 "
            "(a zone split/merge re-keys the replica ring)"
        )
    live = np.ones(rt.cfg.n_nodes, np.int32)
    reps = None
    runtimes = {rt.cfg.n_nodes: rt}

    store = rt.shard_store(
        make_store(cfg.L, params.num_buckets, cfg.capacity,
                   payload_dim=cfg.dim)
    )

    def _charge_refresh() -> int:
        if rt.cfg.node_bits == 0:
            return 0
        return dist_mod.estimate_refresh_bytes(rt.cfg, cfg.capacity, cfg.dim)

    cache = None
    last_refresh = 0
    recalls, staleness, dropped = [], [], []
    handoff_b, refresh_b, nodes_traj, events = [], [], [], []
    repl_b, recov_b, live_traj, recoveries = [], [], [], []
    total_handoff = total_refresh = total_repl = total_recov = 0
    for epoch, vecs, do_refresh, qidx, ideal in _trajectory(cfg):
        ep_handoff = ep_refresh = ep_repl = ep_recov = 0
        for node in kills_by_epoch.get(epoch, ()):
            # fail-stop: the zone AND the node's held replica slices are
            # gone; replicas OF its zone on ring successors survive
            if not live[node]:
                raise ValueError(f"node {node} killed while already dead")
            store, reps = kill_node(rt, store, reps, node)
            live[node] = 0
            if obs is not None:
                obs.flight.note_anomaly(
                    "kill_node", node=int(node), epoch=int(epoch),
                    live_nodes=int(live.sum()),
                )
        if sched is not None and sched[epoch] != rt.cfg.n_nodes:
            # -- membership round: join/leave to the scheduled node count
            n_new = sched[epoch]
            tgt = runtimes.get(n_new)
            if tgt is not None:  # revisited topology: reuse compiled steps
                rt, store, ev = reshard(rt, store, runtime=tgt)
            else:
                mesh = (mesh_for or _zone_mesh)(n_new) if n_new > 1 else None
                rt, store, ev = reshard(
                    rt, store, n_new, mesh=mesh, cap_factor=float(n_new),
                )
            runtimes[n_new] = rt
            events.append(ev)
            if obs is not None:
                obs.flight.note_anomaly(
                    "reshard", epoch=int(epoch), old_n=int(ev.old_n),
                    new_n=int(ev.new_n), handoff_bytes=int(ev.handoff_bytes),
                )
            ep_handoff += ev.handoff_bytes
            total_handoff += ev.handoff_bytes
            # the new owners' NB caches are cold — rewarm immediately
            # (charged as refresh bytes; the store content is unchanged,
            # so this equals the cache of the last announce).  When the
            # round lands ON a refresh epoch the announce below rebuilds
            # the cache anyway: skip the duplicate rewarm and its charge.
            cache = None
            if not do_refresh:
                cache = rt.refresh_cache(store)
                b = _charge_refresh()
                ep_refresh += b
                total_refresh += b
        n_dev = rt.n_devices
        nu_pad = -(-cfg.num_users // n_dev) * n_dev
        nq_pad = -(-cfg.num_queries // n_dev) * n_dev
        if do_refresh:
            # a re-announce revives dead nodes first: the owner (or its
            # replacement) rejoins and this very announce repopulates its
            # zone — charged as one full-zone recovery per revival
            for node in np.flatnonzero(live == 0):
                b = costmodel.estimate_recovery_bytes(
                    cfg.L, rt.topology.buckets_per_node, cfg.capacity,
                    cfg.dim,
                )
                recoveries.append((epoch, int(node), b))
                ep_recov += b
                total_recov += b
                live[node] = 1
            vpad = _pad_to(vecs, nu_pad, 0.0)
            all_ids = _pad_to(
                np.arange(cfg.num_users, dtype=np.int32), nu_pad, -1)
            store = rt.insert(hp, store, vpad, all_ids, epoch)
            if epoch > 0:
                store = rt.expire(store, epoch, ttl=cfg.ttl_epochs)
            # entries left in a mover's OLD buckets must score with its
            # latest announced vector (the id-keyed reference semantics)
            store = rt.payload_sync(store, vpad)
            cache = rt.refresh_cache(store)
            b = _charge_refresh()
            ep_refresh += b
            total_refresh += b
            if replication > 1:
                # the announce fans out to the R-1 replica owners — the
                # replication of the insert/payload-sync writes
                reps = rt.replicate_store(store)
                b = costmodel.estimate_replication_bytes(
                    cfg.L, cfg.num_users, cfg.dim, replication)
                ep_repl += b
                total_repl += b
            last_refresh = epoch
        if epoch == 0:
            if obs is not None:
                # the initial announce: byte charges but no queries —
                # recorded so the ring's records sum to the run TOTALS
                # (per-read-epoch arrays exclude epoch 0 by convention)
                obs.flight.record(QueryRecord(
                    qid=0, kind="epoch",
                    extra=dict(
                        replication_bytes=ep_repl, recovery_bytes=ep_recov,
                        handoff_bytes=ep_handoff, refresh_bytes=ep_refresh,
                        live_nodes=int(live.sum()),
                    ),
                ))
            continue

        kw = {}
        if replication > 1:
            kw = dict(replicas=reps, live=live.copy())
        ids, _, drop = rt.search(
            hp, store, _pad_to(vecs[qidx], nq_pad, 0.0), cache=cache, **kw
        )
        ids = np.asarray(ids)[: cfg.num_queries]
        # host-side self-exclusion: drop the query's own id, keep top-m
        keep = ids != qidx[:, None]
        ids_m = np.full((cfg.num_queries, cfg.m), -1, np.int32)
        for i in range(cfg.num_queries):
            ids_m[i] = ids[i][keep[i]][: cfg.m]
        recalls.append(metrics.recall_at_m(ids_m, ideal))
        # epochs since the last announce (== epoch % refresh_every when
        # refreshes land on schedule) — one convention for all topologies
        staleness.append(epoch - last_refresh)
        dropped.append(int(drop))
        handoff_b.append(ep_handoff)
        refresh_b.append(ep_refresh)
        repl_b.append(ep_repl)
        recov_b.append(ep_recov)
        nodes_traj.append(rt.cfg.n_nodes)
        live_traj.append(int(live.sum()))
        if obs is not None:
            # one EXACT record per read epoch: the StepStats of the epoch's
            # search dispatch plus the epoch's byte charges — summing the
            # ring's ``epoch`` records reproduces the aggregate arrays
            # above bit-for-bit (asserted by the smoke drivers)
            hs = (drop.host() if hasattr(drop, "host")
                  else dict(dropped_probes=int(drop)))
            obs.flight.record(QueryRecord(
                qid=int(epoch), kind="epoch", batch_size=cfg.num_queries,
                **hs,
                extra=dict(
                    replication_bytes=ep_repl, recovery_bytes=ep_recov,
                    handoff_bytes=ep_handoff, refresh_bytes=ep_refresh,
                    recall=float(recalls[-1]), staleness=int(staleness[-1]),
                    live_nodes=int(live.sum()), n_nodes=rt.cfg.n_nodes,
                ),
            ))

    if obs is not None:
        reg = obs.registry
        reg.counter(
            "churn_dropped_probes_total",
            "router-overflow probe drops across all read epochs",
        ).inc(int(np.sum(dropped)))
        for name, total in (
            ("churn_replication_bytes_total", total_repl),
            ("churn_recovery_bytes_total", total_recov),
            ("churn_handoff_bytes_total", total_handoff),
            ("churn_refresh_bytes_total", total_refresh),
        ):
            reg.counter(name).inc(int(total))
        reg.gauge("churn_recall").set(float(recalls[-1]), window="last")
        reg.gauge("churn_recall").set(float(np.mean(recalls)), window="mean")
        reg.gauge("churn_live_nodes").set(int(live.sum()))

    stale_arr = np.asarray(staleness)
    return dict(
        recalls=np.asarray(recalls),
        # one measurement, two names: announce and cache rebuild share the
        # refresh schedule, so store staleness == cache staleness here
        staleness=stale_arr,
        cache_staleness=stale_arr,
        dropped_probes=np.asarray(dropped),
        final_recall=float(recalls[-1]),
        mean_recall=float(np.mean(recalls)),
        refresh_every=cfg.refresh_every,
        # membership accounting (all-zero / constant on a static topology):
        # per-read-epoch byte charges plus run totals, which additionally
        # include the epoch-0 initial announce's cache warm-up
        n_nodes=np.asarray(nodes_traj),
        handoff_bytes=np.asarray(handoff_b, dtype=np.int64),
        refresh_bytes=np.asarray(refresh_b, dtype=np.int64),
        total_handoff_bytes=int(total_handoff),
        total_refresh_bytes=int(total_refresh),
        reshard_events=events,
        # failure accounting (all-zero / constant with no kills): announce
        # fan-out to replicas, zone repopulation on revival, and the live
        # node count each read epoch.  Totals include the epoch-0 announce.
        replication=replication,
        live_nodes=np.asarray(live_traj),
        replication_bytes=np.asarray(repl_b, dtype=np.int64),
        recovery_bytes=np.asarray(recov_b, dtype=np.int64),
        total_replication_bytes=int(total_repl),
        total_recovery_bytes=int(total_recov),
        recoveries=recoveries,
        # store mutation counter after the run — the serving layer's cache
        # invalidation signal (every insert/expire/sync bumped it)
        store_generation=int(store.generation),
    )


def run_churn(cfg: ChurnConfig) -> dict:
    """The reference trajectory: the same driver on the 1-node topology
    (identity router, no collectives)."""
    return run_churn_runtime(cfg, make_churn_runtime(cfg))


def run_churn_distributed(
    cfg: ChurnConfig,
    n_shards: int = 2,
    mesh=None,
    cap_factor: float | None = None,
    obs=None,
) -> dict:
    """The same trajectory on the sharded mesh topology.

    Requires a host mesh whose `model` axis has n_shards devices — in a
    plain CPU process set XLA_FLAGS=--xla_force_host_platform_device_count
    before importing jax (see tests/test_churn.py / bench_churn.py).
    """
    if mesh is None:
        from repro.launch.mesh import make_host_mesh, require_host_devices

        require_host_devices(n_shards)
        mesh = make_host_mesh(data=1, model=n_shards)
    return run_churn_runtime(
        cfg, make_churn_runtime(cfg, n_shards, mesh, cap_factor), obs=obs
    )


@dataclasses.dataclass(frozen=True)
class NodeChurnConfig:
    """The elastic-membership scenario: content churn + queries while the
    node set itself joins and leaves on a schedule.

    `schedule[e]` is the node count during epoch e (0 = the initial
    announce epoch); a short schedule holds its last value.  Entries must
    be powers of two — each change is one `can.py` zone split/merge
    round.  The world trajectory (vectors, churn events, query draws) is
    the SAME RNG stream as the static drivers, so `run_node_churn`
    recalls are directly comparable to `run_churn` on the same
    `ChurnConfig`."""

    churn: ChurnConfig = ChurnConfig()
    schedule: tuple[int, ...] = (1, 2, 4, 2, 1)


def run_node_churn(cfg: NodeChurnConfig, mesh_for=None, obs=None) -> dict:
    """Interleave node join/leave epochs with content churn and queries.

    The topology axis becomes a runtime variable: membership rounds fire
    at the scheduled epochs (`runtime.reshard` — bucket-state handoff to
    the new zone owners, NB-cache rewarm), with handoff bytes charged to
    the cost model alongside the refresh bytes (`handoff_bytes` /
    `refresh_bytes` per epoch in the returned dict, plus run totals).
    Node counts > 1 need that many host devices (see
    `launch.mesh.make_zone_mesh`); pass `mesh_for(n)` to supply meshes
    yourself (e.g. device subsets of a production mesh).
    """
    sched = _expand_schedule(cfg.schedule, cfg.churn.epochs)
    n0 = sched[0]
    mesh = None if n0 == 1 else (mesh_for or _zone_mesh)(n0)
    rt = make_churn_runtime(cfg.churn, n0, mesh=mesh)
    return run_churn_runtime(cfg.churn, rt, schedule=sched,
                             mesh_for=mesh_for, obs=obs)


@dataclasses.dataclass(frozen=True)
class FailureChurnConfig:
    """The availability scenario: content churn + queries while nodes
    suffer FAIL-STOP losses (no handoff) and reads survive on R-way
    replicas (DESIGN.md Sec. 10).

    `kills` is ((epoch, node), ...): each node vanishes at that epoch's
    start and revives at the next announce epoch, which repopulates its
    zone.  The world trajectory is the same RNG stream as every other
    driver on the same `ChurnConfig`, so the no-failure reference run is
    directly comparable epoch by epoch."""

    churn: ChurnConfig = ChurnConfig()
    n_nodes: int = 4
    replication: int = 2
    read_mode: str = "first"        # first | quorum
    kills: tuple[tuple[int, int], ...] = ((3, 1),)


def run_failure_churn(cfg: FailureChurnConfig, mesh_for=None,
                      obs=None) -> dict:
    """Measure recall degradation and recovery across fail-stop kills.

    Runs the SAME runtime (same mesh, same compiled steps, same R and
    read mode) twice over the shared trajectory: once with the failure
    schedule, once without (the reference — at full liveness the replica
    redirect is the identity, so the reference equals the R=1 run).
    Returns the failure run's dict plus:

      reference_recalls   per-epoch recalls of the no-failure run
      recall_gap          reference - failure, per read epoch
      degraded            bool mask: epochs serving with a dead node
      degraded_gap        max gap over degraded epochs (0.0 if none)
      recovered_gap       max gap over post-recovery epochs (parity check)
      recovery_epochs     worst-case epochs from a kill to its revival
    """
    mesh = (mesh_for or _zone_mesh)(cfg.n_nodes)
    rt = make_churn_runtime(
        cfg.churn, cfg.n_nodes, mesh=mesh,
        replication=cfg.replication, read_mode=cfg.read_mode,
    )
    # only the failure run feeds obs: the reference would double-count
    # every byte charge and drop in the flight totals
    failure = run_churn_runtime(cfg.churn, rt, kills=cfg.kills, obs=obs)
    reference = run_churn_runtime(cfg.churn, rt)

    gap = reference["recalls"] - failure["recalls"]
    degraded = failure["live_nodes"] < cfg.n_nodes
    recovered = ~degraded
    # only epochs AFTER the first kill can attest recovery-to-parity
    if degraded.any():
        recovered &= np.arange(degraded.size) > int(np.argmax(degraded))
    recovery_epochs = 0
    for kill_epoch, _node in cfg.kills:
        revived = [e for e, _n, _b in failure["recoveries"]
                   if e > kill_epoch]
        if revived:
            recovery_epochs = max(recovery_epochs,
                                  min(revived) - int(kill_epoch))
    failure.update(
        reference_recalls=reference["recalls"],
        recall_gap=gap,
        degraded=degraded,
        degraded_gap=float(gap[degraded].max()) if degraded.any() else 0.0,
        recovered_gap=float(gap[recovered].max()) if recovered.any() else 0.0,
        recovery_epochs=int(recovery_epochs),
        kills=tuple(cfg.kills),
    )
    return failure

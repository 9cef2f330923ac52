"""One IndexRuntime: the topology-parameterized execution layer (DESIGN.md
Sec. 8).

The paper's central claim (Sec. 4) is that the probe discipline and the
CAN overlay are ONE design — the same bucket geometry decides what is
probed and where it executes.  This module is that claim as code: the
five index operations (search, contains, insert, expire, payload sync)
are implemented ONCE, as step kernels parameterized by a `CanTopology`,
and every execution context is a thin view:

  * `CanTopology(k, n_nodes=1)` — the degenerate mesh.  Every near bucket
    is a free local-bit probe, the router is the identity, and NO
    collectives are traced: the kernels run under plain `jax.jit`.  The
    single-host `LshEngine` (`repro.core.engine`) is a façade over this
    topology and stays bit-identical to its pre-refactor goldens
    (tests/test_runtime.py).
  * `n_nodes > 1` — buckets shard over the mesh `model` axis; the same
    kernels run under `shard_map` with real collectives.  The mesh /
    sharding-spec plumbing lives in `repro.core.distributed` (the
    adapter); the query logic lives here, so the two runtimes cannot
    drift apart — the Bahmani et al. (arXiv:1210.7057) point that
    single-node and distributed LSH should differ only in the
    entry-reorganization layer.

Collectives are abstracted by a tiny `Collectives` pair: `LOCAL` (all
ops are identities on the 1-node topology) and `MeshCollectives` (the
named-axis `lax` collectives).  Kernel bodies are written once against
that protocol; `if cx.n == 1` branches exist only where the topology
genuinely changes the dataflow (the identity router skips the
capacitated all_to_all entirely — probes cannot be dropped on one node).

`IndexRuntime` owns the step constructors and a host-level convenience
API (`search` / `contains` / `insert` / `expire` / `payload_sync` /
`refresh_cache` / `shard_store`), so drivers like `repro.core.churn`
run one scenario loop on ANY topology by construction.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_mod
from repro.core import routing as routing_mod
from repro.core import scoring
from repro.core import store as store_mod
from repro.core.can import CanTopology
from repro.core.can import moved_buckets as can_moved_buckets
from repro.core.corpus import DenseCorpus
from repro.core.hashing import LshParams
from repro.core.scoring import dedupe_topk
from repro.core.store import BucketStore

NEG_INF = float("-inf")


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Static description of one index runtime (any topology).

    `n_nodes=1` is the single-host engine's degenerate mesh; `n_nodes>1`
    is the sharded CAN zone geometry of DESIGN.md Sec. 2.  The legacy
    `DistConfig(n_shards=...)` constructor in `repro.core.distributed`
    builds this class.
    """

    params: LshParams
    variant: str = "cnb"          # lsh | layered | nb | cnb
    m: int = 10                    # results per query (mesh steps bake it)
    n_nodes: int = 1               # topology nodes (power of two)
    routing: str = "alltoall"      # alltoall | allgather (mesh only)
    cap_factor: float = 2.0        # per-destination buffer slack (alltoall)
    probe_local_near: bool = True  # search local-bit near buckets (nb/cnb)
    num_probes: int | None = None  # None => all k 1-near buckets (the paper)
    ranked_probes: bool = False    # margin-ranked probe subset (beyond paper)
    use_kernels: bool = False      # fused Pallas sketch + score/top-m
    replication: int = 1           # R-way zone replication (DESIGN.md Sec. 10)
    read_mode: str = "first"       # first (first live replica) | quorum
    fused: str = "auto"            # fused query mega-kernel: auto | on | off
    score: str = "dot"             # dot | hamming (bit-packed sketch words
    #                                ride every topology: routed steps
    #                                carry [.., W] uint32 query words)

    def __post_init__(self):
        if self.read_mode not in ("first", "quorum"):
            raise ValueError(f"unknown read_mode {self.read_mode!r}")
        if self.fused not in ("auto", "on", "off"):
            raise ValueError(f"unknown fused mode {self.fused!r}")
        if self.score not in ("dot", "hamming"):
            raise ValueError(f"unknown score mode {self.score!r}")
        if self.replication < 1:
            raise ValueError(
                f"replication must be >= 1, got {self.replication}"
            )
        if self.replication > 1:
            if self.replication > self.n_nodes:
                raise ValueError(
                    f"replication R={self.replication} exceeds "
                    f"n_nodes={self.n_nodes} (need R distinct owners)"
                )
            if self.routing != "alltoall":
                raise ValueError(
                    "replication > 1 requires alltoall routing (the "
                    "replica redirect rides the capacitated router)"
                )
            if self.variant == "nb":
                raise ValueError(
                    "replication > 1 does not support the nb variant "
                    "(neighbor forwards assume the primary owner; use cnb)"
                )

    @property
    def topo(self) -> CanTopology:
        return CanTopology(self.params.k, self.n_nodes)

    @property
    def n_shards(self) -> int:
        """Legacy name for `n_nodes` (the mesh `model`-axis size)."""
        return self.n_nodes

    @property
    def node_bits(self) -> int:
        return self.topo.node_bits

    @property
    def local_bits(self) -> int:
        return self.topo.local_bits

    @property
    def probe_spec(self) -> plan_mod.ProbeSpec:
        """The shared probe discipline (same planner on every topology)."""
        return plan_mod.ProbeSpec(
            params=self.params,
            variant=self.variant,
            num_probes=self.num_probes,
            ranked_probes=self.ranked_probes,
        )


# -----------------------------------------------------------------------------
# collectives: the ONLY topology-dependent operations
# -----------------------------------------------------------------------------


class LocalCollectives:
    """The 1-node mesh: every collective is the identity, so kernels trace
    NO communication ops and run under plain `jax.jit` (no mesh needed).
    `routed=False` selects the identity router in the step kernels — no
    send buffers exist, so probes structurally cannot be dropped."""

    n = 1
    routed = False

    def axis_index(self):
        return jnp.int32(0)

    def all_to_all(self, x):
        return x

    def all_gather(self, x):
        return x

    def all_gather_batch(self, x):
        return x

    def ppermute(self, x, perm):
        return x

    def alive(self, live):
        """This node's own bit of the per-node liveness mask.  The 1-node
        topology is always alive (a dead single node has nobody to ask)."""
        return jnp.bool_(True)


LOCAL = LocalCollectives()


@dataclasses.dataclass(frozen=True)
class MeshCollectives:
    """Named-axis collectives for kernels running under shard_map.

    `axis` is the bucket-shard axis (`model`); `batch_axes` are the axes
    the query/vector batch shards over (insert/payload-sync gather them).
    `routed=True`: even a 1-shard mesh runs the capacitated send-buffer
    router (its overflow accounting is part of the mesh-step contract and
    is exercised tier-1 on a single device).
    """

    n: int
    axis: str = "model"
    batch_axes: tuple = ("data", "model")
    routed = True

    def axis_index(self):
        return jax.lax.axis_index(self.axis)

    def all_to_all(self, x):
        return jax.lax.all_to_all(x, self.axis, 0, 0, tiled=True)

    def all_gather(self, x):
        return jax.lax.all_gather(x, self.axis, axis=0, tiled=True)

    def all_gather_batch(self, x):
        return jax.lax.all_gather(x, self.batch_axes, axis=0, tiled=True)

    def ppermute(self, x, perm):
        return jax.lax.ppermute(x, self.axis, perm)

    def alive(self, live):
        """This node's own bit of the traced liveness mask [n] (int32,
        1 = live).  Kernels mask every result row a node emits with its
        own bit, so a dead node's rows are EXCLUDED from merges no matter
        what its (lost) bucket state would have scored — the reads
        protocol's fail-stop guarantee (DESIGN.md Sec. 10)."""
        return (live > 0)[jax.lax.axis_index(self.axis)]


# -----------------------------------------------------------------------------
# shard-local scoring helpers (identical on every topology)
# -----------------------------------------------------------------------------


def _local_include_near(cfg: RuntimeConfig) -> bool:
    return cfg.variant not in ("lsh", "layered") and cfg.probe_local_near


def _node_bit_valid(cfg: RuntimeConfig, mask: jax.Array) -> jax.Array:
    """[r, node_bits] — is the flip of node bit j probed for each query?
    (the planner's mask-layout helper, stacked over this config's bits)"""
    if cfg.node_bits == 0:
        return jnp.zeros(mask.shape + (0,), bool)
    topo = cfg.topo
    return jnp.stack(
        [plan_mod.node_bit_probe_valid(topo, mask, b)
         for b in range(cfg.node_bits)],
        axis=-1,
    )


def _pool_topk(cfg, corpus, q, flat_ids, slot_vecs, m):
    """Score a flattened candidate pool and keep the top m distinct ids.

    Payload source is the one genuine data-model difference between the
    reference engine and the sharded store: `corpus` (id-keyed latest
    vectors — the single-host reference; also handles SparseCorpus) or
    the bucket-slot payloads gathered by the caller (`slot_vecs`).
    """
    if corpus is not None:
        if isinstance(corpus, DenseCorpus):
            vecs = corpus.gather(flat_ids)
            return scoring.score_topk(
                q, flat_ids, vecs, m, use_kernels=cfg.use_kernels
            )
        scores = jax.vmap(corpus.scores_against_dense)(q, flat_ids)
        scores = jnp.where(flat_ids >= 0, scores, jnp.float32(NEG_INF))
        return dedupe_topk(flat_ids, scores, m)
    return scoring.score_topk(
        q, flat_ids, slot_vecs, m, use_kernels=cfg.use_kernels,
        score=cfg.score,
    )


def _score_local(
    cfg: RuntimeConfig,
    store_ids: jax.Array,      # [T, NB_local, C]
    store_payload: jax.Array | None,  # [T, NB_local, C, D] or None (corpus)
    corpus,                    # id-keyed corpus, or None (slot payloads)
    q: jax.Array,              # [r, d]
    table: jax.Array,          # [r] int32
    local_idx: jax.Array,      # [r] int32 bucket index within shard
    mask: jax.Array,           # [r] int32/uint32 probe bitmask (plan)
    exclude: jax.Array | None,  # [r] self ids to drop, or None
    m: int,
    rep_ids: jax.Array | None = None,      # [T, R-1, NB_local, C]
    rep_payload: jax.Array | None = None,  # [T, R-1, NB_local, C, D]
    rep_sel: jax.Array | None = None,      # [r] replica rank to read (0=primary)
):
    """Top-m among (exact + masked local near) buckets of a routed query.

    With `rep_sel` (replication > 1) each routed row reads replica rank
    `rep_sel[i]` of its bucket: rank 0 is this node's primary shard, rank
    r >= 1 is the replica slice holding the zone of the node r positions
    back on the ring (`CanTopology.replicas_of`) — same local indices, so
    the probe set is unchanged."""
    probes, pvalid = plan_mod.shard_local_probes(
        cfg.topo, local_idx, mask, include_near=_local_include_near(cfg)
    )                                                      # [r, P] both
    probes = probes % store_ids.shape[1]  # engine parity: fold OOB codes
    if rep_sel is None:
        cand_ids = store_ids[table[:, None], probes]       # [r, P, C]
    else:
        all_ids = jnp.concatenate(
            [store_ids[:, None], rep_ids], axis=1)         # [T, R, NBl, C]
        cand_ids = all_ids[table[:, None], rep_sel[:, None], probes]
    cand_ids = jnp.where(pvalid[..., None], cand_ids, -1)
    r = q.shape[0]
    flat_ids = cand_ids.reshape(r, -1)
    if exclude is not None:
        flat_ids = jnp.where(flat_ids == exclude[:, None], -1, flat_ids)
    slot_vecs = None
    if corpus is None:
        if rep_sel is None:
            slot_vecs = store_payload[table[:, None], probes]  # [r, P, C, D]
        else:
            all_pay = jnp.concatenate(
                [store_payload[:, None], rep_payload], axis=1)
            slot_vecs = all_pay[table[:, None], rep_sel[:, None], probes]
        slot_vecs = slot_vecs.reshape(r, flat_ids.shape[1], -1)
    return _pool_topk(cfg, corpus, q, flat_ids, slot_vecs, m)


# -----------------------------------------------------------------------------
# fused query mega-kernel dispatch (DESIGN.md Sec. 11)
# -----------------------------------------------------------------------------


def _fused_on(cfg: RuntimeConfig, cx, *, has_payload: bool,
              has_corpus: bool, need_payload: bool = True) -> bool:
    """Should this step take the fused mega-kernel path?

    `auto` engages only where the fused kernel is a strict drop-in:
    slot-embedded payloads (an id-keyed corpus needs the global gather
    the kernel exists to avoid) and a TPU backend — the kernel is
    Mosaic-only (PrefetchScalarGridSpec + TPU compiler params), so on
    GPU it would fail to lower rather than run slow, and on CPU it runs
    in interpret mode — correct but slower than the jitted staged path.
    Both stay on the staged path under `auto`.  Routed topologies fuse
    the post-route local stage: the owner-side rows an all_to_all (or
    all_gather) delivers go through the same kernel, with the
    collectives outside it.  `on` forces the path (including CPU
    interpret) and raises where it cannot apply, instead of silently
    degrading.
    """
    if cfg.fused == "off":
        return False
    blockers = []
    if has_corpus:
        blockers.append("id-keyed corpus scoring")
    if need_payload and not has_payload:
        blockers.append("ids-only store (no payload to score)")
    if cfg.fused == "on":
        if blockers:
            raise ValueError(
                f"fused='on' unsupported here: {'; '.join(blockers)}"
            )
        return True
    return not blockers and jax.default_backend() == "tpu"


def _fused_probe_rows(cfg: RuntimeConfig, nb: int, table, local_idx, mask,
                      rep_sel=None, n_rep: int = 1):
    """(fb [r, P], pword [r]) for the mega-kernel's scalar prefetch.

    `fb` flattens (table, bucket) to a row of the [T*NB, C] store view —
    the gather the kernel's BlockSpec index map performs; `pword` packs
    the planner's per-probe validity lanes into one int32 bitfield
    (bit p = probe p valid; P <= 1 + k < 31 always fits).  With
    `rep_sel` (replication) the store view is the [T*R*NB, C] flatten of
    the primary+replica concat, and each row addresses its selected
    replica rank: fb = ((table*R + rep_sel)*NB + probe).
    """
    probes, pvalid = plan_mod.shard_local_probes(
        cfg.topo, local_idx, mask, include_near=_local_include_near(cfg)
    )                                                      # [r, P] both
    probes = probes % nb  # engine parity: fold OOB codes
    row = table if rep_sel is None else table * n_rep + rep_sel
    fb = row[:, None] * nb + probes
    shifts = jnp.arange(pvalid.shape[1], dtype=jnp.int32)
    pword = jnp.sum(
        pvalid.astype(jnp.int32) << shifts[None, :], axis=1
    ).astype(jnp.int32)
    return fb.astype(jnp.int32), pword


def _fused_search_local(
    cfg: RuntimeConfig,
    store_ids: jax.Array,             # [T, NB, C]
    store_payload: jax.Array,         # [T, NB, C, D] f32 or [T, NB, C, W] u32
    q: jax.Array,                     # [r, d] f32 or [r, W] packed words
    table: jax.Array,                 # [r]
    local_idx: jax.Array,             # [r]
    mask: jax.Array,                  # [r]
    exclude: jax.Array | None,        # [r] or None
    m: int,
    rep_ids: jax.Array | None = None,      # [T, R-1, NB, C]
    rep_payload: jax.Array | None = None,  # [T, R-1, NB, C, D|W]
    rep_sel: jax.Array | None = None,      # [r] replica rank to read
    routed: bool = False,
):
    """Fused twin of `_score_local`: one Pallas call replaces gather +
    score + top-m; no [r, P*C] candidate intermediate exists.
    Bit-identical to the staged path by the `ref.fused_query_ref`
    contract (tests/test_fused.py).  With `rep_sel` (replication > 1)
    the kernel gathers from the flattened primary+replica store view —
    the same rows `_score_local` reads through its replica concat.
    `routed` selects the routed autotune entry (post-all_to_all row
    counts are cap-padded, so the winning block shape can differ)."""
    from repro.kernels import ops

    if rep_sel is None:
        t, nb, c = store_ids.shape
        ids_flat = store_ids.reshape(t * nb, c)
        pay_flat = store_payload.reshape(t * nb, c, store_payload.shape[-1])
        with jax.named_scope("probe_plan"):
            fb, pword = _fused_probe_rows(cfg, nb, table, local_idx, mask)
    else:
        with jax.named_scope("payload_prep"):
            all_ids = jnp.concatenate(
                [store_ids[:, None], rep_ids], axis=1)     # [T, R, NB, C]
            all_pay = jnp.concatenate(
                [store_payload[:, None], rep_payload], axis=1)
        t, n_rep, nb, c = all_ids.shape
        ids_flat = all_ids.reshape(t * n_rep * nb, c)
        pay_flat = all_pay.reshape(t * n_rep * nb, c, all_pay.shape[-1])
        with jax.named_scope("probe_plan"):
            fb, pword = _fused_probe_rows(cfg, nb, table, local_idx, mask,
                                          rep_sel=rep_sel, n_rep=n_rep)
    excl = (
        jnp.full_like(pword, -1) if exclude is None
        else exclude.astype(jnp.int32)
    )  # -1 matches only empty slots == no exclusion
    meta = jnp.stack([pword, excl], axis=1)
    return ops.fused_query(
        ids_flat, pay_flat, q, fb, meta, m=m, score=cfg.score,
        tune_op="fused_query_routed" if routed else "fused_query",
        interpret=jax.default_backend() == "cpu",
    )


def _fused_contains_local(
    cfg: RuntimeConfig,
    store_ids: jax.Array,  # [T, NB, C]
    table: jax.Array,      # [r]
    local_idx: jax.Array,  # [r]
    mask: jax.Array,       # [r]
    target: jax.Array,     # [r]
    rep_ids: jax.Array | None = None,  # [T, R-1, NB, C]
    rep_sel: jax.Array | None = None,  # [r]
    routed: bool = False,
):
    """Fused twin of `_contains_local`: metadata-only, works on ids-only
    stores (no payload blocks travel).  Replica reads flatten the
    primary+replica concat exactly like `_fused_search_local`."""
    from repro.kernels import ops

    if rep_sel is None:
        t, nb, c = store_ids.shape
        ids_flat = store_ids.reshape(t * nb, c)
        fb, pword = _fused_probe_rows(cfg, nb, table, local_idx, mask)
    else:
        all_ids = jnp.concatenate([store_ids[:, None], rep_ids], axis=1)
        t, n_rep, nb, c = all_ids.shape
        ids_flat = all_ids.reshape(t * n_rep * nb, c)
        fb, pword = _fused_probe_rows(cfg, nb, table, local_idx, mask,
                                      rep_sel=rep_sel, n_rep=n_rep)
    meta = jnp.stack([pword, target.astype(jnp.int32)], axis=1)
    return ops.fused_contains(
        ids_flat, fb, meta,
        tune_op="fused_query_routed" if routed else "fused_query",
        interpret=jax.default_backend() == "cpu",
    )


def _score_cache(
    cfg: RuntimeConfig,
    cache_ids: jax.Array,      # [T, nbits, NB_local, C]
    cache_payload: jax.Array,  # [T, nbits, NB_local, C, D]
    q: jax.Array,              # [r, d]
    table: jax.Array,          # [r]
    local_idx: jax.Array,      # [r]
    mask: jax.Array,           # [r]
    m: int,
):
    """CNB: score the masked node-bit near buckets from the neighbor cache.

    Flipping node bit j keeps the local index unchanged, so the near bucket
    of bit j is cache[table, j, local_idx] — a pure local gather, gated per
    query by node bit j of the probe mask.  Under `score="hamming"` the
    cache payload holds the ppermuted packed uint32 words and `q` is the
    routed query's word row — the same packed scoring as the owner stage.
    """
    nbits = cache_ids.shape[1]
    jj = jnp.arange(nbits)[None, :]
    cand_ids = cache_ids[table[:, None], jj, local_idx[:, None]]  # [r, nbits, C]
    cand_ids = jnp.where(_node_bit_valid(cfg, mask)[..., None], cand_ids, -1)
    cand_vec = cache_payload[table[:, None], jj, local_idx[:, None]]
    r = q.shape[0]
    cand_ids = cand_ids.reshape(r, -1)
    cand_vec = cand_vec.reshape(r, cand_ids.shape[1], -1)
    return scoring.score_topk(
        q, cand_ids, cand_vec, m, use_kernels=cfg.use_kernels,
        score=cfg.score,
    )


def _neighbor_parts(
    cfg, cx, store_ids, store_payload, rq, rtable, rlocal, rmask, m
):
    """NB: forward routed queries to each XOR-neighbor; it scores ITS exact
    bucket at the same local index (node-bit flip keeps local bits), then
    returns the partial top-m.  2 ppermutes per node bit; the origin query's
    probe mask gates each bit's contribution."""
    nbit_valid = _node_bit_valid(cfg, rmask)           # [r, nbits]
    ids_parts, sc_parts = [], []
    for j in range(cfg.node_bits):
        perm = cfg.topo.neighbor_perm(j)
        nq = cx.ppermute(rq, perm)
        nt = cx.ppermute(rtable, perm)
        nl = cx.ppermute(rlocal, perm)
        ids_j, sc_j = _score_local(
            dataclasses.replace(cfg, variant="lsh"),   # exact bucket only
            store_ids, store_payload, None, nq, nt, nl,
            jnp.zeros_like(rmask), None, m,
        )
        ids_j = cx.ppermute(ids_j, perm)
        sc_j = cx.ppermute(sc_j, perm)
        keep = nbit_valid[:, j][:, None]
        ids_parts.append(jnp.where(keep, ids_j, -1))
        sc_parts.append(jnp.where(keep, sc_j, NEG_INF))
    return ids_parts, sc_parts


def _merge_topk(ids_list, scores_list, m):
    ids = jnp.concatenate(ids_list, axis=-1)
    scores = jnp.concatenate(scores_list, axis=-1)
    return dedupe_topk(ids, scores, m)


def _flat_plan(cfg: RuntimeConfig, cx, q: jax.Array, hyperplanes: jax.Array):
    """Run the shared planner and flatten to (query, table) granularity.

    The fused Pallas sketch only runs on the 1-node topology: under
    shard_map the sketch stays on the reference path (the kernel shim is
    not traced through collectives), matching the pre-refactor behavior
    of both runtimes.  Codes are bit-identical either way (CI-checked).
    """
    L = cfg.params.L
    b_loc = q.shape[0]
    plan = plan_mod.make_plan(
        cfg.probe_spec, q, hyperplanes, cfg.topo,
        use_kernels=cfg.use_kernels and not cx.routed,
    )
    flat = dict(
        owner=plan.owner.reshape(-1),                   # [b_loc*L]
        local=plan.local_idx.reshape(-1),
        mask=plan.probe_mask.astype(jnp.int32).reshape(-1),
        table=jnp.tile(jnp.arange(L, dtype=jnp.int32), (b_loc,)),
        qidx=jnp.repeat(jnp.arange(b_loc, dtype=jnp.int32), L),
    )
    return plan, flat


def _route_cap(cfg: RuntimeConfig, b_loc: int) -> int:
    cap = int(np.ceil(b_loc * cfg.params.L / cfg.n_nodes * cfg.cap_factor))
    return max(cap, 1)


def _replica_targets(cfg: RuntimeConfig, owner: jax.Array, live: jax.Array):
    """Replica-aware destinations for a flat probe array (DESIGN.md Sec. 10).

    `first` (first-responder): each probe goes to the FIRST live owner on
    its bucket's replica ring (primary, else successor 1, ...).  Probes
    with no live replica keep the (dead) primary — the destination's own
    liveness mask excludes its rows, so they return fill, never garbage.

    `quorum`: each probe fans out to ALL R replica owners (live or not —
    dead destinations self-mask), and the origin merges every returned
    copy.  Returns (dest [f], rep_sel [f], fanout): flat arrays tiled
    rr-major (`fanout = R`) under quorum, untiled (`fanout = 1`) under
    first-responder.
    """
    n, R = cfg.n_nodes, cfg.replication
    live_b = live.astype(jnp.int32) > 0                      # [n]
    if cfg.read_mode == "quorum":
        dest = jnp.concatenate(
            [(owner + rr) % n for rr in range(R)])
        rep_sel = jnp.repeat(
            jnp.arange(R, dtype=jnp.int32), owner.shape[0])
        return dest, rep_sel, R
    dest = owner
    rep_sel = jnp.zeros_like(owner)
    found = live_b[owner]
    for rr in range(1, R):
        cand = (owner + rr) % n
        take = ~found & live_b[cand]
        dest = jnp.where(take, cand, dest)
        rep_sel = jnp.where(take, jnp.int32(rr), rep_sel)
        found = found | live_b[cand]
    return dest, rep_sel, 1


# -----------------------------------------------------------------------------
# per-step observability scalars
# -----------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StepStats:
    """Cheap per-step accounting, the aux output of the search / contains
    steps (DESIGN.md Sec. 12).

    Every field is an int32 scalar except `dropped_by_dest` ([n_nodes]),
    so threading the pytree through jit / shard_map adds no HBM
    intermediates.  The stats are ALWAYS computed — observability on/off
    only gates host-side recording — which is what makes enabling the
    flight recorder structurally unable to change the traced computation
    (the zero-retrace assertion in tests/test_obs.py).

    `int(stats)` returns the dropped-probe count, so every pre-existing
    `ids, scores, dropped = step(...)` consumer keeps working unchanged.
    """

    dropped: jax.Array          # probes lost to router-buffer overflow
    probes_issued: jax.Array    # planned bucket probes: exact + near bits
    probes_routed: jax.Array    # (query, table) rows sent through the router
    nodes_contacted: jax.Array  # distinct (query, destination) deliveries
    replica_fanout: jax.Array   # quorum fan-out factor (1 = first-responder)
    dropped_by_dest: jax.Array  # [n_nodes] per-destination overflow counts

    def __int__(self) -> int:
        return int(self.dropped)

    def host(self) -> dict:
        """Concretize to plain Python (flight-recorder record fields).
        Direct per-leaf reads: measured ~3x cheaper than a batched
        `jax.device_get(self)` (whose tree traversal dominates for six
        tiny leaves) — this sits on the serving hot path when
        observability is on."""
        return dict(
            dropped_probes=int(self.dropped),
            probes_issued=int(self.probes_issued),
            probes_routed=int(self.probes_routed),
            nodes_contacted=int(self.nodes_contacted),
            replica_fanout=int(self.replica_fanout),
            dropped_by_dest=tuple(np.asarray(self.dropped_by_dest).tolist()),
        )

    @staticmethod
    def local(n: int, probes_issued, nodes_contacted) -> "StepStats":
        """Stats for an unrouted step (identity router or allgather):
        nothing enters a capacitated buffer, so nothing can drop."""
        return StepStats(
            dropped=jnp.int32(0),
            probes_issued=probes_issued,
            probes_routed=jnp.int32(0),
            nodes_contacted=jnp.int32(nodes_contacted),
            replica_fanout=jnp.int32(1),
            dropped_by_dest=jnp.zeros((n,), jnp.int32),
        )


def _probes_issued(flat_mask: jax.Array) -> jax.Array:
    """Planned bucket probes for a flat [b*L] probe-mask array: one exact
    bucket per (query, table) row plus one near bucket per set mask bit
    (the planner has already applied ranked-probe selection)."""
    near = jax.lax.population_count(flat_mask.astype(jnp.uint32))
    return jnp.int32(flat_mask.shape[0]) + jnp.sum(near).astype(jnp.int32)


def _routed_stats(route, dest, qidx, b_loc: int, n: int,
                  probes_issued, fanout: int) -> StepStats:
    """Stats for an all_to_all step, from the route plan itself.

    `route.dest` is clamped (overflow rows are parked on destination 0),
    so per-destination drop counts come from the UNCLAMPED `dest` taken
    through `route.order` — the same sorted frame `route.ok` lives in.
    """
    d_true = dest[route.order]                      # unclamped, sorted
    ok = route.ok.astype(jnp.int32)
    touch = jnp.zeros((b_loc, n), jnp.int32).at[
        qidx[route.order], d_true].add(ok)
    return StepStats(
        dropped=route.dropped,
        probes_issued=probes_issued,
        probes_routed=jnp.int32(dest.shape[0]),
        nodes_contacted=jnp.sum(touch > 0).astype(jnp.int32),
        replica_fanout=jnp.int32(fanout),
        dropped_by_dest=jnp.zeros((n,), jnp.int32).at[d_true].add(1 - ok),
    )


# -----------------------------------------------------------------------------
# the search step kernel
# -----------------------------------------------------------------------------


def search_kernel(
    cfg: RuntimeConfig,
    cx,
    m: int,
    hyperplanes: jax.Array,
    store_ids: jax.Array,
    store_payload: jax.Array | None,
    cache_ids: jax.Array | None,
    cache_payload: jax.Array | None,
    q: jax.Array,                     # [b_loc, d] this node's query slice
    *,
    corpus=None,                      # id-keyed corpus (1-node only)
    exclude: jax.Array | None = None,  # [b_loc] self ids (1-node only)
    rep_ids: jax.Array | None = None,      # [T, R-1, NBl, C] (replication>1)
    rep_payload: jax.Array | None = None,  # [T, R-1, NBl, C, D]
    live: jax.Array | None = None,         # [n] int32 liveness mask
):
    """Per-node body of the search step: runs under shard_map on a mesh, or
    under plain jit on the 1-node topology (cx = LOCAL).

    Returns (ids [b_loc, m], scores [b_loc, m], stats `StepStats`) —
    `int(stats)` is the dropped-probe count: this node's (query, table)
    probes that overflowed the capacitated all_to_all send buffers
    (structurally 0 on one node: the identity router has no buffers;
    also 0 under allgather routing).  The remaining stats fields are
    cheap accounting scalars for the observability layer — always
    computed, whether or not anything records them.

    With `cfg.replication > 1` the routed path reads through replicas:
    probes are redirected to live replica owners (`_replica_targets`),
    scored there against the selected replica slice, and every node masks
    its emitted rows with its own `live` bit — a dead node contributes
    fill, never stale or garbage rows.
    """
    if (corpus is not None or exclude is not None) and cx.routed:
        raise ValueError("corpus scoring / wire exclusion are 1-node only")
    if cfg.score == "hamming" and corpus is not None:
        raise ValueError(
            "score='hamming' needs slot-embedded packed payloads, not an "
            "id-keyed corpus"
        )
    reps_on = cfg.replication > 1
    if reps_on and (rep_ids is None or rep_payload is None or live is None):
        raise ValueError(
            "replication > 1 needs rep_ids/rep_payload/live "
            "(IndexRuntime.replicate_store builds the replica slices)"
        )
    L = cfg.params.L
    n = cx.n
    b_loc = q.shape[0]
    # named scopes mark the step's stages in the device trace's op
    # metadata: probe_plan (holding sketch), payload_prep, fused_kernel
    # (kernels/ops.py) and merge
    with jax.named_scope("probe_plan"):
        plan, flat = _flat_plan(cfg, cx, q, hyperplanes)
        probes = _probes_issued(flat["mask"])

    qs = q
    if cfg.score == "hamming":
        # hamming scores against the query's OWN packed sketch words; the
        # planner already computed the codes, so the f32 query vector
        # never reaches the scoring stage — and on routed topologies the
        # [.., W] uint32 words (not the [.., d] f32 rows) are what rides
        # the all_to_all / all_gather wire.
        from repro.core import packed as packed_mod

        qs = packed_mod.pack_codes(plan.codes, cfg.params.k)

    if not cx.routed:
        # Identity router: every probe is local by construction. No send
        # buffers exist, so nothing can be dropped and nothing is traced
        # beyond the gather/score path the reference engine always ran.
        ex = None if exclude is None else exclude[flat["qidx"]]
        if _fused_on(cfg, cx, has_payload=store_payload is not None,
                     has_corpus=corpus is not None):
            ids_r, sc_r = _fused_search_local(
                cfg, store_ids, store_payload, qs[flat["qidx"]],
                flat["table"], flat["local"], flat["mask"], ex, m,
            )                                              # [b_loc*L, m]
        else:
            ids_r, sc_r = _score_local(
                cfg, store_ids, store_payload, corpus,
                qs[flat["qidx"]], flat["table"], flat["local"],
                flat["mask"], ex, m,
            )                                              # [b_loc*L, m]
        with jax.named_scope("merge"):
            ids, sc = dedupe_topk(
                ids_r.reshape(b_loc, L * m), sc_r.reshape(b_loc, L * m), m
            )
        return ids, sc, StepStats.local(n, probes, b_loc)

    if cfg.routing == "allgather":
        ids, sc = _search_allgather(
            cfg, cx, store_ids, store_payload, cache_ids, cache_payload,
            qs, flat, m,
        )
        # every shard answers every query's probes: b_loc * n contacts
        return ids, sc, StepStats.local(n, probes, b_loc * n)

    # ---- all_to_all routing (DHT-lookup analogue) ---------------------------
    dest = flat["owner"]
    fanout = 1
    if reps_on:
        dest, rep_col, fanout = _replica_targets(cfg, dest, live)
        if fanout > 1:  # quorum: rr-major tiling matches rep_col layout
            flat = {k: jnp.tile(v, fanout) for k, v in flat.items()}
    cap = _route_cap(cfg, b_loc) * fanout
    route = routing_mod.plan_routes(dest, n, cap)
    cols = [flat["qidx"], flat["table"], flat["local"], flat["mask"]]
    if reps_on:
        cols.append(rep_col)
    meta = jnp.stack(cols, axis=-1)
    # hamming routes the packed uint32 word rows (W*4 bytes each vs d*4 —
    # the Sec. 3.2 wire saving); fill 0 is safe either way because fill
    # rows carry meta -1 and are excluded by rvalid below, never scored.
    send_q = routing_mod.build_send_buffer(route, n, cap, qs[flat["qidx"]], 0)
    send_meta = routing_mod.build_send_buffer(route, n, cap, meta, -1)

    recv_q = cx.all_to_all(send_q)
    recv_meta = cx.all_to_all(send_meta)
    rq = recv_q.reshape(n * cap, qs.shape[-1])
    rtable = recv_meta[..., 1].reshape(-1)
    rlocal = recv_meta[..., 2].reshape(-1)
    rmask = recv_meta[..., 3].reshape(-1)
    rvalid = rtable >= 0
    rtable_c = jnp.maximum(rtable, 0)
    rlocal_c = jnp.maximum(rlocal, 0)
    rmask_c = jnp.maximum(rmask, 0)

    rrep = None
    if reps_on:
        rrep = jnp.clip(recv_meta[..., 4].reshape(-1), 0, cfg.replication - 1)
        # a dead node's own rows are fill — liveness is enforced where the
        # data lives, so a stale survivor can't resurrect a killed zone
        rvalid &= cx.alive(live)

    if _fused_on(cfg, cx, has_payload=store_payload is not None,
                 has_corpus=False):
        # post-route local stage through the mega-kernel: fill rows score
        # garbage on clamped indices exactly like the staged gather and
        # are masked by rvalid below — bit-identical either way.
        ids_o, sc_o = _fused_search_local(
            cfg, store_ids, store_payload, rq, rtable_c, rlocal_c,
            rmask_c, None, m,
            rep_ids=rep_ids, rep_payload=rep_payload, rep_sel=rrep,
            routed=True,
        )
    else:
        ids_o, sc_o = _score_local(
            cfg, store_ids, store_payload, None, rq, rtable_c, rlocal_c,
            rmask_c, None, m,
            rep_ids=rep_ids, rep_payload=rep_payload, rep_sel=rrep,
        )
    ids_parts, sc_parts = [ids_o], [sc_o]

    if cfg.variant == "cnb" and cache_ids is not None and cfg.node_bits > 0:
        ids_c, sc_c = _score_cache(
            cfg, cache_ids, cache_payload, rq, rtable_c, rlocal_c, rmask_c, m
        )
        if rrep is not None:
            # the neighbor cache mirrors the PRIMARY zone only: replica
            # reads (rep > 0) skip it rather than mix another zone's cache
            prim = (rrep == 0)[:, None]
            ids_c = jnp.where(prim, ids_c, -1)
            sc_c = jnp.where(prim, sc_c, NEG_INF)
        ids_parts.append(ids_c)
        sc_parts.append(sc_c)

    if cfg.variant == "nb":
        ids_n, sc_n = _neighbor_parts(
            cfg, cx, store_ids, store_payload, rq, rtable_c, rlocal_c,
            rmask_c, m,
        )
        ids_parts += ids_n
        sc_parts += sc_n

    ids_r, sc_r = _merge_topk(ids_parts, sc_parts, m)   # [n*cap, m]
    ids_r = jnp.where(rvalid[:, None], ids_r, -1)
    sc_r = jnp.where(rvalid[:, None], sc_r, NEG_INF)

    # ---- return results to origin -------------------------------------------
    back_i = cx.all_to_all(ids_r.reshape(n, cap, m))
    back_s = cx.all_to_all(sc_r.reshape(n, cap, m))
    gather_i = routing_mod.return_to_origin(route, back_i, -1)  # [b*L*fan, m]
    gather_s = routing_mod.return_to_origin(route, back_s, NEG_INF)
    if fanout > 1:
        gather_i = gather_i.reshape(fanout, b_loc, L * m)
        gather_s = gather_s.reshape(fanout, b_loc, L * m)
        gather_i = gather_i.transpose(1, 0, 2).reshape(b_loc, -1)
        gather_s = gather_s.transpose(1, 0, 2).reshape(b_loc, -1)
    else:
        gather_i = gather_i.reshape(b_loc, L * m)
        gather_s = gather_s.reshape(b_loc, L * m)
    ids, sc = dedupe_topk(gather_i, gather_s, m)
    return ids, sc, _routed_stats(
        route, dest, flat["qidx"], b_loc, n, probes, fanout)


def _gather_flat_meta(cx, flat: dict, b_loc: int, L: int, names):
    """all_gather the named per-(query, table) flat fields along the shard
    axis.

    Shared prologue of the two allgather branches (search + contains), so
    the [b_loc, L] re-flatten layout cannot drift between them.  Returns
    ({name: [b_all*L]}, table index [b_all*L], b_all).
    """
    gathered = {
        name: cx.all_gather(flat[name].reshape(b_loc, L)).reshape(-1)
        for name in names
    }
    b_all = next(iter(gathered.values())).shape[0] // L
    rtable = jnp.tile(jnp.arange(L, dtype=jnp.int32), (b_all,))
    return gathered, rtable, b_all


def _search_allgather(
    cfg, cx, store_ids, store_payload, cache_ids, cache_payload, q, flat, m
):
    """Dense fallback: replicate queries along the shard axis, each shard
    scores the (query, table) pairs it owns, results return via all_to_all.
    `q` is the scoring-side query row: [b_loc, d] f32 under dot, the
    [b_loc, W] packed uint32 words under hamming."""
    L, n = cfg.params.L, cx.n
    b_loc = q.shape[0]
    me = cx.axis_index()

    g, rtable, b_all = _gather_flat_meta(
        cx, flat, b_loc, L, ("owner", "local", "mask"))
    q_all = cx.all_gather(q)                                # [b_all, d|W]
    rq = jnp.repeat(q_all, L, axis=0)                       # [b_all*L, d|W]
    rlocal = g["local"]
    rmask = g["mask"]
    mine = g["owner"] == me

    if _fused_on(cfg, cx, has_payload=store_payload is not None,
                 has_corpus=False):
        ids_o, sc_o = _fused_search_local(
            cfg, store_ids, store_payload, rq, rtable, rlocal, rmask,
            None, m, routed=True,
        )
    else:
        ids_o, sc_o = _score_local(
            cfg, store_ids, store_payload, None, rq, rtable, rlocal, rmask,
            None, m,
        )
    ids_parts, sc_parts = [ids_o], [sc_o]
    if cfg.variant == "cnb" and cache_ids is not None and cfg.node_bits > 0:
        ids_c, sc_c = _score_cache(
            cfg, cache_ids, cache_payload, rq, rtable, rlocal, rmask, m
        )
        ids_parts.append(ids_c)
        sc_parts.append(sc_c)
    if cfg.variant == "nb":
        ids_n, sc_n = _neighbor_parts(
            cfg, cx, store_ids, store_payload, rq, rtable, rlocal, rmask, m
        )
        ids_parts += ids_n
        sc_parts += sc_n

    ids_r, sc_r = _merge_topk(ids_parts, sc_parts, m)       # [b_all*L, m]
    ids_r = jnp.where(mine[:, None], ids_r, -1)
    sc_r = jnp.where(mine[:, None], sc_r, NEG_INF)

    # each origin needs rows of its own queries from ALL shards: all_to_all
    # over the origin-major reshape.
    ids_r = ids_r.reshape(n, b_loc * L * m)
    sc_r = sc_r.reshape(n, b_loc * L * m)
    got_i = cx.all_to_all(ids_r)                            # [n, b*L*m]
    got_s = cx.all_to_all(sc_r)
    got_i = got_i.reshape(n, b_loc, L * m).transpose(1, 0, 2).reshape(b_loc, -1)
    got_s = got_s.reshape(n, b_loc, L * m).transpose(1, 0, 2).reshape(b_loc, -1)
    return dedupe_topk(got_i, got_s, m)


# -----------------------------------------------------------------------------
# the contains step kernel (success-probability metric, paper Sec. 6.3)
# -----------------------------------------------------------------------------


def _contains_local(cfg, store_ids, table, local_idx, mask, target,
                    rep_ids=None, rep_sel=None):
    """bool [r]: does `target` sit in the (exact + masked local near)
    buckets of each routed query?  Metadata-only — no payload gathers.
    With `rep_sel` each row reads replica rank rep_sel[i] (as in
    `_score_local`)."""
    probes, pvalid = plan_mod.shard_local_probes(
        cfg.topo, local_idx, mask, include_near=_local_include_near(cfg)
    )
    probes = probes % store_ids.shape[1]
    if rep_sel is None:
        cand = store_ids[table[:, None], probes]            # [r, P, C]
    else:
        all_ids = jnp.concatenate([store_ids[:, None], rep_ids], axis=1)
        cand = all_ids[table[:, None], rep_sel[:, None], probes]
    hit = (cand == target[:, None, None]) & pvalid[..., None]
    return jnp.any(hit, axis=(1, 2))


def _contains_hits(cfg, cx, store_ids, cache_ids, rtable, rlocal, rmask, rtgt,
                   rep_ids=None, rep_sel=None, fused=False):
    """Membership across owner buckets + node-bit coverage (cache or
    neighbor forwards), mirroring the search step's candidate pool.
    `fused` swaps the owner-bucket component for the fused membership
    kernel; the cnb-cache and nb-forward components stay staged (they OR
    booleans in, so the result is identical either way)."""
    if fused:
        hit = _fused_contains_local(cfg, store_ids, rtable, rlocal, rmask,
                                    rtgt, rep_ids=rep_ids, rep_sel=rep_sel,
                                    routed=cx.routed)
    else:
        hit = _contains_local(cfg, store_ids, rtable, rlocal, rmask, rtgt,
                              rep_ids=rep_ids, rep_sel=rep_sel)
    if cfg.variant == "cnb" and cache_ids is not None and cfg.node_bits > 0:
        nbits = cache_ids.shape[1]
        jj = jnp.arange(nbits)[None, :]
        cand = cache_ids[rtable[:, None], jj, rlocal[:, None]]  # [r, nbits, C]
        valid = _node_bit_valid(cfg, rmask)[..., None]
        if rep_sel is not None:
            # cache mirrors the primary zone only (see search_kernel)
            valid &= (rep_sel == 0)[:, None, None]
        hit |= jnp.any((cand == rtgt[:, None, None]) & valid, axis=(1, 2))
    if cfg.variant == "nb":
        nbit_valid = _node_bit_valid(cfg, rmask)
        for j in range(cfg.node_bits):
            perm = cfg.topo.neighbor_perm(j)
            nt = cx.ppermute(rtable, perm)
            nl = cx.ppermute(rlocal, perm)
            ntgt = cx.ppermute(rtgt, perm)
            hit_j = _contains_local(
                dataclasses.replace(cfg, variant="lsh"),
                store_ids, nt, nl, jnp.zeros_like(nl), ntgt,
            )
            hit_j = cx.ppermute(hit_j, perm)
            hit |= hit_j & nbit_valid[:, j]
    return hit


def contains_kernel(
    cfg: RuntimeConfig,
    cx,
    hyperplanes: jax.Array,
    store_ids: jax.Array,
    cache_ids: jax.Array | None,
    q: jax.Array,        # [b_loc, d]
    targets: jax.Array,  # [b_loc] int32
    *,
    rep_ids: jax.Array | None = None,  # [T, R-1, NBl, C] (replication>1)
    live: jax.Array | None = None,     # [n] int32 liveness mask
):
    """Per-node body of `contains`: was target y's id in ANY searched bucket
    of query x?  Routes only metadata (no query payload): membership needs
    bucket ids, not vectors.  Returns (hits bool [b_loc], stats
    `StepStats`) — `int(stats)` is the dropped-probe count."""
    reps_on = cfg.replication > 1
    if reps_on and (rep_ids is None or live is None):
        raise ValueError("replication > 1 needs rep_ids/live")
    L, n = cfg.params.L, cx.n
    b_loc = q.shape[0]
    _, flat = _flat_plan(cfg, cx, q, hyperplanes)
    probes = _probes_issued(flat["mask"])
    flat_tgt = jnp.repeat(targets.astype(jnp.int32), L)

    if not cx.routed:
        # membership needs no payload, so the fused path also serves
        # ids-only stores (need_payload=False)
        if _fused_on(cfg, cx, has_payload=True, has_corpus=False,
                     need_payload=False):
            hit = _fused_contains_local(
                cfg, store_ids, flat["table"], flat["local"], flat["mask"],
                flat_tgt,
            )
        else:
            hit = _contains_hits(
                cfg, cx, store_ids, None, flat["table"], flat["local"],
                flat["mask"], flat_tgt,
            )
        return (hit.reshape(b_loc, L).any(axis=-1),
                StepStats.local(n, probes, b_loc))

    if cfg.routing == "allgather":
        me = cx.axis_index()
        g, rtable, b_all = _gather_flat_meta(
            cx, dict(flat, target=flat_tgt), b_loc, L,
            ("owner", "local", "mask", "target"))
        hit = _contains_hits(
            cfg, cx, store_ids, cache_ids, rtable, g["local"], g["mask"],
            g["target"],
            fused=_fused_on(cfg, cx, has_payload=True, has_corpus=False,
                            need_payload=False),
        )
        hit = hit & (g["owner"] == me)
        # OR across shards == psum of disjoint indicators, then own slice.
        hit_all = jax.lax.psum(
            hit.reshape(b_all, L).any(axis=-1).astype(jnp.int32), cx.axis
        )
        hits = jax.lax.dynamic_slice_in_dim(hit_all, me * b_loc, b_loc) > 0
        return hits, StepStats.local(n, probes, b_loc * n)

    dest = flat["owner"]
    fanout = 1
    if reps_on:
        dest, rep_col, fanout = _replica_targets(cfg, dest, live)
        if fanout > 1:
            flat = {k: jnp.tile(v, fanout) for k, v in flat.items()}
            flat_tgt = jnp.tile(flat_tgt, fanout)
    cap = _route_cap(cfg, b_loc) * fanout
    route = routing_mod.plan_routes(dest, n, cap)
    cols = [flat["qidx"], flat["table"], flat["local"], flat["mask"], flat_tgt]
    if reps_on:
        cols.append(rep_col)
    meta = jnp.stack(cols, axis=-1)
    send_meta = routing_mod.build_send_buffer(route, n, cap, meta, -1)
    recv_meta = cx.all_to_all(send_meta)
    rtable = jnp.maximum(recv_meta[..., 1].reshape(-1), 0)
    rlocal = jnp.maximum(recv_meta[..., 2].reshape(-1), 0)
    rmask = jnp.maximum(recv_meta[..., 3].reshape(-1), 0)
    rtgt = recv_meta[..., 4].reshape(-1)
    rrep = None
    if reps_on:
        rrep = jnp.clip(recv_meta[..., 5].reshape(-1), 0, cfg.replication - 1)

    hit = _contains_hits(cfg, cx, store_ids, cache_ids, rtable, rlocal,
                         rmask, rtgt, rep_ids=rep_ids, rep_sel=rrep,
                         fused=_fused_on(cfg, cx, has_payload=True,
                                         has_corpus=False,
                                         need_payload=False))
    # empty-slot rows carry rtgt = -1, which DOES match empty bucket ids
    # (-1); this validity mask is what discards those spurious hits.
    hit = hit & (recv_meta[..., 1].reshape(-1) >= 0)
    if reps_on:
        hit = hit & cx.alive(live)

    back = cx.all_to_all(hit.reshape(n, cap).astype(jnp.int32))
    got = routing_mod.return_to_origin(route, back, 0)       # [b*L*fan]
    hits = got.reshape(fanout, b_loc, L).any(axis=(0, 2))
    return hits, _routed_stats(
        route, dest, flat["qidx"], b_loc, n, probes, fanout)


# -----------------------------------------------------------------------------
# the insert / payload-sync step kernels (soft-state maintenance)
# -----------------------------------------------------------------------------


def insert_kernel(
    cfg: RuntimeConfig,
    cx,
    hyperplanes: jax.Array,
    st: BucketStore,
    vec: jax.Array,  # [nv_loc, d] this node's slice of the announce batch
    vid: jax.Array,  # [nv_loc] int32 (< 0 entries are padding, skipped)
    now: jax.Array,  # int32 scalar
) -> BucketStore:
    """Per-node body of insert/refresh: each node keeps the vectors whose
    exact buckets it owns (paper Sec. 2.2 — update rate << query rate, so
    the simple gather path is the right trade)."""
    me = cx.axis_index()
    # gather over ALL batch axes: every store replica (data axis) must
    # see every vector, not just its own data-row's slice.
    vec_all = cx.all_gather_batch(vec)
    vid_all = cx.all_gather_batch(vid)
    plan = plan_mod.make_plan(
        # insert wants only the owner/local split of the exact bucket
        dataclasses.replace(cfg.probe_spec, variant="lsh"),
        vec_all, hyperplanes, cfg.topo,
    )
    owner, local = plan.owner, plan.local_idx.astype(jnp.uint32)
    # mark foreign (table, vector) entries invalid: blank foreign rows
    # with id -1; insert_masked routes them out of bounds (mode='drop')
    # so they can't clobber live slots.
    mine_any = owner == me[None, None]                       # [nv, L]
    new = st
    payload = None
    if st.payload is not None:
        if cfg.score == "hamming":
            # hamming stores embed the packed sketch words, not the f32
            # vector — the planner already sketched the batch, so the
            # pack is a pure bit shuffle on codes it computed anyway.
            from repro.core import packed as packed_mod

            W = packed_mod.num_words(cfg.params.k, cfg.params.L)
            if st.payload.dtype != jnp.uint32 or st.payload.shape[-1] != W:
                raise ValueError(
                    "score='hamming' insert needs a packed uint32 payload "
                    f"[..., {W}] — run pack_store_payload on stores built "
                    f"for dot scoring; got {st.payload.dtype} payload with "
                    f"shape {tuple(st.payload.shape)}"
                )
            payload = packed_mod.pack_codes(plan.codes, cfg.params.k)
        else:
            payload = vec_all
    for l in range(cfg.params.L):
        sel = mine_any[:, l]
        ids_l = jnp.where(sel, vid_all, -1)
        codes_l = jnp.where(sel, local[:, l], 0).astype(jnp.uint32)
        new = store_mod.insert_masked(new, l, ids_l, codes_l, now, payload)
    # every node bumps its replica by the same L, so the replicated
    # generation stays consistent across the mesh.
    return new


def payload_sync_kernel(
    cx, store_ids: jax.Array, store_payload: jax.Array, vec: jax.Array
) -> jax.Array:
    """Point every live bucket entry's payload at the latest announced
    vector of its id.

    The corpus-scored reference always scores against the LATEST announced
    vector, while the embedded-payload store keeps whatever was announced
    into each bucket; after a re-announce moves a user to new buckets, the
    copies left in its old buckets (alive until the TTL GC collects them)
    would score with outdated vectors — this restores the reference
    semantics.  Timestamps are untouched, so GC behaviour is unchanged.

    Contract: `vec` row i must be the vector of user id i (dense 0-based
    ids), sharded over the batch axes — the layout the churn driver uses.
    """
    vec_all = cx.all_gather_batch(vec)
    nv = vec_all.shape[0]
    live = (store_ids >= 0) & (store_ids < nv)
    gathered = vec_all[jnp.clip(store_ids, 0, nv - 1)]
    return jnp.where(live[..., None], gathered, store_payload)


def replicate_kernel(cfg: RuntimeConfig, cx, store_ids, store_payload):
    """Per-node body of replica construction: ship this node's zone to its
    R-1 ring successors, one ppermute per replica rank.

    Replica rank r of node j's zone lands on node (j + r) % n
    (`CanTopology.replicas_of`), so node i's received slice r-1 holds the
    zone of node (i - r) % n — same local bucket indices as on the
    primary.  Returns (rep_ids [T, R-1, NBl, C], rep_payload [..., D]).

    Announce-coupled freshness (paper Sec. 4.1): the driver re-runs this
    after every announce round (insert + expire + payload_sync), which IS
    the replication of those writes — soft state needs no separate
    replica-maintenance protocol, and `costmodel.estimate_replication_bytes`
    charges each fan-out.
    """
    n = cx.n
    ids_slices, pay_slices = [], []
    for r in range(1, cfg.replication):
        perm = [(i, (i + r) % n) for i in range(n)]
        ids_slices.append(cx.ppermute(store_ids, perm))
        pay_slices.append(cx.ppermute(store_payload, perm))
    return (
        jnp.stack(ids_slices, axis=1),
        jnp.stack(pay_slices, axis=1),
    )


# -----------------------------------------------------------------------------
# IndexRuntime: step constructors + host-level API over one topology
# -----------------------------------------------------------------------------


class IndexRuntime:
    """The five index operations bound to one topology.

    * ``IndexRuntime(cfg)`` with ``cfg.n_nodes == 1`` and no mesh: steps
      are plain ``jax.jit`` functions — the single-host engine's
      execution context (LshEngine is a façade over this).
    * ``IndexRuntime(cfg, mesh)``: steps are ``shard_map`` collectives
      built by the `repro.core.distributed` adapter; ``cfg.n_nodes`` must
      equal the mesh's `model`-axis size.

    The host-level methods (`search`, `contains`, `insert`, `expire`,
    `payload_sync`, `refresh_cache`, `shard_store`) hide the remaining
    signature differences (device placement, cache plumbing), so scenario
    drivers are topology-blind.  Steps are built lazily and cached.
    """

    def __init__(self, cfg: RuntimeConfig, mesh=None,
                 batch_axes=("data", "model")):
        if mesh is None and cfg.n_nodes != 1:
            raise ValueError(
                f"n_nodes={cfg.n_nodes} needs a mesh (the distributed "
                "adapter); only the 1-node topology runs mesh-free"
            )
        if mesh is not None and mesh.shape["model"] != cfg.n_nodes:
            raise ValueError(
                f"cfg.n_nodes={cfg.n_nodes} != mesh model axis "
                f"{mesh.shape['model']}"
            )
        self.cfg = cfg
        self.mesh = mesh
        self.batch_axes = batch_axes
        self._steps: dict[str, object] = {}

    # -- topology facts -------------------------------------------------------

    @property
    def topology(self) -> CanTopology:
        return self.cfg.topo

    @property
    def is_distributed(self) -> bool:
        return self.mesh is not None

    @property
    def n_devices(self) -> int:
        """Devices the query/vector batch shards over (pad batches to a
        multiple of this)."""
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    def _dist(self):
        from repro.core import distributed as dist

        return dist

    def _step(self, name: str, build):
        if name not in self._steps:
            self._steps[name] = build()
        return self._steps[name]

    # -- raw step functions (unjitted; serve backends wrap + count traces) ----

    def search_step_fn(self, with_corpus: bool = False):
        """The search step as a plain callable.

        1-node: ``fn(hyperplanes, store_ids, payload_or_corpus, q, exclude,
        m)`` (m static under jit).  Mesh: the shard_map'd callable of the
        distributed adapter, ``fn(hyperplanes, ids, payload, [cache...],
        q)`` with ``m = cfg.m`` baked in.
        """
        if self.mesh is None:
            cfg = self.cfg

            if with_corpus:
                def fn(hyperplanes, store_ids, corpus, q, exclude, m):
                    return search_kernel(
                        cfg, LOCAL, m, hyperplanes, store_ids, None,
                        None, None, q, corpus=corpus, exclude=exclude,
                    )
            else:
                def fn(hyperplanes, store_ids, store_payload, q, exclude, m):
                    return search_kernel(
                        cfg, LOCAL, m, hyperplanes, store_ids, store_payload,
                        None, None, q, exclude=exclude,
                    )
            return fn
        if with_corpus:
            raise ValueError("corpus scoring is 1-node only")
        return self._dist().search_step_fn(self.cfg, self.batch_axes)(
            self.mesh
        )

    # -- the five step constructors ------------------------------------------

    def make_search_step(self):
        if self.mesh is None:
            return self._step(
                "search",
                lambda: jax.jit(self.search_step_fn(), static_argnums=(5,)),
            )
        return self._step(
            "search",
            lambda: self._dist().make_search_step(
                self.cfg, self.mesh, self.batch_axes
            ),
        )

    def make_contains_step(self):
        if self.mesh is None:
            cfg = self.cfg

            def fn(hyperplanes, store_ids, q, targets):
                return contains_kernel(
                    cfg, LOCAL, hyperplanes, store_ids, None, q, targets
                )

            return self._step("contains", lambda: jax.jit(fn))
        return self._step(
            "contains",
            lambda: self._dist().make_contains_step(
                self.cfg, self.mesh, self.batch_axes
            ),
        )

    def make_insert_step(self):
        """Insert/refresh step; donates the store on every topology (the
        payload is the largest buffer, and the old generation is dead)."""
        if self.mesh is None:
            cfg = self.cfg

            def fn(hyperplanes, st: BucketStore, vec, vid, now):
                return insert_kernel(cfg, LOCAL, hyperplanes, st, vec, vid,
                                     now)

            return self._step(
                "insert", lambda: jax.jit(fn, donate_argnums=(1,)))
        return self._step(
            "insert",
            lambda: self._dist().make_insert_step(
                self.cfg, self.mesh, self.batch_axes
            ),
        )

    def make_expire_step(self):
        # GC is elementwise over bucket state: the same jit'd op on every
        # topology (shard-local on a mesh store by construction).
        return store_mod.expire

    def make_payload_sync(self):
        if self.mesh is None:
            def fn(st: BucketStore, vec):
                return dataclasses.replace(
                    st,
                    payload=payload_sync_kernel(LOCAL, st.ids, st.payload,
                                                vec),
                    generation=st.generation + 1,
                )

            return self._step(
                "payload_sync", lambda: jax.jit(fn, donate_argnums=(0,))
            )
        return self._step(
            "payload_sync",
            lambda: self._dist().make_payload_sync(
                self.cfg, self.mesh, self.batch_axes
            ),
        )

    def make_refresh_cache(self):
        """CNB neighbor-cache refresh, or None on topologies without
        node bits (1-node: every near bucket is already local)."""
        if self.cfg.node_bits == 0:
            return None
        return self._step(
            "refresh_cache",
            lambda: self._dist().make_refresh_cache(self.cfg, self.mesh),
        )

    def make_replicate_step(self):
        """Replica-slice construction (R-way availability, DESIGN.md
        Sec. 10), or None at replication == 1."""
        if self.cfg.replication == 1:
            return None
        return self._step(
            "replicate",
            lambda: self._dist().make_replicate_store(self.cfg, self.mesh),
        )

    # -- host-level convenience API (topology-blind drivers) ------------------

    def shard_store(self, store: BucketStore) -> BucketStore:
        if self.mesh is None:
            return store
        return self._dist().shard_store(self.mesh, store)

    def _put_batch(self, x, is_vec: bool):
        if self.mesh is None:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(self.batch_axes, None) if is_vec else P(self.batch_axes)
        return jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, spec))

    def insert(self, hyperplanes, store: BucketStore, vec, vid, now):
        step = self.make_insert_step()
        return step(
            hyperplanes, store, self._put_batch(vec, True),
            self._put_batch(vid, False), jnp.int32(now),
        )

    def expire(self, store: BucketStore, now, ttl: int) -> BucketStore:
        return self.make_expire_step()(store, jnp.int32(now), ttl=ttl)

    def payload_sync(self, store: BucketStore, vec, *,
                     hyperplanes=None) -> BucketStore:
        if self.cfg.score == "hamming":
            if hyperplanes is None:
                raise ValueError(
                    "score='hamming' payload_sync needs hyperplanes= to "
                    "re-sketch the announced vectors into packed words"
                )
            from repro.core import hashing
            from repro.core import packed as packed_mod

            codes = hashing.sketch_codes(jnp.asarray(vec), hyperplanes)
            vec = packed_mod.pack_codes(codes, self.cfg.params.k)
        return self.make_payload_sync()(store, self._put_batch(vec, True))

    def refresh_cache(self, store: BucketStore):
        refresh = self.make_refresh_cache()
        if refresh is None:
            return None
        return refresh(store.ids, store.payload)

    def replicate_store(self, store: BucketStore):
        """Build the (rep_ids, rep_payload) slices from the current store,
        or None at replication == 1.  Call after every announce round —
        replica freshness rides the soft-state re-announce cycle."""
        step = self.make_replicate_step()
        if step is None:
            return None
        return step(store.ids, store.payload)

    def _live_arr(self, live):
        if live is None:
            return jnp.ones((self.cfg.n_nodes,), jnp.int32)
        return jnp.asarray(live, jnp.int32)

    def search(self, hyperplanes, store: BucketStore, q, *, cache=None,
               corpus=None, exclude=None, m: int | None = None,
               replicas=None, live=None):
        """(ids [nq, m], scores [nq, m], stats `StepStats`) over this
        topology — `int(stats)` is the dropped-probe count.

        `m` defaults to cfg.m (mesh steps bake it — passing a different m
        there is an error).  `corpus`/`exclude` are the single-host
        reference data model and only exist on the 1-node topology.
        With `cfg.replication > 1`, `replicas` (from `replicate_store`) is
        required and `live` ([n_nodes] 0/1, default all-live) selects the
        replica owners reads may land on.
        """
        if self.cfg.replication > 1 and replicas is None:
            raise ValueError(
                "replication > 1: pass replicas= (see replicate_store)"
            )
        if self.cfg.replication == 1 and (replicas is not None
                                          or live is not None):
            raise ValueError("replicas/live require cfg.replication > 1")
        qd = self._put_batch(q, True)
        if self.mesh is None:
            m = self.cfg.m if m is None else m
            ex = None if exclude is None else jnp.asarray(exclude, jnp.int32)
            if corpus is not None:
                step = self._step(
                    "search_corpus",
                    lambda: jax.jit(self.search_step_fn(with_corpus=True),
                                    static_argnums=(5,)),
                )
                return step(hyperplanes, store.ids, corpus, qd, ex, m)
            step = self.make_search_step()
            return step(hyperplanes, store.ids, store.payload, qd, ex, m)
        if m is not None and m != self.cfg.m:
            raise ValueError(f"mesh steps bake m={self.cfg.m}; got m={m}")
        if corpus is not None or exclude is not None:
            raise ValueError("corpus scoring / exclusion are 1-node only")
        step = self.make_search_step()
        args = (hyperplanes, store.ids, store.payload)
        if cache is not None:
            args += tuple(cache)
        if self.cfg.replication > 1:
            args += (replicas[0], replicas[1], self._live_arr(live))
        return step(*args, qd)

    def contains(self, hyperplanes, store: BucketStore, q, targets, *,
                 cache=None, replicas=None, live=None):
        if self.cfg.replication > 1 and replicas is None:
            raise ValueError(
                "replication > 1: pass replicas= (see replicate_store)"
            )
        if self.cfg.replication == 1 and (replicas is not None
                                          or live is not None):
            raise ValueError("replicas/live require cfg.replication > 1")
        qd = self._put_batch(q, True)
        td = self._put_batch(np.asarray(targets, np.int32), False)
        step = self.make_contains_step()
        if self.mesh is None:
            return step(hyperplanes, store.ids, qd, td)
        args = (hyperplanes, store.ids)
        if cache is not None:
            args += (cache[0],)
        if self.cfg.replication > 1:
            args += (replicas[0], self._live_arr(live))
        return step(*args, qd, td)


# -----------------------------------------------------------------------------
# failure injection: fail-stop kill with NO handoff (DESIGN.md Sec. 10)
# -----------------------------------------------------------------------------


def kill_node(rt: IndexRuntime, store: BucketStore, replicas, node: int):
    """Fail-stop loss of one node: its bucket zone AND its held replica
    slices vanish with NO handoff (contrast `reshard`, the graceful path).

    Models the P2P peer that simply disappears: the zone `zone_range(node)`
    is blanked in the primary store, and the node's replica slices (copies
    of OTHER nodes' zones it was holding) are blanked too — replicas OF its
    zone on its ring successors survive untouched, which is what quorum /
    first-responder reads then serve from.  Bumps `generation` so serve
    caches drop results that may contain the dead node's rows.  Returns
    (store, replicas); pair with a 0 entry in the `live` mask until the
    next re-announce repopulates the zone (`estimate_recovery_bytes`).
    """
    s, e = rt.topology.zone_range(node)
    payload = store.payload
    if payload is not None:
        payload = payload.at[:, s:e].set(jnp.zeros((), payload.dtype))
    new_store = dataclasses.replace(
        store,
        ids=store.ids.at[:, s:e].set(store_mod.EMPTY),
        timestamps=store.timestamps.at[:, s:e].set(0),
        write_ptr=store.write_ptr.at[:, s:e].set(0),
        payload=payload,
        generation=store.generation + 1,
    )
    new_reps = replicas
    if replicas is not None:
        rep_ids, rep_payload = replicas
        new_reps = (
            rep_ids.at[:, :, s:e].set(store_mod.EMPTY),
            rep_payload.at[:, :, s:e].set(jnp.zeros((), rep_payload.dtype)),
        )
    return new_store, new_reps


# -----------------------------------------------------------------------------
# elastic membership: reshard a runtime to a new node count (DESIGN.md Sec. 9)
# -----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReshardEvent:
    """Ledger entry of one membership round (power-of-two join/leave).

    `moved_buckets` counts the bucket rows (across all L tables) whose
    owner changed; `handoff_bytes` is the Table-1-analogue byte charge of
    shipping those rows (ids + timestamps + embedded payloads + ring
    pointers) to the new owners.  The node-churn driver reports these
    alongside the refresh bytes — handoff is never silently uncharged.
    """

    old_n: int
    new_n: int
    moved_buckets: int
    handoff_bytes: int


def gather_store(store: BucketStore) -> BucketStore:
    """Host-global view of a (possibly mesh-sharded) store.

    The zones are contiguous sketch-prefix slices of ONE global bucket
    array, so the globally-assembled state is topology-free: pulling it
    to the host is the simulation-level handoff fabric every reshard
    routes through (real deployments ship only the moved slices — the
    byte charge in `ReshardEvent` is computed for exactly those).
    """
    g = jax.device_get
    return BucketStore(
        ids=jnp.asarray(g(store.ids)),
        timestamps=jnp.asarray(g(store.timestamps)),
        write_ptr=jnp.asarray(g(store.write_ptr)),
        payload=None if store.payload is None else jnp.asarray(
            g(store.payload)),
        generation=jnp.asarray(g(store.generation)),
    )


def reshard(
    rt: IndexRuntime,
    store: BucketStore,
    new_n_nodes: int | None = None,
    *,
    mesh=None,
    runtime: IndexRuntime | None = None,
    cap_factor: float | None = None,
) -> tuple[IndexRuntime, BucketStore, ReshardEvent]:
    """Elastic node membership: split/merge the contiguous sketch-prefix
    CAN zones to `new_n_nodes` owners and hand the bucket state off.

    Power-of-two join/leave per the `can.py` geometry: growing N -> rN
    splits every zone — the incumbent keeps the first subzone, r-1
    joiners take the rest; shrinking merges sibling groups onto the
    group's first node.  The global bucket array is INVARIANT under the
    round (zones are slices of it), and the probe planner derives the
    same probe set on every topology, so search results are bit-identical
    before vs. after a reshard round-trip (pinned in tests/test_runtime.py
    against the checked-in goldens).

    `runtime=` reuses a pre-built target runtime (keeps its compiled
    steps across repeated membership rounds); otherwise a new one is
    built from this runtime's config with `n_nodes=new_n_nodes` (and
    `cap_factor`, default unchanged) on `mesh` (None => the 1-node
    mesh-free context).  NB caches are NOT migrated: their shape is
    topology-dependent, so callers must rebuild via
    `new_rt.refresh_cache(new_store)` — the refresh-byte charge of
    warming the joiners' caches.

    Returns (new_runtime, migrated_store, ReshardEvent).  The migrated
    store's generation is bumped: a membership round is a state event the
    serving layer's sketch-keyed cache must not survive.
    """
    from repro.core import costmodel

    if runtime is not None:
        if mesh is not None or cap_factor is not None:
            raise ValueError(
                "mesh=/cap_factor= don't apply to a prebuilt runtime — "
                "build the target runtime with them instead"
            )
        if new_n_nodes is not None and new_n_nodes != runtime.cfg.n_nodes:
            raise ValueError(
                f"runtime has n_nodes={runtime.cfg.n_nodes}, "
                f"asked for {new_n_nodes}"
            )
        # a membership round replaces ONLY the topology knobs: any other
        # config drift (variant, m, probe budget, routing...) would
        # silently change the query discipline mid-trajectory
        if dataclasses.replace(
            runtime.cfg, n_nodes=rt.cfg.n_nodes,
            cap_factor=rt.cfg.cap_factor,
        ) != rt.cfg:
            raise ValueError(
                "target runtime differs beyond the topology knobs: "
                f"{runtime.cfg} vs {rt.cfg}"
            )
        new_rt = runtime
    else:
        if new_n_nodes is None:
            raise ValueError("need new_n_nodes or a prebuilt runtime")
        cfg = dataclasses.replace(
            rt.cfg,
            n_nodes=int(new_n_nodes),
            cap_factor=float(
                rt.cfg.cap_factor if cap_factor is None else cap_factor
            ),
        )
        new_rt = IndexRuntime(cfg, mesh=mesh, batch_axes=rt.batch_axes)

    host = gather_store(store)
    host = dataclasses.replace(host, generation=host.generation + 1)
    new_store = new_rt.shard_store(host)
    d = 0 if host.payload is None else int(host.payload.shape[-1])
    event = ReshardEvent(
        old_n=rt.cfg.n_nodes,
        new_n=new_rt.cfg.n_nodes,
        moved_buckets=rt.cfg.params.L * can_moved_buckets(
            rt.cfg.topo, new_rt.cfg.topo),
        handoff_bytes=costmodel.estimate_handoff_bytes(
            rt.cfg.params.L, host.ids.shape[1], host.ids.shape[2], d,
            rt.cfg.n_nodes, new_rt.cfg.n_nodes,
        ),
    )
    return new_rt, new_store, event

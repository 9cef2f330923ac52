"""Shared probe planner: ONE query discipline for both runtimes (DESIGN.md
Sec. 3.1).

The paper's contribution is a single probing rule — the exact bucket
g_l(q) plus k 1-near buckets per table, split by the CAN geometry into
free local-bit probes and costed node-bit probes.  This module turns
`(queries, LshParams, variant, num_probes, ranked_probes)` into an
explicit `ProbePlan` pytree consumed by the `IndexRuntime` step kernels
(`repro.core.runtime` — on every topology, DESIGN.md Sec. 8) and the
benchmarks, so the discipline is implemented exactly once:

  * `ProbePlan.probes` — compact per-table probe codes (exact bucket
    first) for stacked gathers and benchmark sweeps;
  * `ProbePlan.probe_mask` — per-(query, table) bitmask of which of the k
    near buckets (bit flips) are probed; the runtime routes this mask
    with the query and applies it at the owner shard (local bits), the
    neighbor cache (node bits, CNB), and the XOR-neighbor forwards
    (node bits, NB) — on the 1-node topology every bit is local, so the
    mask application IS the reference probe set;
  * `ProbePlan.owner` / `ProbePlan.local_idx` — the CAN owner-shard /
    local-bucket split of each exact bucket.

Both views are derived from the same margin ranking / probe budget, so
runtimes on different topologies given the same `ProbeSpec` search the
same buckets — the equivalence the tests pin down.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import costmodel, hashing, multiprobe
from repro.core.can import CanTopology
from repro.core.hashing import LshParams


@dataclasses.dataclass(frozen=True)
class ProbeSpec:
    """Static description of the query discipline (what to probe)."""

    params: LshParams
    variant: str = "cnb"           # lsh | layered | nb | cnb
    num_probes: int | None = None  # None => all k 1-near buckets (the paper)
    ranked_probes: bool = False    # margin-ranked probe subset (beyond paper)

    def __post_init__(self):
        if self.variant not in costmodel.VARIANTS:
            raise ValueError(f"unknown variant {self.variant!r}")
        if self.num_probes is not None and self.num_probes < 0:
            raise ValueError(f"num_probes must be >= 0, got {self.num_probes}")

    @property
    def near_probes(self) -> int:
        """1-near buckets probed per table."""
        if self.variant in ("lsh", "layered"):
            return 0
        k = self.params.k
        return k if self.num_probes is None else min(self.num_probes, k)

    @property
    def probes_per_table(self) -> int:
        """Buckets searched per (query, table), exact bucket included."""
        return 1 + self.near_probes


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ProbePlan:
    """Per-query probe decisions (a pytree of device arrays).

    Shapes below use nq = leading query dims, L = tables, P = 1 + p
    probes per table (`ProbeSpec.probes_per_table`).
    """

    codes: jax.Array       # uint32 [nq, L]    exact sketch codes
    probes: jax.Array      # uint32 [nq, L, P] probe codes, exact first
    probe_mask: jax.Array  # uint32 [nq, L]    bit j set => 1-near bucket
    #                                          (flip of bit j) is probed
    owner: jax.Array       # int32  [nq, L]    owner shard of exact bucket
    local_idx: jax.Array   # int32  [nq, L]    bucket index within shard


def sketch(
    q: jax.Array, hyperplanes: jax.Array, *, use_kernels: bool = False
) -> jax.Array:
    """uint32 codes [..., L] — fused Pallas simhash kernel or the jnp oracle."""
    if use_kernels:
        from repro.kernels import ops

        return ops.simhash(q, hyperplanes)
    return hashing.sketch_codes(q, hyperplanes)


def make_plan(
    spec: ProbeSpec,
    q: jax.Array,                       # [..., d] unit queries
    hyperplanes: jax.Array,             # [L, k, d]
    topology: CanTopology | None = None,
    *,
    use_kernels: bool = False,
) -> ProbePlan:
    """Plan the probes for a batch of queries.

    jit-compatible (all branching is on static `spec` fields); the result
    is a pytree that can cross shard_map / jit boundaries.
    """
    k = spec.params.k
    topo = topology or CanTopology(k, 1 << k)  # paper: one bucket per node
    with jax.named_scope("sketch"):
        codes = sketch(q, hyperplanes, use_kernels=use_kernels)  # [..., L]

    p = spec.near_probes
    full_mask = jnp.uint32((1 << k) - 1)
    if p == 0:
        probes = codes[..., None].astype(jnp.uint32)
        mask = jnp.zeros_like(codes, dtype=jnp.uint32)
    elif p >= k:
        probes = multiprobe.probe_codes(codes, k)
        mask = jnp.full_like(codes, full_mask, dtype=jnp.uint32)
    elif spec.ranked_probes:
        margins = hashing.projection_margins(q, hyperplanes)  # [..., L, k]
        bits = jnp.argsort(margins, axis=-1)[..., :p].astype(jnp.uint32)
        flips = jnp.uint32(1) << bits                          # [..., L, p]
        near = codes[..., None].astype(jnp.uint32) ^ flips
        probes = jnp.concatenate(
            [codes[..., None].astype(jnp.uint32), near], axis=-1
        )
        # bits are distinct, so the sum of their powers of two == their OR
        mask = jnp.sum(flips, axis=-1, dtype=jnp.uint32)
    else:
        near = multiprobe.near_codes(codes, k)[..., :p]
        probes = jnp.concatenate(
            [codes[..., None].astype(jnp.uint32), near], axis=-1
        )
        mask = jnp.full_like(codes, jnp.uint32((1 << p) - 1), dtype=jnp.uint32)

    return ProbePlan(
        codes=codes,
        probes=probes,
        probe_mask=mask,
        owner=topo.node_of(codes).astype(jnp.int32),
        local_idx=topo.local_of(codes).astype(jnp.int32),
    )


# -----------------------------------------------------------------------------
# shard-side views (run inside shard_map at the owner shard)
# -----------------------------------------------------------------------------


def shard_local_probes(
    topo: CanTopology,
    local_idx: jax.Array,    # int32 [...]
    probe_mask: jax.Array,   # uint32/int32 [...] (routed with the query)
    *,
    include_near: bool,
) -> tuple[jax.Array, jax.Array]:
    """Local bucket indices to probe at the owner shard, with validity.

    Returns (buckets [..., P], valid [..., P]): exact bucket first, then
    one entry per local bit; entry 1 + j (the flip of local bit j) is
    valid iff bit j of `probe_mask` is set.  Local-bit probes are free —
    same device — which is why the budget mask, not the buffer layout,
    carries the num_probes discipline here.
    """
    exact = local_idx[..., None]
    always = jnp.ones_like(exact, dtype=bool)
    if not include_near or topo.local_bits == 0:
        return exact, always
    bits = jnp.arange(topo.local_bits, dtype=jnp.uint32)
    near = jnp.bitwise_xor(exact, (1 << bits).astype(local_idx.dtype))
    nvalid = ((probe_mask[..., None].astype(jnp.uint32) >> bits) & 1) > 0
    return (
        jnp.concatenate([exact, near], axis=-1),
        jnp.concatenate([always, nvalid], axis=-1),
    )


def node_bit_probe_valid(
    topo: CanTopology, probe_mask: jax.Array, bit: int
) -> jax.Array:
    """Is the near bucket reached by flipping node bit `bit` probed?

    Node-bit flips keep the local index and move to the XOR-neighbor
    shard; the distributed runtime covers them via the neighbor cache
    (CNB) or neighbor forwards (NB), gated per query by this mask bit.
    """
    shift = jnp.uint32(topo.local_bits + bit)
    return ((probe_mask.astype(jnp.uint32) >> shift) & 1) > 0

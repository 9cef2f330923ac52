"""Cosine LSH via sign random projections (Charikar, STOC'02).

A hash family H over unit vectors where Pr[h(u) = h(v)] = 1 - theta(u,v)/pi
(= angular similarity).  A bucket function g in G concatenates k independent
h's into a k-bit sketch; L independent g's map each vector into L buckets.

Sketches are bit-packed into uint32 codes (k <= 30), which double as CAN
node/zone coordinates (see `repro.core.can`).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MAX_K = 30  # codes are uint32; keep headroom for safe int32 arithmetic.


@dataclasses.dataclass(frozen=True)
class LshParams:
    """Static configuration of the LSH scheme (paper Sec. 3.1)."""

    d: int  # input dimensionality
    k: int  # bits per sketch (hash functions per g)
    L: int  # number of hash tables / buckets per vector
    seed: int = 0

    def __post_init__(self):
        if not (1 <= self.k <= MAX_K):
            raise ValueError(f"k must be in [1, {MAX_K}], got {self.k}")
        if self.L < 1:
            raise ValueError(f"L must be >= 1, got {self.L}")

    @property
    def num_buckets(self) -> int:
        return 1 << self.k


def make_hyperplanes(params: LshParams, dtype=jnp.float32) -> jax.Array:
    """Sample the L*k random hyperplanes, shape [L, k, d].

    Gaussian entries make each row a uniformly random hyperplane normal,
    which is exactly the Goemans-Williamson rounding construction.

    The draw pins the non-partitionable threefry stream: the hyperplanes
    are the index's hash function, so a seed must name the same planes
    on every jax release (newer releases default to the partitionable
    stream, which would re-bucket every stored sketch).
    """
    key = jax.random.PRNGKey(params.seed)
    with jax.threefry_partitionable(False):
        return jax.random.normal(
            key, (params.L, params.k, params.d), dtype=dtype)


def normalize(x: jax.Array, axis: int = -1, eps: float = 1e-12) -> jax.Array:
    """L2-normalize so that cosine similarity == dot product."""
    n = jnp.linalg.norm(x, axis=axis, keepdims=True)
    return x / jnp.maximum(n, eps)


def sketch_bits(x: jax.Array, hyperplanes: jax.Array) -> jax.Array:
    """Sign bits of the random projections.

    Args:
      x: [..., d] vectors.
      hyperplanes: [L, k, d].

    Returns:
      bool [..., L, k]; bit j of table l is (x . h_{l,j} >= 0).
    """
    proj = jnp.einsum("...d,lkd->...lk", x, hyperplanes)
    return proj >= 0


def projection_margins(x: jax.Array, hyperplanes: jax.Array) -> jax.Array:
    """|x . h| per bit, [..., L, k] — the multi-probe ranking signal.

    A small margin means the sign is 'almost flipped': the 1-near bucket
    obtained by flipping that bit is the likeliest to hold near neighbors
    (Lv et al., VLDB'07).  Used by the beyond-paper ranked probing mode.
    """
    proj = jnp.einsum("...d,lkd->...lk", x, hyperplanes)
    return jnp.abs(proj)


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack [..., k] boolean sketch bits into uint32 codes (bit 0 = index 0)."""
    k = bits.shape[-1]
    weights = (jnp.uint32(1) << jnp.arange(k, dtype=jnp.uint32))
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits(codes: jax.Array, k: int) -> jax.Array:
    """Inverse of `pack_bits`: uint32 [...,] -> bool [..., k]."""
    shifts = jnp.arange(k, dtype=jnp.uint32)
    return ((codes[..., None] >> shifts) & jnp.uint32(1)).astype(bool)


def sketch_codes(x: jax.Array, hyperplanes: jax.Array) -> jax.Array:
    """x [..., d] -> uint32 codes [..., L]: the L bucket ids of each vector."""
    return pack_bits(sketch_bits(x, hyperplanes))


@partial(jax.jit, static_argnames=())
def _sketch_codes_jit(x, hyperplanes):
    return sketch_codes(x, hyperplanes)


def sketch_codes_batched(
    x: jax.Array, hyperplanes: jax.Array, batch: int = 65536
) -> np.ndarray:
    """Host-side chunked sketching for large corpora (preprocessing path)."""
    n = x.shape[0]
    out = np.empty((n, hyperplanes.shape[0]), dtype=np.uint32)
    for s in range(0, n, batch):
        e = min(s + batch, n)
        out[s:e] = np.asarray(_sketch_codes_jit(x[s:e], hyperplanes))
    return out


def hamming_distance(a: jax.Array, b: jax.Array) -> jax.Array:
    """Popcount Hamming distance between packed codes (uint32)."""
    x = jnp.bitwise_xor(a.astype(jnp.uint32), b.astype(jnp.uint32))
    return popcount32(x)


def popcount32(x: jax.Array) -> jax.Array:
    """Vectorized 32-bit popcount (SWAR); works on TPU VPU and CPU."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def collision_probability(u: jax.Array, v: jax.Array) -> jax.Array:
    """Analytical Pr[h(u)=h(v)] = angular similarity (Eq. 2/3 of the paper)."""
    un, vn = normalize(u), normalize(v)
    cos = jnp.clip(jnp.sum(un * vn, axis=-1), -1.0, 1.0)
    return 1.0 - jnp.arccos(cos) / jnp.pi

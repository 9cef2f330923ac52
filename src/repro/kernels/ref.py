"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; kernels must match them bit-for-bit (integer
outputs) or to float tolerance (scores).  Top-k selection ties are broken
by lowest index in both ref and kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def simhash_ref(x: jnp.ndarray, hyperplanes: jnp.ndarray) -> jnp.ndarray:
    """Packed sign-random-projection sketches.

    Args:
      x: [n, d] float.
      hyperplanes: [L, k, d] float.
    Returns:
      uint32 [n, L]; bit j of table l is (x . h_{l,j} >= 0).
    """
    proj = jnp.einsum(
        "nd,lkd->nlk", x.astype(jnp.float32), hyperplanes.astype(jnp.float32)
    )
    bits = (proj >= 0).astype(jnp.uint32)
    k = hyperplanes.shape[1]
    weights = jnp.uint32(1) << jnp.arange(k, dtype=jnp.uint32)
    return jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)


def bucket_topk_ref(
    q: jnp.ndarray, cand: jnp.ndarray, valid: jnp.ndarray, m: int
):
    """Fused candidate scoring + top-m.

    Args:
      q: [b, d] unit queries.
      cand: [b, kc, d] candidate vectors (gathered bucket payloads).
      valid: bool [b, kc] — invalid candidates must not be returned.
      m: results per query.
    Returns:
      (scores f32 [b, m], idx int32 [b, m]) — idx into kc, -1 where no valid
      candidate; sorted by descending score, ties -> lowest index.
    """
    scores = jnp.einsum(
        "bd,bkd->bk", q.astype(jnp.float32), cand.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = jnp.where(valid, scores, -jnp.inf)
    kc = scores.shape[1]
    # tie-break by lowest index: subtract a tiny index-based epsilon ordering
    # implemented exactly via lexicographic argmax loop.
    out_s, out_i = [], []
    cur = scores
    idxs = jnp.arange(kc, dtype=jnp.int32)
    for _ in range(m):
        best = jnp.argmax(cur, axis=1)  # first occurrence of max => lowest idx
        s = jnp.take_along_axis(cur, best[:, None], axis=1)[:, 0]
        out_s.append(s)
        out_i.append(jnp.where(jnp.isfinite(s), best.astype(jnp.int32), -1))
        cur = jnp.where(idxs[None, :] == best[:, None], -jnp.inf, cur)
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)


def _popcount32_ref(x: jnp.ndarray) -> jnp.ndarray:
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def hamming_ref(codes: jnp.ndarray, cand_codes: jnp.ndarray) -> jnp.ndarray:
    """Popcount Hamming distances between uint32 codes.

    Args:
      codes: [n] uint32.
      cand_codes: [n, kc] uint32.
    Returns:
      int32 [n, kc].
    """
    x = jnp.bitwise_xor(
        codes[:, None].astype(jnp.uint32), cand_codes.astype(jnp.uint32)
    )
    return _popcount32_ref(x)


def hamming_words_ref(
    codes: jnp.ndarray, cand_codes: jnp.ndarray
) -> jnp.ndarray:
    """Multi-word variant: distances over packed sketch-word rows.

    Args:
      codes: [n, W] uint32 packed words (core.packed layout).
      cand_codes: [n, kc, W] uint32.
    Returns:
      int32 [n, kc] — popcount summed over the word axis.
    """
    x = jnp.bitwise_xor(
        codes[:, None, :].astype(jnp.uint32), cand_codes.astype(jnp.uint32)
    )
    return jnp.sum(_popcount32_ref(x), axis=-1)


def fused_query_ref(
    ids_flat: jnp.ndarray,   # int32 [T*NB, KC]
    pay_flat: jnp.ndarray,   # [T*NB, KC, DW] f32 vectors or uint32 words
    q: jnp.ndarray,          # [r, DW]
    fb: jnp.ndarray,         # int32 [r, P] flattened bucket row per probe
    meta: jnp.ndarray,       # int32 [r, 2] (probe-validity word, exclude id)
    *,
    m: int,
    score: str = "dot",
):
    """Oracle for the fused query mega-kernel: explicit staged pipeline.

    Gathers the probed bucket rows ([r, P, KC] intermediates — exactly
    the HBM traffic the fused kernel exists to avoid), masks candidates
    by probe-validity bit / EMPTY sentinel / exclude id, scores, and
    reduces through `core.scoring.dedupe_topk` — so the oracle IS the
    staged path's semantics, not a re-derivation of them.

    Returns (ids int32 [r, m], scores f32 [r, m]).
    """
    from repro.core.scoring import dedupe_topk  # deps run kernels->core here

    r, n_probes = fb.shape
    kc = ids_flat.shape[-1]
    pw, excl = meta[:, 0], meta[:, 1]
    cand = jnp.take(ids_flat, fb, axis=0)                  # [r, P, KC]
    pvalid = ((pw[:, None] >> jnp.arange(n_probes)) & 1) > 0
    cand = jnp.where(pvalid[:, :, None] & (cand >= 0), cand, -1)
    cand = jnp.where(cand == excl[:, None, None], -1, cand)
    pay = jnp.take(pay_flat, fb, axis=0)                   # [r, P, KC, DW]
    if score == "dot":
        s = jnp.einsum(
            "rd,rpkd->rpk", q.astype(jnp.float32), pay.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
    elif score == "hamming":
        s = -hamming_words_ref(
            q.reshape(r, 1, -1).repeat(n_probes, axis=1).reshape(-1, q.shape[-1]),
            pay.reshape(r * n_probes, kc, -1),
        ).reshape(r, n_probes, kc).astype(jnp.float32)
    else:
        raise ValueError(f"unknown score mode: {score!r}")
    flat_ids = cand.reshape(r, n_probes * kc)
    flat_s = jnp.where(flat_ids >= 0, s.reshape(r, n_probes * kc), -jnp.inf)
    return dedupe_topk(flat_ids, flat_s, m)


def fused_contains_ref(
    ids_flat: jnp.ndarray,   # int32 [T*NB, KC]
    fb: jnp.ndarray,         # int32 [r, P]
    meta: jnp.ndarray,       # int32 [r, 2] (probe-validity word, target id)
) -> jnp.ndarray:
    """Oracle for `fused_contains`: int32 [r, 1] hit flags."""
    r, n_probes = fb.shape
    pw, tgt = meta[:, 0], meta[:, 1]
    cand = jnp.take(ids_flat, fb, axis=0)                  # [r, P, KC]
    pvalid = ((pw[:, None] >> jnp.arange(n_probes)) & 1) > 0
    hit = jnp.any((cand == tgt[:, None, None]) & pvalid[:, :, None], axis=(1, 2))
    return hit.astype(jnp.int32)[:, None]

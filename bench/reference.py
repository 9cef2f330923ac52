"""Plain reference of the served index's answers (numpy, float64).

It imports nothing of the program and takes nothing the program made: it
gets the corpus and the hash planes the benchmark made from the seed and
builds its own picture of the index from the stated semantics:

  * hash: bit j of table l is sign(h_lj . x) >= 0, packed little-endian
    into a k-bit bucket code.  The program projects at its backend's
    default matmul precision, which on a TPU rounds both operands to
    bfloat16 first; `hash_precision="bf16"` applies the same rounding
    (the products of two bfloat16 numbers are exact, so only the order
    of the float32 sum can still differ, by ~1e-7 of a projection).
  * bucket: a ring of `capacity` slots filled in announce order (ids
    ascending), so it holds the newest `capacity` ids hashed to it.
  * probes (cnb, all near buckets): per table the exact bucket and its k
    one-bit neighbours.
  * answer: every distinct id in the probed buckets, scored by the f32
    dot product (here in float64), top m by score with ties to the
    lowest id.

`compare` reads the program's answers against these and returns the
numbers `correct` is decided by.
"""

from __future__ import annotations

import numpy as np


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), returned as float64."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def bucket_codes(x: np.ndarray, planes: np.ndarray,
                 hash_precision: str) -> np.ndarray:
    """uint32 [n, L] bucket codes of the rows of x under the planes."""
    L, k, d = planes.shape
    h = planes.reshape(L * k, d)
    if hash_precision == "bf16":
        xs, hs = bf16_round(x), bf16_round(h)
    elif hash_precision == "f32":
        xs, hs = np.asarray(x, np.float64), np.asarray(h, np.float64)
    else:
        raise ValueError(f"unknown hash precision {hash_precision!r}")
    out = np.empty((x.shape[0], L), np.uint32)
    weights = np.uint32(1) << np.arange(k, dtype=np.uint32)
    for s in range(0, x.shape[0], 1 << 16):
        bits = (xs[s:s + (1 << 16)] @ hs.T >= 0).reshape(-1, L, k)
        out[s:s + (1 << 16)] = (bits * weights).sum(-1, dtype=np.uint32)
    return out


class ReferenceIndex:
    """The index as its semantics define it, built from the corpus."""

    def __init__(self, vecs: np.ndarray, planes: np.ndarray, *,
                 capacity: int, hash_precision: str):
        self.vecs = np.asarray(vecs, np.float32)
        self.planes = np.asarray(planes, np.float32)
        self.hash_precision = hash_precision
        L, k, _ = self.planes.shape
        self.k = k
        codes = bucket_codes(self.vecs, self.planes, hash_precision)
        n = codes.shape[0]
        self.start, self.members = [], []
        for l in range(L):
            order = np.argsort(codes[:, l], kind="stable")  # ids ascending
            sc = codes[order, l].astype(np.int64)
            count = np.bincount(sc, minlength=1 << k)
            end = np.cumsum(count)
            rank_from_end = end[sc] - np.arange(n)       # 1 = newest
            keep = rank_from_end <= capacity
            kept = order[keep].astype(np.int32)
            kept_count = np.minimum(count, capacity)
            start = np.concatenate([[0], np.cumsum(kept_count)])
            self.start.append(start)
            self.members.append(kept)

    def candidates(self, q: np.ndarray) -> np.ndarray:
        """Distinct ids in every probed bucket of one query."""
        codes = bucket_codes(q[None], self.planes, self.hash_precision)[0]
        flips = np.concatenate(
            [[0], np.uint32(1) << np.arange(self.k, dtype=np.uint32)])
        parts = [np.empty(0, np.int32)]
        for l, c in enumerate(codes):
            for b in (c ^ flips).astype(np.int64):
                s, e = self.start[l][b], self.start[l][b + 1]
                parts.append(self.members[l][s:e])
        return np.unique(np.concatenate(parts))

    def answer(self, q: np.ndarray, m: int):
        """(ids [m], scores [m] float64, candidate ids, their scores)."""
        cand = self.candidates(q)
        sc = self.vecs[cand].astype(np.float64) @ np.asarray(q, np.float64)
        order = np.lexsort((cand, -sc))[:m]              # score desc, id asc
        ids = np.full(m, -1, np.int64)
        top = np.full(m, -np.inf)
        ids[:order.size] = cand[order]
        top[:order.size] = sc[order]
        return ids, top, cand, sc


def compare(ref: ReferenceIndex, queries: np.ndarray, got_ids: np.ndarray,
            got_scores: np.ndarray, m: int) -> dict:
    """The number `correct` is decided by, over the sampled answers.

    answer_gap  the widest, over answers and ranks r, of two gaps: how
                far the reference's score of the id the program put at
                rank r lies below the reference's r-th best score (a
                wrong or missed neighbour), and how far the score the
                program returned lies from the reference's float64 score
                of that id (a score computed in lower precision).  An id
                the probes cannot reach, a repeated id, or a rank left
                empty while a candidate remains reads as infinite.

    The two parts are returned beside it as `rank_gap` and `score_gap`.
    """
    inf = dict(answer_gap=float("inf"), rank_gap=float("inf"),
               score_gap=float("inf"))
    rank_gap = 0.0
    score_gap = 0.0
    for q, gi, gs in zip(queries, got_ids, got_scores):
        want_i, want_s, cand, csc = ref.answer(q, m)
        valid = gi >= 0
        if np.any(valid != (want_i >= 0)):
            return inf
        if np.unique(gi[valid]).size != int(valid.sum()):
            return inf
        pos = np.minimum(np.searchsorted(cand, gi[valid]),
                         max(cand.size - 1, 0))
        if cand.size == 0 and valid.any() or np.any(cand[pos] != gi[valid]):
            return inf
        have = csc[pos]
        rank_gap = max(rank_gap, float(np.max(want_s[valid] - have,
                                              initial=0.0)))
        score_gap = max(score_gap, float(np.max(
            np.abs(gs[valid].astype(np.float64) - have), initial=0.0)))
    return dict(answer_gap=max(rank_gap, score_gap), rank_gap=rank_gap,
                score_gap=score_gap)

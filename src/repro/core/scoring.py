"""Shared bucket score + top-m stage (`LocalSimSearch`, Alg. 1 line 11).

One module owns the candidate-scoring semantics for the whole system: the
single-host `LshEngine` and the distributed `shard_map` runtime both call
`score_topk`, so the per-shard search is literally the same code as the
reference path the tests pin down.

Two interchangeable implementations:
  * reference — plain einsum + `dedupe_topk` (the semantic oracle);
  * kernel    — candidates are sorted by id (so the Pallas tie-break
    "lowest index" coincides with the reference's "lowest id"), duplicate
    ids are masked invalid, and the fused `bucket_topk` Pallas kernel does
    score + top-m in VMEM.  Returns bit-identical ids to the reference on
    equal inputs (CI-checked in tests/test_engine.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# a Python float, NOT a jnp scalar: this module is lazily imported from
# inside jitted code (kernels/ref.py), and a module-level jnp constant
# created under an active trace leaks a tracer into later traces
NEG_INF = float("-inf")


def _sorted_dup_mask(ids: jax.Array):
    """Sort candidate ids ascending; mark repeats of the previous entry.

    Returns (order, ids_sorted, dup_mask).  Both top-m implementations share
    this prologue so the dedup semantics (-1 = invalid, lowest id wins score
    ties) cannot drift apart between the reference and kernel paths.
    """
    order = jnp.argsort(ids, axis=-1)
    ids_s = jnp.take_along_axis(ids, order, -1)
    dup = jnp.concatenate(
        [jnp.zeros_like(ids_s[..., :1], bool), ids_s[..., 1:] == ids_s[..., :-1]],
        axis=-1,
    )
    return order, ids_s, dup


def dedupe_topk(ids: jax.Array, scores: jax.Array, m: int):
    """Top-m by score with duplicate ids collapsed (same id => same score).

    ids/scores: [..., K].  Invalid candidates are id -1 / score -inf.
    m may exceed K (fewer live candidates than requested results): the
    tail pads out as id -1 / score -inf rather than tripping top_k's
    k <= K requirement.
    """
    order, ids_s, dup = _sorted_dup_mask(ids)
    sc_s = jnp.take_along_axis(scores, order, -1)
    sc_s = jnp.where(dup | (ids_s < 0), NEG_INF, sc_s)
    k = ids.shape[-1]
    if m > k:
        pad = ids.shape[:-1] + (m - k,)
        ids_s = jnp.concatenate(
            [ids_s, jnp.full(pad, -1, ids_s.dtype)], axis=-1
        )
        sc_s = jnp.concatenate(
            [sc_s, jnp.full(pad, NEG_INF, sc_s.dtype)], axis=-1
        )
    top_s, top_pos = jax.lax.top_k(sc_s, m)
    top_i = jnp.take_along_axis(ids_s, top_pos, -1)
    top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
    top_s = jnp.where(jnp.isfinite(top_s), top_s, -jnp.inf)
    return top_i, top_s


def score_topk(
    q: jax.Array,          # [b, d] unit queries (or [b, W] packed words)
    cand_ids: jax.Array,   # int32 [b, K] candidate ids, -1 = invalid
    cand_vecs: jax.Array,  # f32 [b, K, d] payloads (or uint32 [b, K, W])
    m: int,
    *,
    use_kernels: bool = False,
    interpret: bool | None = None,
    score: str = "dot",
):
    """Score candidates against their query and keep the best m distinct ids.

    `score="dot"` takes f32 payload vectors; `score="hamming"` takes
    bit-packed sketch words (`core.packed` layout) on both sides and
    scores by negated popcount distance — exact integers, so the staged
    and fused paths agree bit-for-bit on scores, not just ids.  The
    kernel path of hamming mode runs the multi-word
    `kernels.ops.hamming` Pallas kernel.

    Returns (ids int32 [b, m], scores f32 [b, m]); empty slots are
    id -1 / score -inf, ordered by descending score.
    """
    if score == "hamming":
        if use_kernels:
            from repro.kernels import ops

            h = ops.hamming(q, cand_vecs, interpret=interpret)
        else:
            from repro.core.packed import hamming_words

            h = hamming_words(q[:, None, :], cand_vecs)
        scores = jnp.where(cand_ids >= 0, -h.astype(jnp.float32), NEG_INF)
        return dedupe_topk(cand_ids, scores, m)
    if not use_kernels:
        # full f32 products (the TPU's default would round to bf16): the
        # fused kernel scores in f32 too, so the two paths rank alike
        scores = jnp.einsum("bkd,bd->bk", cand_vecs, q,
                            precision=jax.lax.Precision.HIGHEST)
        scores = jnp.where(cand_ids >= 0, scores, NEG_INF)
        return dedupe_topk(cand_ids, scores, m)
    return _score_topk_kernel(q, cand_ids, cand_vecs, m, interpret)


def _score_topk_kernel(q, cand_ids, cand_vecs, m, interpret):
    from repro.kernels import ops

    order, ids_s, dup = _sorted_dup_mask(cand_ids)               # [b, K]
    vecs_s = jnp.take_along_axis(cand_vecs, order[..., None], -2)
    valid = (ids_s >= 0) & ~dup
    scores, idx = ops.bucket_topk(q, vecs_s, valid, m, interpret=interpret)
    top_i = jnp.take_along_axis(ids_s, jnp.maximum(idx, 0), -1)
    return jnp.where(idx >= 0, top_i, -1), scores

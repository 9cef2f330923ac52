"""Pallas mega-kernel: fused gather -> score -> top-m for the per-node
query path (DESIGN.md Sec. 11).

The staged path materializes a [r, P*C] candidate id buffer and a
[r, P*C, D] payload buffer in HBM between its gather, score, and top-m
stages.  This kernel runs the whole per-(query, table) pipeline inside
one `pallas_call`: probed bucket rows are gathered straight into VMEM
via scalar-prefetch-driven BlockSpecs (the flattened bucket index of
every (row, probe) pair is prefetched, so the gather IS the block index
map — no gathered intermediate ever exists in HBM), scored on the MXU
(dot product for embedded f32 payloads; for bit-packed sketch words the
hamming distance as two 0/1 bit-plane matmuls, exact in f32),
deduplicated, and reduced to the top m (id, score) pairs per row.

Grid: (r/TB, P, TB) — probe steps and rows-within-block iterate
sequentially ("arbitrary" semantics).  Every block the index maps hand
the kernel is tile-aligned for the TPU: the bucket-id view is the
8-row slab holding the probed row (the kernel picks its sublane), the
payload view is the probed bucket's whole [KC, D] vectors (or its
word-major [W, KC] sketch words), and the query view is the block's
[TB, D|W] rows.  Scores are computed for all TB
query rows against the probed bucket (the MXU's minimum M is 8 anyway)
and row t's lanes are kept by a masked whole-tile update of a
[P, TB, KC] VMEM scratch, so no store is ever sublane-unaligned.  The
final step of each row block marks duplicates and runs the m-step
selection on those [TB, KC] tiles, then writes the [TB, m] outputs.
TB and KC (the per-probe candidate lane width) are the block shape
(`kernels/autotune.py`).

Semantics are pinned bit-exactly to the staged path
(`core.scoring.score_topk` over the stacked gather):
  * candidate validity: probe bit p of the prefetched probe-word must be
    set, slot id >= 0, id != the row's exclude id — EMPTY (-1) sentinels
    ride in-register, there is no separate mask buffer;
  * duplicate ids: the FIRST occurrence in (probe-major, slot-minor)
    flat order survives with its own score — identical to the stable
    id-sort + repeat-of-previous mask in `core.scoring.dedupe_topk`;
  * selection: descending score, ties to the LOWEST id (the staged
    top_k over id-sorted lanes breaks ties the same way).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = float("-inf")
IMAX = 2**31 - 1  # id sentinel > any real id (ids are int32 >= 0)
SLAB = 8          # bucket-id rows per gathered block (the int32 sublane tile)

_ARBITRARY = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
)


def _bit_planes(words, axis: int):
    """uint32 packed words -> f32 0/1 bit planes: bit i of word w lands
    at index 32*w + i of `axis` ([n, W] -> [n, 32W] for axis 1,
    [W, n] -> [32W, n] for axis 0)."""
    w = words.shape[axis]
    shape = list(words.shape)
    shape[axis] = 32 * w
    pos = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis)
    shift = (pos % 32).astype(jnp.uint32)
    bits = jnp.zeros(tuple(shape), jnp.uint32)
    for j in range(w):
        word = words[:, j:j + 1] if axis == 1 else words[j:j + 1, :]
        bits = jnp.where(pos // 32 == j,
                         (jnp.broadcast_to(word, bits.shape) >> shift)
                         & jnp.uint32(1), bits)
    return bits.astype(jnp.int32).astype(jnp.float32)


def _block_scores(pay, q, *, score: str):
    """[TB, KC] scores of every query row of the block against one
    probed bucket: dot product of q [TB, D] with pay [KC, D], or the
    negated hamming distance of q words [TB, W] to the word-major
    pay [W, KC], as popcount(q & ~p) + popcount(~q & p) over 0/1 bit
    planes (exact: at most 32*W per lane)."""
    if score == "dot":
        return jax.lax.dot_general(
            q, pay, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    qb, pb = _bit_planes(q, 1), _bit_planes(pay, 0)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    return -(dot(qb, 1.0 - pb) + dot(1.0 - qb, pb))


def _dup_mask(ids):
    """Per probe tile [TB, KC]: 1 where the lane's id already occurred
    earlier in its row's (probe-major, slot-minor) flat order.

    Lane rotations pair every lane with every other lane of the same row
    in KC - 1 steps; a rotated copy of the lane index tells which partner
    came first, so the result does not depend on the rotation direction.
    """
    n_probes = len(ids)
    tb, kc = ids[0].shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, kc), 1)
    zero = jnp.zeros((tb, kc), jnp.int32)
    dup = []
    for p in range(n_probes):
        d = zero
        for q in range(p):
            d = d | (ids[q] == ids[p]).astype(jnp.int32)
        dup.append(d)

    def body(_, carry):
        rolled, src, dup = carry
        rolled = [pltpu.roll(x, 1, 1) for x in rolled]
        src = pltpu.roll(src, 1, 1)
        earlier = src < lane
        new = []
        for p in range(n_probes):
            hit = (rolled[p] == ids[p]) & earlier
            for q in range(p):
                hit = hit | (rolled[q] == ids[p])
            new.append(dup[p] | hit.astype(jnp.int32))
        return rolled, src, new

    _, _, dup = jax.lax.fori_loop(1, kc, body, (list(ids), lane, dup))
    return dup


def _select_topm(ids, sc, m: int):
    """Dedup (first occurrence wins) + m-step (max score, min id) select
    over per-probe tiles ids/sc [TB, KC].  Returns ([TB, m], [TB, m])."""
    dup = _dup_mask(ids)
    sc = [jnp.where((d > 0) | (x < 0), NEG, s)
          for x, s, d in zip(ids, sc, dup)]
    tb = ids[0].shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, m), 1)
    out_i = jnp.full((tb, m), -1, jnp.int32)
    out_s = jnp.full((tb, m), NEG, jnp.float32)
    for j in range(m):  # m static & small: unrolled selection
        bs = jnp.max(functools.reduce(jnp.maximum, sc), axis=1,
                     keepdims=True)                        # [TB, 1]
        bi = jnp.min(
            functools.reduce(jnp.minimum, [
                jnp.where(s == bs, x, IMAX) for x, s in zip(ids, sc)]),
            axis=1, keepdims=True)                         # [TB, 1]
        out_i = jnp.where(col == j, jnp.where(bs == NEG, -1, bi), out_i)
        out_s = jnp.where(col == j, bs, out_s)
        sc = [jnp.where(x == bi, NEG, s) for x, s in zip(ids, sc)]
    return out_i, out_s


def _slab_row(ids_ref, fb):
    """The probed bucket's [1, KC] id row out of its 8-row slab."""
    slab = ids_ref[...]
    sub = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 0)
    return jnp.sum(jnp.where(sub == fb % SLAB, slab, 0), axis=0,
                   keepdims=True)


def _fused_query_kernel(
    fb_ref, meta_ref,               # scalar prefetch: [r*P], [r*2]
    q_ref, ids_ref, pay_ref,        # blocks: [TB, DW], [8, KC], [1, KC, D]
    #                                 (dot) or [1, W, KC] (hamming)
    ids_out_ref, sc_out_ref,        # blocks: [TB, m]
    id_acc, sc_acc,                 # VMEM scratch: [P, TB, KC]
    *, m: int, tb: int, n_probes: int, score: str,
):
    p = pl.program_id(1)
    t = pl.program_id(2)
    r = pl.program_id(0) * tb + t
    pw, excl = meta_ref[2 * r], meta_ref[2 * r + 1]
    ids_row = _slab_row(ids_ref, fb_ref[r * n_probes + p])   # [1, KC]
    pvalid = ((pw >> p) & 1) > 0
    cand = jnp.where(pvalid & (ids_row >= 0) & (ids_row != excl),
                     ids_row, -1)
    s = _block_scores(pay_ref[0], q_ref[...], score=score)  # [TB, KC]
    mine = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) == t
    id_acc[p] = jnp.where(mine, cand, id_acc[p])
    sc_acc[p] = jnp.where(mine, jnp.where(cand >= 0, s, NEG), sc_acc[p])

    @pl.when((p == n_probes - 1) & (t == tb - 1))
    def _reduce():
        top_i, top_s = _select_topm(
            [id_acc[j] for j in range(n_probes)],
            [sc_acc[j] for j in range(n_probes)], m)
        ids_out_ref[...] = top_i
        sc_out_ref[...] = top_s


@functools.partial(
    jax.jit, static_argnames=("m", "tb", "score", "interpret")
)
def fused_query_pallas(
    ids_flat: jax.Array,   # int32 [T*NB, KC] (rows % 8 == 0, capacity
    #                        padded with -1)
    pay_flat: jax.Array,   # [T*NB, KC, D] f32 vectors, or word-major
    #                        uint32 [T*NB, W, KC] packed sketch words
    q: jax.Array,          # [r, DW] f32 queries or uint32 query words
    fb: jax.Array,         # int32 [r, P] flattened bucket row per probe
    meta: jax.Array,       # int32 [r, 2] (probe-validity word, exclude id)
    *,
    m: int,
    tb: int,
    score: str = "dot",
    interpret: bool = False,
):
    """(ids int32 [r, m], scores f32 [r, m]) — r % tb == 0 required;
    pad rows must carry probe-word 0 (they return all -1 / -inf)."""
    r, n_probes = fb.shape
    kc = ids_flat.shape[1]
    dw = q.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r // tb, n_probes, tb),
        in_specs=[
            pl.BlockSpec((tb, dw), lambda i, p, t, fb_, mt: (i, 0)),
            pl.BlockSpec(
                (SLAB, kc),
                lambda i, p, t, fb_, mt:
                    (fb_[(i * tb + t) * n_probes + p] // SLAB, 0)),
            pl.BlockSpec(
                (1,) + pay_flat.shape[1:],
                lambda i, p, t, fb_, mt:
                    (fb_[(i * tb + t) * n_probes + p], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, m), lambda i, p, t, fb_, mt: (i, 0)),
            pl.BlockSpec((tb, m), lambda i, p, t, fb_, mt: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_probes, tb, kc), jnp.int32),
            pltpu.VMEM((n_probes, tb, kc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _fused_query_kernel,
            m=m, tb=tb, n_probes=n_probes, score=score,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, m), jnp.int32),
            jax.ShapeDtypeStruct((r, m), jnp.float32),
        ],
        compiler_params=_ARBITRARY,
        interpret=interpret,
        name="fused_query",
    )(fb.reshape(-1), meta.reshape(-1), q, ids_flat, pay_flat)


def _fused_contains_kernel(
    fb_ref, meta_ref,               # scalar prefetch: [r*P], [r*2]
    ids_ref,                        # block: [8, KC]
    hit_ref,                        # block: [TB, 1] int32
    *, tb: int, n_probes: int,
):
    p = pl.program_id(1)
    t = pl.program_id(2)
    r = pl.program_id(0) * tb + t
    slab = ids_ref[...]
    sub = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 0)
    pvalid = ((meta_ref[2 * r] >> p) & 1) > 0
    match = ((sub == fb_ref[r * n_probes + p] % SLAB)
             & (slab == meta_ref[2 * r + 1]) & pvalid)
    hit = jnp.max(jnp.max(match.astype(jnp.int32), axis=1, keepdims=True),
                  axis=0, keepdims=True)                   # [1, 1]

    @pl.when((p == 0) & (t == 0))
    def _init():
        hit_ref[...] = jnp.zeros_like(hit_ref)

    mine = jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0) == t
    acc = hit_ref[...]
    hit_ref[...] = jnp.where(mine, acc | hit, acc)


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def fused_contains_pallas(
    ids_flat: jax.Array,   # int32 [T*NB, KC] (rows % 8 == 0)
    fb: jax.Array,         # int32 [r, P]
    meta: jax.Array,       # int32 [r, 2] (probe-validity word, target id)
    *,
    tb: int,
    interpret: bool = False,
):
    """int32 [r, 1]: nonzero iff the target id sits in any valid probed
    bucket of the row.  Same gather discipline as `fused_query_pallas`,
    metadata-only (no payload blocks travel)."""
    r, n_probes = fb.shape
    kc = ids_flat.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r // tb, n_probes, tb),
        in_specs=[
            pl.BlockSpec(
                (SLAB, kc),
                lambda i, p, t, fb_, mt:
                    (fb_[(i * tb + t) * n_probes + p] // SLAB, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i, p, t, fb_, mt: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_fused_contains_kernel, tb=tb, n_probes=n_probes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        compiler_params=_ARBITRARY,
        interpret=interpret,
        name="fused_contains",
    )(fb.reshape(-1), meta.reshape(-1), ids_flat)

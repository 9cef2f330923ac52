"""Pallas TPU kernel: fused bucket scoring + top-m selection.

The paper's per-node `LocalSimSearch` (Alg. 1 line 11 / Alg. 2 line 2):
score a query against every vector in the probed bucket(s) and keep the
best m.  On TPU the bucket payload tile lives in VMEM, the scoring is a
[TB, D] x [TB, TKC, D] batched dot, and the top-m selection is an m-step
argmax loop on the VPU — the score matrix never leaves VMEM.

m is small and static (paper uses m = 10), so the unrolled selection loop
beats a full sort by a wide margin.

Tiling: grid (b/TB, KC/TKC).  KC (candidates per query = L * probes *
capacity, gathered by the caller) is lane-padded to 128 and walked in
TKC = 128-wide chunks; a [TB, m] VMEM carry holds the running top m, which
each chunk's selection merges with its own candidates.  VMEM use is
therefore independent of KC.  Invalid slots carry validity 0 and return
score=-inf, idx=-1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = float("-inf")  # plain Python float: jnp constants can't be captured by kernels
IMAX = 2**31 - 1
TKC = 128  # candidates per grid step: one lane width


def _topk_kernel(q_ref, cand_ref, valid_ref, s_ref, i_ref, run_s, run_i,
                 *, m: int):
    c = pl.program_id(1)
    tb, tkc = valid_ref.shape

    @pl.when(c == 0)
    def _init():
        run_s[...] = jnp.full(run_s.shape, NEG, jnp.float32)
        run_i[...] = jnp.full(run_i.shape, -1, jnp.int32)

    scores = jax.lax.dot_general(
        cand_ref[...],
        q_ref[...],
        (((2,), (1,)), ((0,), (0,))),  # batch over TB, contract D
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [TB, TKC]
    scores = jnp.where(valid_ref[...] > 0, scores, NEG)
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, tkc), 1) + c * tkc
    rs, ri = run_s[...], run_i[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, (tb, m), 1)
    out_s = jnp.full((tb, m), NEG, jnp.float32)
    out_i = jnp.full((tb, m), -1, jnp.int32)
    # running entries all come from earlier chunks (lower indices), so
    # "ties -> lowest index" is the min over both index sets
    for j in range(m):  # m static & small: unrolled argmax selection
        best_s = jnp.maximum(jnp.max(rs, axis=1, keepdims=True),
                             jnp.max(scores, axis=1, keepdims=True))
        best_i = jnp.minimum(
            jnp.min(jnp.where(rs == best_s, ri, IMAX), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(scores == best_s, col, IMAX), axis=1,
                    keepdims=True))
        dead = best_s == NEG
        out_s = jnp.where(slot == j, best_s, out_s)
        out_i = jnp.where(slot == j, jnp.where(dead, -1, best_i), out_i)
        rs = jnp.where(ri == best_i, NEG, rs)
        scores = jnp.where(col == best_i, NEG, scores)
    run_s[...] = out_s
    run_i[...] = out_i

    @pl.when(c == pl.num_programs(1) - 1)
    def _emit():
        s_ref[...] = out_s
        i_ref[...] = out_i


@functools.partial(jax.jit, static_argnames=("m", "tb", "interpret"))
def bucket_topk_pallas(
    q: jax.Array,       # [b, d] float32   (b % tb == 0, d lane-padded)
    cand: jax.Array,    # [b, kc, d] float32 (kc % TKC == 0)
    valid: jax.Array,   # int32 [b, kc] (nonzero = valid candidate)
    *,
    m: int,
    tb: int = 8,
    interpret: bool = False,
):
    b, kc, d = cand.shape
    grid = (b // tb, kc // TKC)
    return pl.pallas_call(
        functools.partial(_topk_kernel, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, d), lambda i, c: (i, 0)),
            pl.BlockSpec((tb, TKC, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((tb, TKC), lambda i, c: (i, c)),
        ],
        out_specs=[
            pl.BlockSpec((tb, m), lambda i, c: (i, 0)),
            pl.BlockSpec((tb, m), lambda i, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m), jnp.float32),
            jax.ShapeDtypeStruct((b, m), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tb, m), jnp.float32),
            pltpu.VMEM((tb, m), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, cand, valid)

"""Pallas TPU kernel: fused sign-random-projection sketching (simhash).

Computes packed LSH sketch codes  codes[n, L] = pack_k(sign(X @ H^T))  in one
pass: the projection matmul runs on the MXU, sign + bit-pack on the VPU, and
only the 4-byte codes leave VMEM — the [n, L*k] projection intermediate never
touches HBM.  This is the hash hot-spot of the paper's pre-processing and
query paths (Sec. 4.1: every user re-hashes periodically; every query hashes
into L sketches).

Tiling: grid (n/TN, d/TD).  d is the contraction dim; a VMEM scratch
accumulator [TN, LK] carries partial projections across d-steps
("arbitrary" semantics); the pack happens on the last d-step.
LK = L*k is zero-padded to a lane multiple (128) by the ops.py wrapper.

Two output layouts, chosen by `packed`:
  * per-table codes uint32 [n, L] (k live bits per lane) — the classic
    layout every bucket mapper consumes;
  * dense packed words uint32 [n, W], W = ceil(L*k/32), the
    `core.packed` layout — global bit l*k + j lands in word (l*k+j)/32.
    Hamming-mode runtimes sketch queries straight into this layout, so
    the unpacked [n, L] intermediate never exists.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _simhash_kernel(
    x_ref, h_ref, out_ref, acc_ref, *, k: int, L: int, packed: bool = False
):
    d_step = pl.program_id(1)
    n_dsteps = pl.num_programs(1)

    @pl.when(d_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # [TN, TD] @ [TD, LKpad] -> [TN, LKpad] partial projection on the MXU.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        h_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(d_step == n_dsteps - 1)
    def _pack():
        # Bits are packed in int32 (the TPU reduces no unsigned type):
        # each word sums distinct powers of two, so the two's-complement
        # sum IS the bit pattern, reinterpreted as uint32 on the way out.
        proj = acc_ref[...]  # [TN, LKpad]
        bits = (proj >= 0).astype(jnp.int32)  # [TN, LKpad]
        # lane l*k + j holds bit j of table l.
        lane = jax.lax.broadcasted_iota(jnp.int32, proj.shape, 1)
        if packed:
            # dense core.packed layout: lane g -> word g/32 bit g%32.
            # padded tail lanes (proj 0 => bit 1) must be masked here.
            n_words = -(-(L * k) // 32)
            live = jnp.where(lane < L * k, bits, 0)
            shifted = live << (lane % 32)
            words = [
                jnp.sum(jnp.where(lane // 32 == w, shifted, 0), axis=1)
                for w in range(n_words)
            ]
        else:
            # (lane % k) is the in-code bit position; padded tail lanes
            # (>= L*k) are never sliced below.
            weighted = bits << (lane % k)
            # per-table static slices + lane reduction (no in-kernel scatter)
            words = [
                jnp.sum(weighted[:, l * k : (l + 1) * k], axis=1)
                for l in range(L)
            ]
        out_ref[...] = jax.lax.bitcast_convert_type(
            jnp.stack(words, axis=1), jnp.uint32)


@functools.partial(
    jax.jit, static_argnames=("k", "L", "tn", "td", "packed", "interpret")
)
def simhash_pallas(
    x: jax.Array,          # [n, d] float32 (padded: n % tn == 0, d % td == 0)
    h_t: jax.Array,        # [d, LKpad] float32, transposed + lane-padded H
    *,
    k: int,
    L: int,
    tn: int = 256,
    td: int = 512,
    packed: bool = False,
    interpret: bool = False,
) -> jax.Array:
    n, d = x.shape
    lkpad = h_t.shape[1]
    grid = (n // tn, d // td)
    width = -(-(L * k) // 32) if packed else L
    return pl.pallas_call(
        functools.partial(_simhash_kernel, k=k, L=L, packed=packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, td), lambda i, j: (i, j)),
            pl.BlockSpec((td, lkpad), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tn, width), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((tn, lkpad), jnp.float32)],
        interpret=interpret,
    )(x, h_t)

"""Pallas TPU kernel: popcount Hamming distance over packed sketch codes.

Two entry points sharing one SWAR popcount (pure VPU bit arithmetic, no
MXU):

  * `hamming_pallas` — single-word codes ([n] vs [n, kc]), used by
    ranked multi-probe planning and Layered-LSH node assignment;
  * `hamming_words_pallas` — multi-word packed rows ([n, W] vs
    word-major [n, W, kc] candidates), the staged scoring primitive
    of `score="hamming"` runtimes; the fused query kernel inlines the
    same popcount for its hamming mode.

Tiling: grid over (n/TN); candidate dim KC is lane-padded to 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _popcount32(x: jax.Array) -> jax.Array:
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _hamming_kernel(codes_ref, cand_ref, out_ref):
    codes = codes_ref[...]  # [TN, 1] uint32
    cand = cand_ref[...]    # [TN, KC] uint32
    out_ref[...] = _popcount32(jnp.bitwise_xor(codes, cand))


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def hamming_pallas(
    codes: jax.Array,       # [n] uint32 (n % tn == 0)
    cand_codes: jax.Array,  # [n, kc] uint32 (kc % 128 == 0)
    *,
    tn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    n, kc = cand_codes.shape
    grid = (n // tn,)
    return pl.pallas_call(
        _hamming_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, 1), lambda i: (i, 0)),
            pl.BlockSpec((tn, kc), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tn, kc), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, kc), jnp.int32),
        interpret=interpret,
    )(codes[:, None], cand_codes)


def _hamming_words_kernel(codes_ref, cand_ref, out_ref):
    codes = codes_ref[...]  # [TN, W, 1] uint32
    cand = cand_ref[...]    # [TN, W, KC] uint32 (word-major)
    out_ref[...] = jnp.sum(
        _popcount32(jnp.bitwise_xor(codes, cand)), axis=1
    )


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def hamming_words_pallas(
    codes: jax.Array,       # [n, W] uint32 packed words (n % tn == 0)
    cand_codes: jax.Array,  # [n, W, kc] uint32, word-major (kc % 128 == 0)
    *,
    tn: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """Word-major candidates keep the candidate axis on the TPU's lanes
    (a [.., kc, W] block would pad W up to 128 lanes in VMEM)."""
    n, w, kc = cand_codes.shape
    grid = (n // tn,)
    return pl.pallas_call(
        _hamming_words_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, w, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((tn, w, kc), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tn, kc), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, kc), jnp.int32),
        interpret=interpret,
    )(codes[:, :, None], cand_codes)

"""The main path's Pallas kernels compile for a TPU v5e at deployment width.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, sublane-unaligned
dynamic stores, scoped-VMEM overruns, or a layout change that copies the
whole store on every call.  These tests compile each kernel for one chip
of a *described* v5e topology — no chip needed, the TPU compiler is
installed — at the width of the 1M-vector deployment that `chip_smoke.py`
serves: T = 4 tables, NB = 2^14 buckets, C = 128 slots, D = 128, W = 2
packed words (k = 14, L = 4), r = 256 probe rows (max_batch 64 x L),
P = 15 probes, m = 10.

The topology is described inside a module-scoped fixture only (never at
import): a process that loads the TPU library holds it until it exits.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

T, NB, C, D, W = 4, 2**14, 128, 128, 2
R, P, M = 256, 15, 10
PLANES = (4, 14, D)   # [L, k, d] hyperplanes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _cases():
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    fq = dict(m=M, tb=8, kc=128, interpret=False)
    return {
        "fused_query_dot": (
            lambda i, p, q, f, mt: ops.fused_query(i, p, q, f, mt,
                                                   score="dot", **fq),
            [((T * NB, C), i32), ((T * NB, C, D), f32), ((R, D), f32),
             ((R, P), i32), ((R, 2), i32)]),
        "fused_query_hamming": (
            lambda i, p, q, f, mt: ops.fused_query(i, p, q, f, mt,
                                                   score="hamming", **fq),
            [((T * NB, C), i32), ((T * NB, C, W), u32), ((R, W), u32),
             ((R, P), i32), ((R, 2), i32)]),
        "fused_contains": (
            lambda i, f, mt: ops.fused_contains(i, f, mt, tb=8,
                                                interpret=False),
            [((T * NB, C), i32), ((R, P), i32), ((R, 2), i32)]),
        "simhash": (
            lambda x, h: ops.simhash(x, h, interpret=False),
            [((R, D), f32), (PLANES, f32)]),
        "simhash_packed": (
            lambda x, h: ops.simhash(x, h, packed=True, interpret=False),
            [((R, D), f32), (PLANES, f32)]),
        "hamming": (
            lambda a, b: ops.hamming(a, b, interpret=False),
            [((R,), u32), ((R, P), u32)]),
        "hamming_words": (
            lambda a, b: ops.hamming(a, b, interpret=False),
            [((R, W), u32), ((R, P * C, W), u32)]),
        "bucket_topk": (
            lambda q, c, v: ops.bucket_topk(q, c, v, M, interpret=False),
            [((R, D), f32), ((R, P * C, D), f32), ((R, P * C), jnp.bool_)]),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    """Compiles to a Mosaic kernel (`tpu_custom_call`) and needs no
    temporary buffer: a relayout of the store, or of a gathered
    candidate tensor, would show up here as GBs of temp."""
    fn, shapes = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20

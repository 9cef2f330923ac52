"""The control comes out not correct, at a size a test run can hold.

The control is the reference itself, put in the program's place and
scoring at the next matmul precision below the configuration's (`high`,
three bfloat16 passes; `bench/control.py`).  Against the committed limit
it must fail, while the reference's own float32 answers (the precision
the configuration states) pass.  The corpus keeps the cell's width
(d = 100) and bucket capacity; it has fewer vectors and buckets.
"""

from __future__ import annotations

import numpy as np
import pytest

import control
import deploy
import reference
from conftest import tiny_config


@pytest.mark.parametrize("seed", [1, 2, 2**32 + 3])
def test_control_fails_and_reference_passes(seed):
    cfg = tiny_config(corpus={"n": 20000, "d": 100},
                      index={"k": 8, "capacity": 128})
    dep = deploy.deployment(cfg)
    vecs, centres = deploy.make_corpus(dep, seed)
    planes = deploy.make_planes(dep, seed)
    queries = deploy.make_queries(dep, centres, seed, 512)
    ref = reference.ReferenceIndex(np.asarray(vecs[:dep.n]),
                                   np.asarray(planes), capacity=dep.capacity,
                                   hash_precision="f32")
    limit = cfg["correct"]["answer_gap"]
    ctl = reference.compare(ref, queries, *control.answers(
        ref, queries, dep.m), dep.m)
    sound = reference.compare(ref, queries, *control.answers(
        ref, queries, dep.m, control.f32_dot), dep.m)
    assert ctl["answer_gap"] > limit
    assert sound["answer_gap"] < limit


def test_high_dot_is_three_bf16_passes():
    rng = np.random.default_rng(0)
    v = rng.standard_normal((64, 100)).astype(np.float32)
    q = rng.standard_normal(100).astype(np.float32)
    exact = v.astype(np.float64) @ q.astype(np.float64)
    err_high = np.abs(control.high_dot(v, q) - exact).max()
    err_f32 = np.abs(control.f32_dot(v, q) - exact).max()
    err_bf16 = np.abs(reference.bf16_round(v) @ reference.bf16_round(q)
                      - exact).max()
    assert err_f32 < err_high < err_bf16

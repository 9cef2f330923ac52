#!/usr/bin/env python3
"""The control: the reference in the program's place, one precision down.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--sound]

The configuration states f32 dot scores at the highest matmul precision.
The control answers the cell's queries from the reference's own index
but scores them as a TPU does at the next precision down, `high`: each
f32 operand split into two bfloat16 parts and the three largest cross
products summed in float32 (the smallest, lo x lo, is dropped).  Its
answers are then read by the same comparison the benchmark uses, so its
`score_gap` is the upper reading a limit must stay under.

The corpus, planes and queries are made on the device from each seed, at
the cell's own size; the program itself does not run.  `--sound` also
prints the reference's own float32 answers (highest precision, as the
configuration states) through the same comparison, for a look at the
lower side.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import deploy  # noqa: E402
import reference  # noqa: E402

SAMPLE = 4096


def _split(x: np.ndarray):
    import ml_dtypes

    x = np.asarray(x, np.float32)
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def high_dot(v: np.ndarray, q: np.ndarray) -> np.ndarray:
    """v [n, d] . q [d] at `high` precision (three bfloat16 passes)."""
    vh, vl = _split(v)
    qh, ql = _split(q)
    return (vh @ qh + vh @ ql + vl @ qh).astype(np.float32)


def f32_dot(v: np.ndarray, q: np.ndarray) -> np.ndarray:
    return (np.asarray(v, np.float32) @ np.asarray(q, np.float32)).astype(
        np.float32)


def answers(ref: reference.ReferenceIndex, queries: np.ndarray, m: int,
            score=high_dot):
    """The reference's answers with its scores taken by `score`."""
    ids = np.full((len(queries), m), -1, np.int32)
    out = np.full((len(queries), m), -np.inf, np.float32)
    for i, q in enumerate(queries):
        cand = ref.candidates(q)
        s = score(ref.vecs[cand], q)
        order = np.lexsort((cand, -s))[:m]
        ids[i, :order.size] = cand[order]
        out[i, :order.size] = s[order]
    return ids, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sound", action="store_true")
    args = ap.parse_args(argv)
    import jax

    import run

    _, cell = run.cell_spec(args.workload)
    dep = deploy.deployment(deploy.load_config(cell["config"]))
    precision = "bf16" if jax.devices()[0].platform == "tpu" else "f32"
    for seed in (int(s) for s in args.seeds.split(",")):
        vecs, centres = deploy.make_corpus(dep, seed)
        planes = deploy.make_planes(dep, seed)
        queries = deploy.make_queries(dep, centres, seed, SAMPLE)
        ref = reference.ReferenceIndex(
            np.asarray(vecs[:dep.n]), np.asarray(planes),
            capacity=dep.capacity, hash_precision=precision)
        out = {"seed": seed, "platform": jax.devices()[0].platform}
        out["control"] = reference.compare(
            ref, queries, *answers(ref, queries, dep.m), dep.m)
        if args.sound:
            out["reference_f32"] = reference.compare(
                ref, queries, *answers(ref, queries, dep.m, f32_dot), dep.m)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

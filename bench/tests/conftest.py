"""Test set-up for the benchmark's own tests (run on the CPU).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

`tiny` gives a deployment of the committed configurations' shape at a
size a CPU test can hold: the same index discipline, fewer vectors and
buckets, and a bucket capacity small enough that rings overflow.
"""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]

sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def tiny_config(name: str = "glove100-dot", **over) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg["corpus"].update(n=3000, d=20)
    cfg["index"].update(k=6, capacity=16)
    cfg["frontend"].update(max_batch=16, queue_capacity=64)
    cfg["assumed"].update(chunk=1024)
    for group, vals in over.items():
        cfg[group].update(vals)
    return cfg


@pytest.fixture
def tiny(monkeypatch):
    """Point the harness at a tiny copy of a configuration."""
    import deploy

    real = deploy.load_config

    def load(name: str) -> dict:
        return tiny_config(name) if name == "glove100-dot" else real(name)

    monkeypatch.setattr(deploy, "load_config", load)
    return load

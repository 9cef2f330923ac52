"""Persistent XLA compile cache for the repo's entry points.

Entry points (`chip_smoke.py`, `launch/serve_retrieval.py`,
`benchmarks/run.py`) call `enable()` before their first compile, so a
second run of the same program on the same machine loads its compiled
steps instead of recompiling them.  Where `JAX_COMPILATION_CACHE_DIR` is
set, jax reads it itself and nothing is set here; otherwise the cache
lives at the fixed path `<repo>/.jax_cache` (ignored by git) — the
directory is part of the cache key, so it must not move between runs.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)

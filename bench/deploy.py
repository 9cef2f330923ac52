"""One deployment of the served LSH index, built on the device from a seed.

A configuration file (`bench/configs/<name>.json`) states the corpus, the
index and the topology.  From `--seed` this module makes, each in one
jitted call on the device:

  * the corpus: clustered unit vectors (ids c*cluster .. (c+1)*cluster - 1
    scatter around one random centre, as embeddings of related items do);
  * the hash planes [L, k, d] the index buckets by;
  * fresh queries: draws around random cluster centres, never repeated.

The store is announced through the runtime's own insert step in fixed-size
chunks (the tail chunk carries id -1 rows, which the insert skips), on
one chip: one CAN zone.  The program receives only these inputs; the
reference (`bench/reference.py`) gets the same corpus and planes and
builds its own index.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Deployment:
    name: str
    n: int                 # corpus vectors
    d: int                 # vector width
    m: int                 # results per query
    k: int                 # sketch bits per table (2^k buckets)
    L: int                 # hash tables
    capacity: int          # slots per bucket (ring, keep newest)
    variant: str           # probe discipline (cnb: exact + k near buckets)
    score: str             # dot over the f32 embedded payload
    n_nodes: int           # CAN zones, one per chip
    replication: int
    max_batch: int         # frontend batch limit
    pipeline_depth: int
    cache: bool            # frontend result cache
    queue_capacity: int
    cluster: int           # corpus vectors per cluster (assumed)
    spread: float          # noise norm around a centre (assumed)
    chunk: int             # insert batch (assumed)

    @property
    def n_pad(self) -> int:
        """Corpus rows made: n rounded up to whole insert chunks."""
        return -(-self.n // self.chunk) * self.chunk

    @property
    def n_clusters(self) -> int:
        return self.n_pad // self.cluster


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache, at the program's fixed place
    (`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`), holding
    every program however small, so that only a checkout's first run of
    a cell compiles."""
    import jax

    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def deployment(cfg: dict) -> Deployment:
    """Flatten a configuration file into the sizes the build uses."""
    flat = {"name": cfg["name"]}
    for group in ("corpus", "index", "topology", "frontend"):
        flat.update(cfg[group])
    flat.update({k: cfg["assumed"][k] for k in ("cluster", "spread", "chunk")})
    return Deployment(**flat)


def seed_words(seed: int, stream: str) -> np.ndarray:
    """Two uint32 words for one named random stream of a run: any whole
    seed (also past 32 bits) maps to independent, repeatable streams."""
    tag = [ord(c) for c in stream]
    return np.random.SeedSequence([int(seed) % 2**64] + tag).generate_state(
        2, np.uint32)


def key(seed: int, stream: str):
    import jax
    import jax.numpy as jnp

    return jax.random.wrap_key_data(jnp.asarray(seed_words(seed, stream)),
                                    impl="threefry2x32")


def _unit(x):
    import jax.numpy as jnp

    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def make_corpus(dep: Deployment, seed: int):
    """(vecs [n_pad, d], centres [n_clusters, d]) f32 on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(kc, kx):
        centres = _unit(jax.random.normal(kc, (dep.n_clusters, dep.d),
                                          jnp.float32))
        noise = jax.random.normal(
            kx, (dep.n_clusters, dep.cluster, dep.d), jnp.float32)
        x = _unit(centres[:, None] + dep.spread / dep.d**0.5 * noise)
        return x.reshape(dep.n_pad, dep.d), centres

    return gen(key(seed, "centres"), key(seed, "corpus"))


def make_planes(dep: Deployment, seed: int):
    """Hash planes [L, k, d] f32: Gaussian rows, uniformly random normals."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: jax.random.normal(
        k, (dep.L, dep.k, dep.d), jnp.float32))(key(seed, "planes"))


def make_queries(dep: Deployment, centres, seed: int, count: int,
                 stream: str = "queries") -> np.ndarray:
    """`count` fresh queries [count, d] f32 on the host: each a new draw
    around a random cluster centre of the corpus (only clusters of real
    rows), so its near neighbours are that cluster's members."""
    import jax
    import jax.numpy as jnp

    real = dep.n // dep.cluster

    @jax.jit
    def gen(kp, kx, centres):
        pick = jax.random.randint(kp, (count,), 0, real)
        noise = jax.random.normal(kx, (count, dep.d), jnp.float32)
        return _unit(centres[pick] + dep.spread / dep.d**0.5 * noise)

    return np.asarray(gen(key(seed, stream + "/pick"),
                          key(seed, stream + "/noise"), centres))


def runtime(dep: Deployment):
    """The `IndexRuntime` the cell serves through: one zone on one chip."""
    from repro.core import IndexRuntime, LshParams, RuntimeConfig

    if dep.n_nodes != 1 or dep.replication != 1:
        raise SystemExit(f"bench: {dep.name} spans {dep.n_nodes} zones with "
                         f"{dep.replication} replicas; this harness builds "
                         "one zone on one chip")
    return IndexRuntime(RuntimeConfig(
        params=LshParams(d=dep.d, k=dep.k, L=dep.L), variant=dep.variant,
        m=dep.m, score=dep.score))


def chunk_ids(dep: Deployment, c0: int) -> np.ndarray:
    ids = np.arange(c0, c0 + dep.chunk, dtype=np.int32)
    ids[ids >= dep.n] = -1
    return ids


def build_index(dep: Deployment, rt, planes, vecs):
    """Announce the corpus through the runtime's insert step, chunk by
    chunk in id order, into a store made on the chip."""
    import jax
    import jax.numpy as jnp

    from repro.core import make_store

    store = jax.jit(lambda: make_store(dep.L, 2**dep.k, dep.capacity,
                                       payload_dim=dep.d,
                                       dtype=jnp.float32))()
    for c0 in range(0, dep.n_pad, dep.chunk):
        store = rt.insert(planes, store, vecs[c0:c0 + dep.chunk],
                          chunk_ids(dep, c0), 0)
    return store


def frontend(dep: Deployment, rt, planes, store):
    from repro.serve import FrontendConfig, RetrievalFrontend, RuntimeBackend

    return RetrievalFrontend(
        RuntimeBackend(rt, hyperplanes=planes, store=store),
        FrontendConfig(m=dep.m, max_batch=dep.max_batch,
                       queue_capacity=dep.queue_capacity, cache=dep.cache,
                       pipeline_depth=dep.pipeline_depth))


def dispatch_shapes(dep: Deployment, rt, sizes) -> list[int]:
    """The padded dispatch sizes the frontend will use for these live
    batch sizes."""
    from repro.serve import dispatch_pad

    return sorted({dispatch_pad(s, rt.n_devices) for s in sizes})


def warm(fe, dep: Deployment, shapes, queries: np.ndarray) -> None:
    """Compile and run each dispatch shape the cell's traffic uses,
    through the frontend itself (intake, staging, reaping and the
    per-generation cost read all run): two batches of each shape, the
    second staged while the first is in flight, as the window will;
    `queries` are warm-up draws that the window never sends again."""
    used = 0
    for s in shapes:
        for _ in range(2):
            for q in queries[used:used + s]:
                fe.submit(q)
            used += s
            fe.step()
        fe.flush()
        fe.take_results()

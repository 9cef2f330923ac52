"""A hot set: a pool of `pool` fresh queries, each query drawn from it
with the probability of its rank r (from 1) proportional to r^-`s`.

Parameters `pool` and `s`.  Repeats hit the frontend's result cache, so
the share the cache answers follows from the pool and the exponent.
"""

import numpy as np


def draw(params: dict, dep, centres, seed: int, count: int):
    import deploy

    pool = deploy.make_queries(dep, centres, seed, int(params["pool"]),
                               stream="pool")
    w = np.arange(1, len(pool) + 1, dtype=float) ** -float(params["s"])
    rng = np.random.default_rng([int(seed) % 2**64, 3])
    return pool[rng.choice(len(pool), count, p=w / w.sum())]

"""Fresh queries: every query a new draw around a random cluster centre
of the corpus, so none repeats and none hits the frontend's result
cache.  No parameters."""


def draw(params: dict, dep, centres, seed: int, count: int):
    import deploy

    return deploy.make_queries(dep, centres, seed, count)

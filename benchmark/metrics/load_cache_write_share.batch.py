"""Share of Scorer.load (the `load` span, once per process, in set-up)
spent the serving-cache miss's rerank norms and cache write
(load.cache_write)."""

from benchmark import program


def read(data):
    return program.load_share(data, "load.cache_write")

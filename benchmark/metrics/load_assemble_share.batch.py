"""Share of Scorer.load (the `load` span, once per process, in set-up)
spent assembling the global CSR postings (load.assemble)."""

from benchmark import program


def read(data):
    return program.load_share(data, "load.assemble")

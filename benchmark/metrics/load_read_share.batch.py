"""Share of Scorer.load (the `load` span, once per process, in set-up)
spent reading the side artifacts and the part shards (load.read, with
the checksum folding of load.verify inside it)."""

from benchmark import program


def read(data):
    return program.load_share(data, "load.read")

"""Share of Scorer.load (the `load` span, once per process, in set-up)
spent building the serving layout (load.layout): block-max bounds and
the tiered layout on the host, and the hot strip's scatter on the
device, its compile included."""

from benchmark import program


def read(data):
    return program.load_share(data, "load.layout")

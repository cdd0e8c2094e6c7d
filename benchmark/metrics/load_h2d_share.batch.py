"""Share of Scorer.load (the `load` span, once per process, in set-up)
spent host staging and enqueueing the uploads to the device (load.h2d;
residency is waited for outside Scorer.load)."""

from benchmark import program


def read(data):
    return program.load_share(data, "load.h2d")

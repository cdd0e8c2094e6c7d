"""Share of Scorer.load (the `load` span, once per process, in set-up)
spent building and uploading the device position table of the phrase
path (load.positions: the part and position files read, the runs
decoded to (docno, position) order, the uploads enqueued)."""

from benchmark import program


def read(data):
    return program.load_share(data, "load.positions")

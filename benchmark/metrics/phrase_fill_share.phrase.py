"""Share of the anchor lanes the device phrase path dispatched in the
traced window that held a real position: the capture's totals of the
program's `phrase.positions` over `phrase.slots` counters. None where
the program has no device phrase path or dispatched nothing."""

from benchmark import program


def read(data):
    if data["trace"] is None:
        return None
    slots = program.capture_count("phrase.slots")
    if not slots:
        return None
    return 100.0 * (program.capture_count("phrase.positions") or 0) / slots

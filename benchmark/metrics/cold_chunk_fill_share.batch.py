"""Share of the lanes the cold chunk stream dispatched in the traced
window that held a real posting: the capture's totals of the program's
`cold.chunk_postings` over `cold.chunk_slots` counters. None where the
program has no chunk stream or streamed nothing."""

from benchmark import program


def read(data):
    if data["trace"] is None:
        return None
    slots = program.capture_count("cold.chunk_slots")
    if not slots:
        return None
    return 100.0 * (program.capture_count("cold.chunk_postings")
                    or 0) / slots

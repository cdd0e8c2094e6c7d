"""Host milliseconds of query analysis per query answered in the traced
window: the capture's `search.analyze` spans (Scorer.analyze_queries,
one per batch) over the queries answered."""

from benchmark import program


def read(data):
    n = data["counters"].get("queries")
    if data["trace"] is None or not n:
        return None
    s = program.capture_s("search.analyze")
    return None if s is None else 1e3 * s / n

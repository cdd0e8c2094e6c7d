"""Host milliseconds of the scorer per phrase query answered in the
traced window: the capture's `search` spans (one per
Scorer.search_batch: query analysis, blocking, dispatch, result
assembly, query log) less the host's waits on the device in them
(`dispatch.device`: each block's sync for its match counts, and the
copy of the answers), over the queries answered. None on a program
without the `search` span."""

from benchmark import program


def read(data):
    n = data["counters"].get("queries")
    if data["trace"] is None or not n:
        return None
    search = program.capture_s("search")
    if search is None:
        return None
    return 1e3 * (search - (program.capture_s("dispatch.device") or 0.0)) / n

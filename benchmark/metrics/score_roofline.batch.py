"""Share of the memory roofline reached by the scoring kernels: the
least time the chip needs to read what the window's queries require
(8 bytes, a docno and a tf, per posting of each distinct query term,
plus 8 bytes per answer slot; benchmark/kinds/batch.py query_bytes)
at the chip's HBM bandwidth (benchmark/peaks.json), over the device
time of the BM25 top-k modules of ops/scoring.py, selected by module
name. The count is of the queries' own postings, not of the layout's
padded shapes; compute is far below its own bound (a few operations per
posting), so bandwidth bounds it."""

SCORING = ("jit_bm25_topk_blockmax", "jit_bm25_topk_tiered",
           "jit_bm25_topk_dense")


def read(data):
    tr, peaks = data["trace"], data["peaks"]
    need = data["work"].get("query_bytes")
    if not tr or not peaks or not need:
        return None
    device_s = sum(tr["module_s"].get(m, 0.0) for m in SCORING)
    if not device_s:
        return None
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / device_s

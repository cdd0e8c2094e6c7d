"""Device milliseconds of the scoring kernels per query answered in the
traced window: the summed device time of the BM25 top-k modules of
ops/scoring.py, selected by module name, over the queries answered.
A traced batch run on the chip ran jit_bm25_topk_blockmax,
jit_bm25_topk_tiered and a few microseconds of jit_convert_element_type
(PERF.md, section 5); other device work is not charged to the kernels."""

SCORING = ("jit_bm25_topk_blockmax", "jit_bm25_topk_tiered",
           "jit_bm25_topk_dense")


def read(data):
    tr, n = data["trace"], data["counters"].get("queries")
    if not tr or not n:
        return None
    device_s = sum(tr["module_s"].get(m, 0.0) for m in SCORING)
    if not device_s:
        return None
    return 1e3 * device_s / n

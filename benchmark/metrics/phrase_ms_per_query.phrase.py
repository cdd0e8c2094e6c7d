"""Device milliseconds of the phrase path per query answered in the
traced window: the summed device time of its modules of
ops/phrase.py, selected by module name (jit_phrase_match, the
ordered-window match of a block's anchor positions, and
jit_phrase_topk, the candidates' scoring and top-k), over the queries
answered. Other device work is not charged to it."""

PHRASE = ("jit_phrase_match", "jit_phrase_topk")


def read(data):
    tr, n = data["trace"], data["counters"].get("queries")
    if not tr or not n:
        return None
    device_s = sum(tr["module_s"].get(m, 0.0) for m in PHRASE)
    if not device_s:
        return None
    return 1e3 * device_s / n

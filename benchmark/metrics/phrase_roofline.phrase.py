"""Share of the memory roofline reached by the phrase path: the least
time the chip needs to read what the window's queries require, at the
chip's HBM bandwidth (benchmark/peaks.json), over the device time of
the phrase path's modules of ops/phrase.py (jit_phrase_match and
jit_phrase_topk), selected by module name. The bytes
(benchmark/kinds/phrase.py phrase_bytes) are, for each query answered,
8 bytes (a docno and a position) per position of each distinct quoted
term, 8 bytes (a docno and a tf) per posting of each distinct query
term, and 8 bytes (a docno and a score) per answer slot: the queries'
own positions and postings, not the lanes the program dispatched. The
work is a few compares a byte, so bandwidth bounds it."""

PHRASE = ("jit_phrase_match", "jit_phrase_topk")


def read(data):
    tr, peaks = data["trace"], data["peaks"]
    need = data["work"].get("phrase_bytes")
    if not tr or not peaks or not need:
        return None
    device_s = sum(tr["module_s"].get(m, 0.0) for m in PHRASE)
    if not device_s:
        return None
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / device_s

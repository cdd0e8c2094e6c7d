"""Share of the window's build wall time in pass 1, the host tokenize
and spill (the build report's timings_s.pass1_tokenize)."""


def read(data):
    b = data["counters"].get("builds")
    if not b or not b["walls"]:
        return None
    return 100.0 * sum(t.get("pass1_tokenize", 0.0)
                       for t in b["timings"]) / sum(b["walls"])

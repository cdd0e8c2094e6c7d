"""Share of the window's build wall time in pass 2, the device group-by
(the build report's timings_s.pass2_combine)."""


def read(data):
    b = data["counters"].get("builds")
    if not b or not b["walls"]:
        return None
    return 100.0 * sum(t.get("pass2_combine", 0.0)
                       for t in b["timings"]) / sum(b["walls"])

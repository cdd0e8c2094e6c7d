"""Device idle share of the phrase cell's traced window: 1 - busy /
window, where busy is the union of the intervals in which an XLA op ran
on the device (benchmark/tracing.py)."""


def read(data):
    tr = data["trace"]
    if not tr or not tr.get("devices") or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

"""Device: share of the traced window in which no operation ran on the
chip, mean over the cell's chips."""


def read(run):
    t = run.trace
    if not t or not t["chips"] or t["window_s"] <= 0:
        return None
    busy = sum(c["busy_s"] for c in t["chips"]) / len(t["chips"])
    return 100.0 * (1.0 - busy / t["window_s"])

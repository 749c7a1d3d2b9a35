"""Fused stages: share of the chips' busy time spent in Pallas kernels
(custom calls in the device trace)."""


def read(run):
    t = run.trace
    if not t or not t["chips"]:
        return None
    busy = sum(c["busy_s"] for c in t["chips"])
    if busy <= 0:
        return None
    return 100.0 * sum(c["custom_call_s"] for c in t["chips"]) / busy

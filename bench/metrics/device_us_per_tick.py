"""Scan tick: device microseconds per simulated tick of one chunk, the
chips' busy time summed over the traced window divided by the ticks the
window's chunk launches executed.  It reads the same work whatever
implements the tick."""


def read(run):
    t = run.trace
    ticks = run.chunk_launches * run.ticks
    if not t or not t["chips"] or ticks <= 0:
        return None
    busy = sum(c["busy_s"] for c in t["chips"])
    if busy <= 0:
        return None
    return 1e6 * busy / ticks

"""Host-device transfer: host milliseconds per traced grid spent putting
each chunk's state and parameters on the chip (``chunk.h2d``) and
pulling its final carry back (``chunk.d2h``)."""
from bench import spans


def read(run):
    s = spans.of_run(run)
    if not s or not s["grids"]:
        return None
    t = s["span_s"]
    if "chunk.h2d" not in t:
        return None
    return 1e3 * (t["chunk.h2d"] + t.get("chunk.d2h", 0.0)) / s["grids"]

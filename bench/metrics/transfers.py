"""Host-device transfer: arrays moved per traced grid, the ``arrays``
counts the program gives its ``chunk.h2d`` and ``chunk.d2h`` spans."""
from bench import spans


def read(run):
    s = spans.of_run(run)
    if not s or not s["grids"] or "chunk.h2d" not in s["span_s"]:
        return None
    return s["transfers"] / s["grids"]

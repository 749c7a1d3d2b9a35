"""Chunk packing: host milliseconds per traced grid in which the main
thread packs or waits for packing: the full-grid pack for the envelope
(``farm.envelope``) and the waits for the prefetch thread
(``farm.pack_wait``)."""
from bench import spans


def read(run):
    s = spans.of_run(run)
    if not s or not s["grids"]:
        return None
    t = s["span_s"]
    return 1e3 * (t.get("farm.envelope", 0.0)
                  + t.get("farm.pack_wait", 0.0)) / s["grids"]

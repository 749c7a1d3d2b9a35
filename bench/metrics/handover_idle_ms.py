"""Farm dispatch: chip-idle milliseconds per traced grid that fall under
one of the program's ``farm.*`` / ``chunk.*`` spans on the main thread
(packing, transfers, dispatch, unpacking between chunks)."""
from bench import spans


def read(run):
    s = spans.of_run(run)
    if not s or not s["chips"] or not s["grids"]:
        return None
    return 1e3 * s["named_idle_s"] / s["grids"]

"""Grid build: host milliseconds to build one grid's scenarios from its
parameters (the benchmark's own ``build`` span), mean over the window."""


def read(run):
    if not run.grids:
        return None
    return 1e3 * sum(g["build_s"] for g in run.grids) / len(run.grids)

"""Compile: programs XLA compiled or loaded from the compile cache inside
the window (``jax.monitoring`` backend-compile events)."""


def read(run):
    return float(run.compiles_in_window)

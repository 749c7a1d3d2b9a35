"""Shared ``lax.scan`` compile-cost machinery for the sweep engines.

Both vectorized engines (:mod:`repro.fabric.sweep` — the single-receiver
datapath grid — and :mod:`repro.fabric.vector` — the whole-fabric grid)
are one ``jax.vmap`` + ``lax.scan`` program whose cold-start cost is
dominated by XLA compiling the scan body.  Three levers live here:

* **unroll.**  ``lax.scan(..., unroll=u)`` duplicates the body ``u``
  times: compile time grows roughly linearly with ``u`` while the
  per-iteration while-loop overhead shrinks.  The unroll is the caller's
  explicit argument, or 1 — nothing read from disk or the environment
  changes the compiled program.

* **donated carries.**  The single-receiver sweep program and the
  fabric engine's adaptive program take their initial scan carry as an
  argument donated via ``donate_argnums``, so XLA reuses the (grid x
  ring-horizon) state buffers instead of keeping both the zero-init
  copy and the running carry alive; the fabric scan program builds its
  zero carry on the device and takes no carry at all.

* **persistent compilation cache.**  The step bodies are deterministic
  functions of the grid *structure*, so their XLA executables are
  reusable across processes.  :func:`configure_persistent_cache` (called
  once by each entry point) points jax's disk cache at
  ``JAX_COMPILATION_CACHE_DIR`` when it is set, else at the fixed
  ``<repo>/.jax_cache`` — a stable path, since the path is part of what
  makes a later process find the entry.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def pick_unroll(unroll="auto") -> int:
    """Scan unroll factor: the explicit argument, or 1 for ``"auto"``."""
    if unroll == "auto":
        return 1
    return max(1, int(unroll))


def configure_persistent_cache() -> str:
    """Enable jax's on-disk executable cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``.
    Every program is cached (no minimum compile time).  Idempotent; the
    only place in the repo that sets ``jax_compilation_cache_dir``."""
    cache_dir = os.path.expanduser(
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR)
    import jax

    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir

"""Jet staged collectives on an 8-device host mesh (the paper's §6.4 story
mapped to TPU: keep the gathered operand out of HBM).

  PYTHONPATH=src python examples/hpc_collectives.py

Runs the three Jet collective primitives against their XLA one-shot
equivalents, verifies numerics, and prints the compiled per-device
collective bytes + temp memory of each — the structural evidence that the
ring-staged version never materializes the gathered tensor.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
import numpy as np                                    # noqa: E402
from jax import shard_map                             # noqa: E402
from jax.sharding import PartitionSpec as P           # noqa: E402

from repro.launch import hlo_analysis                 # noqa: E402
from repro.launch.mesh import make_mesh               # noqa: E402
from repro.parallel import collectives as coll        # noqa: E402

M = 8
MESH = make_mesh((M,), ("model",))


def report(name, fn, in_specs, args, want, out_specs=P()):
    sm = shard_map(fn, mesh=MESH, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    jitted = jax.jit(sm)
    got = jitted(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    compiled = jitted.lower(*args).compile()
    deep = hlo_analysis.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    temp = getattr(mem, "temp_size_in_bytes", -1)
    counts = {k: v for k, v in deep["coll_counts"].items() if v}
    print(f"{name:34s} coll_bytes/dev={deep['coll_total']/1e6:8.3f} MB  "
          f"temp={temp/1e6:8.3f} MB  ops={counts}")
    return got


def main() -> None:
    key = jax.random.key(0)
    d, f, b = 4096, 512, 16
    x = jax.random.normal(key, (b, d), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (d, f), jnp.float32)
    want = x @ w

    print("— allgather-matmul: y = x @ W, W row-sharded over 8 devices —")
    report("xla: all_gather(W) @ x",
           lambda xx, ww: xx @ jax.lax.all_gather(ww, "model", axis=0,
                                                  tiled=True),
           (P(), P("model", None)), (x, w), want)
    report("jet: ring staged (frags=2)",
           lambda xx, ww: coll.ring_allgather_matmul(xx, ww, "model", M,
                                                     frags=2),
           (P(), P("model", None)), (x, w), want)

    print("\n— reduce-scatter of per-rank partials [8, 16, 4096] —")
    y = jax.random.normal(jax.random.key(2), (M, b, d), jnp.float32)
    full = np.asarray(y.sum(axis=0))
    want_stack = np.concatenate(
        [full[:, r * (d // M):(r + 1) * (d // M)] for r in range(M)], axis=0)
    report("xla: psum_scatter",
           lambda yy: jax.lax.psum_scatter(yy[0], "model",
                                           scatter_dimension=1, tiled=True),
           (P("model", None, None),), (y,), want_stack, P("model"))
    report("jet: ring reduce-scatter",
           lambda yy: coll.ring_reduce_scatter(yy[0], "model", M),
           (P("model", None, None),), (y,), want_stack, P("model"))

    print("\n— windowed all-gather (the READ path: <=window in flight) —")
    xs = jax.random.normal(jax.random.key(3), (64, 128), jnp.float32)
    report("xla: one-shot all_gather",
           lambda v: jax.lax.all_gather(v, "model", axis=0, tiled=True),
           (P("model", None),), (xs,), xs)
    report("jet: windowed (window=4)",
           lambda v: coll.windowed_allgather(v, "model", M, window=4),
           (P("model", None),), (xs,), xs)
    print("\nall numerics verified against XLA one-shot equivalents")


if __name__ == "__main__":
    main()

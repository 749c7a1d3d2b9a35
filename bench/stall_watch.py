"""Run a cell's window untraced, as ``bench/run.py`` does, and print the
host-span split of every grid whose farm time is more than twice the
median, to catch the rare stall inside ``run_farm``:

    python3 bench/stall_watch.py --workload <cell> --seed <n> --seconds 150

Each grid is built from ``(seed, i)`` and run through ``run_farm`` with
the program's defaults, as in the window; its split comes from the
manifest the farm returns (the ``*_s`` fields of ``repro.fabric.spans``
summed over chunks, and each chunk's ``device_s``), with the time no
span covers, and the Python garbage collections that ran during the
grid.  No result line, no check: one JSON object per slow grid on
standard output; on standard error a summary with the median split.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import registry  # noqa: E402
from bench import traffic as T  # noqa: E402


def split(manifest: dict, farm_s: float) -> dict:
    """Seconds of each span of one grid, summed over its chunks."""
    from repro.fabric import spans as S
    out = {S.field(n): manifest[S.field(n)] for n in S.RUN_SPANS}
    for n in S.CHUNK_SPANS:
        out[S.field(n)] = sum(r[S.field(n)] for r in manifest["records"])
    main = sum(v for k, v in out.items() if k != "pack_s")
    out["uncovered_s"] = farm_s - main
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = registry.Bench()
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(bench.root,
                                                           ".jax_cache")
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    from repro.fabric._scan import configure_persistent_cache
    from repro.fabric.farm import run_farm
    configure_persistent_cache()
    ns = T.program_namespace()

    collections = []   # (start, seconds) of each garbage collection
    started = {}

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.perf_counter()
        elif "t" in started:
            collections.append((started["t"],
                                time.perf_counter() - started.pop("t")))
    gc.callbacks.append(on_gc)

    # set-up: grid 0 loads the programs, as the run's set-up does
    run_farm(T.build_grid(config, traffic, args.seed, 0, ns)[0], workers=0,
             artifacts=False)
    grids = []
    i = 1
    w0 = time.perf_counter()
    while not grids or time.perf_counter() - w0 < args.seconds:
        scens, _ = T.build_grid(config, traffic, args.seed, i, ns)
        t0 = time.perf_counter()
        out = run_farm(scens, workers=0, artifacts=False)
        t1 = time.perf_counter()
        gcs = [d for s, d in collections if t0 <= s < t1]
        grids.append({"grid": i, "farm_s": t1 - t0, "gc_s": sum(gcs),
                      "gc_max_s": max(gcs, default=0.0),
                      "split": split(out["manifest"], t1 - t0),
                      "device_s_by_chunk": [r["device_s"] for r in
                                            out["manifest"]["records"]]})
        i += 1
    med = statistics.median(g["farm_s"] for g in grids)
    slow = [g for g in grids if g["farm_s"] > 2 * med]
    for g in slow:
        print(json.dumps(g), flush=True)
    typical = {k: statistics.median(g["split"][k] for g in grids)
               for k in grids[0]["split"]}
    print(f"{len(grids)} grids in {time.perf_counter() - w0:.1f} s, median "
          f"farm {med:.4f} s, {len(slow)} over twice the median, gc "
          f"{sum(g['gc_s'] for g in grids):.3f} s in all; median split "
          + json.dumps(typical), file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Readings that the limits of a cell's check are set from.

    python3 bench/readings.py --workload <cell> --seeds 101-112 \
        --control-seeds 101-103 [--grids 2]

In one process: set-up as a run does, then for each seed a short window
of ``--grids`` grids through the timed entry (``run_farm``) and the
check against the reference; for each control seed the same grids
through the control in the program's place and the same check.  The
control is the program's own step functions run in bfloat16, the
precision below the float32 the configurations state (the program's
numpy backend, ``repro.fabric.vector._run_numpy``, at the grid's full
size); it needs no chip, and a call with control seeds alone runs on
any host.  One JSON line per seed and side goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control_farm(scens, **_):
    """The control in the program's place: the whole grid through the
    program's step functions in bfloat16; same result layout as
    ``run_farm``."""
    import ml_dtypes
    from repro.fabric import vector as V
    from repro.fabric.farm import _pick_sparse
    fsp = V.FabricSweepParams.from_scenarios(
        scens, sparse=_pick_sparse(scens, "auto"))
    res = V._run_numpy(fsp, dtype=ml_dtypes.bfloat16)
    return {"results": res, "manifest": {"records": [{"chunk": 0}]}}


def _seeds(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            lo, hi = part.split("-")
            out.extend(range(int(lo), int(hi) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--grids", type=int, default=2)
    args = ap.parse_args(argv)

    import warnings

    from bench import harness, registry
    from bench import traffic as T

    bench = registry.Bench()
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    check_spec = bench.check(cell["name"])
    ns = T.program_namespace()
    program_seeds = _seeds(args.seeds)
    kind = "host"
    if program_seeds:
        # the program's side needs the chip; the control runs on the host
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                               ".jax_cache")
        devs = harness._device_info(int(cell["chips"]), True, bench)
        kind = devs[0].device_kind
        from repro.fabric._scan import configure_persistent_cache
        from repro.fabric.farm import run_farm
        configure_persistent_cache()
        harness._run_grid(run_farm, config, traffic, 1, 0, ns, False)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    sides = [("program", s, run_farm) for s in program_seeds]
    sides += [("control", s, control_farm) for s in _seeds(args.control_seeds)]
    for side, seed, farm in sides:
        t = time.perf_counter()
        with warnings.catch_warnings():
            # bfloat16 overflows are what the control is for
            warnings.simplefilter("ignore", RuntimeWarning)
            grids = [harness._run_grid(farm, config, traffic, seed, i, ns,
                                       False)
                     for i in range(1, args.grids + 1)]
        t_run = time.perf_counter() - t
        t = time.perf_counter()
        numbers = harness.check(config, traffic, seed, grids, check_spec,
                                log)
        print(json.dumps({"workload": cell["name"], "side": side,
                          "seed": seed, "numbers": numbers,
                          "run_s": t_run,
                          "check_s": time.perf_counter() - t,
                          "device": kind}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

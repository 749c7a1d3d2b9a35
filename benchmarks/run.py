"""Benchmark orchestrator: one module per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run [--only name] [--skip name]

Each module prints its CSV (also persisted under experiments/bench/) and a
``#``-prefixed derived-claims line mirroring the paper's headline numbers.
"""
from __future__ import annotations

import argparse
import time
import traceback

MODULES = [
    ("receiver_datapath", "figs 2/3/6/7 — datapath degradation, Jet vs DDIO"),
    ("concurrency_window", "fig 5 — READ concurrency saturation"),
    ("pool_and_escape", "figs 10/11 — pool sizing, recycle, escape ladder"),
    ("traffic_patterns", "fig 9 — OLAP / backup / OLTP"),
    ("fabric", "Clos incast/HoL + vectorized sweep engine"),
    ("hpc_collectives", "fig 13 — MPI collective latency"),
    ("kernels", "Pallas kernel correctness + arithmetic intensity"),
    ("roofline", "dry-run roofline terms per (arch x shape)"),
    ("capacity", "HBM-fit audit per cell"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip", default="")
    args = ap.parse_args()
    skip = set(args.skip.split(",")) if args.skip else set()
    from repro.fabric._scan import configure_persistent_cache
    print(f"# jax persistent compilation cache: "
          f"{configure_persistent_cache()}")

    failures = []
    for name, desc in MODULES:
        if args.only and name != args.only:
            continue
        if name in skip:
            continue
        print(f"\n=== {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            if name == "roofline":
                from . import roofline
                import sys
                argv, sys.argv = sys.argv, ["roofline"]
                try:
                    roofline.main()
                finally:
                    sys.argv = argv
            elif name == "capacity":
                from . import capacity
                capacity.main()
            else:
                mod = __import__(f"benchmarks.bench_{name}",
                                 fromlist=["main"])
                mod.main()
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
        print(f"=== {name} done in {time.time() - t0:.1f}s ===", flush=True)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()

"""One run of one cell: set-up, a closed-loop window of grids through the
sweep farm, the check against the plain reference, and the result line.

The window has one client.  Each request is one grid: build grid ``i``
from ``(seed, i)``, run it with ``repro.fabric.farm.run_farm`` (the
program's own defaults for chunking, incidence, kernels and unroll),
and hold its results on the host; then the next.  Set-up builds and runs
grid 0, which loads or compiles every chunk shape on every device the
cell uses, so nothing compiles inside the window.

With ``--trace 1`` the window runs under the profiler, at most
``TRACE_WINDOW_S`` long, and the result line carries the cell's
per-layer metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
import traceback
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import compare, registry
from bench import traffic as T

#: Compile and cache-load events JAX records, one per program obtained.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: Longest window a ``--trace 1`` run traces; it runs one grid at least,
#: and at this length one grid at most.  The chip records every operation
#: of every tick, about 100 MB of trace a grid, and the run has to read
#: them back.
TRACE_WINDOW_S = 0.25


class Fail(Exception):
    """The run cannot give a result (no chip, unknown device, ...)."""


def process_age_s(fallback_t0: float) -> float:
    """Seconds since this process started (Linux ``/proc``), else since
    ``fallback_t0`` on the ``perf_counter`` clock."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - fallback_t0


class CompileCounter:
    """Counts the programs JAX compiles or loads from its cache."""

    def __init__(self):
        import jax.monitoring
        self.n = 0

        def on_event(event, duration, **kw):
            if event == _COMPILE_EVENT:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)


@contextmanager
def _span(name: str, on: bool):
    if on:
        import jax.profiler
        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield


class Run:
    """What one run saw; the per-layer readers take their numbers from
    here (``bench/metrics/<name>.py``)."""

    def __init__(self, cell: dict, traffic: dict):
        self.cell, self.traffic = cell, traffic
        self.grids: List[dict] = []      # one record per grid in the window
        self.window_s = 0.0
        self.compiles_in_window = 0
        self.trace: Optional[dict] = None   # bench.tracing.reduce output
        self.chips = int(cell["chips"])

    @property
    def ticks(self) -> int:
        """Ticks of one grid point: simulated us at the fabric's 1 us tick
        (``FabricConfig.dt_us``, which the traffic leaves at its default)."""
        return int(round(float(self.traffic["sim_time_s"]) * 1e6))

    @property
    def points(self) -> int:
        return sum(g["points"] for g in self.grids)

    @property
    def chunk_launches(self) -> int:
        return sum(g["chunks"] for g in self.grids)


def _device_info(chips: int, require_tpu: bool, bench: registry.Bench):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu:
        if d0.platform != "tpu":
            raise Fail(f"no TPU: jax found {d0.platform!r} devices")
        peaks = registry._load_json(os.path.join(bench.home, "peaks.json"))
        if d0.device_kind not in peaks["kinds"]:
            raise Fail(f"device kind {d0.device_kind!r} is not in "
                       "bench/peaks.json")
    if len(devs) < chips:
        raise Fail(f"the cell needs {chips} chips, jax found {len(devs)}")
    return devs


def _memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _run_grid(run_farm, config, traffic, seed, i, ns, trace_on):
    t0 = time.perf_counter()
    with _span("build", trace_on):
        scens, _ = T.build_grid(config, traffic, seed, i, ns)
    t1 = time.perf_counter()
    with _span("farm", trace_on):
        out = run_farm(scens, workers=0, artifacts=False)
    with _span("results", trace_on):
        res = {k: np.asarray(v) for k, v in out["results"].items()}
    t2 = time.perf_counter()
    return {"grid": i, "points": len(scens),
            "chunks": len(out["manifest"]["records"]),
            "build_s": t1 - t0, "farm_s": t2 - t1, "results": res}


def _sample(seed: int, grids: List[dict], k: int) -> List[tuple]:
    """``k`` (grid, point) pairs drawn from the seed: point ``j`` from the
    ``j``-th of ``k`` equal strata of the grid, so the sample spans every
    chunk and both halves of the grid; each from a random window grid."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 0xC4EC])
    n = grids[0]["points"]
    out = []
    for j in range(k):
        lo, hi = (j * n) // k, max((j * n) // k + 1, ((j + 1) * n) // k)
        g = grids[int(rng.integers(len(grids)))]
        out.append((g, int(rng.integers(lo, hi))))
    return out


def check(config, traffic, seed, grids, check_spec,
          log=print) -> Dict[str, float]:
    """The widest gap of each compared number over the sampled points.
    The reference runs the points in a pool of worker processes that
    import no JAX (the chip stays this process's)."""
    import multiprocessing as mp
    from concurrent.futures import ProcessPoolExecutor

    sample = _sample(seed, grids, int(check_spec["sample_points"]))
    points = [T.grid_points(traffic, seed, g["grid"])[i] for g, i in sample]
    workers = max(1, min(len(sample), (os.cpu_count() or 2) // 2, 8))
    with ProcessPoolExecutor(workers, mp_context=mp.get_context("spawn")
                             ) as pool:
        refs = list(pool.map(compare.reference_point,
                             [config] * len(points), [traffic] * len(points),
                             points))
    gaps = []
    for (g, i), point, ref in zip(sample, points, refs):
        gaps.append(compare.point_gaps(
            compare.program_answer(g["results"], i), ref))
        log(f"check grid {g['grid']} point {i} {point}: " + " ".join(
            f"{k}={v!r}" for k, v in gaps[-1].items()))
    return compare.widest(gaps)


def _trace_dir(bench: registry.Bench, cell: str) -> str:
    return os.path.join(bench.root, "bench_out", "trace", cell)


def run_cell(argv: List[str], t0: float, require_tpu: bool = True,
             bench: Optional[registry.Bench] = None,
             run_farm: Optional[Callable] = None, out=sys.stdout,
             err=sys.stderr) -> int:
    """Run one cell; print the result line to ``out``.  Tests pass
    ``require_tpu=False`` and a broken ``run_farm``."""
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=err, flush=True)

    bench = bench or registry.Bench()
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    check_spec = bench.check(cell["name"])
    trace_on = bool(args.trace)

    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(bench.root,
                                                           ".jax_cache")
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    try:
        devs = _device_info(int(cell["chips"]), require_tpu, bench)
    except Fail as e:
        log(f"bench: {e}")
        return 2
    from repro.fabric._scan import configure_persistent_cache
    configure_persistent_cache()
    if run_farm is None:
        from repro.fabric.farm import run_farm
    counter = CompileCounter()
    ns = T.program_namespace()
    run = Run(cell, traffic)

    # set-up: grid 0 loads or compiles every chunk shape on every device
    _run_grid(run_farm, config, traffic, args.seed, 0, ns, False)
    setup_s = process_age_s(t0)
    log(f"setup {setup_s!r} s")

    # the window
    if trace_on:
        import jax.profiler
        tdir = _trace_dir(bench, cell["name"])
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    seconds = min(args.seconds, TRACE_WINDOW_S) if trace_on else args.seconds
    c0 = counter.n
    attempted = failed = 0
    i = 1
    w0 = time.perf_counter()
    with _span("window", trace_on):
        while True:
            attempted += 1
            try:
                run.grids.append(_run_grid(run_farm, config, traffic,
                                           args.seed, i, ns, trace_on))
            except Exception:   # a failed grid is counted, not fatal
                failed += 1
                log(traceback.format_exc())
            i += 1
            if time.perf_counter() - w0 >= seconds:
                break
    run.window_s = time.perf_counter() - w0
    run.compiles_in_window = counter.n - c0
    if trace_on:
        jax.profiler.stop_trace()
    memory_peak = _memory_peak(devs[:run.chips])

    if trace_on:
        from bench import tracing
        run.trace = tracing.reduce(tracing.find_xplane(tdir), run.chips)
    log(f"window {run.window_s!r} s, {len(run.grids)} grids, "
        f"{run.points} points, {run.compiles_in_window} compiles")
    log("grid s: " + " ".join(f"{g['grid']}:{g['build_s']:.4f}+"
                              f"{g['farm_s']:.4f}" for g in run.grids))

    # the check, after the window and the memory reading
    numbers = (check(config, traffic, args.seed, run.grids, check_spec,
                     log) if run.grids else {})
    verdict = compare.judge(numbers, check_spec["limits"]) \
        if run.grids else []
    correct = bool(run.grids) and failed == 0 \
        and all(v["ok"] for v in verdict)

    metrics = {}
    if trace_on:
        for m in bench.per_layer(cell["name"]):
            v = bench.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        sim_us = run.points * run.ticks
        e2e = {"sim_rate": sim_us / run.window_s if run.window_s else 0.0,
               "setup_s": setup_s}
        for m in bench.end_to_end(cell["name"]):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if trace_on:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = run.trace["breakdown"]
    # JSON has no infinity: a gap that is not finite prints as null
    line["check"] = {v["name"]: {"value": v["value"] if math.isfinite(
        v["value"]) else None, "limit": v["limit"]} for v in verdict}
    for v in verdict:
        log(f"check {v['name']} {v['value']!r} limit {v['limit']!r} "
            f"{'ok' if v['ok'] else 'FAIL'}")
    print(json.dumps(line), file=out, flush=True)
    return 0

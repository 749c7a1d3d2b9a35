"""Sweep farm: sharded grid execution across devices and processes.

The vector engine runs any structure-sharing grid as ONE XLA program —
which is exactly wrong once grids reach overnight size: a 10–100x grid
compiles one giant program per shape, holds the whole [G, ...] state in
memory at once, and leaves every other core and device idle.  This
module is the firesim-style run-farm layer on top of it:

* **Fixed-shape chunks.**  The grid is split by
  :func:`repro.fabric.scenarios.chunk_plan` into chunks of one or two
  canonical shapes (full chunks + one power-of-two-padded remainder),
  each padded by replicating a real scenario.  Combined with the
  structure **envelope** (:meth:`FabricSweepParams.envelope` of the full
  grid, forwarded to every chunk), all chunks trace the *same* program:
  zero recompiles after the first chunk per canonical shape, and —
  because vmap lanes are independent and every result is per-point —
  bit-identical per-point results vs the monolithic run at fixed dt.

* **Dispatch.**  ``workers <= 1`` runs chunks in-process with host-side
  chunk packing overlapped against device compute (a one-deep prefetch
  thread builds chunk k+1's parameter pack while chunk k executes; the
  compiled program builds its zero carry on the device).  ``workers > 1``
  fans chunks out to a ``spawn`` multiprocessing pool — each worker
  rebuilds the grid from a picklable :class:`GridSpec` (scenario objects
  embed receiver-config closures and do not pickle), shares the on-disk
  XLA compilation cache (:func:`repro.fabric._scan
  .configure_persistent_cache`), and writes its own result shards so a killed parent loses nothing.  A
  chip belongs to one process, so the pool is CPU-only: on a TPU host
  ``workers > 1`` with the jax backend is an error.  In-process chunks
  round-robin over ``jax.devices()`` (one device is a cycle of one).

* **Versioned artifacts + resume.**  Every run writes
  ``experiments/runs/<run_id>/`` (manifest + per-chunk shards + merged
  table; see :mod:`repro.fabric.artifacts`).  ``resume=True`` re-reads
  the manifest, verifies the grid fingerprint, and dispatches only the
  chunks whose shards are missing or unloadable — kill a run at 50% and
  the restart completes the other half.

* **Spans and counters.**  The host work of a run is cut into spans
  (:mod:`repro.fabric.spans`), each a ``jax.profiler.TraceAnnotation``
  (on the profiler's host plane, beside the device trace) and seconds in
  the manifest, always on.  Each chunk record carries, besides
  ``wall_s`` (``params`` to ``unpack``; in pool workers from ``pack``
  on) and ``compiles``:

  - ``pack_s`` (span ``farm.pack``): pad and pack the chunk, on the
    prefetch thread (in pool workers, inline);
  - ``pack_wait_s`` (``farm.pack_wait``): the main thread waits for
    that pack (0 in pool workers);
  - ``params_s`` (``chunk.params``): program lookup, parameters
    packed one buffer per dtype (``vector.packed_params``);
  - ``h2d_s`` (``chunk.h2d``): ``jax.device_put`` of those buffers,
    float32 and int32 (the program makes the zero carry itself);
  - ``dispatch_s`` (``chunk.dispatch``): enqueue the scan program;
  - ``device_s`` (``chunk.device``): wait for the chip to finish;
  - ``d2h_s`` (``chunk.d2h``): pull the result buffers, the carry keys
    the metrics read packed one buffer per dtype (float32, and int32
    with messages);
  - ``unpack_s`` (``chunk.unpack``): metrics from those keys, padding
    cut off;

  the transfer counters ``h2d_arrays``, ``h2d_bytes``,
  ``d2h_arrays`` and ``d2h_bytes`` (buffers put on and pulled off the
  device: one or two each way), and the chunk's shape, ``recv_hosts``
  (receiving hosts, R), ``flows`` (F) and ``wirings`` (distinct
  topology wirings whose routes the pack derived; 1 when every point is
  wired alike, 0 under dynamic routing).  The manifest adds
  ``envelope_s`` (``farm.envelope``: the full grid packed for its
  envelope) with that pack's ``wirings``, ``plan_s`` (``farm.plan``: the
  chunk plan and the grid's fingerprint) and ``merge_s``
  (``farm.merge``: the merged table, and the shard writes of
  in-process chunks).  Spans on the
  profiler carry the chunk and device as arguments, transfers their
  ``arrays`` and ``bytes``.

Command line::

    python -m repro.fabric.farm --grid pod_storm --workers 4
    python -m repro.fabric.farm --grid incast --chunk 16 --resume \
        --run-id run-20260809-...

Peak memory is bounded by chunk size, not grid size; results stream to
disk as chunks finish.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from . import artifacts as A
from . import vector as V
from .scenarios import build_grid, chunk_plan
from .spans import RUN_SPANS, field, new_record, span

# set by _worker_init in pool workers; holds the rebuilt grid + run ctx
_WORKER: dict = {}


@dataclasses.dataclass
class GridSpec:
    """Picklable recipe for a named grid (workers rebuild from this)."""
    name: str
    quick: bool = False
    overrides: Optional[dict] = None

    def build(self):
        return build_grid(self.name, quick=self.quick,
                          **(self.overrides or {}))

    def to_json(self) -> dict:
        return {"name": self.name, "quick": self.quick,
                "overrides": self.overrides or {}}


def _resolve_grid(grid, quick: bool, overrides: Optional[dict]
                  ) -> Tuple[List, List[dict], Optional[GridSpec]]:
    """Accept a grid name, a GridSpec, or a raw scenario list."""
    if isinstance(grid, GridSpec):
        scens, points = grid.build()
        return scens, points, grid
    if isinstance(grid, str):
        spec = GridSpec(grid, quick=quick, overrides=overrides)
        scens, points = spec.build()
        return scens, points, spec
    scens = list(grid)
    return scens, [{} for _ in scens], None


# the engine choice lives in vector; bench/readings.py and the tests
# import it from here under this name
_pick_sparse = V.pick_sparse


def _pad_chunk(scens: Sequence, entry: dict) -> Tuple[List, int]:
    """Chunk scenarios padded to the canonical dispatch shape.

    Padding replicates the chunk's first scenario: a duplicate of a real
    point adds nothing to the any-over-points capability flags or ring
    maxima (the envelope already floors those anyway) and its lane is
    sliced off before results leave this module.
    """
    real = list(scens[entry["start"]:entry["stop"]])
    n_pad = entry["padded"] - len(real)
    return real + [real[0]] * n_pad, len(real)


def _pack_chunk(scens: Sequence, entry: dict, sparse: bool,
                envelope: dict, record: dict):
    with span("farm.pack", record):
        padded, n_real = _pad_chunk(scens, entry)
        fsp = V.FabricSweepParams.from_scenarios(padded, sparse=sparse,
                                                 envelope=envelope)
    record.update(recv_hosts=fsp.n_recv, flows=fsp.n_flows,
                  wirings=fsp.wirings)
    return fsp, n_real


def _chunk_record(entry: dict, device, worker: str) -> dict:
    return new_record(chunk=entry["chunk"], start=entry["start"],
                      stop=entry["stop"], padded=entry["padded"],
                      device=str(device) if device is not None
                      else "default", worker=worker)


def _execute_packed(fsp, n_real: int, backend: str, unroll,
                    device=None, record: Optional[dict] = None
                    ) -> Tuple[Dict[str, np.ndarray], int]:
    """Run one packed chunk (on ``device`` when given), slice off
    padding, count compiles; spans are timed into ``record``."""
    c0 = V.PROGRAM_COMPILES
    out = V.run_packed(fsp, backend, unroll, device=device, record=record)
    with span("chunk.unpack", record):
        out = {k: np.asarray(v)[:n_real] for k, v in out.items()}
    return out, V.PROGRAM_COMPILES - c0


# --------------------------------------------------------------------------- #
# In-process dispatch (single worker, optional multi-device round-robin)
# --------------------------------------------------------------------------- #
def _device_cycle(backend: str):
    """Devices to round-robin chunks over; [None] = no jax device."""
    if backend != "jax":
        return [None]
    import jax
    return list(jax.devices())


def _run_chunks_inprocess(scens, plan, todo, sparse, envelope, backend,
                          unroll, rdir: Optional[str],
                          times: dict) -> List[dict]:
    """Execute ``todo`` chunks in this process.

    Host-side prep (scenario padding + parameter packing, pure numpy) is
    overlapped with device compute via a one-deep prefetch thread: while
    chunk k runs under jax, chunk k+1 is already being packed.  Each
    finished chunk is sliced to its real points and streamed to its
    shard before the next result materializes, so peak memory tracks the
    chunk shape, not the grid.  Shard writes add to ``times["merge_s"]``.
    """
    from concurrent.futures import ThreadPoolExecutor

    cycle = _device_cycle(backend)
    devices = [cycle[i % len(cycle)] for i in range(len(todo))]
    records = [_chunk_record(plan[k], dev, "inprocess")
               for k, dev in zip(todo, devices)]
    with ThreadPoolExecutor(max_workers=1,
                            thread_name_prefix="farm-pack") as pool:
        nxt = pool.submit(_pack_chunk, scens, plan[todo[0]], sparse,
                          envelope, records[0])
        for i, (k, rec) in enumerate(zip(todo, records)):
            with span("farm.pack_wait", rec):
                fsp, n_real = nxt.result()
            if i + 1 < len(todo):
                nxt = pool.submit(_pack_chunk, scens, plan[todo[i + 1]],
                                  sparse, envelope, records[i + 1])
            t0 = time.perf_counter()
            out, rec["compiles"] = _execute_packed(
                fsp, n_real, backend, unroll, devices[i], rec)
            rec["wall_s"] = time.perf_counter() - t0
            if rdir is not None:
                with span("farm.merge", times):
                    A.save_chunk(rdir, k, out, meta=rec)
            else:
                rec["results"] = out
    return records


# --------------------------------------------------------------------------- #
# Multiprocess dispatch (spawn pool; workers rebuild the grid by name)
# --------------------------------------------------------------------------- #
def _worker_init(spec_json: dict, sparse: bool, envelope: dict,
                 backend: str, rdir: str) -> None:
    """Pool initializer: rebuild the grid once per worker process."""
    from ._scan import configure_persistent_cache
    configure_persistent_cache()   # share the on-disk XLA cache
    spec = GridSpec(spec_json["name"], spec_json["quick"],
                    spec_json["overrides"] or None)
    scens, _ = spec.build()
    _WORKER.update(scens=scens, sparse=sparse, envelope=envelope,
                   backend=backend, rdir=rdir)


def _worker_run_chunk(entry: dict) -> dict:
    """Run one chunk inside a pool worker; writes the shard itself so a
    killed parent cannot lose finished work."""
    w = _WORKER
    rec = _chunk_record(entry, None, f"pid{os.getpid()}")
    t0 = time.perf_counter()
    fsp, n_real = _pack_chunk(w["scens"], entry, w["sparse"],
                              w["envelope"], rec)
    out, rec["compiles"] = _execute_packed(fsp, n_real, w["backend"],
                                           "auto", record=rec)
    rec["wall_s"] = time.perf_counter() - t0
    A.save_chunk(w["rdir"], entry["chunk"], out, meta=rec)
    return rec


def _run_chunks_pool(spec: GridSpec, plan, todo, sparse, envelope,
                     backend, workers: int, rdir: str) -> List[dict]:
    import multiprocessing as mp

    ctx = mp.get_context("spawn")   # fork after jax init is unsafe
    n = min(workers, len(todo))
    with ctx.Pool(n, initializer=_worker_init,
                  initargs=(spec.to_json(), sparse, envelope, backend,
                            rdir)) as pool:
        records = pool.map(_worker_run_chunk, [plan[k] for k in todo])
    return records


# --------------------------------------------------------------------------- #
# The farm entry point
# --------------------------------------------------------------------------- #
def run_farm(grid: Union[str, GridSpec, Sequence],
             workers: int = 0,
             chunk_size: int = 16,
             backend: str = "jax",
             incidence: str = "auto",
             unroll="auto",
             quick: bool = False,
             grid_overrides: Optional[dict] = None,
             out_dir: str = A.DEFAULT_RUNS_DIR,
             run_id: Optional[str] = None,
             resume: bool = False,
             artifacts: bool = True) -> dict:
    """Execute a scenario grid as fixed-shape chunks and gather versioned
    artifacts.

    ``grid`` is a registry name (:data:`repro.fabric.scenarios.GRIDS`),
    a :class:`GridSpec`, or a raw scenario list (in-process only — raw
    lists cannot cross to spawn workers).  Returns ``{"run_id",
    "run_dir", "manifest", "results"}`` where ``results`` is the merged
    ``{metric: array[G]}`` table in input order, bit-identical at fixed
    dt to ``run_fabric_sweep(grid)`` run monolithically.

    ``resume=True`` with an existing ``run_id`` skips chunks whose
    shards already load; the manifest records which chunks ran in which
    invocation (``records[k]["worker"]``).  ``artifacts=False`` keeps
    everything in memory (bench/smoke use; implies no resume).
    """
    scens, points, spec = _resolve_grid(grid, quick, grid_overrides)
    if not scens:
        raise ValueError("empty grid")
    if workers > 1 and spec is None:
        warnings.warn("raw scenario lists cannot be shipped to worker "
                      "processes (unpicklable closures); running "
                      "in-process instead — pass a named grid for "
                      "multiprocess dispatch", RuntimeWarning,
                      stacklevel=2)
        workers = 0
    if workers > 1 and not artifacts:
        raise ValueError("multiprocess dispatch requires artifacts "
                         "(workers stream shards to disk)")
    if workers > 1 and backend == "jax":
        import jax
        if jax.default_backend() == "tpu":
            raise ValueError(
                f"workers={workers} with backend='jax' on a TPU host: a "
                "chip belongs to one process, so worker processes cannot "
                "share it — use workers=0 (chunks round-robin over the "
                "local chips in this process)")

    sparse = _pick_sparse(scens, incidence)
    times = {field(n): 0.0 for n in RUN_SPANS}
    with span("farm.envelope", times):
        full = V.FabricSweepParams.from_scenarios(scens, sparse=sparse)
        envelope = full.envelope()
    with span("farm.plan", times):
        plan = chunk_plan(len(scens), chunk_size)
        fingerprint = A.config_hash(scens)

    rdir = None
    done: List[int] = []
    if artifacts:
        run_id = run_id or A.new_run_id()
        rdir = A.run_dir(run_id, out_dir)
        prev = A.read_manifest(rdir)
        if resume and prev is not None:
            if prev.get("config_hash") != fingerprint:
                raise ValueError(
                    f"resume mismatch: run {run_id} was recorded for a "
                    f"different grid (hash {prev.get('config_hash')} != "
                    f"{fingerprint})")
            done = A.completed_chunks(rdir, len(plan))
        manifest = {
            "run_id": run_id, "status": "running",
            "grid": spec.to_json() if spec else {"name": "<inline>"},
            "n_points": len(scens), "chunk_size": chunk_size,
            "chunks": len(plan), "plan": plan,
            "backend": backend, "engine":
                "sparse" if sparse else "dense",
            "envelope": {k: (bool(v) if isinstance(v, (bool, np.bool_))
                             else int(v)) for k, v in envelope.items()},
            "structure_key": full.structure_key,
            "wirings": full.wirings,
            "config_hash": fingerprint, "git_sha": A.git_sha(),
            "workers": workers, "records": (prev or {}).get("records",
                                                            []),
        }
        A.write_manifest(rdir, manifest)
    else:
        manifest = {"run_id": run_id or "<in-memory>",
                    "status": "running", "wirings": full.wirings,
                    "records": []}

    todo = [e["chunk"] for e in plan if e["chunk"] not in set(done)]
    t0 = time.perf_counter()
    if todo:
        if workers > 1:
            new_recs = _run_chunks_pool(spec, plan, todo, sparse,
                                        envelope, backend, workers,
                                        rdir)
        else:
            new_recs = _run_chunks_inprocess(scens, plan, todo, sparse,
                                             envelope, backend, unroll,
                                             rdir, times)
    else:
        new_recs = []
    wall = time.perf_counter() - t0

    if rdir is not None:
        with span("farm.merge", times):
            results = A.merge_chunks(rdir, plan, len(scens))
        kept = [r for r in manifest["records"]
                if r["chunk"] not in set(todo)]
        manifest["records"] = sorted(kept + new_recs,
                                     key=lambda r: r["chunk"])
        manifest["status"] = "complete"
        manifest["wall_s"] = wall
        manifest["resumed_chunks"] = sorted(done)
        manifest.update(times)
        A.write_manifest(rdir, manifest)
    else:
        results: Dict[str, np.ndarray] = {}
        with span("farm.merge", times):
            for rec in new_recs:
                out = rec.pop("results")
                for k, v in out.items():
                    if k not in results:
                        results[k] = np.zeros((len(scens),) + v.shape[1:],
                                              v.dtype)
                    results[k][rec["start"]:rec["stop"]] = v
        manifest["records"] = new_recs
        manifest["status"] = "complete"
        manifest["wall_s"] = wall
        manifest.update(times)

    return {"run_id": manifest["run_id"], "run_dir": rdir,
            "manifest": manifest, "results": results,
            "points": points}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.fabric.farm",
        description="Run a scenario grid as a chunked sweep farm.")
    ap.add_argument("--grid", required=True,
                    help="named grid from repro.fabric.scenarios.GRIDS")
    ap.add_argument("--workers", type=int, default=0,
                    help="worker processes (<=1: in-process dispatch)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="grid points per chunk")
    ap.add_argument("--backend", default="jax",
                    choices=("jax", "numpy"))
    ap.add_argument("--incidence", default="auto",
                    choices=("auto", "dense", "sparse"))
    ap.add_argument("--quick", action="store_true",
                    help="use the registry's shrunken smoke axes")
    ap.add_argument("--out-dir", default=A.DEFAULT_RUNS_DIR)
    ap.add_argument("--run-id", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="skip chunks whose shards already exist")
    args = ap.parse_args(argv)

    from ._scan import configure_persistent_cache
    configure_persistent_cache()
    res = run_farm(args.grid, workers=args.workers,
                   chunk_size=args.chunk, backend=args.backend,
                   incidence=args.incidence, quick=args.quick,
                   out_dir=args.out_dir, run_id=args.run_id,
                   resume=args.resume)
    m = res["manifest"]
    ran = [r for r in m["records"] if r["chunk"]
           not in set(m.get("resumed_chunks", []))]
    print(f"run {res['run_id']}: {m['n_points']} points, "
          f"{m['chunks']} chunks ({len(m.get('resumed_chunks', []))} "
          f"resumed), engine={m['engine']}, "
          f"wall={m['wall_s']:.2f}s, "
          f"compiles={sum(r['compiles'] for r in ran)}")
    if res["run_dir"]:
        print(f"artifacts: {res['run_dir']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

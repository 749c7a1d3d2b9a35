"""Sweep-farm tests: chunk/padding invariance, artifacts + resume, and
device dispatch.

The farm's core promise is that chunking is *invisible*: a grid run as
one monolithic program, as several chunks, and as chunks padded with
duplicate points must produce bit-identical per-point results at fixed
dt — held here for the numpy (f64) and jax (f32) engines, for a faults
grid (whose counter-based loss RNG must stay realization-identical
across chunk boundaries), and against the scalar driver golden.
"""
from __future__ import annotations

import os
import warnings

import numpy as np
import pytest

from repro.fabric import artifacts as A
from repro.fabric import vector as V
from repro.fabric.farm import GridSpec, run_farm
from repro.fabric.scenarios import (build_grid, chunk_plan, incast_grid,
                                    lossy_incast_grid)
from repro.fabric.vector import FabricSweepParams, run_fabric_sweep


def _grid(n=8):
    scens, _ = incast_grid(burst_mb=tuple(0.25 * (i + 1)
                                          for i in range(n // 4)),
                           n_senders=4, sim_time_s=0.001)
    return scens[:n]


def _assert_identical(a: dict, b: dict, label: str) -> None:
    assert set(a) == set(b)
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert np.array_equal(x, y, equal_nan=True), \
            f"{label}: metric {k} differs"


# --------------------------------------------------------------------------- #
# chunk planning
# --------------------------------------------------------------------------- #
def test_chunk_plan_shapes():
    plan = chunk_plan(23, 8)
    assert [(e["stop"] - e["start"], e["padded"]) for e in plan] == \
        [(8, 8), (8, 8), (7, 8)]           # remainder pads up to pow2<=8
    assert plan[-1]["padded"] >= plan[-1]["stop"] - plan[-1]["start"]
    # at most two canonical shapes per plan
    assert len({e["padded"] for e in plan}) <= 2
    # full coverage, no overlap
    covered = [i for e in plan for i in range(e["start"], e["stop"])]
    assert covered == list(range(23))


def test_chunk_plan_rejects_bad_input():
    with pytest.raises(ValueError):
        chunk_plan(0, 8)
    with pytest.raises(ValueError):
        chunk_plan(8, 0)


def test_envelope_forces_structure_key():
    # heterogeneous grid: first half carries CC + faults, second half
    # is plain — naive per-chunk packing would change capability flags
    from repro.fabric.cc import CcConfig
    from repro.fabric.faults import FaultConfig
    scens = _grid(8)
    for sc in scens[:4]:
        sc.fabric.cc = CcConfig(algo="timely")
        sc.fabric.faults = FaultConfig(loss_rate=1e-4, seed=7)
    full = FabricSweepParams.from_scenarios(scens)
    env = full.envelope()
    for lo, hi in ((0, 4), (4, 8)):
        chunk = FabricSweepParams.from_scenarios(scens[lo:hi],
                                                 envelope=env)
        assert chunk.structure_key == full.structure_key
    # without the envelope the plain chunk traces a smaller program
    bare = FabricSweepParams.from_scenarios(scens[4:])
    assert bare.structure_key != full.structure_key


# --------------------------------------------------------------------------- #
# chunk/padding invariance
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_farm_bit_identical_vs_monolithic(backend):
    scens = _grid(8)
    mono = run_fabric_sweep(scens, backend=backend)
    farm = run_farm(scens, workers=0, chunk_size=4, backend=backend,
                    artifacts=False)
    _assert_identical(mono, farm["results"], f"farm-{backend}")


def test_padded_chunks_bit_identical_numpy():
    # 7 real points with chunk_size=4 -> chunks (4, 3-padded-to-4):
    # the padded lane replicates a real scenario and must not perturb
    # any real point
    scens = _grid(8)[:7]
    mono = run_fabric_sweep(scens, backend="numpy")
    farm = run_farm(scens, workers=0, chunk_size=4, backend="numpy",
                    artifacts=False)
    plan = farm["manifest"]["records"]
    assert [r["padded"] for r in plan] == [4, 4]
    assert [r["stop"] - r["start"] for r in plan] == [4, 3]
    _assert_identical(mono, farm["results"], "farm-padded")


def test_faults_grid_chunk_invariance_numpy():
    # counter-based loss RNG hashes (tick, link, seed) only — chunk
    # boundaries must not shift any realization
    scens, _ = lossy_incast_grid(loss_rate=(0.01, 0.05),
                                 n_senders=4, sim_time_s=0.001)
    assert len(scens) == 4
    mono = run_fabric_sweep(scens, backend="numpy")
    farm = run_farm(scens, workers=0, chunk_size=3, backend="numpy",
                    artifacts=False)   # chunks (3, 1): boundary mid-grid
    _assert_identical(mono, farm["results"], "farm-faults")
    assert np.asarray(mono["retransmit_bytes"]).sum() > 0  # non-trivial


def test_farm_matches_scalar_golden():
    scens = _grid(4)
    farm = run_farm(scens, workers=0, chunk_size=3, backend="numpy",
                    artifacts=False)
    ref = scens[2].run()   # point in the second (padded) chunk
    got = np.asarray(farm["results"]["flow_goodput_gbps"][2])
    want = np.array([ref.flow_goodput_gbps[f]
                     for f in range(len(scens[2].flows))])
    np.testing.assert_allclose(got, want, rtol=1e-9)


# --------------------------------------------------------------------------- #
# artifacts + resume
# --------------------------------------------------------------------------- #
def test_resume_reexecutes_only_missing_chunks(tmp_path):
    td = str(tmp_path)
    res = run_farm("incast", quick=True, workers=0, chunk_size=6,
                   backend="numpy", out_dir=td)
    m = res["manifest"]
    assert m["status"] == "complete"
    assert m["chunks"] == 3
    assert os.path.exists(os.path.join(res["run_dir"],
                                       "manifest.json"))
    # kill-at-50% simulation: drop one shard, resume
    os.remove(A.chunk_path(res["run_dir"], 1))
    res2 = run_farm("incast", quick=True, workers=0, chunk_size=6,
                    backend="numpy", out_dir=td, run_id=res["run_id"],
                    resume=True)
    m2 = res2["manifest"]
    assert sorted(m2["resumed_chunks"]) == [0, 2]
    reran = [r["chunk"] for r in m2["records"]
             if r["chunk"] not in m2["resumed_chunks"]]
    assert reran == [1]
    _assert_identical(res["results"], res2["results"], "resume")


def test_resume_rejects_different_grid(tmp_path):
    td = str(tmp_path)
    res = run_farm("incast", quick=True, workers=0, chunk_size=8,
                   backend="numpy", out_dir=td)
    with pytest.raises(ValueError, match="resume mismatch"):
        run_farm("mixed_fleet", quick=True, workers=0, chunk_size=8,
                 backend="numpy", out_dir=td, run_id=res["run_id"],
                 resume=True)


def test_artifacts_roundtrip(tmp_path):
    rdir = str(tmp_path / "run")
    out = {"m": np.arange(6, dtype=np.float64).reshape(3, 2)}
    A.save_chunk(rdir, 0, out, meta={"chunk": 0})
    loaded = A.load_chunk(rdir, 0)
    assert loaded is not None
    results, meta = loaded
    assert meta["chunk"] == 0
    np.testing.assert_array_equal(results["m"], out["m"])
    # corrupt shard -> treated as missing (resume re-runs it)
    with open(A.chunk_path(rdir, 0), "wb") as f:
        f.write(b"garbage")
    assert A.load_chunk(rdir, 0) is None
    assert A.completed_chunks(rdir, 1) == []


def test_grid_spec_picklable_and_deterministic():
    import pickle
    spec = GridSpec("incast", quick=True)
    spec2 = pickle.loads(pickle.dumps(spec))
    a, _ = spec.build()
    b, _ = spec2.build()
    assert [s.name for s in a] == [s.name for s in b]


# --------------------------------------------------------------------------- #
# device dispatch
# --------------------------------------------------------------------------- #
def test_farm_on_local_devices_bit_identical_vs_monolithic():
    # chunks round-robin over jax.devices() (a cycle of one on a
    # single-device host) and each record names the device it ran on
    import jax
    scens = _grid(8)
    mono = run_fabric_sweep(scens, backend="jax")
    farm = run_farm(scens, workers=0, chunk_size=2, backend="jax",
                    artifacts=False)
    _assert_identical(mono, farm["results"], "local-devices")
    devs = [str(d) for d in jax.devices()]
    assert [r["device"] for r in farm["manifest"]["records"]] == \
        [devs[i % len(devs)] for i in range(4)]


def test_raw_scenarios_with_workers_fall_back_inprocess():
    scens = _grid(4)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        farm = run_farm(scens, workers=4, chunk_size=4,
                        backend="numpy", artifacts=False)
    assert any("raw scenario lists" in str(w.message) for w in rec)
    assert farm["manifest"]["records"][0]["worker"] == "inprocess"


# --------------------------------------------------------------------------- #
# program-cache accounting
# --------------------------------------------------------------------------- #
def test_zero_recompiles_after_warmup():
    scens = _grid(8)
    run_farm(scens, workers=0, chunk_size=4, backend="jax",
             artifacts=False)                       # warmup compiles
    farm = run_farm(scens, workers=0, chunk_size=4, backend="jax",
                    artifacts=False)
    assert sum(r["compiles"]
               for r in farm["manifest"]["records"]) == 0


def test_named_grid_registry():
    scens, points = build_grid("incast", quick=True)
    assert len(scens) == len(points) == 16
    with pytest.raises(ValueError, match="unknown grid"):
        build_grid("nope")


# --------------------------------------------------------------------------- #
# host spans and transfer counters
# --------------------------------------------------------------------------- #
_MAIN_SPANS = ("pack_wait", "params", "h2d", "dispatch", "device", "d2h",
               "unpack")


def _assert_span_fields(rec: dict) -> None:
    from repro.fabric import spans as S
    for name in S.CHUNK_SPANS:
        assert rec[S.field(name)] >= 0.0, name
    for c in S.TRANSFER_COUNTERS:
        assert rec[c] >= 0, c
    # the main thread's spans lie inside the chunk's wall time, but for
    # the wait on the prefetch thread, which comes before it
    assert sum(rec[n + "_s"] for n in _MAIN_SPANS) \
        <= rec["wall_s"] + rec["pack_wait_s"]


def _leaves(tree):
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    return len(leaves), sum(int(np.prod(x.shape)) * x.dtype.itemsize
                            for x in leaves)


def test_chunk_records_carry_spans_and_transfer_counts():
    """Each chunk record times every span and counts the arrays and bytes
    put on the device (the packed parameter buffers) and pulled back (the
    packed result buffers), as the program's own packing gives them:
    one or two buffers each way, fewer bytes in than the state and
    parameters the chunk once put leaf by leaf."""
    import jax
    scens = _grid(8)
    farm = run_farm(scens, workers=0, chunk_size=4, backend="jax",
                    artifacts=False)
    m = farm["manifest"]
    assert m["envelope_s"] > 0 and m["plan_s"] > 0 and m["merge_s"] > 0
    env = FabricSweepParams.from_scenarios(scens).envelope()
    fsp = FabricSweepParams.from_scenarios(scens[:4], envelope=env)
    bufs = V.packed_params(fsp)
    program = V._jax_program(fsp, V.pick_unroll("auto"), "ref")
    final = jax.eval_shape(program, *bufs)
    p = V._np_params(fsp, np.float32)
    s0 = V._init_state(np, (fsp.n_points,), fsp, p, np.float32)
    assert len(m["records"]) == 2
    for rec in m["records"]:
        _assert_span_fields(rec)
        assert rec["pack_s"] > 0 and rec["device_s"] > 0
        assert (rec["h2d_arrays"], rec["h2d_bytes"]) == _leaves(bufs)
        assert (rec["d2h_arrays"], rec["d2h_bytes"]) == _leaves(final)
        assert 1 <= rec["h2d_arrays"] <= 2 and 1 <= rec["d2h_arrays"] <= 2
        assert rec["h2d_bytes"] < _leaves((s0, p))[1]


def test_chunk_records_same_fields_on_every_path(tmp_path, monkeypatch):
    """The numpy backend, the artifacts run and a pool worker's chunk
    write the same record fields; the numpy engine moves no arrays."""
    from repro.fabric import farm as F
    from repro.fabric import spans as S
    res = run_farm("incast", quick=True, workers=0, chunk_size=8,
                   backend="numpy", out_dir=str(tmp_path))
    disk = A.read_manifest(res["run_dir"])
    assert disk["envelope_s"] > 0 and disk["merge_s"] > 0
    fields = set(disk["records"][0])
    for rec in disk["records"]:
        assert set(rec) == fields
        _assert_span_fields(rec)
        assert all(rec[c] == 0 for c in S.TRANSFER_COUNTERS)
    spec = GridSpec("incast", quick=True)
    scens, _ = spec.build()
    env = FabricSweepParams.from_scenarios(scens).envelope()
    monkeypatch.setattr(F, "_WORKER", dict(
        scens=scens, sparse=False, envelope=env, backend="numpy",
        rdir=str(tmp_path / "pool")))
    rec = F._worker_run_chunk(chunk_plan(len(scens), 8)[1])
    assert set(rec) == fields and rec["pack_wait_s"] == 0.0
    assert rec["pack_s"] > 0
    _assert_span_fields(rec)


def test_chunk_records_count_receivers_and_flows(tmp_path, monkeypatch):
    """Every chunk record carries its chunk's shape, receiving hosts (R)
    and flows (F), on the in-process path and in a pool worker."""
    from repro.fabric import farm as F
    from repro.fabric import spans as S
    scens = _grid(8)
    fsp = FabricSweepParams.from_scenarios(scens)
    assert (fsp.n_recv, fsp.n_flows) == (len(fsp.recv_hosts), 5)
    farm = run_farm(scens, workers=0, chunk_size=4, backend="numpy",
                    artifacts=False)
    for rec in farm["manifest"]["records"]:
        assert set(S.SHAPE_COUNTERS) <= set(rec)
        assert (rec["recv_hosts"], rec["flows"]) == (fsp.n_recv,
                                                     fsp.n_flows)
    spec = GridSpec("incast", quick=True)
    pool_scens, _ = spec.build()
    env = FabricSweepParams.from_scenarios(pool_scens).envelope()
    monkeypatch.setattr(F, "_WORKER", dict(
        scens=pool_scens, sparse=False, envelope=env, backend="numpy",
        rdir=str(tmp_path / "pool")))
    rec = F._worker_run_chunk(chunk_plan(len(pool_scens), 8)[0])
    want = FabricSweepParams.from_scenarios(pool_scens[:8])
    assert (rec["recv_hosts"], rec["flows"]) == (want.n_recv,
                                                 want.n_flows)
    assert S.new_record(chunk=0)["recv_hosts"] == 0

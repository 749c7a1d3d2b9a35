"""The split of the chips' idle time by the program's own host spans
(``bench/spans.py``) and the readers built on it: a synthetic trace with
known answers, a real profile of the farm on the CPU, and excerpts of
``--trace 1`` runs recorded on the chip."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import excerpt_spans, registry, spans, tracing  # noqa: E402

READERS = ("handover_idle_ms", "pack_wait_ms", "transfer_ms", "transfers")


def _profile(planes):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(excerpt_spans.to_text_proto(planes,
                                                                   0))


def _synthetic():
    """Window [0, 200) ns; the chip busy in [80, 140); the main thread
    builds in [2, 10), runs the farm in [10, 190) under the program's
    spans (``chunk.h2d`` nested in ``chunk.params``) and gathers results
    in [190, 196); the prefetch thread packs in [14, 58)."""
    def ev(name, s, e, **args):
        return (name, s, e - s, args)
    chip = ("/device:TPU:0", [("XLA Ops", [
        ev("fusion.1", 80, 110, hlo_category="loop fusion"),
        ev("custom-call.3", 110, 140, hlo_category="custom-call")])])
    main = ("python3", [
        ev("window", 0, 200), ev("build", 2, 10), ev("farm", 10, 190),
        ev("farm.envelope", 12, 30),
        ev("farm.pack_wait", 36, 60, chunk=0, device="TPU_0"),
        ev("chunk.params", 60, 74, chunk=0, device="TPU_0"),
        ev("chunk.h2d", 64, 70, chunk=0, device="TPU_0", arrays=95,
           bytes=1000),
        ev("chunk.device", 74, 150, chunk=0, device="TPU_0"),
        ev("chunk.d2h", 150, 160, chunk=0, device="TPU_0", arrays=48,
           bytes=900),
        ev("chunk.unpack", 160, 170, chunk=0, device="TPU_0"),
        ev("farm.merge", 174, 186), ev("results", 190, 196)])
    prefetch = ("python3", [ev("farm.pack", 14, 58, chunk=0,
                               device="TPU_0")])
    return _profile([chip, ("/host:CPU", [main, prefetch])])


def test_innermost_names_each_piece_after_the_inner_span():
    sp = [("a", 0, 10), ("b", 2, 5), ("c", 5, 5), ("d", 12, 14),
          ("e", 12, 13)]
    assert spans.innermost(sp) == [(0, 2, "a"), (2, 5, "b"), (5, 10, "a"),
                                   (12, 13, "e"), (13, 14, "d")]


def test_synthetic_gaps_go_to_the_main_threads_innermost_span():
    pd = _synthetic()
    s = spans.reduce_spans(pd)
    assert s["chips"] == 1 and s["grids"] == 1
    # idle inside farm: [10, 80) and [140, 190); the prefetch thread's
    # farm.pack over [30, 36) takes nothing
    assert s["farm_idle_s"] == pytest.approx(120e-9)
    assert s["named_idle_s"] == pytest.approx(104e-9)
    assert s["idle_by_span"] == pytest.approx({
        "farm.envelope": 18e-9, "farm.pack_wait": 24e-9,
        "chunk.params": 8e-9, "chunk.h2d": 6e-9, "chunk.device": 16e-9,
        "chunk.d2h": 10e-9, "chunk.unpack": 10e-9, "farm.merge": 12e-9})
    assert "farm.pack" not in s["idle_by_span"]
    assert s["span_s"]["chunk.params"] == pytest.approx(14e-9)
    assert s["transfers"] == 95 + 48
    # the gaps of the existing reduction, same order and lengths; only
    # the farm gaps are relabelled
    old = tracing.reduce_profile(pd)["breakdown"]["idle_gaps"]
    assert [g[1] for g in s["idle_gaps"]] == [g[1] for g in old]
    assert [g[0] for g in old] == ["farm", "farm", "build", "results",
                                   "other", "other"]
    assert [g[0] for g in s["idle_gaps"]] == [
        "farm:farm.pack_wait", "farm:farm.merge", "build", "results",
        "other", "other"]


def test_readers_on_the_synthetic_trace(monkeypatch):
    got = spans.reduce_spans(_synthetic())
    monkeypatch.setattr(spans, "of_run", lambda run: got)
    b = registry.Bench()
    want = {"handover_idle_ms": 104e-6, "pack_wait_ms": 42e-6,
            "transfer_ms": 16e-6, "transfers": 143.0}
    for name in READERS:
        assert b.reader(name)(None) == pytest.approx(want[name]), name


DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = sorted(f for f in os.listdir(DATA) if f.endswith(".pbtxt"))
NEW_DIR = os.path.join(DATA, "spans")
NEW = sorted(f for f in os.listdir(NEW_DIR) if f.endswith(".pbtxt")) \
    if os.path.isdir(NEW_DIR) else []


def _recorded(path):
    from jax.profiler import ProfileData
    with open(path) as f:
        return ProfileData.from_text_proto(f.read())


@pytest.mark.parametrize("name", OLD)
def test_trace_without_program_spans_reads_nothing(name, monkeypatch):
    """A trace of a program without the spans (the excerpts recorded
    before them) reduces to nothing, and every new reader gives none."""
    assert spans.reduce_spans(_recorded(os.path.join(DATA, name))) is None
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    b = registry.Bench()
    for r in READERS:
        assert b.reader(r)(None) is None


def test_of_run_without_a_trace():
    class Untraced:
        trace = None
    assert spans.of_run(Untraced()) is None


def test_farm_profile_on_cpu_agrees_with_its_records(tmp_path,
                                                      monkeypatch):
    """The farm's spans land on the profiler's main host line, and the
    reduction of a real profile (found the way a run finds it) counts
    the arrays the chunk records count."""
    import jax
    from bench import harness
    from repro.fabric.farm import run_farm
    from repro.fabric.scenarios import incast_grid
    scens, _ = incast_grid(burst_mb=(0.25, 0.5), n_senders=4,
                           sim_time_s=0.0002)
    run_farm(scens, workers=0, chunk_size=4, artifacts=False)
    tdir = str(tmp_path / "trace")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("farm"):
            out = run_farm(scens, workers=0, chunk_size=4, artifacts=False)
    jax.profiler.stop_trace()
    recs = out["manifest"]["records"]
    monkeypatch.setattr(harness, "_trace_dir", lambda bench, cell: tdir)

    class Traced:
        cell, chips = {"name": "clos2-membw"}, 1
    from jax.profiler import ProfileData
    whole = spans.reduce_spans(ProfileData.from_file(
        tracing.find_xplane(tdir)))
    run = Traced()
    run.trace = {"window_s": whole["window_s"]}
    got = spans.of_run(run)
    assert got is not None and got["grids"] == 1 and got["chips"] == 0
    assert got["transfers"] == sum(r["h2d_arrays"] + r["d2h_arrays"]
                                   for r in recs)
    assert got["span_s"]["chunk.device"] == pytest.approx(
        sum(r["device_s"] for r in recs), rel=0.05, abs=1e-3)
    run.trace = {"window_s": whole["window_s"] + 1.0}
    assert spans.of_run(run) is None     # not the trace the run reduced


def _code_transfers(cell: str):
    """Arrays put on and pulled off the chip by each chunk of ``cell``,
    from the program's own packing of the cell's grid, and the chunks a
    grid has."""
    import jax
    from bench import traffic as T
    from repro.fabric import vector as V
    from repro.fabric.farm import _pick_sparse
    from repro.fabric.scenarios import chunk_plan
    b = registry.Bench()
    w = b.cell(cell)
    tr = dict(b.traffic(w["traffic"]), sim_time_s=0.0001)
    scens, _ = T.build_grid(b.config(w["config"]), tr, 1, 1,
                            T.program_namespace())
    sparse = _pick_sparse(scens, "auto")
    env = V.FabricSweepParams.from_scenarios(scens, sparse=sparse).envelope()
    plan = chunk_plan(len(scens), 16)
    fsp = V.FabricSweepParams.from_scenarios(
        scens[:plan[0]["padded"]], sparse=sparse, envelope=env)
    p = V._np_params(fsp, np.float32)
    s0 = V._init_state(np, (fsp.n_points,), fsp, p, np.float32)
    carry = len(jax.tree_util.tree_leaves(s0))
    return len(p) + carry, carry, len(plan)


@pytest.mark.parametrize("name", NEW)
def test_recorded_handover_is_named(name):
    """Excerpts of ``--trace 1`` runs on one v5e around the longest
    chunk hand-over (``bench/excerpt_spans.py``): at least 90% of the
    chips' idle time inside ``farm`` lies under a named program span,
    the gaps keep the lengths ``bench/tracing.py`` gives them, and each
    transfer moves as many arrays as the program's packing makes."""
    pd = _recorded(os.path.join(NEW_DIR, name))
    s = spans.reduce_spans(pd)
    assert s is not None and s["chips"] == 1
    assert s["farm_idle_s"] > 0
    assert s["named_idle_s"] >= 0.9 * s["farm_idle_s"]
    old = tracing.reduce_profile(pd)["breakdown"]["idle_gaps"]
    assert [g[1] for g in s["idle_gaps"]] == [g[1] for g in old]
    for (new, _), (was, _) in zip(s["idle_gaps"], old):
        assert new == was or (was == "farm" and new.startswith("farm:"))
    assert s["idle_gaps"][0][0].startswith("farm:")
    cell = name.split(".")[0]
    h2d, d2h, chunks = _code_transfers(cell)
    want = {"chunk.h2d": h2d, "chunk.d2h": d2h}
    moved = [(n, a["arrays"]) for n, _, _, a in
             spans.program_spans(spans._main_line(pd))
             if n in spans.TRANSFERS]
    assert moved and all(k == want[n] for n, k in moved)
    # per grid, the count the transfers reader gives
    assert chunks * (h2d + d2h) == {"clos2-membw": 572,
                                    "pod256-incast": 145}[cell]

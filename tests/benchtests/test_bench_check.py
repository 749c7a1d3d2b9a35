"""The check that decides ``correct``, driven through a whole run of a
small cell on the CPU with the chip look skipped: a sound run is
correct; the control (the program's step functions in bfloat16) and each
fault planted under the timed path are not."""
import io
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, registry  # noqa: E402

CELL = "clos2-membw"
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def small_bench(tmp_path_factory):
    """The clos2-membw cell at a test's size: 32 points (two farm chunks)
    of 200 ticks, a 4-point sample, the cell's own limits."""
    root = tmp_path_factory.mktemp("benchroot")
    home = root / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), home,
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    spec["workloads"] = [dict(cell, traffic="small")]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for c in spec["configs"]:
        os.makedirs(root / os.path.dirname(c["file"]), exist_ok=True)
        shutil.copy(os.path.join(ROOT, c["file"]), root / c["file"])
    tr = json.load(open(home / "traffic" / (cell["traffic"] + ".json")))
    tr["name"], tr["sim_time_s"] = "small", 0.0002
    for ax in tr["axes"]:
        if ax["name"] == "cpu_membw_gbps":
            ax["n"] = 2
    (home / "traffic" / "small.json").write_text(json.dumps(tr))
    chk = json.load(open(home / "checks" / (CELL + ".json")))
    chk["sample_points"] = 4
    (home / "checks" / (CELL + ".json")).write_text(json.dumps(chk))
    import jax
    old = (jax.config.jax_compilation_cache_dir,
           os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    yield registry.Bench(str(root), str(home))
    # run_cell points the compile cache into its checkout; undo that
    jax.config.update("jax_compilation_cache_dir", old[0])
    if old[1] is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old[1]
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def _run(bench, run_farm=None):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(["--workload", CELL, "--seed", str(SEED),
                           "--seconds", "0.05", "--trace", "0"], 0.0,
                          require_tpu=False, bench=bench,
                          run_farm=run_farm, out=out, err=err)
    assert rc == 0, err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "check"
    assert err.getvalue().strip().splitlines()[-1].startswith("check ")
    return line


def _farm():
    from repro.fabric.farm import run_farm
    return run_farm


def test_sound_run_is_correct(small_bench):
    line = _run(small_bench)
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"sim_rate", "setup_s"}
    assert line["metrics"]["sim_rate"]["value"] > 0
    assert all(v["value"] <= v["limit"] for v in line["check"].values())


def test_control_bfloat16_is_not_correct(small_bench):
    from bench.readings import control_farm
    line = _run(small_bench, control_farm)
    assert line["correct"] is False


def test_fault_state_unchanged(small_bench, monkeypatch):
    """The scan step hands back its state unchanged."""
    from repro.fabric import vector as V
    real = V._make_step

    def frozen(*a, **k):
        real(*a, **k)
        return lambda s, t, it=None: s
    monkeypatch.setattr(V, "_make_step", frozen)
    monkeypatch.setattr(V, "_PROGRAMS", {})
    assert _run(small_bench, _farm())["correct"] is False


def test_fault_half_the_grid_left_out(small_bench):
    """The farm runs half of the points and fills the other half with
    the answers it has."""
    farm = _farm()

    def half(scens, **kw):
        n = len(scens) // 2
        out = farm(list(scens[:n]) * 2, **kw)
        return out
    assert _run(small_bench, half)["correct"] is False


def test_fault_chunks_of_other_chips_left_out(small_bench):
    """Only the first chunk's answers come back; the chunks the other
    chips ran are left out of the merge."""
    farm = _farm()

    def first_chunk_only(scens, **kw):
        out = farm(scens, **kw)
        n = out["manifest"]["records"][0]["stop"]
        for v in out["results"].values():
            v[n:] = 0
        return out
    assert _run(small_bench, first_chunk_only)["correct"] is False


def test_fault_answer_altered(small_bench, monkeypatch):
    """Delivered bytes are altered by 1% where the results are made."""
    from repro.fabric import vector as V
    real = V._results

    def altered(s, fsp):
        out = real(s, fsp)
        out["flow_delivered_bytes"] = out["flow_delivered_bytes"] * 1.01
        return out
    monkeypatch.setattr(V, "_results", altered)
    assert _run(small_bench, _farm())["correct"] is False


def test_judge_fails_what_is_not_finite():
    from bench import compare
    v = compare.judge({"delivered_rel": float("inf"), "cnp_rel": 0.1},
                      {"delivered_rel": 1.0, "cnp_rel": 0.5})
    assert {x["name"]: x["ok"] for x in v} == {"delivered_rel": False,
                                               "cnp_rel": True}
    assert np.isfinite(compare.point_gaps(
        {"delivered": np.ones(2), "completion": np.array([np.inf, 3.0]),
         "pause": 0.0, "cnp": np.zeros(1), "ecn": 0.0},
        {"delivered": np.ones(2), "completion": np.array([np.inf, 3.0]),
         "pause": 0.0, "cnp": np.zeros(1), "ecn": 0.0,
         "sim_us": 10.0})["completion_us"])

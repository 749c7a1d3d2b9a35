"""The benchmark finds each cell's files by name, and refuses to run
where it cannot measure."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import registry  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    b = registry.Bench()
    w = b.cell(cell)
    cfg = b.config(w["config"])
    assert cfg["name"] == w["config"]
    tr = b.traffic(w["traffic"])
    assert tr["name"] == w["traffic"] and tr["axes"]
    chk = b.check(cell)
    assert chk["sample_points"] >= 1
    assert set(chk["limits"]) <= {"delivered_rel", "completion_us",
                                  "pause_rel", "cnp_rel", "ecn_rel"}
    for m in b.end_to_end(cell):
        assert m["name"] in ("sim_rate", "setup_s")
    for m in b.per_layer(cell):
        assert callable(b.reader(m["name"]))


def test_spec_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    for c in SPEC["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e


def test_new_cell_from_new_files_is_picked_up(tmp_path):
    home = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), home)
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "clos2-tiny",
                              "config": "testbed100g-clos2",
                              "traffic": "tiny", "chips": 1, "why": "t"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    tr = json.load(open(home / "traffic" / "membw.json"))
    tr["name"], tr["sim_time_s"] = "tiny", 0.0001
    (home / "traffic" / "tiny.json").write_text(json.dumps(tr))
    chk = json.load(open(home / "checks" / "clos2-membw.json"))
    (home / "checks" / "clos2-tiny.json").write_text(json.dumps(chk))
    (home / "metrics" / "grids_in_window.py").write_text(
        "def read(run):\n    return float(len(run.grids))\n")
    spec["per_layer"].append({"name": "grids_in_window", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "grid build", "moves": "sim_rate"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    b = registry.Bench(str(tmp_path), str(home))
    w = b.cell("clos2-tiny")
    assert b.traffic(w["traffic"])["sim_time_s"] == 0.0001
    assert b.check("clos2-tiny") == chk
    names = [m["name"] for m in b.per_layer("clos2-tiny")]
    assert "grids_in_window" in names and "chip_concurrency" not in names

    class FakeRun:
        grids = [1, 2, 3]
    assert b.reader("grids_in_window")(FakeRun()) == 3.0


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_exits_nonzero_without_tpu():
    r = _run(["--workload", CELLS[0], "--seed", str(2**31 + 11),
              "--seconds", "1", "--trace", "0"], ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """A checkout of BENCHMARK.json and the files under ``paths`` alone
    holds no system to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""

"""The replicated-storage deployment (``pod256-storage-r3`` under
``storage_r3``): its grid has the shape the configuration states, and a
small copy of it, placed by the same rule, runs the same in the vector
engine, the numpy float64 backend, the scalar driver and the plain
reference, where the check holds it correct and the bfloat16 control
not."""
import io
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import compare, harness, registry  # noqa: E402
from bench import traffic as T  # noqa: E402

CELL = "pod256-replwrite"
SEED = 2**31 + 4099


def _cell():
    b = registry.Bench()
    w = b.cell(CELL)
    return b.config(w["config"]), b.traffic(w["traffic"])


def _pod(host: str) -> int:
    return int(re.match(r"p(\d+)h", host).group(1))


def test_grid_is_the_stated_deployment():
    config, traffic = _cell()
    scens, points = T.build_grid(config, traffic, SEED, 1)
    assert len(scens) == len(points) == T.grid_size(traffic) == 16
    flows = scens[0].flows
    assert len(flows) == 384
    assert sorted({f.tag for f in flows}) == ["client", "replica"]
    ens = sorted({f.dst for f in flows})
    assert len(ens) == 128
    # clients on leaves 0-1, extent nodes on leaves 2-3 of every pod
    assert all(re.match(r"p\dh[23]_", h) for h in ens)
    assert all(re.match(r"p\dh[01]_", f.src) for f in flows
               if f.tag == "client")
    fan_in = {h: sum(f.dst == h for f in flows) for h in ens}
    assert set(fan_in.values()) == {3}
    sends = {h: sum(f.src == h for f in flows) for h in ens}
    assert set(sends.values()) == {2}
    # every write lands on three extent nodes in three pods, none in the
    # client's own pod, and every path crosses pods
    by_src = {}
    for f in flows:
        by_src.setdefault(f.src, []).append(f.dst)
    for f in flows:
        if f.tag != "client":
            continue
        replicas = [f.dst] + by_src[f.dst]
        pods = {_pod(h) for h in replicas}
        assert len(replicas) == 3 and len(pods) == 3
        assert _pod(f.src) not in pods
    assert all(_pod(f.src) != _pod(f.dst) for f in flows)
    # the same data goes to every replica
    for s in scens:
        assert len({f.burst_bytes for f in s.flows}) == 1


def test_program_and_reference_read_the_same_deployment():
    config, traffic = _cell()
    point = T.grid_points(traffic, SEED, 2)[11]
    prog = T.build_point(config, traffic, point, T.program_namespace())
    ref = T.build_point(config, traffic, point, T.reference_namespace())
    key = [(f.src, f.dst, f.burst_bytes, f.tag) for f in prog.flows]
    assert key == [(f.src, f.dst, f.burst_bytes, f.tag)
                   for f in ref["flows"]]
    assert len(prog.topology.hosts) == len(ref["topology"].hosts) == 256
    for h in ("p0h2_0", "p3h3_15"):
        hp = prog.fabric.receiver_cfg(h)
        hr = ref["fabric"].receiver_cfg(h)
        for k in ("mode", "pfc_enabled", "cpu_membw_gbps", "pcie_gbps",
                  "jet_pool_bytes", "ddio_bytes", "line_rate_gbps"):
            assert getattr(hp, k) == getattr(hr, k), k
    assert prog.fabric.switch.pfc_enabled \
        == ref["fabric"].switch.pfc_enabled


# --------------------------------------------------------------------------- #
# A small copy: 4 pods x 2 leaves x 2 hosts, clients on leaf 0, extent
# nodes on leaf 1, ~300 ticks
# --------------------------------------------------------------------------- #
SMALL = {"pods": 4, "leaves_per_pod": 2, "hosts_per_leaf": 2,
         "spines_per_pod": 2, "sspines_per_plane": 2}


def _small():
    """The configuration cut to :data:`SMALL`, and the traffic's flow
    groups with leaves 0-1 folded onto leaf 0 and 2-3 onto leaf 1."""
    config, traffic = _cell()
    config = dict(config, **SMALL)

    def fold(host):
        return re.sub(r"h(\d)_", lambda m: f"h{int(m.group(1)) // 2}_",
                      host)
    groups, seen = [], set()
    for g in traffic["flows"]:
        g = dict(g, src=fold(g["src"]), dst=fold(g["dst"]))
        if (g["src"], g["dst"]) not in seen:
            seen.add((g["src"], g["dst"]))
            groups.append(g)
    traffic = dict(traffic, name="storage_r3_small", sim_time_s=0.0003,
                   flows=groups)
    return config, traffic


def test_small_copy_keeps_the_placement():
    config, traffic = _small()
    scens, _ = T.build_grid(config, traffic, SEED, 1)
    flows = scens[0].flows
    assert len(flows) == 24
    ens = sorted({f.dst for f in flows})
    assert len(ens) == 8 and all(re.match(r"p\dh1_", h) for h in ens)
    assert {sum(f.dst == h for f in flows) for h in ens} == {3}


@pytest.fixture(scope="module")
def small_runs():
    from repro.fabric.vector import run_fabric_sweep
    config, traffic = _small()
    scens, points = T.build_grid(config, traffic, SEED, 1)
    jax_res = run_fabric_sweep(scens, backend="jax", incidence="sparse")
    np_res = run_fabric_sweep(scens, backend="numpy", incidence="sparse")
    return config, traffic, scens, points, jax_res, np_res


def test_small_copy_vector_engine_matches_numpy_and_scalar(small_runs):
    _, _, scens, _, jax_res, np_res = small_runs
    d64 = np.asarray(np_res["flow_delivered_bytes"], float)
    d32 = np.asarray(jax_res["flow_delivered_bytes"], float)
    assert d64.min() > 0
    assert np.max(np.abs(d32 - d64) / np.maximum(d64, 1e3)) < 1e-3
    assert np.allclose(np.asarray(jax_res["ecn_marked_bytes"], float),
                       np.asarray(np_res["ecn_marked_bytes"], float),
                       rtol=1e-3, atol=1e3)
    for i in (0, 5, 10, 15):
        r = scens[i].run()
        scalar = [r.flow_delivered_bytes[k] for k in range(len(d64[i]))]
        assert np.allclose(d64[i], scalar, rtol=1e-9, atol=1e-3)
        assert np_res["ecn_marked_bytes"][i] == pytest.approx(
            r.ecn_marked_bytes, rel=1e-9, abs=1e-3)
        assert np_res["pause_total_us"][i] == pytest.approx(
            sum(r.pause_link_us.values()), rel=1e-9, abs=1e-6)


def test_small_copy_matches_the_plain_reference(small_runs):
    config, traffic, _, points, jax_res, _ = small_runs
    for i in (3, 12):
        ref = compare.reference_point(config, traffic, points[i])
        gaps = compare.point_gaps(compare.program_answer(jax_res, i), ref)
        assert gaps["delivered_rel"] < 1e-3
        assert gaps["ecn_rel"] < 1e-3


@pytest.fixture(scope="module")
def small_bench(tmp_path_factory):
    """The cell at the small copy's size: 16 points of 300 ticks, a
    4-point sample, the cell's own limits."""
    root = tmp_path_factory.mktemp("storageroot")
    home = root / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), home,
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    cfg_entry = next(c for c in spec["configs"]
                     if c["name"] == cell["config"])
    config, traffic = _small()
    spec["configs"] = [dict(cfg_entry, file="bench/configs/small.json")]
    spec["workloads"] = [dict(cell, traffic=traffic["name"])]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (home / "configs" / "small.json").write_text(json.dumps(config))
    (home / "traffic" / (traffic["name"] + ".json")).write_text(
        json.dumps(traffic))
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
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_small_cell_is_correct_and_its_control_is_not(small_bench):
    from bench.readings import control_farm
    line = _run(small_bench)
    assert line["correct"] is True, line["check"]
    assert _run(small_bench, control_farm)["correct"] is False

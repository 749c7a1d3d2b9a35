"""The traffic generator: deterministic per seed, the same structure and
chunk shapes in every grid of a cell, different values per seed."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import registry  # noqa: E402
from bench import traffic as T  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
BIG_SEED = 2**31 + 12345


def _cell(name):
    b = registry.Bench()
    w = b.cell(name)
    return b.config(w["config"]), b.traffic(w["traffic"])


def _pack(config, scens):
    from repro.fabric import vector as V
    from repro.fabric.farm import _pick_sparse
    return V.FabricSweepParams.from_scenarios(
        scens, sparse=_pick_sparse(scens, "auto"))


@pytest.mark.parametrize("cell", CELLS)
def test_grids_share_structure_and_chunk_shapes(cell):
    from repro.fabric.scenarios import chunk_plan
    config, traffic = _cell(cell)
    keys, plans, envs = set(), set(), []
    for i in range(3):
        scens, points = T.build_grid(config, traffic, BIG_SEED, i)
        assert len(scens) == T.grid_size(traffic) == len(points)
        fsp = _pack(config, scens)
        assert fsp.sparse == (config["engine"] == "sparse")
        assert fsp.ticks == round(traffic["sim_time_s"] * 1e6)
        keys.add((fsp.structure_key, fsp.n_flows, fsp.n_ports,
                  fsp.ring_len, fsp.cnp_ring))
        plans.add(tuple(e["padded"] for e in chunk_plan(len(scens), 16)))
        envs.append(fsp.envelope())
    assert len(keys) == 1 and len(plans) == 1
    assert all(e == envs[0] for e in envs)


@pytest.mark.parametrize("cell", CELLS)
def test_deterministic_per_seed_and_different_across_seeds(cell):
    config, traffic = _cell(cell)
    a = T.grid_points(traffic, BIG_SEED, 4)
    assert a == T.grid_points(traffic, BIG_SEED, 4)
    assert a != T.grid_points(traffic, BIG_SEED + 1, 4)
    assert a != T.grid_points(traffic, BIG_SEED, 5)
    # drawn values stay inside their ranges
    for ax in traffic["axes"]:
        if "draw" in ax:
            v = np.array([p[ax["name"]] for p in a])
            assert (v >= ax["low"]).all() and (v <= ax["high"]).all()


def test_program_and_reference_read_the_same_numbers():
    config, traffic = _cell(CELLS[0])
    point = T.grid_points(traffic, BIG_SEED, 1)[7]
    prog = T.build_point(config, traffic, point, T.program_namespace())
    ref = T.build_point(config, traffic, point, T.reference_namespace())
    assert [(f.src, f.dst, f.burst_bytes, f.tag) for f in prog.flows] == \
        [(f.src, f.dst, f.burst_bytes, f.tag) for f in ref["flows"]]
    assert type(prog.flows[0]).__module__.startswith("repro.")
    assert type(ref["flows"][0]).__module__.startswith("bench.reference.")
    hp = prog.fabric.receiver_cfg("h1_0")
    hr = ref["fabric"].receiver_cfg("h1_0")
    for k in ("mode", "pfc_enabled", "cpu_membw_gbps", "jet_pool_bytes",
              "ddio_bytes", "line_rate_gbps"):
        assert getattr(hp, k) == getattr(hr, k), k
    assert prog.fabric.switch.pfc_enabled == ref["fabric"].switch.pfc_enabled


def test_axis_targets():
    config, traffic = _cell(CELLS[0])
    tr = dict(traffic, axes=[
        {"name": "pool_mb", "values": [0.5], "scale": 1048576, "int": True,
         "to": ["receiver[h1_0].jet_pool_bytes"]},
        {"name": "per_tc", "values": [False], "to": ["switch.per_tc"]},
        {"name": "burst_mb", "values": [2.0], "scale": 1e6,
         "to": ["flow[incast].burst_bytes"]},
        {"name": "mode", "values": ["ddio"], "to": ["receiver.mode"]}])
    sc = T.build_point(config, tr, T.grid_points(tr, 1, 0)[0],
                       T.program_namespace())
    assert sc.fabric.receiver_cfg("h1_0").jet_pool_bytes == 524288
    assert sc.fabric.receiver_cfg("h1_1").jet_pool_bytes == \
        config["receiver"]["args"]["jet_pool_bytes"]
    assert sc.fabric.receiver_cfg("h1_1").mode == "ddio"
    assert sc.fabric.switch.per_tc is False
    assert {f.burst_bytes for f in sc.flows if f.tag == "incast"} == {2e6}
    assert [f.burst_bytes for f in sc.flows if f.tag == "victim"] == [None]
    with pytest.raises(ValueError):
        T.build_point(config, dict(tr, axes=[dict(tr["axes"][0],
                                                  to=["nowhere.x"])]),
                      {"pool_mb": 0.5}, T.program_namespace())

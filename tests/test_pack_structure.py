"""The static-route structure check of the grid packer, and the
topology invariants it runs on every point.

``FabricSweepParams.from_scenarios`` freezes routes as structure on the
static path, so every grid point must route every flow as point 0 does.
:meth:`Topology.route` reads only the wiring (host attachment, the spine
and super-spine lists, which links exist), so a point wired like one
already checked is not routed again; only a new wiring re-derives every
flow's route.  These tests hold that shortcut to the per-point, per-flow
comparison it replaces: the same ``structure_key`` and the same packed
arrays, bit for bit, on the sparse pod path with and without failure
windows, and the same error when a point routes differently.  They also
pin every error :meth:`Topology.validate` raises, message for message.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import sys

import numpy as np
import pytest

from repro.core.datapath import QoS
from repro.fabric import vector as V
from repro.fabric.fabric import FabricConfig, Flow
from repro.fabric.farm import _pick_sparse, run_farm
from repro.fabric.scenarios import Scenario, _recv_factory
from repro.fabric.switch import SwitchConfig
from repro.fabric.topology import (Link, Topology, _bidi, clos,
                                   make_pod_clos)
from repro.fabric.vector import FabricSweepParams

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SIM_S = 0.0005
POD = dict(pods=3, leaves_per_pod=2, hosts_per_leaf=2, spines_per_pod=2,
           sspines_per_plane=2)
#: ``structure_key`` of :func:`_pod_grid` (and of the failure-window
#: grid), as the per-point route check packed them before the wiring
#: shortcut: a change here is a change of the compiled program.
POD_KEY = "527b61e32faba1fe78cc58937b08e6b5057134dc"
POD_FAIL_KEY = "1aa031262a1e214886a6f825690d509aabcfa974"


def _pod_flows():
    """Cross-pod incast into ``p0h0_0`` (super-spine routes), an
    intra-pod cross-leaf flow (a shallow route with two candidates) and
    an intra-leaf flow."""
    flows = [Flow(src=f"p{pi}h{li}_{hi}", dst="p0h0_0", burst_bytes=1e5,
                  tag="incast")
             for pi in (1, 2) for li in range(2) for hi in range(2)]
    flows.append(Flow(src="p0h1_0", dst="p0h0_1", tag="victim",
                      qos=QoS.LOW))
    flows.append(Flow(src="p1h0_0", dst="p1h0_1", burst_bytes=5e4))
    return flows


def _pod_point(i: int, topo=None) -> Scenario:
    """Point ``i``: its own pod fabric with its own link rates."""
    if topo is None:
        topo = make_pod_clos(**POD, host_gbps=100.0 + 25.0 * i,
                             leaf_spine_gbps=200.0 + 50.0 * i,
                             spine_sspine_gbps=400.0 - 50.0 * i)
    fab = FabricConfig(sim_time_s=SIM_S,
                       switch=SwitchConfig(pfc_enabled=bool(i % 2)),
                       receiver_cfg=_recv_factory(
                           "jet" if i % 2 else "ddio", bool(i % 2)))
    return Scenario(name=f"pod{i}", topology=topo, flows=_pod_flows(),
                    fabric=fab)


def _pod_grid(n: int = 4):
    return [_pod_point(i) for i in range(n)]


def _pod_fail_grid():
    """The pod grid with a leaf-uplink failure window on point 2 and a
    flap on point 3: the sparse failure-window (``pack_fail``) path."""
    scens = _pod_grid()
    scens[2].topology.fail_link("p0l0", "p0s0", at_us=100.0,
                                restore_us=300.0)
    scens[3].topology.flap_link("p1l0", "p1s1", start_us=50.0,
                                period_us=200.0, down_us=80.0)
    return scens


def _per_point_pack(monkeypatch, scens, **kw) -> FabricSweepParams:
    """Pack ``scens`` with the wiring shortcut off: every point's every
    flow is routed and compared with point 0's, as before it."""
    with monkeypatch.context() as m:
        m.setattr(V, "_same_wiring", lambda a, b: False)
        return FabricSweepParams.from_scenarios(scens, **kw)


def _assert_same_pack(a: FabricSweepParams, b: FabricSweepParams) -> None:
    """Every field but ``wirings`` equal, arrays bit for bit and in the
    same dtype."""
    for fld in dataclasses.fields(FabricSweepParams):
        if fld.name == "wirings":
            continue
        x, y = getattr(a, fld.name), getattr(b, fld.name)
        if isinstance(x, dict):
            assert sorted(x) == sorted(y), fld.name
            for k in x:
                assert x[k].dtype == y[k].dtype, (fld.name, k)
                np.testing.assert_array_equal(x[k], y[k],
                                              err_msg=f"{fld.name}[{k}]")
        elif isinstance(x, np.ndarray):
            assert x.dtype == y.dtype, fld.name
            np.testing.assert_array_equal(x, y, err_msg=fld.name)
        elif fld.name in ("occ", "dest"):
            assert len(x) == len(y), fld.name
            for u, v in zip(x, y):
                np.testing.assert_array_equal(u, v, err_msg=fld.name)
        else:
            assert x == y, fld.name


# --------------------------------------------------------------------------- #
# The wiring shortcut packs what the per-point route check packed
# --------------------------------------------------------------------------- #
def test_distinct_pod_objects_pack_like_per_point_check(monkeypatch):
    scens = _pod_grid()
    assert len({id(s.topology) for s in scens}) == len(scens)
    routes = [[s.topology.route(f.src, f.dst, fid)
               for fid, f in enumerate(s.flows)] for s in scens]
    assert all(r == routes[0] for r in routes)
    assert {len(r) for r in routes[0]} == {3, 5, 7}
    fast = FabricSweepParams.from_scenarios(scens, sparse=True)
    slow = _per_point_pack(monkeypatch, scens, sparse=True)
    _assert_same_pack(fast, slow)
    assert fast.structure_key == POD_KEY
    assert not fast.pack_fail and fast.wirings == 1
    # with the shortcut off, every point (point 0 too) took the check
    assert slow.wirings == 1 + len(scens)
    # the link rates differ per point and reach the packed parameters
    assert len({tuple(r) for r in fast.pvals["gbps"]}) == len(scens)


def test_failure_window_path_packs_like_per_point_check(monkeypatch):
    scens = _pod_fail_grid()
    fast = FabricSweepParams.from_scenarios(scens, sparse=True)
    slow = _per_point_pack(monkeypatch, scens, sparse=True)
    _assert_same_pack(fast, slow)
    assert fast.pack_fail and fast.any_flap
    assert fast.pause_extra is not None
    assert fast.structure_key == POD_FAIL_KEY
    assert fast.wirings == 1
    assert (fast.pvals["fail_at"][2] < V.NEVER_TICK).any()
    assert (fast.pvals["fail_at"][[0, 1, 3]] == V.NEVER_TICK).all()


def test_chunk_under_envelope_packs_like_per_point_check(monkeypatch):
    scens = _pod_fail_grid()
    env = FabricSweepParams.from_scenarios(scens, sparse=True).envelope()
    fast = FabricSweepParams.from_scenarios(scens[:2], sparse=True,
                                            envelope=env)
    slow = _per_point_pack(monkeypatch, scens[:2], sparse=True,
                           envelope=env)
    _assert_same_pack(fast, slow)
    assert fast.structure_key == POD_FAIL_KEY


def test_dense_two_tier_grid_packs_like_per_point_check(monkeypatch):
    def point(i):
        topo = clos(2, 3, 2, host_gbps=100.0 + 50.0 * i,
                    uplink_gbps=400.0 - 100.0 * i)
        flows = [Flow(src=f"h1_{k}", dst="h0_0", burst_bytes=1e5)
                 for k in range(3)] + [Flow(src="h0_1", dst="h0_2")]
        fab = FabricConfig(sim_time_s=SIM_S,
                           receiver_cfg=_recv_factory("jet", False))
        return Scenario(name=f"c{i}", topology=topo, flows=flows,
                        fabric=fab)
    scens = [point(i) for i in range(3)]
    fast = FabricSweepParams.from_scenarios(scens)
    slow = _per_point_pack(monkeypatch, scens)
    _assert_same_pack(fast, slow)
    assert fast.wirings == 1


def test_shared_topology_object_is_one_wiring():
    topo = make_pod_clos(**POD)
    scens = [_pod_point(i, topo) for i in range(3)]
    assert FabricSweepParams.from_scenarios(scens, sparse=True).wirings == 1


def test_point_0_routes_once_per_flow(monkeypatch):
    """A grid wired alike derives point 0's routes and no others."""
    calls = []
    route = Topology.route

    def counted(self, *a):
        calls.append(self)
        return route(self, *a)
    monkeypatch.setattr(Topology, "route", counted)
    scens = _pod_grid()
    FabricSweepParams.from_scenarios(scens, sparse=True)
    assert len(calls) == len(scens[0].flows)
    assert all(t is scens[0].topology for t in calls)


# --------------------------------------------------------------------------- #
# Points wired differently
# --------------------------------------------------------------------------- #
def _drop_link(topo: Topology, a: str, b: str) -> None:
    del topo.links[(a, b)]
    del topo.links[(b, a)]


def test_removed_leaf_spine_link_raises_shared_routes():
    scens = _pod_grid()
    _drop_link(scens[1].topology, "p0l0", "p0s0")
    scens[1].topology.validate()          # still a legal fabric
    with pytest.raises(ValueError, match="grid points must share routes "
                                         r"\(same topology structure\)"):
        FabricSweepParams.from_scenarios(scens, sparse=True)


def test_removed_link_raises_on_dense_path_too():
    scens = [Scenario(name=f"c{i}", topology=clos(2, 2, 2),
                      flows=[Flow(src="h1_0", dst="h0_0"),
                             Flow(src="h1_1", dst="h0_0")],
                      fabric=FabricConfig(
                          sim_time_s=SIM_S,
                          receiver_cfg=_recv_factory("jet", False)))
             for i in range(2)]
    _drop_link(scens[1].topology, "leaf1", "spine1")
    with pytest.raises(ValueError, match="grid points must share routes"):
        FabricSweepParams.from_scenarios(scens)


def test_second_wiring_with_same_routes_counts_two(monkeypatch):
    """A link no route reads (host to host) is a second wiring: its
    routes are derived once, checked equal, and the pack is unchanged."""
    scens = _pod_grid()
    for s in scens[2:]:
        _bidi(s.topology.links, "p2h0_0", "p2h1_1", 100.0)
        s.topology.validate()
    fast = FabricSweepParams.from_scenarios(scens, sparse=True)
    assert fast.wirings == 2
    _assert_same_pack(fast, FabricSweepParams.from_scenarios(
        _pod_grid(), sparse=True))
    _assert_same_pack(fast, _per_point_pack(monkeypatch, scens,
                                            sparse=True))


def test_reordered_spines_is_another_wiring():
    """The spine list's order picks the ECMP path, so it is wiring."""
    scens = _pod_grid()
    scens[1].topology.spines.reverse()
    with pytest.raises(ValueError, match="grid points must share routes"):
        FabricSweepParams.from_scenarios(scens, sparse=True)


def test_dynamic_routing_grid_counts_no_wiring():
    scens = _pod_grid()
    for s in scens:
        s.topology = clos(2, 2, 2)
        s.flows = [Flow(src="h1_0", dst="h0_0"),
                   Flow(src="h1_1", dst="h0_0")]
    scens[1].topology.fail_link("leaf1", "spine0", at_us=100.0)
    fsp = FabricSweepParams.from_scenarios(scens)
    assert fsp.dyn_route and fsp.wirings == 0


# --------------------------------------------------------------------------- #
# The counter on the farm's records and on the benchmark's grids
# --------------------------------------------------------------------------- #
def test_farm_records_carry_wirings():
    scens = _pod_grid()
    out = run_farm(scens, workers=0, chunk_size=2, backend="numpy",
                   artifacts=False)
    m = out["manifest"]
    assert m["wirings"] == 1
    assert len(m["records"]) == 2
    assert all(r["wirings"] == 1 for r in m["records"])


def _bench_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _bench_cells())
def test_bench_grid_is_one_wiring(cell, monkeypatch):
    """Each cell's grid builds a fabric per point; all are wired alike,
    and the pack equals the per-point route check's."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import registry
    from bench import traffic as T
    b = registry.Bench()
    w = b.cell(cell)
    scens, _ = T.build_grid(b.config(w["config"]), b.traffic(w["traffic"]),
                            2**31 + 77, 0)
    assert len({id(s.topology) for s in scens}) == len(scens)
    sparse = _pick_sparse(scens, "auto")
    fast = FabricSweepParams.from_scenarios(scens[:4], sparse=sparse)
    assert fast.wirings == 1
    _assert_same_pack(fast, _per_point_pack(monkeypatch, scens[:4],
                                            sparse=sparse))
    assert FabricSweepParams.from_scenarios(scens,
                                            sparse=sparse).wirings == 1


# --------------------------------------------------------------------------- #
# Topology.validate: one case per error it raises
# --------------------------------------------------------------------------- #
def _two_tier() -> Topology:
    t = clos(2, 2, 2)
    return Topology(list(t.hosts), list(t.leaves), list(t.spines),
                    dict(t.links), dict(t.host_leaf))


def _pod() -> Topology:
    t = make_pod_clos(2, 2, 2)
    return Topology(list(t.hosts), list(t.leaves), list(t.spines),
                    dict(t.links), dict(t.host_leaf),
                    super_spines=list(t.super_spines))


def _dup_name(t):
    t.spines.append("leaf0")


def _unattached_host(t):
    t.host_leaf["h0_1"] = "leaf9"


def _one_way_access(t):
    del t.links[("leaf0", "h0_0")]


def _key_mismatch(t):
    t.links[("leaf0", "spine1")] = Link("leaf0", "spine0", 400.0)


def _zero_rate(t):
    t.links[("spine1", "leaf1")] = Link("spine1", "leaf1", 0.0)


def _no_reverse(t):
    del t.links[("spine0", "leaf1")]


def _idle_spine(t):
    t.spines.append("spine9")


def _idle_super_spine(t):
    t.super_spines.append("ss9")


def _super_spines_without_spines(t):
    # a super-spine with no spine tier is first caught as unwired
    t.spines.clear()


def _no_spines(t):
    t.spines.clear()


def _unknown_failure(t):
    t.link_down[("leaf0", "leaf1")] = (0.0, 10.0)


def _unknown_flap(t):
    t.link_flaps[("h0_0", "spine0")] = (0.0, 10.0, 5.0)


VALIDATE_CASES = [
    ("names", _two_tier, _dup_name, "node names must be unique"),
    ("host_leaf", _two_tier, _unattached_host,
     "host h0_1 not attached to a leaf"),
    ("access_link", _two_tier, _one_way_access,
     "host h0_0 missing bidirectional access link"),
    ("key_payload", _two_tier, _key_mismatch,
     "link key leaf0->spine1 mismatches payload"),
    ("rate", _two_tier, _zero_rate,
     "link spine1->leaf1 has non-positive rate"),
    ("reverse", _two_tier, _no_reverse,
     "link leaf1->spine0 has no reverse link"),
    ("spine_unwired", _two_tier, _idle_spine,
     "spine spine9 not connected to any leaf"),
    ("pod_spine_unwired", _pod, _idle_spine,
     "spine spine9 not connected to any leaf"),
    ("super_spine_unwired", _pod, _idle_super_spine,
     "super-spine ss9 not connected to any spine"),
    ("super_spines_without_spines", _pod, _super_spines_without_spines,
     "super-spine ss0 not connected to any spine"),
    ("multi_leaf_without_spines", _two_tier, _no_spines,
     "multi-leaf topology requires spines"),
    ("unknown_failure", _two_tier, _unknown_failure,
     "failure scheduled on unknown link leaf0->leaf1"),
    ("unknown_flap", _pod, _unknown_flap,
     "flap scheduled on unknown link h0_0->spine0"),
]


@pytest.mark.parametrize("make,breaks,msg",
                         [c[1:] for c in VALIDATE_CASES],
                         ids=[c[0] for c in VALIDATE_CASES])
def test_validate_raises(make, breaks, msg):
    t = make()
    t.validate()
    breaks(t)
    with pytest.raises(ValueError, match="^" + re.escape(msg) + "$"):
        t.validate()


@pytest.mark.parametrize("build", [
    lambda: clos(1, 3, 0), lambda: clos(3, 2, 4),
    lambda: make_pod_clos(1, 2, 2), lambda: make_pod_clos(**POD)],
    ids=["one_leaf", "two_tier", "one_pod", "three_pod"])
def test_validate_accepts_presets_with_schedules(build):
    t = build()
    key = next(iter(t.links))
    t.fail_link(*key, at_us=10.0, restore_us=20.0)
    t.flap_link(*key, start_us=0.0, period_us=10.0, down_us=3.0)
    t.validate()


def test_validate_accepts_partial_wiring():
    t = make_pod_clos(2, 2, 2)
    _drop_link(t, "p0l0", "p0s0")
    t.validate()

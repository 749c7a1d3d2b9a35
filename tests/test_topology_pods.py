"""Pod-scale Clos fabrics (ISSUE 9): 3-level topology construction,
three-engine equivalence on the sparse-incidence vector engine, and
the edge-case regressions that rode along:

* partially-wired fabrics: wiring-restricted candidate sets and a clear
  ``ValueError`` on unroutable pairs (instead of a ``KeyError`` on a
  nonexistent link);
* zero-uptime links leave the ``pause_storm`` / ``uplink_imbalance``
  denominators in both the scalar driver and the vector mirror;
* histogram-domain overflow is explicit (``overflow_count``, widened
  error bound, percentile-as-lower-bound) instead of a silent midpoint
  below the true latency.

Equivalence contract (same as the 2-tier suite): the float64 numpy
backend reproduces scalar ``run_fabric`` essentially exactly (<1e-9),
the float32 jax backend tracks numpy to <=5e-4 — including a scheduled
failure + flap under per-TC PFC, where the sparse engine's packed
fail/flap windows must agree with the scalar tick loop.
"""
import math

import numpy as np
import pytest

from repro.core.datapath import QoS
from repro.core.simulator import testbed_100g
from repro.fabric import scenarios as SC
from repro.fabric.fabric import FabricConfig, Flow
from repro.fabric.messages import (HIST_MAX_US, LogHistogram,
                                   MessageConfig, MessageTracker,
                                   percentile_from_counts)
from repro.fabric.routing import RoutingConfig
from repro.fabric.scenarios import Scenario, _recv_factory
from repro.fabric.switch import SwitchConfig
from repro.fabric.topology import Topology, _bidi, make_pod_clos
from repro.fabric.vector import run_fabric_sweep

SIM_S = 0.002

# outputs every engine must agree on
KEYS = ("flow_goodput_gbps", "flow_completion_us",
        "incast_completion_us", "victim_goodput_gbps", "pause_fanout",
        "ecn_marked_bytes", "switch_dropped_bytes")


def _maxrel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert (np.isfinite(a) == np.isfinite(b)).all(), \
        "finite/inf pattern mismatch"
    m = np.isfinite(a) & np.isfinite(b)
    if not m.any():
        return 0.0
    return float(np.max(np.abs(a[m] - b[m])
                        / np.maximum(np.abs(b[m]), 1e-9)))


def _scalar_ref(scens):
    res = [sc.run() for sc in scens]
    F = len(scens[0].flows)
    return res, {
        "flow_goodput_gbps": np.array(
            [[r.flow_goodput_gbps[f] for f in range(F)] for r in res]),
        "flow_completion_us": np.array(
            [[r.flow_completion_us[f] for f in range(F)] for r in res]),
        "incast_completion_us": np.array(
            [r.incast_completion_us for r in res]),
        "victim_goodput_gbps": np.array(
            [r.victim_goodput_gbps for r in res]),
        "pause_fanout": np.array([r.pause_fanout for r in res]),
        "ecn_marked_bytes": np.array([r.ecn_marked_bytes for r in res]),
        "switch_dropped_bytes": np.array(
            [r.switch_dropped_bytes for r in res]),
    }


# --------------------------------------------------------------------------- #
# 3-level topology construction
# --------------------------------------------------------------------------- #
class TestMakePodClos:
    def test_tiers_naming_and_speeds(self):
        t = make_pod_clos(2, 2, 2)
        t.validate()
        assert len(t.hosts) == 8
        assert t.leaves == ["p0l0", "p0l1", "p1l0", "p1l1"]
        assert t.spines == ["p0s0", "p0s1", "p1s0", "p1s1"]
        assert t.super_spines == ["ss0", "ss1"]
        assert t.host_leaf["p1h0_1"] == "p1l0"
        # per-tier link speeds (and their reverse directions)
        assert t.link("p0h0_0", "p0l0").gbps == 100.0
        assert t.link("p0s0", "p0l0").gbps == 200.0
        assert t.link("p0s0", "ss0").gbps == 400.0
        assert t.link("ss0", "p1s0").gbps == 400.0

    def test_single_pod_degenerates_to_two_tier(self):
        t = make_pod_clos(1, 2, 2)
        t.validate()
        assert t.super_spines == []
        # intra-pod cross-leaf route stays 3-hop interior (5 nodes)
        assert len(t.route("p0h0_0", "p0h1_0", 0)) == 5

    def test_cross_pod_routes_are_plane_aligned(self):
        t = make_pod_clos(2, 2, 2)
        r = t.route("p0h0_0", "p1h1_0", 3)
        assert len(r) == 7 and r[3] in t.super_spines
        for sl, sa, ss, sb, dl in t.candidate_paths("p0h0_0", "p1h1_0"):
            # choosing the source pod's spine chooses the plane
            assert sa[-1] == ss[-1] == sb[-1]

    def test_per_tier_oversubscription(self):
        t = make_pod_clos(2, 2, 4, host_gbps=100.0,
                          leaf_spine_gbps=200.0, spine_sspine_gbps=400.0)
        assert t.oversubscription("p0l0") == pytest.approx(1.0)
        assert t.spine_oversubscription("p0s0") == pytest.approx(1.0)

    def test_invalid_dims_raise(self):
        with pytest.raises(ValueError, match="pod-Clos"):
            make_pod_clos(0, 2, 2)
        with pytest.raises(ValueError, match="pod-Clos"):
            make_pod_clos(2, 2, 0)

    def test_fail_and_flap_any_tier(self):
        t = make_pod_clos(2, 2, 2)
        t.fail_link("p0h0_0", "p0l0", at_us=10.0, restore_us=20.0)
        t.fail_link("p0l0", "p0s0", at_us=10.0, restore_us=20.0)
        t.fail_link("p0s0", "ss0", at_us=10.0, restore_us=20.0)
        t.flap_link("p1s1", "ss1", start_us=0.0, period_us=10.0,
                    down_us=4.0)
        t.validate()
        assert not t.link_up_at(("p0s0", "ss0"), 15.0)
        assert not t.link_up_at(("ss0", "p0s0"), 15.0)   # bidi
        assert t.link_up_at(("p0s0", "ss0"), 25.0)
        assert not t.link_up_at(("ss1", "p1s1"), 12.0)   # flap down-phase
        with pytest.raises(ValueError, match="no link"):
            t.fail_link("p0l0", "ss0", at_us=1.0)        # not a wired pair


# --------------------------------------------------------------------------- #
# Satellite: partially-wired fabrics (wiring-restricted candidates)
# --------------------------------------------------------------------------- #
class TestPartialWiring:
    def _partial(self, rescue_spine: bool):
        """2 leaves whose local spines do not interconnect them; with
        ``rescue_spine`` a third spine wires to both."""
        links = {}
        _bidi(links, "a0", "l0", 100.0)
        _bidi(links, "b0", "l1", 100.0)
        _bidi(links, "l0", "s0", 200.0)
        _bidi(links, "l1", "s1", 200.0)
        spines = ["s0", "s1"]
        if rescue_spine:
            _bidi(links, "l0", "s2", 200.0)
            _bidi(links, "l1", "s2", 200.0)
            spines.append("s2")
        t = Topology(hosts=["a0", "b0"], leaves=["l0", "l1"],
                     spines=spines, links=links,
                     host_leaf={"a0": "l0", "b0": "l1"})
        t.validate()
        return t

    def test_candidate_spines_restricted_to_wired(self):
        assert self._partial(False).candidate_spines("a0", "b0") == []
        assert self._partial(True).candidate_spines("a0", "b0") == ["s2"]

    def test_route_never_picks_unwired_spine(self):
        t = self._partial(True)
        # every flow id must hash onto the one wired candidate, never
        # KeyError on a nonexistent (leaf, spine) link
        for fid in range(8):
            assert t.route("a0", "b0", fid) == ["a0", "l0", "s2", "l1",
                                                "b0"]

    def test_unroutable_pair_raises_clear_error(self):
        t = self._partial(False)
        with pytest.raises(ValueError, match="unroutable"):
            t.route("a0", "b0", 0)
        with pytest.raises(ValueError, match="unroutable"):
            t.candidate_paths("a0", "b0")


# --------------------------------------------------------------------------- #
# Satellite: zero-uptime links leave the storm/imbalance denominators
# --------------------------------------------------------------------------- #
def _storm(**kw):
    return SC.pod_pfc_storm(pods=2, leaves_per_pod=2, hosts_per_leaf=2,
                            buffer_kb=32.0, sim_time_s=SIM_S, **kw)


class TestZeroUptimeExclusion:
    def test_scalar_dead_link_excluded(self):
        base = _storm().run()
        sc = _storm()
        sc.topology.fail_link("p1l1", "p1s1", at_us=0.0)
        dead = sc.run()
        assert ("p1l1", "p1s1") in dead.dead_links
        assert dead.n_pausable_links < base.n_pausable_links
        # a *late* failure keeps some uptime: not excluded
        sc2 = _storm()
        sc2.topology.fail_link("p1l1", "p1s1",
                               at_us=SIM_S * 1e6 / 2.0)
        assert sc2.run().n_pausable_links == base.n_pausable_links

    def test_vector_mirror_matches_scalar(self):
        sc = _storm()
        sc.topology.fail_link("p1l1", "p1s1", at_us=0.0)
        r = sc.run()
        out = run_fabric_sweep([sc], backend="numpy")
        assert int(out["n_pausable_links"][0]) == r.n_pausable_links
        assert float(out["pause_storm"][0]) == \
            pytest.approx(r.pause_storm(), rel=1e-9)


# --------------------------------------------------------------------------- #
# Satellite: explicit histogram-domain overflow
# --------------------------------------------------------------------------- #
class TestHistogramOverflow:
    def test_loghistogram_overflow_is_explicit(self):
        h = LogHistogram()
        for _ in range(9):
            h.add(10.0)
        h.add(HIST_MAX_US * 4.0)
        assert h.n == 10 and h.overflow_count == 1
        assert math.isinf(h.rel_error_bound())
        # the overflowed rank reports the domain ceiling (a lower
        # bound), not an in-range midpoint below the true latency
        assert h.percentile(99.0) == h.hi
        assert h.percentile(50.0) < h.hi        # in-range ranks intact

    def test_no_overflow_keeps_finite_bound(self):
        h = LogHistogram()
        h.add(10.0)
        assert h.overflow_count == 0
        assert math.isfinite(h.rel_error_bound())

    def test_percentile_from_counts_overflow(self):
        counts = np.zeros((2, 16))
        counts[:, 3] = 10.0
        ov = np.array([0.0, 90.0])
        p99 = percentile_from_counts(counts, 99.0, overflow=ov)
        assert p99[0] < HIST_MAX_US          # pure in-range: midpoint
        assert p99[1] == HIST_MAX_US         # rank lands in overflow
        # a rank inside the in-range mass is unaffected by overflow
        p5 = percentile_from_counts(counts, 5.0, overflow=ov)
        assert p5[0] == p5[1] < HIST_MAX_US

    def test_tracker_counts_overflow_exact_percentile_intact(self):
        tr = MessageTracker(MessageConfig(msg_bytes=1000.0, window=None))
        tr.observe(1.0, injected=1000.0, delivered=0.0, start_us=0.0)
        tr.observe(HIST_MAX_US * 2.0, injected=1000.0, delivered=1000.0)
        assert tr.done == 1 and tr.overflow_count == 1
        assert tr.percentile(50.0) > HIST_MAX_US


# --------------------------------------------------------------------------- #
# Three-engine equivalence on pod fabrics (sparse-incidence engine)
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def pod_grid():
    scens, _ = SC.pod_incast_grid(pods=2, leaves_per_pod=2,
                                  hosts_per_leaf=2, burst_mb=0.2,
                                  sim_time_s=SIM_S)
    _, ref = _scalar_ref(scens)
    return scens, ref


def _fail_tc_scenario(flap: bool = False):
    """Cross-pod incast + victim + low-priority flow under per-TC PFC,
    with a mid-window leaf-uplink failure (and optionally a flap on a
    second uplink) — the case where the sparse engine's packed failure
    windows must reproduce the scalar tick loop."""
    topo = make_pod_clos(2, 2, 2)
    topo.fail_link("p0l0", "p0s0", at_us=300.0, restore_us=1200.0)
    if flap:
        topo.flap_link("p1l0", "p1s0", start_us=200.0, period_us=400.0,
                       down_us=150.0)
    flows = [Flow(src=f"p1h{li}_{hi}", dst="p0h0_0", burst_bytes=2e5,
                  qos=QoS.NORMAL, tag="incast")
             for li in range(2) for hi in range(2)]
    flows.append(Flow(src="p0h1_0", dst="p0h0_1", tag="victim"))
    flows.append(Flow(src="p1h0_1", dst="p0h1_1", qos=QoS.LOW))
    fab = FabricConfig(
        sim_time_s=SIM_S,
        switch=SwitchConfig(pfc_enabled=True, per_tc=True),
        receiver_cfg=_recv_factory("ddio", True))
    return Scenario(name="pod_fail_tc" + ("_flap" if flap else ""),
                    topology=topo, flows=flows, fabric=fab)


class TestPodEquivalence:
    def test_numpy_matches_scalar(self, pod_grid):
        scens, ref = pod_grid
        out = run_fabric_sweep(scens, backend="numpy")
        for k in KEYS:
            assert _maxrel(out[k], ref[k]) < 1e-9, k

    def test_jax_matches_numpy(self, pod_grid):
        scens, _ = pod_grid
        ref = run_fabric_sweep(scens, backend="numpy")
        out = run_fabric_sweep(scens, backend="jax")
        for k in KEYS:
            assert _maxrel(out[k], ref[k]) <= 5e-4, k

    @pytest.mark.parametrize("flap", [False, True])
    def test_failure_per_tc_pfc(self, flap):
        sc = _fail_tc_scenario(flap)
        _, ref = _scalar_ref([sc])
        out = run_fabric_sweep([sc], backend="numpy")
        for k in KEYS:
            assert _maxrel(out[k], ref[k]) < 1e-9, k
        jx = run_fabric_sweep([sc], backend="jax")
        for k in KEYS:
            assert _maxrel(jx[k], out[k]) <= 5e-4, k

    def test_pod_shuffle_crosses_super_spine(self):
        sc = SC.pod_shuffle(pods=2, leaves_per_pod=2, hosts_per_leaf=2,
                            shuffle_mb=0.2, sim_time_s=SIM_S)
        _, ref = _scalar_ref([sc])
        out = run_fabric_sweep([sc], backend="numpy")
        for k in KEYS:
            assert _maxrel(out[k], ref[k]) < 1e-9, k
        # traffic actually transits the super-spine tier
        assert float(out["uplink_util_max"][0]) > 0.0


def _incast_modes():
    scens, _ = SC.fabric_grid(
        lambda mode: SC.incast(n_senders=4, mode=mode, burst_mb=0.2,
                               sim_time_s=SIM_S),
        mode=["ddio", "jet"])
    return scens


def _receive_branches():
    """Two 2-tier points through every branch of the receive stage both
    engines share: a Jet receiver whose small pool spills LOW-class bytes
    and escapes by replace, then copy, then ECN, under per-TC host PFC;
    and a DDIO receiver with cache misses under whole-link host PFC.
    The switches run per-TC PFC with buffers small enough to pause."""
    def point(kind):
        sc = SC.incast(n_senders=4, mode="jet", pfc=True, burst_mb=0.5,
                       sim_time_s=SIM_S)
        for i, fl in enumerate(sc.flows):
            fl.qos = (QoS.LOW, QoS.NORMAL, QoS.HIGH)[i % 3]

        def recv(host):
            if kind == "jet":
                return testbed_100g("jet", pfc_enabled=True,
                                    host_pfc_per_tc=True,
                                    jet_pool_bytes=256 << 10,
                                    straggler_frac=0.3,
                                    mem_esc_bytes=64 << 10)
            return testbed_100g("ddio", pfc_enabled=True,
                                cpu_membw_gbps=1990.0)
        sc.fabric.receiver_cfg = recv
        sc.fabric.switch = SwitchConfig(pfc_enabled=True, per_tc=True,
                                        port_buffer_bytes=128 << 10)
        return sc
    return [point("jet"), point("ddio")]


class TestSparseEngineContract:
    @pytest.mark.parametrize("grid, reached", [
        (_incast_modes, ()),
        (_receive_branches, ("replaces", "copies", "ecns", "mem_fb",
                             "miss_sum", "pfc_us", "pause_us")),
    ], ids=["incast", "receive_branches"])
    def test_two_tier_sparse_matches_dense_exactly(self, grid, reached,
                                                   monkeypatch):
        from repro.fabric import vector as V
        carry = {}
        results = V._results

        def keep(s, fsp):
            carry["sparse" if fsp.sparse else "dense"] = s
            return results(s, fsp)
        monkeypatch.setattr(V, "_results", keep)
        scens = grid()
        dense = run_fabric_sweep(scens, backend="numpy",
                                 incidence="dense")
        sparse = run_fabric_sweep(scens, backend="numpy",
                                  incidence="sparse")
        both = set(dense) & set(sparse)
        assert set(KEYS) <= both
        for k in sorted(both):
            np.testing.assert_array_equal(dense[k], sparse[k], err_msg=k)
        # the grid reaches the branches it is meant to, in both engines
        for k in reached:
            assert np.asarray(carry["dense"][k]).max() > 0, k
            np.testing.assert_array_equal(carry["dense"][k],
                                          carry["sparse"][k], err_msg=k)

    def test_dense_rejects_super_spine_topology(self):
        sc = SC.pod_incast(pods=2, leaves_per_pod=2, hosts_per_leaf=2,
                           sim_time_s=SIM_S)
        with pytest.raises(ValueError, match="sparse"):
            run_fabric_sweep([sc], backend="numpy", incidence="dense")

    def test_sparse_rejects_dynamic_features(self):
        sc = SC.incast(n_senders=2, sim_time_s=SIM_S)
        sc.fabric.routing = RoutingConfig(mode="adaptive")
        with pytest.raises(ValueError, match="static_ecmp"):
            run_fabric_sweep([sc], backend="numpy", incidence="sparse")
        sc2 = SC.incast(n_senders=2, sim_time_s=SIM_S)
        sc2.fabric.msg = MessageConfig()
        with pytest.raises(ValueError, match="message layer"):
            run_fabric_sweep([sc2], backend="numpy",
                             incidence="sparse")

    def test_sparse_rejects_adaptive_dt(self):
        sc = SC.pod_incast(pods=2, leaves_per_pod=2, hosts_per_leaf=2,
                           sim_time_s=SIM_S)
        with pytest.raises(ValueError, match="dense-engine only"):
            run_fabric_sweep([sc], backend="jax", adaptive_dt=True)

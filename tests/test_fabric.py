"""Fabric subsystem: topology invariants, switch ECN/PFC mechanics,
single-host equivalence with run_sim, vectorized-sweep agreement, and the
fleet-level incast/HoL phenomenology the fabric exists to reproduce."""
import math
import os

import numpy as np
import pytest

from repro.core import simulator as S
from repro.fabric import (FabricConfig, Flow, SwitchConfig, run_fabric,
                          scenarios, topology)
from repro.fabric.switch import OutputPort
from repro.fabric.sweep import grid_configs, run_sweep


# --------------------------------------------------------------------------- #
# topology
# --------------------------------------------------------------------------- #
def test_clos_invariants():
    topo = topology.clos(n_leaves=3, hosts_per_leaf=4, n_spines=2,
                         host_gbps=100.0, uplink_gbps=400.0)
    topo.validate()
    assert len(topo.hosts) == 12
    assert topo.bisection_gbps() == 3 * 2 * 400.0
    # 4x100 host-facing vs 2x400 spine-facing per leaf
    assert topo.oversubscription("leaf0") == pytest.approx(0.5)
    # every link has a reverse twin with equal capacity
    for (a, b), l in topo.links.items():
        assert topo.links[(b, a)].gbps == l.gbps


def test_routes_and_ecmp():
    topo = topology.clos(n_leaves=2, hosts_per_leaf=2, n_spines=2)
    # intra-leaf short-circuits the spine tier
    assert topo.route("h0_0", "h0_1", 0) == ["h0_0", "leaf0", "h0_1"]
    # cross-leaf transits exactly one spine; ECMP spreads by flow id
    r0 = topo.route("h0_0", "h1_0", 0)
    r1 = topo.route("h0_0", "h1_0", 1)
    assert len(r0) == 5 and r0[2] == "spine0" and r1[2] == "spine1"
    links = topo.route_links("h0_0", "h1_0", 0)
    assert [l.src for l in links] == ["h0_0", "leaf0", "spine0", "leaf1"]
    with pytest.raises(ValueError):
        topo.route("h0_0", "h0_0", 0)


def test_validate_catches_broken_topologies():
    topo = topology.clos(2, 2, 1)
    bad = topology.Topology(topo.hosts, topo.leaves, topo.spines,
                            dict(topo.links), dict(topo.host_leaf))
    del bad.links[("leaf0", "h0_0")]          # one-way access link
    with pytest.raises(ValueError):
        bad.validate()
    bad2 = topology.Topology(topo.hosts, topo.leaves, [], topo.links,
                             topo.host_leaf)
    with pytest.raises(ValueError):
        bad2.validate()                        # multi-leaf needs spines


# --------------------------------------------------------------------------- #
# switch mechanics
# --------------------------------------------------------------------------- #
def _port(**kw):
    cfg = SwitchConfig(port_buffer_bytes=1 << 20, **kw)
    return OutputPort(topology.Link("a", "b", 80.0), cfg)


def test_port_ecn_marks_past_knee():
    p = _port(ecn_kmin_frac=0.25)
    p.enqueue(0, 200 << 10, 0.0, None)          # queue was 0: unmarked
    assert p.marked_bytes == 0
    p.enqueue(0, 100 << 10, 0.0, None)          # queue 200 KB, still < knee
    assert p.marked_bytes == 0
    p.enqueue(0, 200 << 10, 0.0, None)          # queue 300 KB > 256 KB knee
    assert p.marked_bytes == pytest.approx(200 << 10)
    # drained bytes carry their marks out proportionally
    out = p.drain(10.0)                          # 80 Gbps * 10 us = 100 KB
    (fid, b, m) = out[0]
    assert fid == 0 and b == pytest.approx(1e5)
    assert 0.0 < m < b


def test_port_tail_drop_and_conservation():
    p = _port()
    lost = p.enqueue(0, 3 << 20, 0.0, None)      # 3 MB into a 1 MB buffer
    assert lost == pytest.approx(2 << 20)
    assert p.queued_bytes == pytest.approx(1 << 20)
    total_out = 0.0
    for _ in range(200):
        total_out += sum(b for _, b, _m in p.drain(10.0))
    assert total_out == pytest.approx(1 << 20)
    assert p.queued_bytes == pytest.approx(0.0, abs=1e-6)


def test_port_pfc_hysteresis():
    p = _port(pfc_enabled=True, pfc_xoff_frac=0.5, pfc_xon_frac=0.25)
    p.enqueue(7, 600 << 10, 0.0, ("x", "a"), tc=1)
    p.update_pfc()
    # pause is per (ingress link, traffic class): only TC 1 is targeted
    assert p.pause_asserted and p.pause_targets() == {(("x", "a"), 1)}
    assert p.tc_asserted == [False, True, False]
    # draining below xon releases the pause
    while p.queued_bytes > 0.25 * (1 << 20):
        p.drain(10.0)
    p.update_pfc()
    assert not p.pause_asserted and p.pause_targets() == set()


# --------------------------------------------------------------------------- #
# single-host fabric == run_sim (the acceptance anchor)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["ddio", "jet"])
def test_single_pair_matches_run_sim(mode):
    ref = S.run_sim(S.testbed_100g(mode, sim_time_s=0.005))
    r = scenarios.single_pair(mode, sim_time_s=0.005).run()
    got = r.per_host["h0_1"]
    assert got.goodput_gbps == pytest.approx(ref.goodput_gbps, rel=0.05)
    # the refactor keeps them numerically identical, not merely close
    assert got.goodput_gbps == pytest.approx(ref.goodput_gbps, rel=1e-9)
    assert got.cnp_count == ref.cnp_count
    assert got.ddio_miss_rate == pytest.approx(ref.ddio_miss_rate)


# --------------------------------------------------------------------------- #
# vectorized sweep vs numpy reference vs run_sim
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def sweep_grid():
    cfgs, pts = grid_configs(
        S.testbed_100g, mode="ddio", sim_time_s=0.004,
        msg_bytes=[64 << 10, 256 << 10, 1 << 20],
        cpu_membw_gbps=[1200.0, 1500.0, 1760.0],
        ddio_bytes=[4 << 20, 6 << 20],
        num_qps=[16, 32])
    assert len(cfgs) >= 32                      # acceptance: >=32-point grid
    return cfgs


def test_sweep_vectorized_matches_numpy(sweep_grid):
    ref = run_sweep(sweep_grid, backend="numpy")
    got = run_sweep(sweep_grid, backend="jax")
    for key in ("goodput_gbps", "cnp_count", "ddio_miss_rate",
                "pfc_pause_us", "dropped_bytes"):
        a, b = got[key], ref[key]
        assert np.all(np.abs(a - b) <= 0.01 * np.abs(b) + 1e-6), key


def test_sweep_numpy_matches_run_sim(sweep_grid):
    sample = sweep_grid[::8]
    seq = np.array([S.run_sim(c).goodput_gbps for c in sample])
    ref = run_sweep(list(sample), backend="numpy")["goodput_gbps"]
    assert np.all(np.abs(ref - seq) <= 0.01 * seq + 1e-6)


def test_sweep_jet_escape_ladder():
    cfgs, _ = grid_configs(
        S.testbed_100g, mode="jet", sim_time_s=0.004,
        jet_pool_bytes=[2 << 20, 12 << 20],
        straggler_frac=[0.005, 0.3])
    out_np = run_sweep(cfgs, backend="numpy")
    out_jx = run_sweep(cfgs, backend="jax")
    # the tight-pool/heavy-straggler corner must engage the ladder...
    assert out_np["escape_replaces"].max() > 0
    # ...identically in both backends
    for key in ("escape_replaces", "escape_copies", "escape_ecn"):
        np.testing.assert_allclose(out_jx[key], out_np[key])


def test_sweep_rejects_mixed_timebases():
    cfgs = [S.testbed_100g("jet", sim_time_s=0.004),
            S.testbed_100g("jet", sim_time_s=0.008)]
    with pytest.raises(ValueError):
        run_sweep(cfgs)


def test_sweep_unroll_is_a_pure_perf_knob(sweep_grid):
    """The scan unroll factor (1 by default, see fabric._scan)
    must never change results — same program, different loop shape."""
    sample = list(sweep_grid[::12])
    a = run_sweep(sample, backend="jax")          # unroll="auto"
    b = run_sweep(sample, backend="jax", unroll=4)
    for key in ("goodput_gbps", "cnp_count", "dropped_bytes"):
        np.testing.assert_allclose(a[key], b[key], rtol=1e-6)


def test_persistent_cache_dir(monkeypatch, tmp_path):
    """The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else to the fixed ``<repo>/.jax_cache``; the unroll is the explicit
    argument or 1."""
    import jax
    from repro.fabric import _scan
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert _scan.configure_persistent_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert _scan.configure_persistent_cache() == _scan.REPO_CACHE_DIR
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert _scan.REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old[1])
    assert _scan.pick_unroll("auto") == 1
    assert _scan.pick_unroll(4) == 4


# --------------------------------------------------------------------------- #
# incast / PFC phenomenology
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def incast_pair():
    lossy = scenarios.incast(n_senders=8, mode="ddio", pfc=False,
                             burst_mb=1.0, sim_time_s=0.015).run()
    pfc = scenarios.incast(n_senders=8, mode="ddio", pfc=True,
                           burst_mb=1.0, sim_time_s=0.015).run()
    return lossy, pfc


def test_incast_completion_grows_with_fanin():
    fct = []
    for n in (2, 8):
        r = scenarios.incast(n_senders=n, mode="ddio", pfc=False,
                             burst_mb=1.0, with_victim=False,
                             sim_time_s=0.02).run()
        assert math.isfinite(r.incast_completion_us), n
        fct.append(r.incast_completion_us)
    assert fct[1] > 1.5 * fct[0]


def test_pfc_is_lossless_but_spreads_pauses(incast_pair):
    lossy, pfc = incast_pair
    # lossy fabric drops at the congested leaf port, PFC does not
    assert lossy.switch_dropped_bytes > 0
    assert pfc.switch_dropped_bytes == 0
    assert lossy.pause_fanout == 0
    # pause frames propagate beyond the congested downlink
    assert pfc.pause_fanout >= 2
    assert sum(pfc.pause_link_us.values()) > 0


def test_pfc_head_of_line_blocks_victim(incast_pair):
    lossy, pfc = incast_pair
    # the victim shares only the source leaf with the incast, yet PFC
    # pauses collapse its goodput; the lossy fabric leaves it unharmed
    assert pfc.victim_goodput_gbps < 0.5 * lossy.victim_goodput_gbps
    assert lossy.victim_goodput_gbps > 20.0


def test_incast_receiver_results_per_host():
    r = scenarios.incast(n_senders=4, mode="jet", burst_mb=0.5,
                         sim_time_s=0.01).run()
    assert set(r.per_host) == {"h1_0", "h1_1"}
    assert r.per_host["h1_0"].goodput_gbps > 0
    # every incast flow completed and is accounted
    for fid, tag in r.flow_tags.items():
        if tag == "incast":
            assert math.isfinite(r.flow_completion_us[fid])
            assert r.flow_delivered_bytes[fid] >= 0.5e6 - 1e3

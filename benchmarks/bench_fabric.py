"""Fabric benchmarks: Clos incast/HoL behaviour + both vectorized engines.

Four parts:

1. **Incast scaling** — N storage senders burst into one Jet/DDIO receiver
   across a 2-leaf Clos; reports incast completion time, victim-flow
   goodput and (with PFC) pause fan-out — the fleet-level pathologies a
   single-receiver simulator cannot show.
2. **Equivalence anchor** — a 1-sender/1-receiver fabric must reproduce
   ``run_sim(testbed_100g(...))`` goodput (acceptance: within 5%; actual:
   exact, the fabric is cut-through at 1 tick).
3. **Datapath sweep engine** — a >=32-point receiver-knob grid advanced by
   the jax vmap+scan engine vs the batched-numpy reference vs sequential
   ``run_sim``; also times the scan ``unroll`` over {1, 4, 8} (cold
   compile + warm run recorded for each; the winner is printed, and
   nothing is written — the engines run at unroll 1 unless a caller
   passes another) and records before (the old hard-coded ``unroll=8``)
   vs after (fastest unroll + donated carry) compile and run times.
4. **Fabric sweep engine** — a >=32-point *fabric* grid (mode x PFC x
   burst over the incast-8 scenario) advanced by
   ``repro.fabric.vector.run_fabric_sweep`` vs the scalar ``run_fabric``
   loop vs the batched-numpy reference; acceptance: <=1e-3 max relative
   deviation on per-flow goodput / incast completion and >=5x warm
   speedup over the scalar loop.
5. **Routing grid** — the dynamic-routing program: routing mode x
   link-failure schedule over ``link_failure_incast`` as ONE vector
   program (per-tick ``[G, F]`` route state, failure masks, spray
   settling); records warm speedup vs the scalar loop and the
   numpy-vs-scalar deviation, so the regression gate covers the
   per-tick routing state too.
6. **Message grid** — the op-layer program: msg-size x window x CC
   (DCQCN / Timely / HPCC) over the 8-to-1 verbs incast as ONE vector
   program carrying per-flow completion rings + log-bucket latency
   histograms; records warm speedup vs the scalar loop, the exactness
   of the numpy engine's message bookkeeping (counts / completion
   times vs the scalar tracker) and the histogram-p99 error vs the
   scalar exact percentile, gating the documented ~4.6% bound.
7. **Scale (pod) grid** — the sparse-incidence program: the same
   3-level cross-pod incast grid at 64 and 256 hosts, each advanced as
   ONE jax program; XLA's compiled cost analysis gives per-tick flops
   at both sizes and the growth exponent
   ``log(cost ratio) / log(host ratio)`` documents the ~linear
   (sub-quadratic) scaling in fabric size that the dense ``[P, F]``
   incidence cannot offer (its one-hot products grow with
   flows x ports, i.e. quadratically in hosts).
8. **Faults grid** — the robustness program: loss-rate x recovery-mode
   over the lossy 8-to-1 verbs incast as ONE vector program carrying
   the per-flow RTO/retransmit ledgers, plus a receiver crash--restart
   point; records warm speedup vs the scalar loop and gates the fault
   accounting (counter-based hashing makes the loss realization
   engine-identical: retransmit/dropped bytes agree to f64 round-off,
   message counts exactly, and the zero-loss selective point drops
   exactly zero packets — only real wire loss or go-back-N duplicate
   discards may feed ``dropped_pkts``).

9. **Farm** — the chunked sweep farm (``repro.fabric.farm``) vs the
   monolithic single-program run on the 64-point incast grid: gates
   exact result equality (chunk padding + structure envelope must not
   perturb any real point), zero program-cache recompiles after
   warmup, and the multiprocess warm speedup on multi-core hosts.

Everything is also written machine-readable to
``experiments/bench/BENCH_fabric.json`` so the perf trajectory is
tracked across PRs.  ``--quick`` shrinks sim time and grids for CI.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
from typing import Dict, List

import numpy as np

from repro.core import simulator as S
from repro.fabric import scenarios as SC
from repro.fabric import vector as V
from repro.fabric._scan import configure_persistent_cache
from repro.fabric.fused import AdaptiveConfig, program_op_stats
from repro.fabric.scenarios import fabric_grid
from repro.fabric.sweep import grid_configs, run_sweep
from repro.fabric.vector import run_fabric_sweep

from .common import OUT_DIR, emit

NAME = "fabric"
PAPER_REF = "§2.1/§6 testbed at fleet scale"
JSON_PATH = os.path.join(OUT_DIR, "BENCH_fabric.json")

QUICK = False

UNROLL_CANDIDATES = (1, 4, 8)


def _sim_time(full: float) -> float:
    return 0.004 if QUICK else full


def run_incast() -> List[Dict]:
    rows: List[Dict] = []
    for mode in ("ddio", "jet"):
        for n in (2, 4, 8):
            for pfc in (False, True):
                sc = SC.incast(n_senders=n, mode=mode, pfc=pfc,
                               burst_mb=1.0, sim_time_s=_sim_time(0.02))
                r = sc.run()
                rx = r.per_host["h1_0"]
                rows.append({
                    "scenario": sc.name,
                    "mode": mode, "senders": n, "pfc": int(pfc),
                    "incast_fct_us": r.incast_completion_us,
                    "victim_gbps": r.victim_goodput_gbps,
                    "recv_gbps": rx.goodput_gbps,
                    "pause_fanout": r.pause_fanout,
                    "ecn_mb": r.ecn_marked_bytes / 1e6,
                    "dropped_mb": r.switch_dropped_bytes / 1e6,
                })
    return rows


def run_equivalence() -> List[Dict]:
    rows: List[Dict] = []
    for mode in ("ddio", "jet"):
        ref = S.run_sim(S.testbed_100g(mode, sim_time_s=_sim_time(0.01)))
        got = SC.single_pair(mode, sim_time_s=_sim_time(0.01)).run() \
            .per_host["h0_1"]
        rows.append({
            "mode": mode,
            "run_sim_gbps": ref.goodput_gbps,
            "fabric_gbps": got.goodput_gbps,
            "rel_err": abs(got.goodput_gbps - ref.goodput_gbps)
            / max(ref.goodput_gbps, 1e-9),
        })
    return rows


def run_sweep_bench() -> List[Dict]:
    cfgs, _ = grid_configs(
        S.testbed_100g, mode="ddio", sim_time_s=_sim_time(0.01),
        msg_bytes=[64 << 10, 128 << 10, 256 << 10, 512 << 10,
                   768 << 10, 1 << 20],
        cpu_membw_gbps=[1200.0, 1400.0, 1500.0, 1600.0, 1760.0, 1900.0],
        ddio_bytes=[4 << 20, 6 << 20])

    # -- unroll timing over {1, 4, 8}: cold (compile) + warm per factor --- #
    times = {}
    for u in UNROLL_CANDIDATES:
        t0 = time.time()
        run_sweep(cfgs, backend="jax", unroll=u)
        cold = time.time() - t0
        # best-of-N: a single noisy warm sample must not crown the
        # wrong unroll
        warm, _ = _best_of(lambda: run_sweep(cfgs, backend="jax",
                                             unroll=u))
        times[u] = (cold, warm)
    best = min(times, key=lambda u: times[u][1])

    # fastest unroll, program cached
    t_warm, jx = _best_of(lambda: run_sweep(cfgs, backend="jax",
                                            unroll=best))
    t0 = time.time()
    ref = run_sweep(cfgs, backend="numpy")
    t_np = time.time() - t0
    t0 = time.time()
    seq = np.array([S.run_sim(c).goodput_gbps for c in cfgs])
    t_seq = time.time() - t0

    g_jx, g_np = jx["goodput_gbps"], ref["goodput_gbps"]
    dev_np = float(np.max(np.abs(g_jx - g_np) / np.maximum(g_np, 1e-9)))
    dev_seq = float(np.max(np.abs(g_np - seq) / np.maximum(seq, 1e-9)))
    return [{
        "grid_points": len(cfgs),
        "seq_run_sim_s": t_seq,
        "numpy_batched_s": t_np,
        # before: the old hard-coded unroll=8 (no donation existed then
        # either, but compile time dominates the cold number)
        "before_cold_s": times[8][0],
        "before_warm_s": times[8][1],
        # after: fastest unroll + donated scan carry
        "after_cold_s": times[best][0],
        "after_warm_s": t_warm,
        "best_unroll": best,
        "unroll_times": {str(u): {"cold_s": c, "warm_s": w}
                         for u, (c, w) in times.items()},
        "speedup_cold": t_seq / times[best][0],
        "speedup_warm": t_seq / t_warm,
        "max_rel_dev_vs_numpy": dev_np,
        "max_rel_dev_numpy_vs_run_sim": dev_seq,
    }]


def _best_of(fn, reps: int = 3):
    """Best-of-N wall clock for a *warm* (already-compiled) call,
    returning ``(best_seconds, last_result)``.  The bench hosts are
    shared single-core VMs where a single sample routinely eats a
    30-60% neighbor-noise spike; the minimum over a few reps is the
    standard estimator for the true cost of a deterministic program
    (the scalar reference runs long enough to average the noise out
    and stays single-shot)."""
    best, out = float("inf"), None
    for _ in range(reps):
        t0 = time.time()
        out = fn()
        best = min(best, time.time() - t0)
    return best, out


def _profile_program(scens, t_cold: float, t_warm: float) -> Dict:
    """Dispatch/op-count attribution for one vector-grid section: the
    per-tick wall clock, the compile-vs-warm split, and the jaxpr op
    census of the (cached) fixed-dt program — so a perf regression can
    be blamed on either op growth (census moved) or runtime (census
    flat, wall clock moved)."""
    import jax.numpy as jnp

    fsp = V.FabricSweepParams.from_scenarios(scens)
    fn = V._jax_program(fsp, 1, "ref")
    stats = program_op_stats(
        fn, *[jnp.asarray(b) for b in V.packed_params(fsp)])
    return {
        "ticks": fsp.ticks,
        "per_tick_ms_warm": t_warm / fsp.ticks * 1e3,
        "compile_s": max(t_cold - t_warm, 0.0),
        "op_count_total": stats["op_count_total"],
        "op_count_step": stats["op_count_step"],
        "op_kinds": stats["op_kinds"],
    }


def _incast_grid():
    """The >=32-point incast fabric grid shared by the fixed-dt sweep
    bench and the adaptive-dt bench (same scenarios -> same cached
    program -> the adaptive comparison is apples-to-apples)."""
    bursts = ([0.5, 1.0, 2.0, 4.0] if QUICK else
              [0.25, 0.5, 0.75, 1.0, 1.5, 2.0, 2.5, 3.0,
               3.5, 4.0, 5.0, 6.0])
    scens, _ = fabric_grid(
        lambda mode, pfc, burst_mb: SC.incast(
            n_senders=8, mode=mode, pfc=pfc, burst_mb=burst_mb,
            sim_time_s=_sim_time(0.02)),
        mode=["ddio", "jet"], pfc=[False, True], burst_mb=bursts)
    return scens


def run_fabric_sweep_bench() -> List[Dict]:
    scens = _incast_grid()

    t0 = time.time()
    scalar = [sc.run() for sc in scens]
    t_scalar = time.time() - t0
    t0 = time.time()
    jx = run_fabric_sweep(scens, backend="jax")
    t_cold = time.time() - t0
    t_warm, jx = _best_of(lambda: run_fabric_sweep(scens, backend="jax"))
    t0 = time.time()
    ref = run_fabric_sweep(scens, backend="numpy")
    t_np = time.time() - t0

    F = len(scens[0].flows)
    gp_sc = np.array([[r.flow_goodput_gbps[f] for f in range(F)]
                      for r in scalar])
    cp_sc = np.array([[r.flow_completion_us[f] for f in range(F)]
                      for r in scalar])

    def rel(a, b):
        """Max relative deviation; inf if the engines disagree about
        which entries are finite (e.g. one thinks a flow completed and
        the other does not) — a masked mean must never hide that."""
        if not (np.isfinite(a) == np.isfinite(b)).all():
            return float("inf")
        m = np.isfinite(b)
        if not m.any():
            return 0.0
        return float(np.max(np.abs(a[m] - b[m])
                            / np.maximum(np.abs(b[m]), 1e-9)))

    inc_sc = np.array([r.incast_completion_us for r in scalar])
    inc_jx = jx["incast_completion_us"]
    fin = np.isfinite(inc_jx)
    return [{
        **_profile_program(scens, t_cold, t_warm),
        "grid_points": len(scens),
        "flows": F,
        "scalar_run_fabric_s": t_scalar,
        "numpy_batched_s": t_np,
        "jax_cold_s": t_cold,
        "jax_warm_s": t_warm,
        "speedup_cold": t_scalar / t_cold,
        "speedup_warm": t_scalar / t_warm,
        "dev_goodput_vs_scalar": rel(jx["flow_goodput_gbps"], gp_sc),
        "dev_completion_vs_scalar": rel(jx["flow_completion_us"], cp_sc),
        "dev_incast_fct_vs_scalar": rel(jx["incast_completion_us"],
                                        inc_sc),
        "dev_goodput_vs_numpy": rel(jx["flow_goodput_gbps"],
                                    ref["flow_goodput_gbps"]),
        "mean_incast_fct_us": (float(inc_jx[fin].mean())
                               if fin.any() else None),
        "unfinished_incast_points": int((~fin).sum()),
        "mean_victim_gbps": float(jx["victim_goodput_gbps"].mean()),
        "max_pause_fanout": int(jx["pause_fanout"].max()),
    }]


def _xla_flops(scens) -> Dict:
    """Compiled-cost census of one vector-grid program: lower the
    (cached) fixed-dt program for the grid and ask XLA's cost model for
    the flop count.  Unlike the jaxpr op census (which counts program
    *structure* and is size-independent), the compiled cost grows with
    the array extents — exactly the quantity whose growth law the scale
    bench gates."""
    import jax
    import jax.numpy as jnp

    fsp = V.FabricSweepParams.from_scenarios(scens, sparse=True)
    fn = V._jax_program(fsp, 1, "ref")
    ca = jax.jit(fn).lower(
        *[jnp.asarray(b) for b in V.packed_params(fsp)]).compile() \
        .cost_analysis()
    return {"flops": float(ca.get("flops", float("nan"))),
            "ticks": fsp.ticks, "flows": fsp.n_flows,
            "ports": fsp.n_ports, "points": fsp.n_points}


def run_scale_bench() -> List[Dict]:
    """Pod-scale cost growth of the sparse-incidence engine: the same
    3-level cross-pod incast grid at 64 and 256 hosts, each advanced
    as ONE jax program.  The gated number is the growth exponent
    ``log(flops ratio) / log(host ratio)`` of XLA's compiled per-tick
    cost — segment-sum over a static incidence list is linear in
    (flows + ports), so the exponent must stay well under 2 (the dense
    one-hot engine's flows x ports products would put it at ~2)."""
    sim_s = 0.002 if QUICK else 0.004
    rows: List[Dict] = []
    for hosts, (pods, leaves, hpl) in ((64, (2, 2, 16)),
                                       (256, (4, 4, 16))):
        scens, _ = SC.pod_incast_grid(
            mode=("jet", "ddio"), pfc=(False,), pods=pods,
            leaves_per_pod=leaves, hosts_per_leaf=hpl,
            burst_mb=0.2, sim_time_s=sim_s)
        cost = _xla_flops(scens)
        t0 = time.time()
        run_fabric_sweep(scens, backend="jax")
        t_cold = time.time() - t0
        t_warm, out = _best_of(lambda: run_fabric_sweep(scens,
                                                        backend="jax"))
        fin = np.isfinite(out["incast_completion_us"])
        rows.append({
            "hosts": hosts,
            "pods": pods, "leaves_per_pod": leaves,
            "hosts_per_leaf": hpl,
            "grid_points": cost["points"],
            "flows": cost["flows"], "ports": cost["ports"],
            "ticks": cost["ticks"],
            "flops_per_tick": cost["flops"] / cost["ticks"],
            "jax_cold_s": t_cold, "jax_warm_s": t_warm,
            "per_tick_ms_warm": t_warm / cost["ticks"] * 1e3,
            "mean_incast_fct_us": (
                float(out["incast_completion_us"][fin].mean())
                if fin.any() else None),
        })
    small, big = rows
    host_ratio = big["hosts"] / small["hosts"]
    cost_ratio = big["flops_per_tick"] / small["flops_per_tick"]
    warm_ratio = big["jax_warm_s"] / small["jax_warm_s"]
    return [{
        "host_ratio": host_ratio,
        "flops_ratio": cost_ratio,
        "warm_ratio": warm_ratio,
        # compiled-cost growth law: 1.0 = linear in hosts, 2.0 = the
        # dense engine's quadratic one-hot products
        "growth_exponent": math.log(cost_ratio) / math.log(host_ratio),
        "warm_growth_exponent": (math.log(warm_ratio)
                                 / math.log(host_ratio)),
        "sizes": rows,
    }]


def run_routing_bench() -> List[Dict]:
    # bursts must overflow the 4 MB downlink buffer partition or the
    # whole incast teleports past the uplinks (cut-through) before the
    # failure fires; 8 x 1 MB keeps uplink traffic alive for ms, and
    # adaptive's post-failure FCT lands ~5 ms -> quick sim stays 8 ms
    scens, pts = SC.routing_grid(
        modes=("static_ecmp", "weighted_ecmp", "adaptive", "spray"),
        fail_at_us=(math.inf, 150.0),
        sim_time_s=0.008 if QUICK else 0.02, burst_mb=1.0)

    t0 = time.time()
    scalar = [sc.run() for sc in scens]
    t_scalar = time.time() - t0
    t0 = time.time()
    run_fabric_sweep(scens, backend="jax")
    t_cold = time.time() - t0
    t_warm, jx = _best_of(lambda: run_fabric_sweep(scens, backend="jax"))
    t0 = time.time()
    ref = run_fabric_sweep(scens, backend="numpy")
    t_np = time.time() - t0

    F = len(scens[0].flows)
    gp_sc = np.array([[r.flow_goodput_gbps[f] for f in range(F)]
                      for r in scalar])
    dev_np = float(np.max(
        np.abs(ref["flow_goodput_gbps"] - gp_sc)
        / np.maximum(np.abs(gp_sc), 1e-9)))
    rr_sc = np.array([r.reroute_count for r in scalar])
    fct = {(p["routing"], math.isfinite(p["fail_at_us"])):
           jx["incast_completion_us"][i] for i, p in enumerate(pts)}
    return [{
        **_profile_program(scens, t_cold, t_warm),
        "grid_points": len(scens),
        "flows": F,
        "scalar_run_fabric_s": t_scalar,
        "numpy_batched_s": t_np,
        "jax_cold_s": t_cold,
        "jax_warm_s": t_warm,
        "speedup_warm": t_scalar / t_warm,
        # float64 reference vs scalar driver across every routing mode
        # and failure schedule (routing decisions must agree exactly)
        "dev_goodput_numpy_vs_scalar": dev_np,
        "reroutes_match": bool(
            (ref["reroute_count"] == rr_sc).all()),
        "static_fail_stalls": bool(
            not np.isfinite(fct[("static_ecmp", True)])),
        "adaptive_fail_fct_us": float(fct[("adaptive", True)]),
        "spray_fail_fct_us": float(fct[("spray", True)]),
        "max_reroutes": int(ref["reroute_count"].max()),
        "mean_uplink_util_max": float(ref["uplink_util_max"].mean()),
    }]


def run_messages_bench() -> List[Dict]:
    sizes = [64.0] if QUICK else [16.0, 64.0, 256.0]
    wins = [16] if QUICK else [4, 16]
    scens, pts = SC.message_sweep_grid(
        msg_kb=sizes, window=wins, verb=("write",),
        algo=("dcqcn", "timely", "hpcc"),
        sim_time_s=_sim_time(0.01))

    t0 = time.time()
    scalar = [sc.run() for sc in scens]
    t_scalar = time.time() - t0
    t0 = time.time()
    run_fabric_sweep(scens, backend="jax")
    t_cold = time.time() - t0
    t_warm, jx = _best_of(lambda: run_fabric_sweep(scens, backend="jax"))
    t0 = time.time()
    ref = run_fabric_sweep(scens, backend="numpy")
    t_np = time.time() - t0

    F = len(scens[0].flows)
    cnt_sc = np.array([[len(r.msg_latency_us.get(f, []))
                        for f in range(F)] for r in scalar])
    last_sc = np.array([[r.msg_last_done_us.get(f, 0.0)
                         for f in range(F)] for r in scalar])
    p99_sc = np.array([r.msg_percentile(99.0) for r in scalar])
    # numpy bookkeeping is exact: counts bit-equal, times to 1e-9
    count_mismatch = int(np.abs(ref["msg_count"] - cnt_sc).sum())
    dev_last = float(np.max(np.abs(ref["msg_last_done_us"] - last_sc)
                            / np.maximum(np.abs(last_sc), 1e-9)))
    # histogram estimate vs exact percentile: the documented bound
    p99_err = float(np.max(np.abs(ref["msg_p99_us"] - p99_sc)
                           / np.maximum(p99_sc, 1e-9)))
    p99 = {(p["algo"], p["window"]): float(jx["msg_p99_us"][i])
           for i, p in enumerate(pts)}
    wmax = max(wins)
    return [{
        **_profile_program(scens, t_cold, t_warm),
        "grid_points": len(scens),
        "flows": F,
        "scalar_run_fabric_s": t_scalar,
        "numpy_batched_s": t_np,
        "jax_cold_s": t_cold,
        "jax_warm_s": t_warm,
        "speedup_warm": t_scalar / t_warm,
        "count_mismatch_numpy_vs_scalar": count_mismatch,
        "dev_last_done_numpy_vs_scalar": dev_last,
        "p99_hist_err_vs_exact": p99_err,
        "total_messages": int(ref["msg_count_total"].sum()),
        "mean_rate_mops": float(ref["msg_rate_mops"].mean()),
        "dcqcn_p99_us": p99[("dcqcn", wmax)],
        "timely_p99_us": p99[("timely", wmax)],
        "hpcc_p99_us": p99[("hpcc", wmax)],
    }]


def run_faults_bench() -> List[Dict]:
    from repro.fabric.faults import FaultConfig

    rates = (0.0, 0.01) if QUICK else (0.0, 0.002, 0.01, 0.05)
    scens, pts = SC.lossy_incast_grid(
        loss_rate=rates, recovery=("go_back_n", "selective"),
        sim_time_s=_sim_time(0.004))

    t0 = time.time()
    scalar = [sc.run() for sc in scens]
    t_scalar = time.time() - t0
    t0 = time.time()
    run_fabric_sweep(scens, backend="jax")
    t_cold = time.time() - t0
    t_warm, jx = _best_of(lambda: run_fabric_sweep(scens, backend="jax"))
    t0 = time.time()
    ref = run_fabric_sweep(scens, backend="numpy")
    t_np = time.time() - t0

    F = len(scens[0].flows)
    # the counter-based hash gives every engine the same loss
    # realization -> the fault accounting must agree to f64 round-off
    retx_sc = np.array([r.retransmit_bytes for r in scalar])
    drop_sc = np.array([r.dropped_pkts for r in scalar])
    cnt_sc = np.array([[len(r.msg_latency_us.get(f, []))
                        for f in range(F)] for r in scalar])
    dev_retx = float(np.max(np.abs(ref["retransmit_bytes"] - retx_sc)
                            / np.maximum(retx_sc, 1.0)))
    dev_drop = float(np.max(np.abs(ref["dropped_pkts"] - drop_sc)
                            / np.maximum(drop_sc, 1.0)))
    count_mismatch = int(np.abs(ref["msg_count"] - cnt_sc).sum())
    # zero wire loss + selective: nothing gaps, nothing is discarded —
    # dropped_pkts must be exactly 0 (go-back-N still discards dups on
    # RNIC admission shortfalls, so only the selective point qualifies)
    lossless_sel = [i for i, p in enumerate(pts)
                    if p["loss_rate"] == 0.0
                    and p["recovery"] == "selective"]
    lossless_sel_dropped = float(ref["dropped_pkts"][lossless_sel].sum())

    def pick(arr, rec, rate):
        return next(float(arr[i]) for i, p in enumerate(pts)
                    if p["recovery"] == rec and p["loss_rate"] == rate)

    worst = max(rates)

    # crash--restart: receiver dies mid-incast, the RTO ledgers replay
    crash = SC.lossy_incast(loss_rate=0.005, recovery="selective",
                            sim_time_s=_sim_time(0.004))
    crash.fabric.faults = FaultConfig(loss_rate=0.005, seed=7).crash(
        "h1_0", at_us=400.0, restart_us=600.0)
    cr_sc = crash.run()
    cr_np = run_fabric_sweep([crash], backend="numpy")
    cr_dev = abs(float(np.ravel(cr_np["crash_recovery_us"][0])[0])
                 - cr_sc.crash_recovery_us["h1_0"])

    return [{
        **_profile_program(scens, t_cold, t_warm),
        "grid_points": len(scens),
        "flows": F,
        "scalar_run_fabric_s": t_scalar,
        "numpy_batched_s": t_np,
        "jax_cold_s": t_cold,
        "jax_warm_s": t_warm,
        "speedup_warm": t_scalar / t_warm,
        "dev_retransmit_numpy_vs_scalar": dev_retx,
        "dev_dropped_numpy_vs_scalar": dev_drop,
        "count_mismatch_numpy_vs_scalar": count_mismatch,
        "lossless_sel_dropped_pkts": lossless_sel_dropped,
        "crash_recovery_dev_us": cr_dev,
        "crash_recovery_us": cr_sc.crash_recovery_us["h1_0"],
        "gbn_retx_mb_worst": pick(ref["retransmit_bytes"], "go_back_n",
                                  worst) / 1e6,
        "sel_retx_mb_worst": pick(ref["retransmit_bytes"], "selective",
                                  worst) / 1e6,
        "gbn_p999_us_worst": pick(jx["msg_p999_us"], "go_back_n", worst),
        "sel_p999_us_worst": pick(jx["msg_p999_us"], "selective", worst),
    }]


def run_adaptive_bench() -> List[Dict]:
    """Adaptive time-stepping on a *drain-bounded* incast grid: every
    burst finite (no open victim flow) and small enough that every
    point completes well inside the horizon, leaving the long quiet
    tail that event-aware stepping exists to skip.  (The fabric-sweep
    grid above deliberately includes points whose incast never
    finishes, and open victims sit in a permanent DCQCN sawtooth —
    per-tick dynamics the stride correctly refuses to coarsen; the
    stride is also a grid-wide lockstep reduction, so one busy point
    pins the whole grid at fine dt.)  Gated on what adaptivity
    promises — macro-tick coarsening (iterations << ticks) within the
    documented delivered-bytes bound — with wall clock recorded
    honestly: the jax backend trades the scan for a
    ``lax.while_loop`` whose per-iteration cost on CPU can eat part of
    the iteration savings."""
    bursts = [0.25] if QUICK else [0.25, 0.5]
    scens, _ = fabric_grid(
        lambda mode, pfc, burst_mb: SC.incast(
            n_senders=8, mode=mode, pfc=pfc, burst_mb=burst_mb,
            with_victim=False, sim_time_s=_sim_time(0.02)),
        mode=["ddio", "jet"], pfc=[False, True], burst_mb=bursts)
    cfg = AdaptiveConfig()

    t0 = time.time()
    [sc.run() for sc in scens]
    t_scalar = time.time() - t0
    run_fabric_sweep(scens, backend="jax")
    t_fixed, fine = _best_of(lambda: run_fabric_sweep(scens,
                                                      backend="jax"))
    t0 = time.time()
    run_fabric_sweep(scens, backend="jax", adaptive_dt=True)
    t_cold = time.time() - t0
    t_warm, ad = _best_of(lambda: run_fabric_sweep(
        scens, backend="jax", adaptive_dt=True))

    ticks = V.FabricSweepParams.from_scenarios(scens).ticks
    iters = int(np.ravel(ad["adaptive_iterations"])[0])
    db_a, db_f = ad["flow_delivered_bytes"], fine["flow_delivered_bytes"]
    dev = float(np.max(np.abs(db_a - db_f) / np.maximum(db_f, 1.0)))
    ca, cf = ad["flow_completion_us"], fine["flow_completion_us"]
    both = np.isfinite(ca) & np.isfinite(cf)
    shift = float(np.abs(ca[both] - cf[both]).max()) if both.any() else 0.0
    return [{
        "grid_points": len(scens),
        "ticks": ticks,
        "adaptive_iterations": iters,
        "coarsen_ratio": ticks / max(iters, 1),
        "scalar_run_fabric_s": t_scalar,
        "jax_fixed_warm_s": t_fixed,
        "jax_adaptive_cold_s": t_cold,
        "jax_adaptive_warm_s": t_warm,
        "speedup_warm_vs_scalar": t_scalar / t_warm,
        "speedup_warm_vs_fixed": t_fixed / t_warm,
        "dev_delivered_vs_fixed": dev,
        "rel_bytes_bound": cfg.rel_bytes_bound,
        "max_completion_shift_us": shift,
        "max_stride": cfg.max_stride,
    }]


def run_farm_bench() -> List[Dict]:
    """Sweep farm vs the monolithic single-program run on the
    64-point incast grid (16-pt with ``--quick``).

    Three gated promises: (1) **equal results** — the farm's chunked,
    envelope-forced programs must reproduce the monolithic run exactly
    (``dev_farm_vs_mono`` is an exact-zero ceiling); (2) **zero
    recompiles after warmup** — a second farm pass over the same plan
    must hit the program cache on every chunk
    (``recompiles_after_warmup``, exact-zero); (3) **warm speedup** —
    on a multi-core host the multiprocess farm beats the monolithic
    program >=2x (``speedup_warm``; the quick floor is lower because CI
    runs single-core in-process dispatch, where chunking can only cost
    a little, never win).  The multiprocess timing re-spawns the worker
    pool per rep, so it includes the real dispatch overhead an
    overnight run pays; workers share the on-disk XLA cache."""
    import tempfile

    from repro.fabric.farm import run_farm

    scens, _ = SC.build_grid("incast", quick=QUICK)
    chunk = 8 if QUICK else 16
    cpus = os.cpu_count() or 1
    workers = min(4, cpus) if (not QUICK and cpus >= 2) else 0

    run_fabric_sweep(scens, backend="jax")               # compile mono
    t_mono, mono = _best_of(lambda: run_fabric_sweep(scens,
                                                     backend="jax"))

    warm = run_farm(scens, workers=0, chunk_size=chunk,
                    backend="jax", artifacts=False)   # chunk compile
    t_farm_ip, farm = _best_of(lambda: run_farm(
        scens, workers=0, chunk_size=chunk, backend="jax",
        artifacts=False))
    recompiles = sum(r["compiles"]
                     for r in farm["manifest"]["records"])

    dev = 0.0
    for k in mono:
        a = np.asarray(mono[k], np.float64)
        b = np.asarray(farm["results"][k], np.float64)
        a = np.where(np.isfinite(a), a, -1.0)
        b = np.where(np.isfinite(b), b, -1.0)
        dev = max(dev, float(np.max(np.abs(a - b))))

    t_farm = t_farm_ip
    if workers > 1:
        with tempfile.TemporaryDirectory() as td:
            t_farm, _ = _best_of(lambda: run_farm(
                "incast", quick=QUICK, workers=workers,
                chunk_size=chunk, backend="jax", out_dir=td), reps=2)

    return [{
        "grid_points": len(scens),
        "chunk_size": chunk,
        "chunks": len(warm["manifest"]["records"]),
        "workers": workers,
        "mono_warm_s": t_mono,
        "farm_inprocess_warm_s": t_farm_ip,
        "farm_warm_s": t_farm,
        "speedup_warm": t_mono / t_farm,
        "dev_farm_vs_mono": dev,
        "recompiles_after_warmup": recompiles,
        "warmup_compiles": sum(r["compiles"] for r in
                               warm["manifest"]["records"]),
    }]


def _jsonable(obj):
    """Strict-JSON payload: non-finite floats become None (json.dump's
    Infinity/NaN literals break jq / JSON.parse on the CI artifact)."""
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (float, np.floating)):
        return float(obj) if np.isfinite(obj) else None
    if isinstance(obj, np.integer):
        return int(obj)
    return obj


def run() -> List[Dict]:
    return run_incast()


def main() -> None:
    print(f"# jax persistent compilation cache: "
          f"{configure_persistent_cache()}")
    rows = run_incast()
    emit(NAME, rows)
    eq = run_equivalence()
    emit(NAME + "_equivalence", eq)
    sw = run_sweep_bench()
    emit(NAME + "_sweep", sw, quiet=True)
    fs = run_fabric_sweep_bench()
    emit(NAME + "_vector", fs)
    sc = run_scale_bench()
    emit(NAME + "_scale", sc)
    rt = run_routing_bench()
    emit(NAME + "_routing", rt)
    ms = run_messages_bench()
    emit(NAME + "_messages", ms)
    ft = run_faults_bench()
    emit(NAME + "_faults", ft)
    ad = run_adaptive_bench()
    emit(NAME + "_adaptive", ad)
    fm = run_farm_bench()
    emit(NAME + "_farm", fm)

    os.makedirs(OUT_DIR, exist_ok=True)
    with open(JSON_PATH, "w") as f:
        json.dump(_jsonable({"quick": QUICK, "incast": rows,
                             "equivalence": eq, "sweep": sw[0],
                             "fabric_sweep": fs[0],
                             "scale": sc[0],
                             "routing": rt[0],
                             "messages": ms[0],
                             "faults": ft[0],
                             "adaptive": ad[0],
                             "farm": fm[0]}), f, indent=2)

    worst_eq = max(r["rel_err"] for r in eq)
    s, v = sw[0], fs[0]
    print(f"# single-pair fabric == run_sim within {worst_eq:.2%} "
          f"(acceptance 5%)")
    print(f"# datapath sweep {s['grid_points']} pts: best unroll "
          f"{s['best_unroll']}; cold {s['before_cold_s']:.1f}s -> "
          f"{s['after_cold_s']:.1f}s, warm {s['before_warm_s']:.2f}s -> "
          f"{s['after_warm_s']:.2f}s; x{s['speedup_warm']:.1f} warm vs "
          f"sequential run_sim; dev vs numpy "
          f"{s['max_rel_dev_vs_numpy']:.3%}")
    print(f"# fabric sweep {v['grid_points']} pts x {v['flows']} flows: "
          f"x{v['speedup_warm']:.1f} warm / x{v['speedup_cold']:.1f} cold "
          f"vs scalar run_fabric (acceptance >=5x warm); goodput dev "
          f"{v['dev_goodput_vs_scalar']:.2e}, incast-FCT dev "
          f"{v['dev_incast_fct_vs_scalar']:.2e} (acceptance <=1e-3); "
          f"{v['per_tick_ms_warm']:.3f} ms/tick warm, "
          f"{v['op_count_step']} ops/step ({v['op_kinds']} kinds), "
          f"compile {v['compile_s']:.1f}s")
    sb = sc[0]
    b64, b256 = sb["sizes"]
    print(f"# pod scale {b64['hosts']} -> {b256['hosts']} hosts (one "
          f"program each, {b256['flows']} flows / {b256['ports']} ports "
          f"at {b256['hosts']}): compiled-cost growth exponent "
          f"{sb['growth_exponent']:.2f} (1.0 linear, 2.0 dense-quadratic"
          f"); warm {b64['per_tick_ms_warm']:.3f} -> "
          f"{b256['per_tick_ms_warm']:.3f} ms/tick "
          f"(exp {sb['warm_growth_exponent']:.2f})")
    a = ad[0]
    print(f"# adaptive dt, drain-bounded {a['grid_points']}-pt grid: "
          f"{a['adaptive_iterations']} iterations for {a['ticks']} ticks "
          f"(x{a['coarsen_ratio']:.1f} coarsening, stride cap "
          f"{a['max_stride']}); delivered dev vs fixed dt "
          f"{a['dev_delivered_vs_fixed']:.2e} (bound "
          f"{a['rel_bytes_bound']:.0%}); warm "
          f"x{a['speedup_warm_vs_scalar']:.1f} vs scalar / "
          f"x{a['speedup_warm_vs_fixed']:.2f} vs fixed-dt jax")
    r = rt[0]
    print(f"# routing grid {r['grid_points']} pts (mode x failure, one "
          f"program): x{r['speedup_warm']:.1f} warm vs scalar; numpy dev "
          f"{r['dev_goodput_numpy_vs_scalar']:.2e}; static stalls on "
          f"failure: {r['static_fail_stalls']}, adaptive FCT "
          f"{r['adaptive_fail_fct_us']:.0f} us")
    m = ms[0]
    print(f"# message grid {m['grid_points']} pts (size x window x CC, "
          f"one program): x{m['speedup_warm']:.1f} warm vs scalar; "
          f"numpy count mismatch {m['count_mismatch_numpy_vs_scalar']}, "
          f"hist-p99 err {m['p99_hist_err_vs_exact']:.2%} (bound 4.6%); "
          f"p99 dcqcn {m['dcqcn_p99_us']:.0f} us vs timely "
          f"{m['timely_p99_us']:.0f} / hpcc {m['hpcc_p99_us']:.0f} us")
    ff = ft[0]
    print(f"# faults grid {ff['grid_points']} pts (loss x recovery, one "
          f"program): x{ff['speedup_warm']:.1f} warm vs scalar; retx dev "
          f"{ff['dev_retransmit_numpy_vs_scalar']:.2e}, count mismatch "
          f"{ff['count_mismatch_numpy_vs_scalar']}; at worst loss "
          f"go-back-N replays {ff['gbn_retx_mb_worst']:.1f} MB "
          f"(p999 {ff['gbn_p999_us_worst']:.0f} us) vs selective "
          f"{ff['sel_retx_mb_worst']:.1f} MB "
          f"(p999 {ff['sel_p999_us_worst']:.0f} us); crash recovery "
          f"{ff['crash_recovery_us']:.0f} us (engine dev "
          f"{ff['crash_recovery_dev_us']:.1e})")
    fa = fm[0]
    print(f"# farm {fa['grid_points']} pts in {fa['chunks']} chunks of "
          f"{fa['chunk_size']} ({fa['workers']} workers): warm "
          f"x{fa['speedup_warm']:.2f} vs monolithic "
          f"({fa['mono_warm_s']:.2f}s -> {fa['farm_warm_s']:.2f}s); "
          f"dev {fa['dev_farm_vs_mono']:.1e}, "
          f"{fa['recompiles_after_warmup']} recompiles after warmup")
    print(f"# machine-readable: {os.path.abspath(JSON_PATH)}")


if __name__ == "__main__":
    QUICK = "--quick" in sys.argv[1:]
    main()

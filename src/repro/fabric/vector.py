"""Vectorized fabric engine: whole-grid multi-host simulation.

``run_fabric`` advances one scenario with Python dicts of ``SenderHost`` /
``Switch`` / ``ReceiverHost`` objects — minutes per grid point for the
fleet experiments the paper cares about (incast completion, victim-flow
goodput, PFC pause fan-out, Lamda §5-6).  This module packs the *entire*
tick body into stacked arrays and advances all grid points at once:

* per-flow DCQCN/offer state as ``[F]`` arrays (``[G, F]`` across the
  grid) — rate machines, injected/delivered byte counters, CNP pacing,
  plus a circular delay ring for CNP propagation (``cnp_delay_us``);
* per-port queue state as ``[P, F]`` byte/mark matrices covering the NIC
  egress queues and every switch output port on some flow's path — a
  flow's bytes belong to exactly one traffic class, so the classed
  ``[Q, P]`` per-TC occupancy / PFC assert / pause state is derived with
  one ``[Q, F] @ [F, P]`` one-hot matmul and the drain's strict-priority
  budget grants are priority-unrolled over ``Q`` (the PR 3 receiver-block
  pattern); legacy per-link points collapse every flow onto TC 0;
* per-receiver datapath state as ``[R]`` arrays — including the
  :class:`~repro.core.datapath.HostDatapath` QoS admission classes as a
  stacked ``[G, Q, R]`` block (``Q = 3`` service classes, priority-order
  space/drain grants, §5 low-QoS DRAM spill) — plus ``[R, H]`` circular
  release rings (the ``sweep.py`` ring trick);
* routing as per-tick state: on the static fast path (every point
  ``static_ecmp`` with no failure schedule) :meth:`Topology.route` is
  precomputed into flow->port incidence one-hots exactly as before; in
  dynamic-routing land the port set covers every *candidate* uplink/
  downlink (``[S, F, P]`` one-hots), the spine choice is a ``[G, F]``
  scan carry updated each tick (argmin/hash/softmax-free weight
  arithmetic identical to :mod:`repro.fabric.routing`), link failures
  are per-point ``[G, P]`` tick windows that zero budgets and drop
  in-flight bytes, and spray's reorder settling is one more slot-major
  ring.  Either way each forwarding stage stays a gather, a batch
  enqueue and a scatter — no data-dependent control flow.

One ``jax.vmap`` over the scenario grid x one ``jax.lax.scan`` over ticks
= one XLA program; a batched-numpy backend runs the *same* step function
(float64) as the verification reference, mirroring the single-source-of-
truth design of :mod:`repro.fabric.sweep`.

Semantics are exactly the batch-fluid tick of :func:`repro.fabric.run_fabric`
(see its module docstring): four tier-ordered forwarding stages with
cut-through within the tick, proportional buffer-space allocation and a
single pre-batch ECN-knee decision per port per stage, receiver CNPs to
the heaviest recently-arriving flow (lowest flow id on ties), per-flow
DCQCN CNP pacing of switch ECN marks, and per-priority PFC pause
propagation targeted at the ``(ingress link, tc)`` pairs of flows queued
in over-watermark classes.  A
1-sender/1-receiver grid therefore reproduces ``run_sim`` goodput, and
small incast grids match the scalar driver per flow.

Grid points must share the topology *structure* (same node/link graph,
same flows, same receiver set, same tick count); everything numeric may
vary per point: receiver ``SimConfig`` knobs, ``SwitchConfig`` scalars
(including the strict/WRR scheduler and per-TC host PFC), link rates,
per-flow offered load / burst size / start time, and — the PR 5 lift —
routing mode and link-failure schedules.  The former "grid points must
share routes" restriction only survives on the static fast path, where
frozen routes *are* the structure.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.datapath import N_QOS
from ..core.dcqcn import DcqcnConfig
from .cc import CcConfig
from .hosts import hold_us_baseline, hold_us_jet
from .faults import link_salt, loss_threshold
from .messages import (HIST_BUCKETS, HIST_MIN_US, MSG_COUNT_EPS, hist_ratio,
                       percentile_from_counts)
from .topology import NEVER_TICK
from ._scan import pick_unroll
from .spans import span, transfer
from . import fused
from .fused import AdaptiveConfig

#: Name scope of the receive stage (stage 3 and the receivers' CNP
#: target pick): under jax its ops carry it in their op-name metadata,
#: so a device trace can put time on the stage.
RECV_SCOPE = "fabric.recv"


def _scope(xp, name: str):
    """``jax.named_scope(name)`` when tracing with ``jax.numpy``; nothing
    under numpy.  Op metadata only: the ops themselves are unchanged."""
    if xp is np:
        return contextlib.nullcontext()
    import jax
    return jax.named_scope(name)


_STAGES = 4          # NIC egress, leaf uplink, spine, leaf downlink
# sparse-incidence stage slots (3-level pod fabrics): NIC egress,
# leaf uplink, spine uplink (-> super-spine), super-spine, spine
# downlink, leaf downlink.  A 2-tier flow simply leaves slots 2-3 empty.
_STAGES_SP = 6

# pvals entries that stay integer (tick indices, codes, ring offsets)
_INT_KEYS = frozenset(["d_base", "d_strag", "cnp_dly", "fail_at",
                       "fail_until", "rmode", "flet", "settle", "sched",
                       "cc_algo", "f_salt", "f_thr", "f_cthr",
                       "flap_start", "flap_period", "flap_down",
                       "crash_at", "crash_until", "rto_ticks",
                       "nack_ticks", "rto_cap"])

# CcConfig knobs stacked per flow when any point runs a non-DCQCN
# controller (masked `where` lanes select the algorithm per flow)
_CC_SCALARS = [
    ("cc_minr", lambda c: c.min_rate_gbps),
    ("base_rtt", lambda c: c.base_rtt_us),
    ("cc_upd", lambda c: c.update_us),
    ("t_low", lambda c: c.t_low_us),
    ("t_high", lambda c: c.t_high_us),
    ("tl_beta", lambda c: c.timely_beta),
    ("tl_add", lambda c: c.timely_add_gbps),
    ("tl_a", lambda c: c.timely_ewma),
    ("hp_eta", lambda c: c.hpcc_eta),
    ("hp_ai", lambda c: c.hpcc_ai_gbps),
]
_CC_DEFAULT = CcConfig()


# --------------------------------------------------------------------------- #
# Packing: scenarios -> static structure + stacked per-point parameters
# --------------------------------------------------------------------------- #
_RECV_SCALARS = [
    ("jet", lambda c: 1.0 if c.mode == "jet" else 0.0),
    ("pfc_en", lambda c: 1.0 if c.pfc_enabled else 0.0),
    ("wm_cnp", lambda c: 1.0 if c.rnic_ecn_cnp else 0.0),
    ("line1", lambda c: c.line_rate_gbps),
    ("pcie", lambda c: c.pcie_gbps),
    ("membw", lambda c: c.membw_total_gbps),
    ("cpu_bw", lambda c: c.cpu_membw_gbps),
    ("qp_bytes", lambda c: c.num_qps * c.msg_bytes),
    ("ddio", lambda c: c.ddio_bytes),
    ("knee", lambda c: c.miss_knee),
    ("rnic_buf", lambda c: c.rnic_buffer_bytes),
    ("xoff", lambda c: c.pfc_xoff),
    ("xon", lambda c: c.pfc_xon),
    ("ecn_th", lambda c: c.ecn_threshold),
    ("cnp_iv", lambda c: c.cnp_interval_us),
    ("pool", lambda c: c.jet_pool_bytes),
    ("sfrac", lambda c: c.straggler_frac),
    ("safe", lambda c: c.cache_safe),
    ("danger", lambda c: c.cache_danger),
    ("mem_esc", lambda c: c.mem_esc_bytes),
]

_DCQCN_SCALARS = [
    ("dline", lambda d: d.line_rate_gbps),
    ("minr", lambda d: d.min_rate_gbps),
    ("g", lambda d: d.g),
    ("a_tmr", lambda d: d.alpha_timer_us),
    ("r_tmr", lambda d: d.rate_timer_us),
    ("bctr", lambda d: d.byte_counter_mb * (1 << 20)),
    ("ai", lambda d: d.ai_rate_gbps),
    ("hai", lambda d: d.hai_rate_gbps),
    ("fth", lambda d: float(d.f_threshold)),
]

_SWITCH_SCALARS = [
    ("buf", lambda s: float(s.port_buffer_bytes)),
]

# per-TC switch knobs: resolved to [N_QOS]-vectors per grid point (the
# scalar fields with optional tc_* overrides, see SwitchConfig)
_SWITCH_TC = [
    ("kmin", lambda s, tc: s.kmin_frac(tc)),
    ("sw_xoff", lambda s, tc: s.xoff_frac(tc)),
    ("sw_xon", lambda s, tc: s.xon_frac(tc)),
]


def _same_wiring(a, b) -> bool:
    """Whether topologies ``a`` and ``b`` give every flow the same
    :meth:`~repro.fabric.topology.Topology.route`: it reads only host
    attachment, the spine and super-spine lists (their order picks the
    ECMP path) and which links exist, never rates or failure schedules."""
    return a is b or (a.host_leaf == b.host_leaf and a.spines == b.spines
                      and a.super_spines == b.super_spines
                      and a.links.keys() == b.links.keys())


@dataclasses.dataclass
class FabricSweepParams:
    """Static fabric structure + stacked per-point parameters.

    Shapes: F flows, P ports, R receivers, G grid points, H ring horizon.
    """
    # -- static structure (shared by every grid point) ----------------------
    port_keys: List[Tuple[str, str]]     # port id -> out-link key
    recv_hosts: List[str]
    flow_tags: List[str]
    stage_mask: np.ndarray               # [S, P] bool: ports of each stage
    occ: List[np.ndarray]                # S x [P, F]: flow's port per stage
    dest: List[np.ndarray]               # 3 x [P, F]: routing after stage k
    recv_onehot: np.ndarray              # [R, F]
    recv_of: np.ndarray                  # [F] int32
    qos_of: np.ndarray                   # [F] int32: flow's admission class
    prev_onehot: np.ndarray              # [P, F, P]: ingress port of (p, f)
    owner_recv: np.ndarray               # [P] int32: stage-3 port's receiver
    # -- per-point parameters ----------------------------------------------
    pvals: Dict[str, np.ndarray]         # [G], [G, F], [G, R] or [G, P]
    n_points: int
    n_flows: int
    n_ports: int
    n_recv: int
    ticks: int
    dt_us: float
    ring_len: int
    cnp_ring: int                        # CNP propagation ring length
    structure_key: str
    # -- dynamic-routing structure (None on the static fast path) -----------
    # With any point in dynamic-routing land (mode != static_ecmp or a
    # failure schedule), ports cover every *candidate* uplink/downlink
    # and the spine choice becomes per-tick carry state [G, F].
    upP: Optional[np.ndarray] = None     # [S, F, P] candidate uplink 1-hot
    dnP: Optional[np.ndarray] = None     # [S, F, P] candidate downlink
    candS: Optional[np.ndarray] = None   # [S, F] bool candidacy
    crossF: Optional[np.ndarray] = None  # [F] bool: cross-leaf flow
    T1: Optional[np.ndarray] = None      # [P, F, P] uplink->downlink map
    init_spine: Optional[np.ndarray] = None   # [F] int32 (fid % S)
    dyn_route: bool = False
    any_wrr: bool = False                # any point schedules WRR drain
    host_tc: bool = False                # any point runs per-TC host PFC
    settle_ring: int = 1                 # Hs (spray reorder settling)
    n_spines: int = 0
    any_cc: bool = False                 # any point runs a non-DCQCN CC
    any_msg: bool = False                # any point runs the message layer
    msg_ring: int = 1                    # Lm (message start-time ring)
    any_flt: bool = False                # any point attaches a FaultConfig
    any_flap: bool = False               # any point schedules link flaps
    # -- sparse-incidence structure (3-level pod fabrics) --------------------
    # Queue state becomes [.., 2, S, F] slot entries (S = _STAGES_SP):
    # slot (s, f) holds flow f's bytes queued at ``port_of[s, f]``
    # (n_ports = "slot unused").  ``prv_port`` is each slot's ingress
    # port (PFC pause target), ``nxt_slot`` the next occupied slot a
    # stage's drain output enqueues into (_STAGES_SP = "delivered").
    sparse: bool = False
    port_of: Optional[np.ndarray] = None     # [6, F] int32
    prv_port: Optional[np.ndarray] = None    # [6, F] int32
    nxt_slot: Optional[np.ndarray] = None    # [6, F] int32
    pack_fail: bool = False              # sparse grid with failure windows
    # candidate-ingress pause structure under failure schedules: the
    # scalar driver treats shallow (intra-pod, multi-candidate) flows as
    # rerouteable, so their last-hop queue pauses *every* candidate
    # downlink and every candidate hop joins the pausable denominator
    # (OutputPort.static_ingress semantics).  [2, E] (flow, target port)
    # extra pause pairs, plus the candidate hop ports for n_pausable.
    pause_extra: Optional[np.ndarray] = None
    pausable_extra: Optional[np.ndarray] = None
    # distinct wirings whose routes the static-path check derived (1 when
    # every point is wired like point 0; 0 on the dynamic-routing branch)
    wirings: int = 0

    def envelope(self) -> dict:
        """Chunk-boundary envelope of this packing: the capability
        flags and ring horizons a *sub-grid* packing must be floored at
        to trace the identical program (pass to
        :meth:`from_scenarios` via ``envelope=``).  Pack the full grid
        once, then pack each chunk under the full grid's envelope — the
        chunks then share one ``structure_key`` (one cached compilation
        per canonical chunk shape) and reproduce the monolithic run
        bit-for-bit."""
        return {"ring_len": self.ring_len, "cnp_ring": self.cnp_ring,
                "settle_ring": self.settle_ring,
                "msg_ring": self.msg_ring,
                "dyn": self.dyn_route or self.pack_fail,
                "wrr": self.any_wrr, "host_tc": self.host_tc,
                "cc": self.any_cc, "msg": self.any_msg,
                "flt": self.any_flt, "flap": self.any_flap}

    @classmethod
    def from_scenarios(cls, scens: Sequence, sparse: bool = False,
                       envelope: Optional[dict] = None
                       ) -> "FabricSweepParams":
        """Pack a grid of :class:`~repro.fabric.scenarios.Scenario`-likes
        (anything with ``.topology``, ``.flows``, ``.fabric``).

        ``sparse=True`` packs the segmented-incidence structure instead
        of the dense port x flow one-hots — required for 3-level
        (super-spine) topologies, and the scalable choice for any large
        static fabric.  Sparse packing supports static ECMP plus
        failure/flap windows and the CC zoo; dynamic routing modes, the
        message layer and FaultConfig injection stay dense-only.

        ``envelope`` (see :meth:`envelope`) floors the capability flags
        and ring horizons at the values of a *larger* grid this packing
        is a chunk of.  The flags (``dyn``/``wrr``/``cc``/``msg``/
        ``flt``/…) and ring lengths (``ring_len``/``cnp_ring``/…) are
        normally "any/max over the grid", so slicing a heterogeneous
        grid would give each chunk a different compiled program *and*
        different semantics than the monolithic run.  Passing the full
        grid's envelope forces every chunk onto the monolithic grid's
        program structure, which is what makes chunked execution
        bit-identical to the one-program run (the sweep-farm contract,
        held by ``tests/test_farm.py``)."""
        if not scens:
            raise ValueError("empty fabric sweep grid")
        s0 = scens[0]
        topo0, flows0 = s0.topology, s0.flows
        dt = s0.fabric.dt_us
        ticks = int(s0.fabric.sim_time_s * 1e6 / dt)
        F = len(flows0)
        # engine-level capability flags: shared *structure*, selected per
        # point by plain parameters (rmode / sched / hpfc)
        dyn = any(s.fabric.routing.is_dynamic or bool(s.topology.link_down)
                  or bool(s.topology.link_flaps) for s in scens)
        any_wrr = any(s.fabric.switch.scheduler == "wrr" for s in scens)
        any_flt = any(s.fabric.faults is not None for s in scens)
        any_flap = any(bool(s.topology.link_flaps) for s in scens)
        recv_hosts = sorted({f.dst for f in flows0})
        host_tc = any(s.fabric.switch.per_tc
                      and s.fabric.receiver_cfg(h).host_pfc_per_tc
                      for s in scens for h in recv_hosts)

        # message layer / CC zoo: per-flow Flow overrides falling back to
        # the FabricConfig defaults, resolved exactly as run_fabric does
        def msg_of(s):
            return [f.msg if f.msg is not None else s.fabric.msg
                    for f in s.flows]

        def cc_of(s):
            return [f.cc if f.cc is not None else s.fabric.cc
                    for f in s.flows]

        any_msg = any(m is not None for s in scens for m in msg_of(s))
        any_cc = any(c is not None and c.algo != "dcqcn"
                     for s in scens for c in cc_of(s))
        # chunk-boundary envelope: floor the capability flags at the
        # enclosing grid's, so every chunk traces the monolithic
        # program (a chunk with no msg/cc/fault/dynamic points must not
        # silently compile the cheaper structure)
        env = dict(envelope or {})
        dyn = dyn or bool(env.get("dyn"))
        any_wrr = any_wrr or bool(env.get("wrr"))
        any_flt = any_flt or bool(env.get("flt"))
        any_flap = any_flap or bool(env.get("flap"))
        host_tc = host_tc or bool(env.get("host_tc"))
        any_msg = any_msg or bool(env.get("msg"))
        any_cc = any_cc or bool(env.get("cc"))
        pods = any(s.topology.super_spines for s in scens)
        pack_fail = False
        if sparse:
            # sparse incidence freezes routes as structure: static ECMP
            # only, with failure/flap windows and the CC zoo as
            # per-point parameters
            if any(s.fabric.routing.is_dynamic for s in scens):
                raise ValueError(
                    "sparse incidence supports static_ecmp routing only; "
                    "dynamic routing modes need the dense engine "
                    "(2-tier topologies)")
            if any_msg:
                raise ValueError("sparse incidence does not support the "
                                 "message layer; use the dense engine")
            if any_flt:
                raise ValueError("sparse incidence does not support "
                                 "FaultConfig injection; use the dense "
                                 "engine")
            pack_fail = dyn         # only failure/flap schedules remain
            dyn = False
        elif pods:
            raise ValueError(
                "3-level (super-spine) topologies need the sparse-"
                "incidence engine: run_fabric_sweep(..., "
                "incidence='auto' or 'sparse')")
        if any_msg:
            for s in scens:
                for m in msg_of(s):
                    if m is not None and m.window is None:
                        raise ValueError(
                            "MessageConfig.window=None (unbounded) is "
                            "scalar-only; the vector engines carry "
                            "message starts in a fixed ring — set a "
                            "finite window or use run_fabric")
        for s in scens:
            s.topology.validate()
            if s.fabric.dt_us != dt or \
                    int(s.fabric.sim_time_s * 1e6 / s.fabric.dt_us) != ticks:
                raise ValueError("grid points must share dt and sim_time")
            if len(s.flows) != F or any(
                    (a.src, a.dst, a.tag, a.qos)
                    != (b.src, b.dst, b.tag, b.qos)
                    for a, b in zip(s.flows, flows0)):
                raise ValueError("grid points must share the flow set "
                                 "(src/dst/tag/qos); offered/burst/start "
                                 "may vary")
        wirings = 0
        if not dyn:
            # static fast path: routes are frozen structure and must
            # agree.  Topology.route reads only the wiring, so a point
            # wired like one already checked has the same routes by
            # construction; each new wiring re-derives every flow's route
            routes = [topo0.route(f.src, f.dst, fid)
                      for fid, f in enumerate(flows0)]
            checked = [topo0]
            for s in scens:
                tt = s.topology
                if any(_same_wiring(tt, w) for w in checked):
                    continue
                if any(tt.route(f.src, f.dst, fid) != routes[fid]
                       for fid, f in enumerate(s.flows)):
                    raise ValueError("grid points must share routes (same "
                                     "topology structure)")
                checked.append(tt)
            wirings = len(checked)
        else:
            # dynamic-routing land: routes are per-tick state, so only the
            # node/link *structure* must agree; routing mode and failure
            # schedules are per-point parameters
            for s in scens:
                tt = s.topology
                if (sorted(tt.links) != sorted(topo0.links)
                        or tt.host_leaf != topo0.host_leaf
                        or tt.spines != topo0.spines
                        or tt.leaves != topo0.leaves):
                    raise ValueError(
                        "grid points must share topology structure "
                        "(nodes and links); link rates, failure "
                        "schedules and routing mode may vary")

        # ---- ports on some flow's path, tagged with their stage ---------- #
        port_id: Dict[Tuple[str, str], int] = {}
        port_stage: List[int] = []

        def add(key, stage):
            pid = port_id.setdefault(key, len(port_id))
            if pid == len(port_stage):
                port_stage.append(stage)
            elif port_stage[pid] != stage:
                raise ValueError(f"port {key} used in two stages")
            return pid

        Sn = len(topo0.spines)
        cols = np.arange(F)
        upP = dnP = candS = crossF = T1 = init_spine = None
        port_of = prv_port = nxt_slot = None
        pause_extra = pausable_extra = None
        if sparse:
            # six tier-ordered stage slots; each flow occupies the slots
            # of its frozen route (2/4/6 hops) and every port belongs to
            # exactly one slot, so per-(port, TC) totals are segment
            # sums over the S*F (slot, flow) entries instead of [P, F]
            # one-hot products — cost grows with flows x hops, not
            # flows x ports
            slot_of = {3: (0, 5), 5: (0, 1, 4, 5), 7: tuple(range(6))}
            stage_ports = np.full((_STAGES_SP, F), -1, np.int64)
            for fid, nodes in enumerate(routes):
                slots = slot_of.get(len(nodes))
                if slots is None:
                    raise ValueError(
                        f"unsupported route length {len(nodes)}")
                for sl_i, hop in zip(slots, zip(nodes, nodes[1:])):
                    stage_ports[sl_i, fid] = add(hop, sl_i)
            # scalar twin under failure schedules: run_fabric treats a
            # shallow (intra-pod, multi-candidate) flow as rerouteable,
            # so its last-hop queue pauses the whole candidate downlink
            # set and every candidate hop joins the pausable ports
            # (OutputPort.static_ingress semantics); deep super-spine
            # routes stay frozen exact chains in both drivers
            ex_f, ex_p, cand_ports = [], [], []
            if pack_fail:
                for fid, f in enumerate(flows0):
                    if len(routes[fid]) != 5:
                        continue
                    paths = topo0.candidate_paths(f.src, f.dst)
                    if len(paths) <= 1:
                        continue
                    frozen_dn = stage_ports[4, fid]
                    for pth in paths:
                        pu = add((pth[0], pth[1]), 1)
                        pd = add((pth[1], pth[2]), 4)
                        cand_ports += [pu, pd]
                        if pd != frozen_dn:
                            ex_f.append(fid)
                            ex_p.append(pd)
            if ex_f:
                pause_extra = np.array([ex_f, ex_p], np.int32)
            if cand_ports:
                pausable_extra = np.array(sorted(set(cand_ports)),
                                          np.int32)
            P = len(port_id)
            port_keys = list(port_id)
            port_of = np.where(stage_ports >= 0, stage_ports,
                               P).astype(np.int32)
            prv_port = np.full((_STAGES_SP, F), P, np.int32)
            nxt_slot = np.full((_STAGES_SP, F), _STAGES_SP, np.int32)
            for fid in range(F):
                used = np.flatnonzero(stage_ports[:, fid] >= 0)
                for a, b in zip(used, used[1:]):
                    nxt_slot[a, fid] = b
                    prv_port[b, fid] = stage_ports[a, fid]
            occ, dest = [], []
            prev_onehot = np.zeros((0, F, 0))
        elif not dyn:
            stage_ports = np.full((_STAGES, F), -1, np.int32)
            prev_port = np.full((_STAGES, F), -1, np.int32)
            for fid, nodes in enumerate(routes):
                if len(nodes) == 3:                   # intra-leaf
                    src, leaf, dst = nodes
                    p0 = add((src, leaf), 0)
                    p3 = add((leaf, dst), 3)
                    stage_ports[0, fid], stage_ports[3, fid] = p0, p3
                    prev_port[3, fid] = p0
                else:                                 # via one spine
                    src, sl, spine, dl, dst = nodes
                    p0 = add((src, sl), 0)
                    p1 = add((sl, spine), 1)
                    p2 = add((spine, dl), 2)
                    p3 = add((dl, dst), 3)
                    stage_ports[:, fid] = (p0, p1, p2, p3)
                    prev_port[1, fid], prev_port[2, fid], \
                        prev_port[3, fid] = p0, p1, p2
            P = len(port_id)
            port_keys = list(port_id)

            def onehot(idx):                          # [P, F] from [F] ids
                oh = np.zeros((P, F))
                valid = idx >= 0
                oh[idx[valid], cols[valid]] = 1.0
                return oh

            occ = [onehot(stage_ports[k]) for k in range(_STAGES)]
            # destination port after stages 0..2 (stage 3 -> receivers)
            d0 = np.where(stage_ports[1] >= 0, stage_ports[1],
                          stage_ports[3])
            dest = [onehot(d0), onehot(stage_ports[2]),
                    onehot(stage_ports[3])]
            prev_onehot = np.zeros((P, F, P))
            for k in range(1, _STAGES):
                for fid in range(F):
                    p, pr = stage_ports[k, fid], prev_port[k, fid]
                    if p >= 0 and pr >= 0:
                        prev_onehot[p, fid, pr] = 1.0
        else:
            # every candidate uplink/downlink joins the port set; the
            # per-tick routing weights decide where bytes actually go
            hl = topo0.host_leaf
            stage0 = np.full(F, -1, np.int64)
            stage3 = np.full(F, -1, np.int64)
            up_ids = np.full((Sn, F), -1, np.int64)
            dn_ids = np.full((Sn, F), -1, np.int64)
            for fid, f in enumerate(flows0):
                sl, dl = hl[f.src], hl[f.dst]
                if f.src == f.dst:
                    raise ValueError("flow endpoints must differ")
                stage0[fid] = add((f.src, sl), 0)
                if sl == dl:
                    stage3[fid] = add((sl, f.dst), 3)
                else:
                    if not Sn:
                        raise ValueError(f"no spine connects {sl}->{dl}")
                    for si, sp in enumerate(topo0.spines):
                        up_ids[si, fid] = add((sl, sp), 1)
                        dn_ids[si, fid] = add((sp, dl), 2)
                    stage3[fid] = add((dl, f.dst), 3)
            P = len(port_id)
            port_keys = list(port_id)

            def onehot(idx):
                oh = np.zeros((P, F))
                valid = idx >= 0
                oh[idx[valid], cols[valid]] = 1.0
                return oh

            candS = up_ids >= 0
            crossF = candS.any(0) if Sn else np.zeros(F, bool)
            occ1 = np.zeros((P, F))
            occ2 = np.zeros((P, F))
            upP = np.zeros((Sn, F, P))
            dnP = np.zeros((Sn, F, P))
            T1 = np.zeros((P, F, P))
            prev_onehot = np.zeros((P, F, P))
            for fid in range(F):
                p0, p3 = stage0[fid], stage3[fid]
                if crossF[fid]:
                    for si in range(Sn):
                        pu, pd = up_ids[si, fid], dn_ids[si, fid]
                        occ1[pu, fid] = occ2[pd, fid] = 1.0
                        upP[si, fid, pu] = dnP[si, fid, pd] = 1.0
                        T1[pu, fid, pd] = 1.0
                        prev_onehot[pu, fid, p0] = 1.0
                        prev_onehot[pd, fid, pu] = 1.0
                        # a rerouted/sprayed flow's bytes at the host
                        # port have mixed provenance: pause targeting
                        # covers the whole candidate set (same contract
                        # as OutputPort.static_ingress in the scalar
                        # driver)
                        prev_onehot[p3, fid, pd] = 1.0
                else:
                    prev_onehot[p3, fid, p0] = 1.0
            occ = [onehot(stage0), occ1, occ2, onehot(stage3)]
            # dest[0] covers only intra-leaf flows (cross-leaf stage-0
            # output is routed by the per-tick weights); dest[1] is
            # replaced by the T1 map
            dest = [onehot(np.where(crossF, -1, stage3)),
                    np.zeros((P, F)), onehot(stage3)]
            init_spine = np.where(crossF, cols % max(Sn, 1), 0) \
                .astype(np.int32)

        R = len(recv_hosts)
        ridx = {h: i for i, h in enumerate(recv_hosts)}
        recv_of = np.array([ridx[f.dst] for f in flows0], np.int32)
        qos_of = np.array([int(f.qos) for f in flows0], np.int32)
        n_stages = _STAGES_SP if sparse else _STAGES
        stage_mask = np.zeros((n_stages, P), bool)
        for p, st in enumerate(port_stage):
            stage_mask[st, p] = True
        recv_onehot = np.zeros((R, F))
        recv_onehot[recv_of, cols] = 1.0
        owner_recv = np.full(P, -1, np.int32)
        for (a, b), pid in port_id.items():
            if port_stage[pid] == n_stages - 1:
                owner_recv[pid] = ridx[b]

        # ---- stacked per-point parameters -------------------------------- #
        G = len(scens)
        pv: Dict[str, List] = {k: [] for k in
                               ["gbps", "ecn_en", "can_assert",
                                "line", "cap", "burst", "start", "cnp_iv_f",
                                "d_base", "d_strag", "cnp_dly", "clsF",
                                "on_us", "off_us", "fail_at", "fail_until",
                                "rmode", "flet", "hystb", "settle",
                                "sched", "quanta", "hpfc",
                                "m_bytes", "m_win", "m_extra", "cc_algo",
                                "f_salt", "f_thr", "f_cthr", "f_mtu",
                                "flap_start", "flap_period", "flap_down",
                                "crash_at", "crash_until", "rec_en",
                                "rec_sel", "rto_ticks", "nack_ticks",
                                "rto_cap", "rto_mult"]}
        for name, _ in _RECV_SCALARS + _DCQCN_SCALARS + _SWITCH_SCALARS \
                + _SWITCH_TC + _CC_SCALARS:
            pv[name] = []
        # switch traffic class of each flow as a [Q, F] one-hot, built
        # once from flows0: the structure check above rejects grids
        # whose points disagree on Flow.qos.  Legacy per-link points
        # collapse every flow onto TC 0 (one queue, one watermark pair
        # — exactly the pre-per-TC pause semantics)
        cls_true = np.zeros((N_QOS, F))
        cls_true[[int(f.qos) for f in flows0], np.arange(F)] = 1.0
        cls_legacy = np.zeros((N_QOS, F))
        cls_legacy[0, :] = 1.0
        for s in scens:
            topo, sw = s.topology, s.fabric.switch
            for name, fn in _SWITCH_SCALARS:
                pv[name].append(fn(sw))
            for name, fn in _SWITCH_TC:
                pv[name].append([fn(sw, tc) for tc in range(N_QOS)])
            pv["clsF"].append(cls_true if sw.per_tc else cls_legacy)
            pv["gbps"].append([topo.links[k].gbps for k in port_keys])
            is_switch = np.array(port_stage) > 0
            pv["ecn_en"].append(is_switch * float(sw.ecn_enabled))
            pv["can_assert"].append(is_switch * float(sw.pfc_enabled))
            rcfgs = {h: s.fabric.receiver_cfg(h) for h in recv_hosts}
            for h, c in rcfgs.items():
                if c.cpu_membw_schedule is not None:
                    raise ValueError("cpu_membw_schedule is not sweepable; "
                                     "use run_fabric for scheduled "
                                     "contention")
                if c.host_pfc_per_tc and not sw.per_tc:
                    # same contract as run_fabric: the per-class gate
                    # needs classes to exist on the wire
                    raise ValueError("host_pfc_per_tc requires "
                                     "SwitchConfig.per_tc")
            for name, fn in _RECV_SCALARS:
                pv[name].append([fn(rcfgs[h]) for h in recv_hosts])
            d_b, d_s = [], []
            for h in recv_hosts:
                c = rcfgs[h]
                hold = hold_us_jet(c) if c.mode == "jet" \
                    else hold_us_baseline(c)
                d_b.append(max(1, int(hold / dt)))
                d_s.append(max(1, int(hold * c.straggler_mult / dt)))
            pv["d_base"].append(d_b)
            pv["d_strag"].append(d_s)
            # per-flow NP->RP propagation delay (Flow override, falling
            # back to the FabricConfig scalar)
            pv["cnp_dly"].append([
                max(0, int(round(
                    (f.cnp_delay_us if f.cnp_delay_us is not None
                     else s.fabric.cnp_delay_us) / dt)))
                for f in s.flows])
            rc = s.fabric.routing
            if dyn or pack_fail:
                ft = s.topology.failure_ticks(dt)
                nv = (NEVER_TICK, NEVER_TICK)
                pv["fail_at"].append([ft.get(k, nv)[0] for k in port_keys])
                pv["fail_until"].append([ft.get(k, nv)[1]
                                         for k in port_keys])
            if dyn:
                pv["rmode"].append(rc.mode_code())
                pv["flet"].append(max(1, int(round(rc.flowlet_gap_us
                                                   / dt))))
                pv["hystb"].append(rc.hysteresis_frac
                                   * sw.port_buffer_bytes)
                stl = int(round(rc.spray_settle_us / dt)) \
                    if rc.mode == "spray" else 0
                pv["settle"].append([stl if crossF[fid] else 0
                                     for fid in range(F)])
            if any_wrr:
                pv["sched"].append(1 if sw.scheduler == "wrr" else 0)
                pv["quanta"].append(list(sw.quanta()))
            if host_tc:
                pv["hpfc"].append([
                    1.0 if (sw.per_tc and rcfgs[h].host_pfc_per_tc)
                    else 0.0 for h in recv_hosts])
            line = [s.topology.access_gbps(f.src) for f in s.flows]
            pv["line"].append(line)
            msgs, ccs = msg_of(s), cc_of(s)
            # the per-op issue gap is one more rate ceiling (the Mops
            # plateau): folded into the offered cap — min() is order-free,
            # so this matches SenderHost.offer's separate clamp exactly
            pv["cap"].append([
                min(np.inf if f.offered_gbps is None else f.offered_gbps,
                    np.inf if m is None else m.op_rate_gbps)
                for f, m in zip(s.flows, msgs)])
            if any_msg:
                # m_bytes=inf disables the layer per flow: zero messages
                # ever start or complete and the window room is infinite
                pv["m_bytes"].append([np.inf if m is None
                                      else float(m.msg_bytes)
                                      for m in msgs])
                pv["m_win"].append([1.0 if m is None else float(m.window)
                                    for m in msgs])
                pv["m_extra"].append([0.0 if m is None else m.extra_us
                                      for m in msgs])
            if any_cc:
                cl = [c if c is not None else _CC_DEFAULT for c in ccs]
                pv["cc_algo"].append([c.code() for c in cl])
                for name, fn in _CC_SCALARS:
                    pv[name].append([fn(c) for c in cl])
            pv["burst"].append([np.inf if f.burst_bytes is None
                                else f.burst_bytes for f in s.flows])
            pv["start"].append([f.start_us for f in s.flows])
            pv["on_us"].append([np.inf if f.on_off_us is None
                                else f.on_off_us[0] for f in s.flows])
            pv["off_us"].append([0.0 if f.on_off_us is None
                                 else f.on_off_us[1] for f in s.flows])
            pv["cnp_iv_f"].append([rcfgs[f.dst].cnp_interval_us
                                   for f in s.flows])
            # a CcConfig(algo="dcqcn") carrying a DcqcnConfig override
            # replaces the per-line-rate defaults (make_controller)
            dcq = [c.dcqcn if (c is not None and c.algo == "dcqcn"
                               and c.dcqcn is not None)
                   else DcqcnConfig(line_rate_gbps=lr)
                   for c, lr in zip(ccs, line)]
            for name, fn in _DCQCN_SCALARS:
                pv[name].append([fn(d) for d in dcq])
            if any_flap:
                fl = topo.flap_ticks(dt)
                nf = (NEVER_TICK, 2, 1)
                pv["flap_start"].append([fl.get(k, nf)[0]
                                         for k in port_keys])
                pv["flap_period"].append([fl.get(k, nf)[1]
                                          for k in port_keys])
                pv["flap_down"].append([fl.get(k, nf)[2]
                                        for k in port_keys])
            if any_flt:
                # fault layer: per-port hash salts/thresholds, crash
                # windows per receiver, per-flow recovery knobs — a
                # faults-None point packs never-firing values and
                # mtu=inf, so its dropped_pkts stays exactly 0
                ff = s.fabric.faults
                if ff is None:
                    pv["f_salt"].append([0] * P)
                    pv["f_thr"].append([0] * P)
                    pv["f_cthr"].append([0] * P)
                    pv["crash_at"].append([NEVER_TICK] * R)
                    pv["crash_until"].append([NEVER_TICK] * R)
                    pv["f_mtu"].append(np.inf)
                else:
                    pv["f_salt"].append([link_salt(a, b, ff.seed)
                                         for a, b in port_keys])
                    pv["f_thr"].append([loss_threshold(ff.rate_for(a, b))
                                        for a, b in port_keys])
                    # corruption (CRC fail) only on receiver access links
                    pv["f_cthr"].append([
                        loss_threshold(ff.corrupt_rate) if b in ridx
                        else 0 for a, b in port_keys])
                    ca, cu = [NEVER_TICK] * R, [NEVER_TICK] * R
                    for ch, (a_us, r_us) in ff.crashes.items():
                        if ch not in ridx:
                            raise ValueError(
                                f"crash scheduled on {ch!r}, which is "
                                "not a receiver in this fabric")
                        at = max(0, int(round(a_us / dt)))
                        ca[ridx[ch]] = at
                        cu[ridx[ch]] = max(at + 1, int(round(r_us / dt)))
                    pv["crash_at"].append(ca)
                    pv["crash_until"].append(cu)
                    pv["f_mtu"].append(ff.mtu_bytes)
                # recovery ledgers engage per flow iff a FaultConfig is
                # attached AND the flow carries a MessageConfig — same
                # rule as run_fabric
                pv["rec_en"].append([
                    1.0 if (ff is not None and m is not None) else 0.0
                    for m in msgs])
                pv["rec_sel"].append([
                    1.0 if (m is not None and m.recovery == "selective")
                    else 0.0 for m in msgs])
                pv["rto_ticks"].append([
                    1 if m is None else max(1, int(round(m.rto_us / dt)))
                    for m in msgs])
                pv["nack_ticks"].append([
                    1 if m is None else max(1, int(round(m.nack_us / dt)))
                    for m in msgs])
                pv["rto_cap"].append([0 if m is None else int(m.rto_cap)
                                      for m in msgs])
                pv["rto_mult"].append([1.0 if m is None
                                       else float(m.rto_backoff)
                                       for m in msgs])
        pvals = {k: np.asarray(v, np.int32 if k in _INT_KEYS
                               else np.float64)
                 for k, v in pv.items() if v}
        H = int(max(pvals["d_base"].max(), pvals["d_strag"].max())) + 2
        Hc = int(pvals["cnp_dly"].max()) + 1
        Hs = int(pvals["settle"].max()) + 1 if dyn else 1
        # message start-time ring: the window bound keeps outstanding
        # <= W+1; +4 leaves slack for float32 count jitter at boundaries
        Lm = int(pvals["m_win"].max()) + 4 if any_msg else 1
        # chunk-boundary envelope: ring horizons are grid maxima, so a
        # chunk's rings are floored at the enclosing grid's to share the
        # monolithic program's shapes (a longer ring is semantically
        # inert — unread slots hold zeros)
        H = max(H, int(env.get("ring_len", 0)))
        Hc = max(Hc, int(env.get("cnp_ring", 0)))
        if dyn:
            Hs = max(Hs, int(env.get("settle_ring", 0)))
        if any_msg:
            Lm = max(Lm, int(env.get("msg_ring", 0)))

        h = hashlib.sha1()
        extras = [a for a in (upP, dnP, candS, crossF, T1, init_spine,
                              port_of, prv_port, nxt_slot,
                              pause_extra, pausable_extra)
                  if a is not None]
        for arr in (stage_mask, *occ, *dest, recv_onehot, recv_of, qos_of,
                    prev_onehot, owner_recv, *extras):
            h.update(np.ascontiguousarray(arr).tobytes())
        h.update(repr((F, P, R, ticks, dt, H, Hc, Hs, Sn, dyn, any_wrr,
                       host_tc, any_cc, any_msg, Lm, any_flt,
                       any_flap, sparse, pack_fail)).encode())
        return cls(port_keys=port_keys, recv_hosts=recv_hosts,
                   flow_tags=[f.tag for f in flows0],
                   stage_mask=stage_mask, occ=occ, dest=dest,
                   recv_onehot=recv_onehot, recv_of=recv_of, qos_of=qos_of,
                   prev_onehot=prev_onehot, owner_recv=owner_recv,
                   pvals=pvals, n_points=G, n_flows=F, n_ports=P, n_recv=R,
                   ticks=ticks, dt_us=dt, ring_len=H, cnp_ring=Hc,
                   structure_key=h.hexdigest(),
                   upP=upP, dnP=dnP, candS=candS, crossF=crossF, T1=T1,
                   init_spine=init_spine, dyn_route=dyn, any_wrr=any_wrr,
                   host_tc=host_tc, settle_ring=Hs,
                   n_spines=Sn if dyn else 0,
                   any_cc=any_cc, any_msg=any_msg, msg_ring=Lm,
                   any_flt=any_flt, any_flap=any_flap,
                   sparse=sparse, port_of=port_of, prv_port=prv_port,
                   nxt_slot=nxt_slot, pack_fail=pack_fail,
                   pause_extra=pause_extra,
                   pausable_extra=pausable_extra, wirings=wirings)


# --------------------------------------------------------------------------- #
# The shared per-tick step (numpy [G, ...] and jax vmapped [...])
# --------------------------------------------------------------------------- #
def _contractions(xp):
    """``(matmul, einsum)`` for namespace ``xp``.  The jax versions run
    at ``HIGHEST`` precision: at default precision the TPU computes f32
    products in bf16 passes, and the one-hot products below move byte
    counts (~1e6) that bf16 would round by ~0.4%.  numpy has no
    precision argument, and on CPU the f32 product is already exact."""
    if xp is np:
        return np.matmul, np.einsum
    import jax
    hi = jax.lax.Precision.HIGHEST
    return (functools.partial(xp.matmul, precision=hi),
            functools.partial(xp.einsum, precision=hi))


def _make_step(xp, ring_set, st, p, dt: float, H: int, dtype, Hc: int = 1,
               opts: Optional[dict] = None):
    """Build ``step(state, t) -> state`` in array namespace ``xp``.

    ``st`` holds the static structure arrays (no grid axis), ``p`` the
    per-point parameters ([G, ...] under numpy, [...] under vmap).  All
    array ops broadcast over an optional leading grid axis, so the same
    closure is the numpy reference and the vmapped jax program.

    Queued bytes and their ECN-marked subset travel together as one
    ``[2, P, F]`` array (axis -3: 0 = bytes, 1 = marks) and the two
    release rings as one ``[2, R, H]`` array — on the CPU backend per-op
    dispatch dominates at these shapes, so halving the op count nearly
    halves the tick.  Per-point constants are hoisted out of the scan
    body for the same reason.

    ``opts`` carries the trace-time capability flags from
    :class:`FabricSweepParams` (``dyn`` routing, ``wrr`` scheduling,
    ``host_tc`` receiver PFC, ``Hs`` spray-settle ring, ``Sn`` spines,
    ``flt`` fault injection + recovery, ``flap`` link-flap schedules):
    with everything off this builds exactly the pre-routing-layer
    program, so static grids stay bit-identical and pay nothing.
    """
    o = opts or {}
    dyn, wrr = o.get("dyn", False), o.get("wrr", False)
    host_tc, Hs = o.get("host_tc", False), o.get("Hs", 1)
    Sn = o.get("Sn", 0)
    any_cc, any_msg = o.get("cc", False), o.get("msg", False)
    Lm = o.get("Lm", 1)
    flt, flap = o.get("flt", False), o.get("flap", False)
    # fused-kernel tier for the two priority water-fills ("ref" is the
    # inline formulation; "pallas"/"interpret" need the jnp namespace)
    impl = o.get("impl", "ref") if xp is not np else "ref"
    mm, es = _contractions(xp)
    f = dtype
    bpt = f(1e9 / 8.0 * dt * 1e-6)       # bytes per (Gbps * tick)
    fdt = f(dt)
    zero, one, tiny = f(0.0), f(1.0), f(1e-30)
    half, inf = f(0.5), f(np.inf)
    eps_q = f(1e-9)
    arangeF = xp.arange(st["recv_of"].shape[0], dtype=xp.int32)
    # loop-invariant per-point quantities, computed once outside the scan
    budget = p["gbps"] * bpt
    budget_crumb = budget * f(1e-6)
    budgetP = budget                     # step() shadows `budget` locally
    buf = p["buf"][..., None]
    # switch traffic classes: clsF is the per-point [Q, F] flow->TC
    # one-hot (all flows on TC 0 for legacy per-link points); the per-TC
    # knee/watermark thresholds broadcast as [.., Q, 1] against [.., Q, P]
    clsF = p["clsF"]
    buf_tc = p["buf"][..., None, None]
    kmin_th = p["kmin"][..., None] * buf_tc
    ecn_on = p["ecn_en"] > 0.5
    can_assert = p["can_assert"] > 0.5
    sxoff = p["sw_xoff"][..., None]
    sxon = p["sw_xon"][..., None]
    # on-off burst trains: sources offer only while the duty-cycle phase
    # is inside the on-window (off_us == 0 means always on)
    onoff = p["off_us"] > zero
    period = xp.where(onoff, p["on_us"] + p["off_us"], one)
    jet = p["jet"] > 0.5
    avail_dram = xp.maximum(zero, p["membw"] - p["cpu_bw"])
    jet_cap = xp.minimum(p["pcie"], p["line1"] * 4.0) * bpt
    strag_share = xp.where(jet, p["sfrac"], zero)
    inv_knee = one / (p["knee"] * p["ddio"])
    rx_pfc_en = p["pfc_en"] > 0.5
    wm_en = p["wm_cnp"] > 0.5
    linecap = xp.minimum(p["line"], p["cap"])
    if wrr:
        quantaQ = p["quanta"][..., None]            # [.., Q, 1]
        is_wrr = (p["sched"] == 1)[..., None, None]  # [.., 1, 1]
    if host_tc:
        hpfc_b = (p["hpfc"] > half)[..., None, :]   # [.., 1, R]
        rx_pfc_tc = rx_pfc_en[..., None, :]
        xoffQ = p["xoff"][..., None, :]
        xonQ = p["xon"][..., None, :]
    if dyn and Sn:
        bufSF = p["buf"][..., None, None]           # vs [.., S, F]
        hystF = p["hystb"][..., None]               # vs [.., F]
        arangeS = xp.arange(Sn, dtype=xp.int32)[:, None]
    if any_cc:
        # algorithm lanes (CcConfig.code: 0 dcqcn, 1 timely, 2 hpcc)
        is_dcqcn = p["cc_algo"] == 0
        timely_m = p["cc_algo"] == 1
        hpcc_m = p["cc_algo"] == 2
        inv_brtt = one / p["base_rtt"]              # [.., F]
        u_floor = f(0.01)
    if any_msg:
        arangeL = xp.arange(Lm, dtype=xp.int32)[:, None]       # [L, 1]
        arangeB = xp.arange(HIST_BUCKETS, dtype=xp.int32)[:, None, None]
        hist_lo = f(HIST_MIN_US)
        inv_lr = f(1.0 / np.log(hist_ratio()))
        eps_m = f(MSG_COUNT_EPS)
        wbytes = p["m_win"] * p["m_bytes"]          # window, in bytes
    if flt:
        # fault layer (repro.fabric.faults): per-flow recovery masks and
        # the per-port counter-hash salts.  The scalar hash is
        # ((t+1)*M + (salt+1)*9973) % 65536; here the tick multiplier is
        # applied as a split modmul — (t+1) reduced mod 65536 then split
        # into hi/lo bytes, with 256*40503 % 65536 = 14080 and
        # 256*24593 % 65536 = 4352 — so every intermediate product stays
        # far inside int32 at any tick count, and all three engines see
        # bit-identical fault realizations
        rec_en = p["rec_en"]                        # exact 1.0 / 0.0
        rec_keep = one - rec_en
        sel_b = p["rec_sel"] > half
        gbn_b = (rec_en > half) & ~sel_b
        saltp = (p["f_salt"] + 1) * 9973 % 65536    # [.., P]
        rto_f = p["rto_ticks"].astype(dtype)

        def ledger(s, lost_f):
            """Route per-flow lost bytes [.., F]: the fluid core's
            instant re-credit, or the recovery ledger where engaged
            (run_fabric's ``lose()``); go-back-N losses gap the
            receiver window."""
            s["inj_lo"] = s["inj_lo"] - lost_f * rec_keep
            s["lost"] = s["lost"] + lost_f * rec_en
            s["gapped"] = s["gapped"] | (gbn_b & (lost_f > zero))

    def cut(s, fire):
        """DCQCN on_cnp for flows where ``fire`` holds."""
        s = dict(s)
        s["rt"] = xp.where(fire, s["rc"], s["rt"])
        s["rc"] = xp.where(
            fire, xp.maximum(p["minr"], s["rc"] * (1.0 - s["alpha"] / 2.0)),
            s["rc"])
        s["alpha"] = xp.where(
            fire, xp.minimum(one, (1.0 - p["g"]) * s["alpha"] + p["g"]),
            s["alpha"])
        for k in ("t_us", "byts", "t_stage", "b_stage", "a_tus"):
            s[k] = xp.where(fire, zero, s[k])
        return s

    def class_tot(q0):
        """Per-(port, TC) occupancy [.., Q, P] from per-flow bytes
        [.., P, F] — one small matmul with the class one-hot."""
        return mm(clsF, xp.swapaxes(q0, -1, -2))

    def drain(s, k, upf=None):
        """Stage-k ports forward up to rate*dt: per-class budget grants
        (strict priority unrolled over Q, or WRR water-filling where a
        point schedules it), pro rata across the flows of a class.
        ``upf`` zeroes the budget of dead links.  Returns the per-(port,
        flow) drained tensor ``out`` [.., 2, P, F] — dynamic routing
        needs the port-level provenance at the uplink stage."""
        qm = s["qm"]
        q0 = qm[..., 0, :, :]
        qtc = class_tot(q0)                       # [.., Q, P]
        budget0 = budget if upf is None else budget * upf
        # strict-priority budget grants as one fused water-fill stage:
        # each class takes min(1, left/demand), leftover budget below
        # 1e-6 of the link budget clamps to zero (rounding crumbs after
        # a class eats the whole budget must not become micro-byte
        # trickles for the next class — they would trigger full-size
        # discrete CNPs downstream); relative, so f32 and f64 backends
        # agree with the scalar driver on every grant/no-grant decision
        # (OutputPort.drain).  The ref tier is op for op the unrolled
        # loop it replaced; pallas/interpret run the VMEM kernel.
        can_q = st["stage"][k] & ~s["paused"] & (qtc > zero)  # [.., Q, P]
        frac_q = fused.priority_grants(
            xp, qtc, can_q if impl == "ref"
            else xp.where(can_q, one, zero),
            budget0, budget_crumb, one, zero, impl=impl)
        if wrr:
            # weighted water-filling over backlogged unpaused classes,
            # unrolled Q rounds with the exact op order of
            # OutputPort._wrr_fracs (float64 reference == scalar driver)
            rem = xp.where(can_q, qtc, zero)
            alloc = xp.zeros_like(qtc)
            bl = budget0
            for _ in range(N_QOS):
                wq = xp.where(rem > zero, quantaQ, zero)
                wsum = wq.sum(-2)                 # [.., P]
                share = bl[..., None, :] * wq \
                    / xp.maximum(wsum, tiny)[..., None, :]
                take = xp.minimum(share, rem)
                alloc = alloc + take
                rem = rem - take
                bl = bl - take.sum(-2)
                bl = xp.where(bl < budget_crumb, zero, bl)
            frac_wrr = xp.where(qtc > zero,
                                alloc / xp.maximum(qtc, tiny), zero)
            frac_q = xp.where(is_wrr, frac_wrr, frac_q)
        # scatter per-class grants to (port, flow); one class per flow,
        # so the matmul contraction has a single nonzero term
        frac_pf = mm(xp.swapaxes(frac_q, -1, -2), clsF)
        can_pf = mm(xp.swapaxes(xp.where(can_q, one, zero),
                                       -1, -2), clsF)
        out = qm * frac_pf[..., None, :, :]
        qm = qm - out
        # sub-1e-9 residues vanish with their marks (the scalar driver's
        # dict-entry cleanup, per drained class)
        gone = (can_pf > half) & (qm[..., 0, :, :] < eps_q)
        s["qm"] = xp.where(gone[..., None, :, :], zero, qm)
        return s, out

    def enqueue(s, A):
        """Batch-enqueue routed arrivals ``A`` [.., 2, P, F]:
        proportional split of each class's buffer partition, one ECN
        knee decision per (port, TC) against that class's pre-batch
        occupancy."""
        q0 = s["qm"][..., 0, :, :]
        qtc = class_tot(q0)                       # [.., Q, P] pre-batch
        tot_q = class_tot(A[..., 0, :, :])
        space_q = xp.maximum(buf_tc - qtc, zero)
        scale_q = xp.where(tot_q > space_q,
                           space_q / xp.maximum(tot_q, tiny), one)
        scale_pf = mm(xp.swapaxes(scale_q, -1, -2), clsF)
        take = A * scale_pf[..., None, :, :]
        lost = (A - take)[..., 0, :, :]
        # fluid go-back-N: tail-dropped bytes re-open the sender's tap
        # (or wait in the recovery ledger where it is engaged)
        if flt:
            ledger(s, lost.sum(-2))
        else:
            s["inj_lo"] = s["inj_lo"] - lost.sum(-2)
        s["sw_dropped"] = s["sw_dropped"] + lost.sum((-1, -2))
        mark_q = ecn_on[..., None, :] & (qtc > kmin_th)
        mark_pf = mm(xp.swapaxes(xp.where(mark_q, one, zero),
                                        -1, -2), clsF)        # [.., P, F]
        dm = xp.where(mark_pf > half,
                      take[..., 0, :, :] - take[..., 1, :, :], zero)
        s["ecn_marked"] = s["ecn_marked"] + dm.sum((-1, -2))
        s["qm"] = s["qm"] + take + dm[..., None, :, :] * st["sel1"]
        return s

    fold_at = f(65536.0)

    def fold(s, hi, lo):
        """Drain a split accumulator's low part into its high part once it
        outgrows 64 KiB.  Keeping per-tick increments on a small-magnitude
        accumulator bounds float32 rounding drift to O(10) bytes over a
        run — tight enough that closed-flow completion thresholds stay
        meaningful — while costing three element-wise ops per tick."""
        full = xp.abs(s[lo]) >= fold_at
        s[hi] = s[hi] + xp.where(full, s[lo], zero)
        s[lo] = xp.where(full, zero, s[lo])

    def step(s, t, it=None):
        # ``t`` is the simulated tick (timers, event windows, fault
        # hashes); ``it`` the iteration counter indexing the slot-major
        # delay rings.  The fine-tick backends pass it = t (identical
        # expressions, so the scan program is unchanged); the adaptive
        # backends advance t by the macro stride while it steps by one,
        # keeping ring writes/reads dense — a delay of d ticks becomes
        # d iterations, exact whenever the stride is 1 and within the
        # documented coarsening bound otherwise.
        if it is None:
            it = t
        s = dict(s)
        now = (xp.asarray(t, dtype) + one) * fdt
        fold(s, "injected", "inj_lo")
        fold(s, "delivered", "deliv_lo")

        # ---- 0. link failure / flap / crash events ------------------------ #
        upf = None
        D0 = None
        route_oh = None
        if dyn:
            downP = (t >= p["fail_at"]) & (t < p["fail_until"])   # [.., P]
            edgeP = t == p["fail_at"]
            if flap:
                # periodic flaps fold into the same down/edge masks
                # (Topology.flap_ticks: down for the first `down` ticks
                # of each `period` cycle from `start`)
                since = t - p["flap_start"]
                live = t >= p["flap_start"]
                downP = downP | (live
                                 & (since % p["flap_period"]
                                    < p["flap_down"]))
                edgeP = edgeP | (live & (since % p["flap_period"] == 0))
            upf = xp.where(downP, zero, one)
            failf = xp.where(edgeP, one, zero)
            # in-flight bytes die with the link; fluid go-back-N
            # re-credits them for retransmission (run_fabric step 0)
            lostF = (s["qm"][..., 0, :, :] * failf[..., :, None]).sum(-2)
            if flt:
                ledger(s, lostF)
                s["flt_drop"] = s["flt_drop"] + lostF.sum(-1)
            else:
                s["inj_lo"] = s["inj_lo"] - lostF
            s["sw_dropped"] = s["sw_dropped"] + lostF.sum(-1)
            s["qm"] = s["qm"] * (one - failf)[..., None, :, None]
        if flt:
            # NIC/host crash: everything queued on the crashed
            # receiver's access link dies and its admission state
            # zeroes (ReceiverHost.crash_reset); cumulative accounting
            # counters and the CNP pacing clock survive the crash
            crash_now = t == p["crash_at"]                        # [.., R]
            crashP = crash_now[..., st["owner_clamp"]] \
                & st["owner_valid"]                               # [.., P]
            deadQ = xp.where(crashP[..., None, :, None], s["qm"], zero)
            lostC = deadQ[..., 0, :, :].sum(-2)
            ledger(s, lostC)
            s["flt_drop"] = s["flt_drop"] + lostC.sum(-1)
            s["sw_dropped"] = s["sw_dropped"] + lostC.sum(-1)
            s["qm"] = s["qm"] - deadQ
            cz = xp.where(crash_now, zero, one)
            for ck in ("resident", "strag_res", "esc_debt", "repl_debt",
                       "repl_mem", "ecn_tus"):
                s[ck] = s[ck] * cz
            s["qos_q"] = s["qos_q"] * cz[..., None, :]
            s["ring"] = s["ring"] * cz[..., None, None, :]
            s["pfc"] = s["pfc"] & ~(crash_now[..., None, :] if host_tc
                                    else crash_now)
            s["heavy"] = xp.where(crash_now, -1, s["heavy"])
            # the cleared RNIC gate unpauses the access link this very
            # tick (the scalar driver reads rx.pfc_paused live in its
            # drain); switch-asserted pauses persist via the carried
            # link-pause mask
            s["paused"] = xp.where(crashP[..., None, :], s["lpause"],
                                   s["paused"])
            # stochastic loss/corruption: one counter hash per (link,
            # tick); when it fires, everything the port drains this
            # tick is lost on the wire (ECN marks die with the bytes)
            tr = (t + 1) % 65536
            thi, tlo = tr // 256, tr % 256
            hl = (thi * 14080 + tlo * 40503 + saltp) % 65536
            hc = (thi * 4352 + tlo * 24593 + saltp) % 65536
            dropP = (hl < p["f_thr"]) | (hc < p["f_cthr"])        # [.., P]

            def kill(s, out):
                """Apply this tick's stochastic drops to one drained
                stage [.., 2, P, F] — before tx accounting and
                forwarding, as run_fabric's drain loop."""
                dead = xp.where(dropP[..., None, :, None], out, zero)
                lost_k = dead[..., 0, :, :].sum(-2)
                ledger(s, lost_k)
                s["flt_drop"] = s["flt_drop"] + lost_k.sum(-1)
                return out - dead

        # ---- 1. senders: DCQCN advance + offer ---------------------------- #
        adv = now > p["start"]
        # the DCQCN timer machinery only moves DCQCN-lane flows; the CC
        # block after forwarding writes the timely/hpcc rates instead
        dadv = (adv & is_dcqcn) if any_cc else adv
        adv_dt = xp.where(dadv, fdt, zero)
        a_tus = s["a_tus"] + adv_dt
        a_fire = dadv & (a_tus >= p["a_tmr"])
        s["alpha"] = xp.where(a_fire, (1.0 - p["g"]) * s["alpha"],
                              s["alpha"])
        s["a_tus"] = xp.where(a_fire, zero, a_tus)
        t_us = s["t_us"] + adv_dt
        byts = xp.where(dadv, s["byts"] + s["rc"] * bpt, s["byts"])
        t_fire = dadv & (t_us >= p["r_tmr"])
        s["t_stage"] = s["t_stage"] + t_fire
        s["t_us"] = xp.where(t_fire, zero, t_us)
        b_fire = dadv & (byts >= p["bctr"])
        s["b_stage"] = s["b_stage"] + b_fire
        s["byts"] = xp.where(b_fire, zero, byts)
        fired = t_fire | b_fire
        stage = xp.minimum(s["t_stage"], s["b_stage"])
        s["rt"] = xp.where(fired & (stage == p["fth"]),
                           xp.minimum(p["dline"], s["rt"] + p["ai"]),
                           s["rt"])
        s["rt"] = xp.where(fired & (stage > p["fth"]),
                           xp.minimum(p["dline"], s["rt"] + p["hai"]),
                           s["rt"])
        s["rc"] = xp.where(fired,
                           xp.minimum(p["dline"],
                                      0.5 * (s["rc"] + s["rt"])),
                           s["rc"])

        gbps = xp.minimum(s["rc"], linecap)
        room = xp.maximum(p["burst"] - (s["injected"] + s["inj_lo"]), zero)
        # burst-train duty cycle: the DCQCN machine keeps running, the
        # tap only opens during the on-phase (matches SenderHost.offer)
        active = adv & (~onoff | (xp.fmod(now - p["start"], period)
                                  < p["on_us"]))
        offer = xp.where(active, xp.minimum(gbps * bpt, room), zero)
        if any_msg:
            # outstanding message window: injection never runs more than
            # W*msg_bytes ahead of delivery (start-of-tick counters, the
            # exact clamp SenderHost.offer applies via window_room)
            wroom = xp.maximum(
                wbytes - (s["injected"] + s["inj_lo"]
                          - s["delivered"] - s["deliv_lo"]), zero)
            offer = xp.minimum(offer, wroom)
        # source-side backpressure: the NIC queue never overflows, bytes
        # that don't fit in the flow's class partition stay un-injected
        off_pf = st["occ"][0] * offer[..., None, :]
        tot_q = class_tot(off_pf)                         # [.., Q, P]
        space_q = xp.maximum(
            buf_tc - class_tot(s["qm"][..., 0, :, :]), zero)
        scale_q = xp.where(tot_q > space_q,
                           space_q / xp.maximum(tot_q, tiny), one)
        scale_pf = mm(xp.swapaxes(scale_q, -1, -2), clsF)
        take_f = offer * (st["occ"][0] * scale_pf).sum(-2)
        s["inj_lo"] = s["inj_lo"] + take_f
        s["qm"] = s["qm"] + \
            (st["occ"][0] * take_f[..., None, :])[..., None, :, :] \
            * st["sel0"]

        # ---- 1.5 routing weights (after injection, as run_fabric) --------- #
        if dyn:
            if Sn:
                # idle-gap flowlet tracking (run_fabric step 1): a flow
                # injecting again after more than flowlet_gap ticks of
                # silence opens a new flowlet; a continuously-backlogged
                # flow never re-hashes (injection only touches NIC ports,
                # so the uplink occupancies read below are unaffected)
                act = take_f > zero
                boundary = act & ((t - s["flet_last"])
                                  > p["flet"][..., None])
                k_new = s["flet_k"] + boundary.astype(xp.int32)
                s["flet_k"] = k_new
                s["flet_last"] = xp.where(act, xp.asarray(t, xp.int32),
                                          s["flet_last"])
                # per-tick spine selection (run_fabric step 1.5): uplink
                # occupancy/up-state per candidate as [.., S, F] blocks
                occP = s["qm"][..., 0, :, :].sum(-1)              # [.., P]
                occS = es('sfp,...p->...sf', st["upP"], occP)
                up1 = es('sfp,...p->...sf', st["upP"], upf)
                up2 = es('sfp,...p->...sf', st["dnP"], upf)
                upS = st["candS"] & (up1 > half) & (up2 > half)
                free = xp.where(upS, xp.maximum(bufSF - occS, zero), zero)
                cur = s["route"]                                  # [.., F]
                cur_oh = arangeS == cur[..., None, :]             # [.., S, F]
                occ_cur = (occS * xp.where(cur_oh, one, zero)).sum(-2)
                up_cur = (upS & cur_oh).any(-2)
                any_up = upS.any(-2)
                # adaptive: least-congested up candidate + hysteresis
                occ_masked = xp.where(upS, occS, inf)
                best = xp.argmin(occ_masked, -2).astype(xp.int32)
                occ_best = occ_masked.min(-2)
                adapt = xp.where(
                    any_up & (~up_cur | (occ_best < occ_cur - hystF)),
                    best, cur)
                # weighted ECMP: flowlet-boundary (or dead-path) re-hash
                # against the free-space-weighted cumulative distribution;
                # thresholding against the cumsum's own last element keeps
                # the pick identical to routing.weighted_pick (modular
                # reduction of k keeps every product inside int32)
                kred = k_new % 65536
                hv = ((arangeF + 1) * 40503 + kred * 9973) % 65536
                hsh = hv.astype(dtype) / f(65536.0)               # [.., F]
                cum = xp.cumsum(free, -2)
                tot = cum[..., Sn - 1, :]                         # [.., F]
                pick = xp.argmax(cum > (hsh * tot)[..., None, :],
                                 -2).astype(xp.int32)
                repick = boundary | ~up_cur
                wec = xp.where(repick & (tot > zero), pick, cur)
                m = p["rmode"][..., None]                         # [.., 1]
                choice = xp.where(m == 2, adapt,
                                  xp.where(m == 1, wec, cur))
                s["reroutes"] = s["reroutes"] + \
                    xp.where(choice != cur, one, zero)
                s["route"] = choice
                ch_oh = xp.where(arangeS == choice[..., None, :],
                                 one, zero)
                route_oh = ch_oh
                totS = tot[..., None, :]
                spray_w = xp.where(totS > zero,
                                   free / xp.maximum(totS, tiny), ch_oh)
                W = xp.where(m[..., None] == 3, spray_w, ch_oh)
                D0 = st["dest"][0] + es('...sf,sfp->...pf',
                                               W, st["upP"])
            else:
                D0 = st["dest"][0]

        # ---- 2. tier-ordered forwarding (cut-through within the tick) ---- #
        s, out = drain(s, 0, upf)
        if flt:
            out = kill(s, out)
        if any_cc:
            # per-tick drained bytes per port: the txRate leg of the
            # HPCC-style INT signal (run_fabric's tick_tx)
            txP = out[..., 0, :, :].sum(-1)
        fbm = (st["occ"][0] * out).sum(-2)
        if dyn:
            # cross-leaf stage-0 output follows this tick's routing
            # weights; intra-leaf flows ride the static dest[0] part
            s = enqueue(s, D0[..., None, :, :] * fbm[..., None, :])
        else:
            s = enqueue(s, st["dest"][0] * fbm[..., None, :])
        s, out = drain(s, 1, upf)
        if flt:
            out = kill(s, out)
        if any_cc:
            txP = txP + out[..., 0, :, :].sum(-1)
        if dyn:
            # uplink-stage output keeps its port-level provenance: the
            # static [P, F, P] map sends bytes drained at (leaf, spine)
            # to that spine's downlink toward the flow's leaf
            s["tx"] = s["tx"] + out[..., 0, :, :].sum(-1)
            s = enqueue(s, es('...cpf,pfq->...cqf',
                                     out, st["T1"]))
        else:
            fbm = (st["occ"][1] * out).sum(-2)
            s = enqueue(s, st["dest"][1] * fbm[..., None, :])
        s, out = drain(s, 2, upf)
        if flt:
            out = kill(s, out)
        if any_cc:
            txP = txP + out[..., 0, :, :].sum(-1)
        fbm = (st["occ"][2] * out).sum(-2)
        s = enqueue(s, st["dest"][2] * fbm[..., None, :])
        s, out = drain(s, 3, upf)
        if flt:
            out = kill(s, out)
        if any_cc:
            txP = txP + out[..., 0, :, :].sum(-1)
        fbm = (st["occ"][3] * out).sum(-2)
        if Hs > 1:
            # spray reorder settling: sprayed arrivals wait settle ticks
            # in a slot-major ring before receiver admission (per-flow
            # read offset; 0 = read the slot just written = pass-through)
            s["sring"] = ring_set(s["sring"], it % Hs, fbm)
            sidx = (it - p["settle"]) % Hs
            fbm = xp.take_along_axis(s["sring"], sidx[..., None, None, :],
                                     -3)[..., 0, :, :]
        arr_b = fbm[..., 0, :]
        arr_m = fbm[..., 1, :]
        if flt:
            # crashed receivers discard arrivals until restart, then a
            # gapped go-back-N window discards the rest as duplicates
            # (run_fabric step 3 order: crash first, then dup
            # suppression; duplicates go straight back to the ledger)
            crashF = ((t >= p["crash_at"])
                      & (t < p["crash_until"]))[..., st["recv_of"]]
            dead_b = xp.where(crashF, arr_b, zero)
            ledger(s, dead_b)
            s["flt_drop"] = s["flt_drop"] + dead_b.sum(-1)
            arr_b = arr_b - dead_b
            arr_m = xp.where(crashF, zero, arr_m)
            dup_b = xp.where(s["gapped"], arr_b, zero)
            s["lost"] = s["lost"] + dup_b
            s["flt_drop"] = s["flt_drop"] + dup_b.sum(-1)
            arr_b = arr_b - dup_b
            arr_m = xp.where(s["gapped"], zero, arr_m)

        # ---- 2.2 delay/INT telemetry -> CC zoo updates -------------------- #
        # end-of-forwarding queue state along each flow's current path,
        # folded into rtt = base + sum(q/budget) and util = max per-hop
        # (txRate/B + qlen/(B*T)) — run_fabric's loop as masked lanes
        if any_cc:
            qP = s["qm"][..., 0, :, :].sum(-1)                # [.., P]
            if dyn and Sn:
                leg1 = es('...sf,sfp->...pf', route_oh, st["upP"])
                leg2 = es('...sf,sfp->...pf', route_oh, st["dnP"])
            elif dyn:
                leg1 = leg2 = None
            else:
                leg1, leg2 = st["occ"][1], st["occ"][2]
            qd = zero
            util = zero
            for leg in (st["occ"][0], leg1, leg2, st["occ"][3]):
                if leg is None:
                    continue
                # [P, F] (static) or [.., P, F] (routed) one-hot gathers
                q_l = (leg * qP[..., :, None]).sum(-2)        # [.., F]
                tx_l = (leg * txP[..., :, None]).sum(-2)
                b_l = (leg * budgetP[..., :, None]).sum(-2)
                ok = b_l > zero
                qd = qd + xp.where(ok, q_l / xp.maximum(b_l, tiny), zero)
                u_l = xp.where(ok, (tx_l + q_l * (fdt * inv_brtt))
                               / xp.maximum(b_l, tiny), zero)
                util = xp.maximum(util, u_l)
            rtt = p["base_rtt"] + qd * fdt
            ctus = s["cc_tus"] + fdt
            fire = ctus >= p["cc_upd"]
            s["cc_tus"] = xp.where(fire, zero, ctus)
            # Timely: smoothed RTT gradient picks the branch
            ft = fire & timely_m
            diff = rtt - s["prev_rtt"]
            rd_new = (1.0 - p["tl_a"]) * s["rtt_diff"] + p["tl_a"] * diff
            s["prev_rtt"] = xp.where(ft, rtt, s["prev_rtt"])
            s["rtt_diff"] = xp.where(ft, rd_new, s["rtt_diff"])
            grad = rd_new * inv_brtt
            rc = s["rc"]
            r_tim = xp.where(
                rtt < p["t_low"], rc + p["tl_add"],
                xp.where(rtt > p["t_high"],
                         rc * (one - p["tl_beta"]
                               * (one - p["t_high"] / rtt)),
                         xp.where(grad <= zero, rc + p["tl_add"],
                                  rc * xp.maximum(
                                      zero, one - p["tl_beta"] * grad))))
            rc_tim = xp.minimum(p["line"],
                                xp.maximum(p["cc_minr"], r_tim))
            # HPCC: drive max per-hop utilization toward eta
            fh = fire & hpcc_m
            mult = xp.clip(p["hp_eta"] / xp.maximum(util, u_floor),
                           half, f(2.0))
            rc_hp = xp.minimum(p["line"],
                               xp.maximum(p["cc_minr"],
                                          rc * mult + p["hp_ai"]))
            s["rc"] = xp.where(ft, rc_tim, xp.where(fh, rc_hp, rc))

        # ---- 3. receivers advance one tick (HostDatapath, stacked) -------- #
        with _scope(xp, RECV_SCOPE):
            arr_rb = st["recv_onehot"] * arr_b[..., None, :]
            # QoS-classed arrivals [.., Q, R] (admission class x receiver)
            arr_cr = (st["cls_recv"] * arr_b[..., None, None, :]).sum(-1)
            arr_tot = arr_cr.sum(-2)
            # admission: RNIC buffer space granted in QoS-priority order —
            # the second fused priority water-fill (HostDatapath.admit_link)
            space_r = xp.maximum(p["rnic_buf"] - s["qos_q"].sum(-2), zero)
            acc_cr = fused.priority_admit(xp, arr_cr, space_r, impl=impl)
            accepted = acc_cr[..., 0, :]
            for q_i in range(1, N_QOS):
                accepted = accepted + acc_cr[..., q_i, :]
            if flt:
                # first byte accepted after a crash restart stamps the
                # crash-recovery latency (run_fabric step 3)
                rec_hit = (t >= p["crash_until"]) & (accepted > zero) \
                    & xp.isinf(s["crash_rec"])
                s["crash_rec"] = xp.where(
                    rec_hit, now - p["crash_at"].astype(dtype) * fdt,
                    s["crash_rec"])
            s["rnic_drop"] = s["rnic_drop"] + (arr_tot - accepted)
            s["qos_q"] = s["qos_q"] + acc_cr

            ws = p["qp_bytes"] + s["resident"]
            miss = xp.clip((ws - p["ddio"]) * inv_knee, zero, one)
            s["miss_sum"] = s["miss_sum"] + xp.where(jet, zero, miss)
            ddio_bw = xp.where(miss > 1e-9,
                               xp.minimum(p["pcie"],
                                          avail_dram / (2.0 * miss + tiny)),
                               p["pcie"])
            # drain budget granted in QoS-priority order; under Jet pool
            # pressure (< cache_safe free) the LOW class spills to DRAM (§5)
            budget = xp.where(jet, jet_cap, ddio_bw * bpt)
            pool_free = xp.maximum(zero, p["pool"] - s["resident"])
            spill = jet & (pool_free / p["pool"] < p["safe"])
            pf = xp.where(jet, pool_free, inf)
            drained = pool_drained = fallback = zero
            new_q = []
            for q_i in range(N_QOS):
                qq = s["qos_q"][..., q_i, :]
                take = xp.minimum(xp.minimum(qq, budget), pf)
                if q_i == N_QOS - 1:        # LOW spills instead of waiting
                    take = xp.where(spill, xp.minimum(qq, budget), take)
                    spilled = xp.where(spill, take, zero)
                else:
                    spilled = zero
                pf = pf - (take - spilled)
                budget = budget - take
                new_q.append(qq - take)
                drained = drained + take
                pool_drained = pool_drained + (take - spilled)
                fallback = fallback + spilled
            s["qos_q"] = xp.stack(new_q, -2)
            s["nic_dram"] = s["nic_dram"] + \
                xp.where(jet, fallback, drained * 2.0 * miss)
            s["mem_fb"] = s["mem_fb"] + fallback
            strag_part = pool_drained * strag_share
            parts = xp.stack([pool_drained * (1.0 - strag_share), strag_part],
                             -2)
            # ring layout [H, 2, R]: the write is a contiguous leading-axis
            # slice update, which XLA aliases in place inside the scan carry
            s["ring"] = ring_set(s["ring"], it % H, parts)
            s["resident"] = s["resident"] + pool_drained
            s["strag_res"] = s["strag_res"] + strag_part
            s["drained"] = s["drained"] + drained

            idx = (it - p["d2"]) % H                  # [.., 2, R]
            r2 = xp.take_along_axis(s["ring"], idx[..., None, :, :],
                                    -3)[..., 0, :, :]
            r2 = xp.where(it >= p["d2"], r2, zero)
            for j, is_strag in ((0, False), (1, True)):
                r = r2[..., j, :]
                void = xp.minimum(r, s["esc_debt"])
                s["esc_debt"] = s["esc_debt"] - void
                r = r - void
                repay = xp.minimum(void, s["repl_debt"])
                s["repl_debt"] = s["repl_debt"] - repay
                s["repl_mem"] = xp.maximum(zero, s["repl_mem"] - repay)
                s["resident"] = xp.maximum(zero, s["resident"] - r)
                if is_strag:
                    s["strag_res"] = xp.maximum(zero, s["strag_res"] - r)

            # Jet escape ladder (paper Algorithm 1)
            avail = xp.maximum(zero, p["pool"] - s["resident"]) / p["pool"]
            esc_on = jet & (avail < p["safe"])
            can_rep = s["repl_mem"] < p["mem_esc"]
            x_rep = xp.where(esc_on & can_rep,
                             xp.maximum(zero,
                                        xp.minimum(s["strag_res"],
                                                   p["mem_esc"]
                                                   - s["repl_mem"])),
                             zero)
            s["resident"] = s["resident"] - x_rep
            s["strag_res"] = s["strag_res"] - x_rep
            s["esc_debt"] = s["esc_debt"] + x_rep
            s["repl_debt"] = s["repl_debt"] + x_rep
            s["repl_mem"] = s["repl_mem"] + x_rep
            s["esc_dram"] = s["esc_dram"] + 0.1 * x_rep
            s["replaces"] = s["replaces"] + (x_rep > zero)
            x_cop = xp.where(esc_on & ~can_rep, s["strag_res"], zero)
            s["resident"] = s["resident"] - x_cop
            s["strag_res"] = s["strag_res"] - x_cop
            s["esc_debt"] = s["esc_debt"] + x_cop
            s["esc_dram"] = s["esc_dram"] + x_cop
            s["copies"] = s["copies"] + (x_cop > zero)
            avail2 = xp.maximum(zero, p["pool"] - s["resident"]) / p["pool"]
            in_danger = esc_on & (avail2 < p["danger"])
            s["ecn_tus"] = xp.where(in_danger, s["ecn_tus"] + fdt,
                                    s["ecn_tus"])
            esc_fire = in_danger & (s["ecn_tus"] >= p["cnp_iv"])
            s["ecn_tus"] = xp.where(esc_fire, zero, s["ecn_tus"])
            s["cnps"] = s["cnps"] + esc_fire
            s["ecns"] = s["ecns"] + esc_fire
            s["pool_sum"] = s["pool_sum"] + xp.where(jet, s["resident"], zero)
            s["pool_peak"] = xp.maximum(s["pool_peak"],
                                        xp.where(jet, s["resident"], zero))

            # receiver congestion signalling
            q_frac = s["qos_q"].sum(-2) / p["rnic_buf"]
            if host_tc:
                # per-class receiver gate ([.., Q, R] pause state): per-TC
                # points watermark each class's occupancy of its 1/N_QOS
                # buffer partition (ReceiverHost's arithmetic, op for op),
                # legacy points see the total occupancy in every row —
                # identical decisions to the scalar whole-link gate
                frac_c = s["qos_q"] / (p["rnic_buf"] / f(N_QOS))[..., None, :]
                sel = xp.where(hpfc_b, frac_c, q_frac[..., None, :])
                s["pfc"] = rx_pfc_tc & xp.where(s["pfc"], sel >= xonQ,
                                                sel > xoffQ)
                pfc_any = s["pfc"].any(-2)
            else:
                s["pfc"] = rx_pfc_en & xp.where(s["pfc"], q_frac >= p["xon"],
                                                q_frac > p["xoff"])
                pfc_any = s["pfc"]
            s["pfc_us"] = s["pfc_us"] + xp.where(pfc_any, fdt, zero)
            cnp_tus = s["cnp_tus"] + fdt
            wm_fire = wm_en & (q_frac > p["ecn_th"]) \
                & (cnp_tus >= p["cnp_iv"])
            s["cnp_tus"] = xp.where(wm_fire, zero, cnp_tus)
            s["cnps"] = s["cnps"] + wm_fire

        # ---- 4. feedback routes back to the senders ----------------------- #
        # per-class acceptance share: a flow recovers the share its own
        # admission class received (matches HostDatapath.admit_link)
        share_cr = xp.where(arr_cr > zero,
                            acc_cr / xp.maximum(arr_cr, tiny), zero)
        deliv = arr_b * share_cr[..., st["cls_of"], st["recv_of"]]
        s["deliv_lo"] = s["deliv_lo"] + deliv
        # RNIC tail drops are retransmitted too (fluid RC / the ledger)
        if flt:
            ledger(s, arr_b - deliv)
        else:
            s["inj_lo"] = s["inj_lo"] - (arr_b - deliv)
        s["completion"] = xp.where(
            xp.isinf(s["completion"])
            & (s["delivered"] + s["deliv_lo"] >= p["burst_done"]),
            now, s["completion"])

        # receiver CNPs hit the heaviest recently-arriving flow (lowest
        # flow id on ties); with nothing arriving the previous target
        # stays throttled, as in run_fabric/run_sim
        with _scope(xp, RECV_SCOPE):
            has_arr = arr_tot > zero
            heavy_new = xp.argmax(arr_rb, -1).astype(xp.int32)
            s["heavy"] = xp.where(has_arr, heavy_new, s["heavy"])
        is_heavy = arangeF == s["heavy"][..., st["recv_of"]]
        f_esc = is_heavy & esc_fire[..., st["recv_of"]]
        f_wm = is_heavy & wm_fire[..., st["recv_of"]]
        # switch ECN marks -> per-flow CNPs, paced per DCQCN NP
        s["backlog"] = s["backlog"] + arr_m
        pace_tus = s["pace_tus"] + fdt
        pace_fire = (s["backlog"] > zero) & (pace_tus >= p["cnp_iv_f"])
        s["pace_tus"] = xp.where(pace_fire, zero, pace_tus)
        s["backlog"] = xp.where(pace_fire, zero, s["backlog"])
        # CNP propagation ring [Hc, 3, F]: notifications generated this
        # tick (slot t % Hc) cut their sender its *own* cnp_delay ticks
        # later — the delay is per flow, so the read index is a [F]
        # gather (slot (t - delay_f) % Hc; Hc > every delay, so for
        # t < delay the read lands on a slot not yet written, which
        # still holds zero)
        fires = xp.stack([xp.where(f_esc, one, zero),
                          xp.where(f_wm, one, zero),
                          xp.where(pace_fire, one, zero)], -2)
        s["cring"] = ring_set(s["cring"], it % Hc, fires)
        cidx = (it - p["cnp_dly"]) % Hc
        due = xp.take_along_axis(s["cring"], cidx[..., None, None, :],
                                 -3)[..., 0, :, :]
        for j in range(3):
            fire_c = due[..., j, :] > half
            if any_cc:
                # timely/hpcc ignore CNPs (CongestionControl.on_cnp)
                fire_c = fire_c & is_dcqcn
            s = cut(s, fire_c)

        # ---- 5. per-priority PFC pause propagation ------------------------ #
        q0 = s["qm"][..., 0, :, :]
        frac_occ = class_tot(q0) / buf_tc                     # [.., Q, P]
        s["asserted"] = can_assert[..., None, :] & \
            xp.where(s["asserted"], frac_occ >= sxon, frac_occ > sxoff)
        # a flow contributes a pause iff its own class is over watermark
        # at the port it is queued in: scatter the per-class assert state
        # back to (port, flow), then to that flow's class on its ingress
        # link — [.., Q, P*F] @ [P*F, P] per class
        assert_pf = mm(xp.swapaxes(
            xp.where(s["asserted"], one, zero), -1, -2), clsF)
        contrib = xp.where((assert_pf > half) & (q0 > zero), one, zero)
        contrib_q = contrib[..., None, :, :] * clsF[..., :, None, :]
        flat = contrib_q.reshape(contrib_q.shape[:-2] + (-1,))
        link_paused = mm(flat, st["prev_mat"]) > zero   # [.., Q, P]
        link_any = link_paused.any(-2)
        s["pause_us"] = s["pause_us"] + xp.where(link_any, fdt, zero)
        s["pause_tc_us"] = s["pause_tc_us"] + \
            xp.where(link_paused, fdt, zero)
        s["ever_paused"] = s["ever_paused"] | link_any
        if flt:
            # switch-asserted pause mask, carried so a crash can rebuild
            # the pause state of its access ports without the RNIC gate
            s["lpause"] = link_paused
            # PFC-deadlock watchdog (faults.has_pause_cycle, vectorized):
            # count a tick whenever the switch-asserted pause graph of
            # any single class holds a directed cycle — the per-class
            # [Q, P] mask lifts to node adjacencies through the static
            # port -> (u, v) one-hot and closes in log2(N) squarings
            n_dl = int(round(float(np.sqrt(st["dl_E"].shape[-1]))))
            cyc = fused.cycle_flags(
                xp, xp.where(link_paused, one, zero), st["dl_E"],
                n_dl, one)
            s["deadlock"] = s["deadlock"] + xp.where(cyc, one, zero)
        # the receiver RNIC gate: whole access link (legacy — broadcast
        # across the class axis) or per admission class (host_pfc_per_tc,
        # [.., Q, R] state gathered per stage-3 port)
        if host_tc:
            rx_gate = s["pfc"][..., st["owner_clamp"]] & st["owner_valid"]
            s["paused"] = link_paused | rx_gate
        else:
            rx_gate = s["pfc"][..., st["owner_clamp"]] & st["owner_valid"]
            s["paused"] = link_paused | rx_gate[..., None, :]

        # ---- 6. message-layer crossings (MessageTracker, stacked) --------- #
        # end-of-tick byte counters (post re-credit, so go-back-N losses
        # keep the affected messages open): ceil counts starts (first
        # byte enters the stream), floor counts completions, both with
        # the MSG_COUNT_EPS slack; the start-time ring plays the
        # tracker's per-message start list
        if any_msg:
            inj_tot = s["injected"] + s["inj_lo"]
            del_tot = s["delivered"] + s["deliv_lo"]
            mb = p["m_bytes"]
            ns = xp.ceil(inj_tot / mb - eps_m).astype(xp.int32)
            hw = s["m_hw"]
            new_s = xp.maximum(ns - hw, 0)         # go-back-N: hw grows
            woff = (arangeL - hw[..., None, :] % Lm) % Lm   # [.., L, F]
            wmask = woff < new_s[..., None, :]
            s["mring"] = xp.where(wmask, now - fdt, s["mring"])
            hw = hw + new_s
            s["m_hw"] = hw
            nd = xp.minimum(xp.floor(del_tot / mb + eps_m)
                            .astype(xp.int32), hw)
            done = s["m_done"]
            new_d = xp.maximum(nd - done, 0)
            roff = (arangeL - done[..., None, :] % Lm) % Lm
            rmask = roff < new_d[..., None, :]
            lat = now - s["mring"] + p["m_extra"][..., None, :]
            s["m_lat"] = s["m_lat"] + xp.where(rmask, lat, zero).sum(-2)
            # fixed-bucket log histogram (messages.hist_bucket
            # arithmetic); latencies above the histogram ceiling land in
            # the explicit overflow counter instead of the last bucket,
            # so pod-scale cross-tier tails can't silently report a
            # midpoint below the true value (LogHistogram.overflow_count)
            bi = xp.floor(xp.log(xp.maximum(lat, hist_lo) / hist_lo)
                          * inv_lr).astype(xp.int32)
            over = bi > HIST_BUCKETS - 1
            bi = xp.clip(bi, 0, HIST_BUCKETS - 1)
            inc = (arangeB == bi[..., None, :, :]) \
                & rmask[..., None, :, :] \
                & ~over[..., None, :, :]           # [.., B, L, F]
            s["m_hist"] = s["m_hist"] + xp.where(inc, one, zero).sum(-2)
            s["m_over"] = s["m_over"] + xp.where(rmask & over, one,
                                                 zero).sum(-2)
            s["m_done"] = done + new_d
            s["m_last"] = xp.where(new_d > 0, now, s["m_last"])

        # ---- 6.5 retransmit timers (run_fabric step 3.7) ------------------ #
        # after the message observe, so both engines record this tick's
        # latencies against the pre-fire injected count; the re-credit
        # reopens the sender's tap from the next offer on.  The timer
        # runs while the ledger is non-empty; go-back-N backs the RTO
        # off exponentially (k reset on delivery progress), selective
        # fires after the fixed NACK delay (FlowRecovery.tick)
        if flt:
            prog = deliv > zero
            k = xp.where(prog, 0, s["rto_k"])
            has = s["lost"] > zero
            timer = xp.where(has, s["rto_t"] + 1, 0)
            kc = xp.minimum(k, p["rto_cap"])
            dl_gbn = xp.floor(rto_f * p["rto_mult"]
                              ** kc.astype(dtype)).astype(xp.int32)
            dl = xp.where(sel_b, p["nack_ticks"], dl_gbn)
            fire = has & (timer >= dl)
            credit = xp.where(fire, s["lost"], zero)
            s["inj_lo"] = s["inj_lo"] - credit
            s["retx"] = s["retx"] + credit
            s["lost"] = xp.where(fire, zero, s["lost"])
            s["gapped"] = s["gapped"] & ~fire
            s["rto_t"] = xp.where(fire, 0, timer)
            s["rto_k"] = xp.where(fire & gbn_b,
                                  xp.minimum(k + 1, p["rto_cap"]), k)
        return s

    return step


def _make_step_sparse(xp, ring_set, st, p, dt: float, H: int, dtype,
                      Hc: int = 1, opts: Optional[dict] = None):
    """Build the sparse-incidence ``step(state, t)`` (pod-scale fabrics).

    Tick semantics match :func:`_make_step` exactly, but queue state
    lives as ``[.., 2, S, F]`` *slot* entries (S = 6 tier-ordered stage
    slots; slot ``(s, f)`` is queued at port ``port_of[s, f]``) instead
    of the dense ``[.., 2, P, F]`` port x flow matrix.  Per-(port, TC)
    totals are segment-sums over the S*F (slot, flow) entries and every
    per-port decision (drain fraction, buffer scale, ECN knee, PFC
    assert) comes back to the flows as a padded flat gather at the
    static ``tc * (P+1) + port`` indices — per-tick cost grows with
    flows x hops, not flows x ports, which is what lets a 256-512-host
    pod sweep trace as one jax program.

    Supported per-point features: static ECMP, failure/flap windows,
    strict/WRR scheduling, per-TC switch PFC and per-TC host PFC, burst
    trains, the CNP ring, the CC zoo (DCQCN/Timely/HPCC per flow — the
    delay/INT telemetry walks the route slots in tier order, so the
    per-leg RTT sum accumulates in the dense engine's leg order and
    2-tier grids stay bit-equal) and the full receiver block.  Dynamic
    routing, the message layer and FaultConfig injection stay on the
    dense engine (:meth:`FabricSweepParams.from_scenarios` rejects them
    with a clear error under ``sparse=True``).
    """
    o = opts or {}
    wrr, host_tc = o.get("wrr", False), o.get("host_tc", False)
    any_cc = o.get("cc", False)
    impl = o.get("impl", "ref") if xp is not np else "ref"
    fail = "fail_at" in p
    flap = "flap_start" in p
    f = dtype
    S = _STAGES_SP
    F = int(st["recv_of"].shape[0])
    P = int(st["stage"].shape[-1])
    Ppad = P + 1                     # column P = "slot unused" dummy
    QPpad = N_QOS * Ppad
    if xp is np:
        def seg_sum(vals, idx, size):
            """Batched segment-sum: scatter-add ``vals`` [.., N] at
            ``idx`` [N] into [.., size]."""
            lead = vals.shape[:-1]
            vf = np.ascontiguousarray(vals).reshape(-1, vals.shape[-1])
            acc = np.zeros((vf.shape[0], size), vals.dtype)
            np.add.at(acc, (np.arange(vf.shape[0])[:, None],
                            np.asarray(idx)[None, :]), vf)
            return acc.reshape(lead + (size,))
    else:
        def seg_sum(vals, idx, size):
            return xp.zeros(vals.shape[:-1] + (size,),
                            vals.dtype).at[..., idx].add(vals)

    def segQ(vals, idx):
        """Scatter flow values to [.., Q, P] per-(TC, port) totals
        (dummy pad column sliced off)."""
        return seg_sum(vals, idx, QPpad) \
            .reshape(vals.shape[:-1] + (N_QOS, Ppad))[..., :P]

    def gQ(x_qp, idx):
        """Gather a per-(TC, port) array [.., Q, P] back to flows: zero
        pad column for unused slots, flatten, fancy-gather at the flat
        (tc, port) indices (``idx`` [F] or [S, F])."""
        pad = xp.zeros(x_qp.shape[:-1] + (1,), x_qp.dtype)
        xf = xp.concatenate([x_qp, pad], -1)
        return xf.reshape(xf.shape[:-2] + (QPpad,))[..., idx]

    bpt = f(1e9 / 8.0 * dt * 1e-6)       # bytes per (Gbps * tick)
    fdt = f(dt)
    zero, one, tiny = f(0.0), f(1.0), f(1e-30)
    half, inf = f(0.5), f(np.inf)
    eps_q = f(1e-9)
    arangeF = xp.arange(F, dtype=xp.int32)
    budget = p["gbps"] * bpt
    budget_crumb = budget * f(1e-6)
    buf_tc = p["buf"][..., None, None]
    kmin_th = p["kmin"][..., None] * buf_tc
    ecn_on = p["ecn_en"] > 0.5
    can_assert = p["can_assert"] > 0.5
    sxoff = p["sw_xoff"][..., None]
    sxon = p["sw_xon"][..., None]
    onoff = p["off_us"] > zero
    period = xp.where(onoff, p["on_us"] + p["off_us"], one)
    jet = p["jet"] > 0.5
    avail_dram = xp.maximum(zero, p["membw"] - p["cpu_bw"])
    jet_cap = xp.minimum(p["pcie"], p["line1"] * 4.0) * bpt
    strag_share = xp.where(jet, p["sfrac"], zero)
    inv_knee = one / (p["knee"] * p["ddio"])
    rx_pfc_en = p["pfc_en"] > 0.5
    wm_en = p["wm_cnp"] > 0.5
    linecap = xp.minimum(p["line"], p["cap"])
    if wrr:
        quantaQ = p["quanta"][..., None]            # [.., Q, 1]
        is_wrr = (p["sched"] == 1)[..., None, None]  # [.., 1, 1]
    if host_tc:
        hpfc_b = (p["hpfc"] > half)[..., None, :]   # [.., 1, R]
        rx_pfc_tc = rx_pfc_en[..., None, :]
        xoffQ = p["xoff"][..., None, :]
        xonQ = p["xon"][..., None, :]
    if any_cc:
        # algorithm lanes (CcConfig.code: 0 dcqcn, 1 timely, 2 hpcc)
        is_dcqcn = p["cc_algo"] == 0
        timely_m = p["cc_algo"] == 1
        hpcc_m = p["cc_algo"] == 2
        inv_brtt = one / p["base_rtt"]              # [.., F]
        u_floor = f(0.01)
        # padded per-port budget for the telemetry gathers (column P =
        # "slot unused", budget 0 -> the leg drops out, as the dense
        # engine's zero one-hot columns)
        budget_pad = xp.concatenate(
            [budget, xp.zeros(budget.shape[:-1] + (1,), budget.dtype)],
            -1)
        po_flat = st["port_of"].reshape(S * F)      # [S*F] flat slots

    def cut(s, fire):
        """DCQCN on_cnp for flows where ``fire`` holds."""
        s = dict(s)
        s["rt"] = xp.where(fire, s["rc"], s["rt"])
        s["rc"] = xp.where(
            fire, xp.maximum(p["minr"], s["rc"] * (1.0 - s["alpha"] / 2.0)),
            s["rc"])
        s["alpha"] = xp.where(
            fire, xp.minimum(one, (1.0 - p["g"]) * s["alpha"] + p["g"]),
            s["alpha"])
        for k in ("t_us", "byts", "t_stage", "b_stage", "a_tus"):
            s[k] = xp.where(fire, zero, s[k])
        return s

    def qtc_all(qm):
        """Full per-(TC, port) occupancy [.., Q, P]: one scatter of all
        S*F slot entries (each port hosts exactly one slot's entries)."""
        v = qm[..., 0, :, :]
        return segQ(v.reshape(v.shape[:-2] + (S * F,)), st["qp_flat"])

    def drain(s, k, upf=None):
        """Stage-k ports forward up to rate*dt — the dense drain's
        grants on the slot-k row.  Returns per-flow drained [.., 2, F]
        (the slot row IS the port-level provenance)."""
        qm = s["qm"]
        qrow = qm[..., :, k, :]                   # [.., 2, F]
        qtc = segQ(qrow[..., 0, :], st["qp_idx"][k])
        budget0 = budget if upf is None else budget * upf
        can_q = st["stage"][k] & ~s["paused"] & (qtc > zero)
        frac_q = fused.priority_grants(
            xp, qtc, can_q if impl == "ref"
            else xp.where(can_q, one, zero),
            budget0, budget_crumb, one, zero, impl=impl)
        if wrr:
            rem = xp.where(can_q, qtc, zero)
            alloc = xp.zeros_like(qtc)
            bl = budget0
            for _ in range(N_QOS):
                wq = xp.where(rem > zero, quantaQ, zero)
                wsum = wq.sum(-2)                 # [.., P]
                share = bl[..., None, :] * wq \
                    / xp.maximum(wsum, tiny)[..., None, :]
                take = xp.minimum(share, rem)
                alloc = alloc + take
                rem = rem - take
                bl = bl - take.sum(-2)
                bl = xp.where(bl < budget_crumb, zero, bl)
            frac_wrr = xp.where(qtc > zero,
                                alloc / xp.maximum(qtc, tiny), zero)
            frac_q = xp.where(is_wrr, frac_wrr, frac_q)
        frac_f = gQ(frac_q, st["qp_idx"][k])      # [.., F]
        out = qrow * frac_f[..., None, :]
        left = qrow - out
        # sub-1e-9 residues vanish with their marks (dense drain)
        can_f = gQ(xp.where(can_q, one, zero), st["qp_idx"][k])
        gone = (can_f > half) & (left[..., 0, :] < eps_q)
        left = xp.where(gone[..., None, :], zero, left)
        s["qm"] = qm - (qrow - left)[..., :, None, :] * st["row_oh"][k]
        return s, out

    def enqueue(s, A, k):
        """Batch-enqueue stage-k output ``A`` [.., 2, F] at each flow's
        next slot: proportional split of the class partition, one ECN
        knee per (port, TC) against pre-batch occupancy."""
        dq = st["dq_idx"][k]
        qtc = qtc_all(s["qm"])
        tot_q = segQ(A[..., 0, :], dq)
        space_q = xp.maximum(buf_tc - qtc, zero)
        scale_q = xp.where(tot_q > space_q,
                           space_q / xp.maximum(tot_q, tiny), one)
        take = A * gQ(scale_q, dq)[..., None, :]
        lost = (A - take)[..., 0, :]
        s["inj_lo"] = s["inj_lo"] - lost
        s["sw_dropped"] = s["sw_dropped"] + lost.sum(-1)
        mark_q = ecn_on[..., None, :] & (qtc > kmin_th)
        mark_f = gQ(xp.where(mark_q, one, zero), dq)
        dm = xp.where(mark_f > half,
                      take[..., 0, :] - take[..., 1, :], zero)
        s["ecn_marked"] = s["ecn_marked"] + dm.sum(-1)
        s["qm"] = s["qm"] + \
            (take + dm[..., None, :] * st["selm"])[..., :, None, :] \
            * st["nxt_oh"][k]
        return s

    fold_at = f(65536.0)

    def fold(s, hi, lo):
        full = xp.abs(s[lo]) >= fold_at
        s[hi] = s[hi] + xp.where(full, s[lo], zero)
        s[lo] = xp.where(full, zero, s[lo])

    def step(s, t, it=None):
        if it is None:
            it = t
        s = dict(s)
        now = (xp.asarray(t, dtype) + one) * fdt
        fold(s, "injected", "inj_lo")
        fold(s, "delivered", "deliv_lo")

        # ---- 0. link failure / flap windows ------------------------------- #
        upf = None
        if fail:
            downP = (t >= p["fail_at"]) & (t < p["fail_until"])   # [.., P]
            edgeP = t == p["fail_at"]
            if flap:
                since = t - p["flap_start"]
                live = t >= p["flap_start"]
                downP = downP | (live
                                 & (since % p["flap_period"]
                                    < p["flap_down"]))
                edgeP = edgeP | (live & (since % p["flap_period"] == 0))
            upf = xp.where(downP, zero, one)
            failf = xp.where(edgeP, one, zero)
            failp = xp.concatenate(
                [failf, xp.zeros(failf.shape[:-1] + (1,), failf.dtype)],
                -1)
            fail_sf = failp[..., st["port_of"]]               # [.., S, F]
            lostF = (s["qm"][..., 0, :, :] * fail_sf).sum(-2)
            s["inj_lo"] = s["inj_lo"] - lostF
            s["sw_dropped"] = s["sw_dropped"] + lostF.sum(-1)
            s["qm"] = s["qm"] * (one - fail_sf)[..., None, :, :]

        # ---- 1. senders: DCQCN advance + offer ---------------------------- #
        adv = now > p["start"]
        # the DCQCN timer machinery only moves DCQCN-lane flows; the CC
        # block after forwarding writes the timely/hpcc rates instead
        dadv = (adv & is_dcqcn) if any_cc else adv
        adv_dt = xp.where(dadv, fdt, zero)
        a_tus = s["a_tus"] + adv_dt
        a_fire = dadv & (a_tus >= p["a_tmr"])
        s["alpha"] = xp.where(a_fire, (1.0 - p["g"]) * s["alpha"],
                              s["alpha"])
        s["a_tus"] = xp.where(a_fire, zero, a_tus)
        t_us = s["t_us"] + adv_dt
        byts = xp.where(dadv, s["byts"] + s["rc"] * bpt, s["byts"])
        t_fire = dadv & (t_us >= p["r_tmr"])
        s["t_stage"] = s["t_stage"] + t_fire
        s["t_us"] = xp.where(t_fire, zero, t_us)
        b_fire = dadv & (byts >= p["bctr"])
        s["b_stage"] = s["b_stage"] + b_fire
        s["byts"] = xp.where(b_fire, zero, byts)
        fired = t_fire | b_fire
        stage = xp.minimum(s["t_stage"], s["b_stage"])
        s["rt"] = xp.where(fired & (stage == p["fth"]),
                           xp.minimum(p["dline"], s["rt"] + p["ai"]),
                           s["rt"])
        s["rt"] = xp.where(fired & (stage > p["fth"]),
                           xp.minimum(p["dline"], s["rt"] + p["hai"]),
                           s["rt"])
        s["rc"] = xp.where(fired,
                           xp.minimum(p["dline"],
                                      0.5 * (s["rc"] + s["rt"])),
                           s["rc"])

        gbps = xp.minimum(s["rc"], linecap)
        room = xp.maximum(p["burst"] - (s["injected"] + s["inj_lo"]), zero)
        active = adv & (~onoff | (xp.fmod(now - p["start"], period)
                                  < p["on_us"]))
        offer = xp.where(active, xp.minimum(gbps * bpt, room), zero)
        # source-side backpressure at the NIC queue (slot 0's port)
        qtcI = qtc_all(s["qm"])
        tot_q = segQ(offer, st["qp_idx"][0])
        space_q = xp.maximum(buf_tc - qtcI, zero)
        scale_q = xp.where(tot_q > space_q,
                           space_q / xp.maximum(tot_q, tiny), one)
        take_f = offer * gQ(scale_q, st["qp_idx"][0])
        s["inj_lo"] = s["inj_lo"] + take_f
        s["qm"] = s["qm"] + take_f[..., None, None, :] * st["sel_inj"]

        # ---- 2. tier-ordered forwarding (cut-through within the tick) ---- #
        out = None
        if any_cc:
            txPp = xp.zeros(budget_pad.shape, budget_pad.dtype)
        for k in range(S):
            if not st["stage_any"][k]:
                continue
            s, out = drain(s, k, upf)
            if any_cc:
                # per-tick drained bytes per port: the txRate leg of the
                # HPCC-style INT signal (run_fabric's tick_tx)
                txPp = txPp + seg_sum(out[..., 0, :], st["port_of"][k],
                                      Ppad)
            if k in (1, 2):
                # fabric-uplink tx accounting (leaf->spine, spine->ss)
                txk = seg_sum(out[..., 0, :], st["port_of"][k], Ppad)
                s["tx"] = s["tx"] + txk[..., :P]
            if k < S - 1:
                s = enqueue(s, out, k)
        arr_b = out[..., 0, :]
        arr_m = out[..., 1, :]

        # ---- 2.2 delay/INT telemetry -> CC zoo updates -------------------- #
        # end-of-forwarding queue state along each flow's route slots,
        # folded into rtt = base + sum(q/budget) and util = max per-hop
        # (txRate/B + qlen/(B*T)) — the dense engine's leg loop as
        # padded gathers at port_of[k].  Slots are visited in tier
        # order, so on a 2-tier grid the qd accumulation order matches
        # the dense legs (occ0, occ1, occ2, occ3) term for term.
        if any_cc:
            v = s["qm"][..., 0, :, :]
            qPp = seg_sum(v.reshape(v.shape[:-2] + (S * F,)), po_flat,
                          Ppad)                               # [.., P+1]
            qd = zero
            util = zero
            for k in range(S):
                if not st["stage_any"][k]:
                    continue
                po_k = st["port_of"][k]                       # [F]
                q_l = qPp[..., po_k]
                tx_l = txPp[..., po_k]
                b_l = budget_pad[..., po_k]
                ok = b_l > zero
                qd = qd + xp.where(ok, q_l / xp.maximum(b_l, tiny), zero)
                u_l = xp.where(ok, (tx_l + q_l * (fdt * inv_brtt))
                               / xp.maximum(b_l, tiny), zero)
                util = xp.maximum(util, u_l)
            rtt = p["base_rtt"] + qd * fdt
            ctus = s["cc_tus"] + fdt
            fire = ctus >= p["cc_upd"]
            s["cc_tus"] = xp.where(fire, zero, ctus)
            # Timely: smoothed RTT gradient picks the branch
            ft = fire & timely_m
            diff = rtt - s["prev_rtt"]
            rd_new = (1.0 - p["tl_a"]) * s["rtt_diff"] + p["tl_a"] * diff
            s["prev_rtt"] = xp.where(ft, rtt, s["prev_rtt"])
            s["rtt_diff"] = xp.where(ft, rd_new, s["rtt_diff"])
            grad = rd_new * inv_brtt
            rc = s["rc"]
            r_tim = xp.where(
                rtt < p["t_low"], rc + p["tl_add"],
                xp.where(rtt > p["t_high"],
                         rc * (one - p["tl_beta"]
                               * (one - p["t_high"] / rtt)),
                         xp.where(grad <= zero, rc + p["tl_add"],
                                  rc * xp.maximum(
                                      zero, one - p["tl_beta"] * grad))))
            rc_tim = xp.minimum(p["line"],
                                xp.maximum(p["cc_minr"], r_tim))
            # HPCC: drive max per-hop utilization toward eta
            fh = fire & hpcc_m
            mult = xp.clip(p["hp_eta"] / xp.maximum(util, u_floor),
                           half, f(2.0))
            rc_hp = xp.minimum(p["line"],
                               xp.maximum(p["cc_minr"],
                                          rc * mult + p["hp_ai"]))
            s["rc"] = xp.where(ft, rc_tim, xp.where(fh, rc_hp, rc))

        # ---- 3. receivers advance one tick (HostDatapath, stacked) -------- #
        with _scope(xp, RECV_SCOPE):
            arr_rb = st["recv_onehot"] * arr_b[..., None, :]
            arr_cr = (st["cls_recv"] * arr_b[..., None, None, :]).sum(-1)
            arr_tot = arr_cr.sum(-2)
            space_r = xp.maximum(p["rnic_buf"] - s["qos_q"].sum(-2), zero)
            acc_cr = fused.priority_admit(xp, arr_cr, space_r, impl=impl)
            accepted = acc_cr[..., 0, :]
            for q_i in range(1, N_QOS):
                accepted = accepted + acc_cr[..., q_i, :]
            s["rnic_drop"] = s["rnic_drop"] + (arr_tot - accepted)
            s["qos_q"] = s["qos_q"] + acc_cr

            ws = p["qp_bytes"] + s["resident"]
            miss = xp.clip((ws - p["ddio"]) * inv_knee, zero, one)
            s["miss_sum"] = s["miss_sum"] + xp.where(jet, zero, miss)
            ddio_bw = xp.where(miss > 1e-9,
                               xp.minimum(p["pcie"],
                                          avail_dram / (2.0 * miss + tiny)),
                               p["pcie"])
            budget_r = xp.where(jet, jet_cap, ddio_bw * bpt)
            pool_free = xp.maximum(zero, p["pool"] - s["resident"])
            spill = jet & (pool_free / p["pool"] < p["safe"])
            pf = xp.where(jet, pool_free, inf)
            drained = pool_drained = fallback = zero
            new_q = []
            for q_i in range(N_QOS):
                qq = s["qos_q"][..., q_i, :]
                take = xp.minimum(xp.minimum(qq, budget_r), pf)
                if q_i == N_QOS - 1:        # LOW spills instead of waiting
                    take = xp.where(spill, xp.minimum(qq, budget_r), take)
                    spilled = xp.where(spill, take, zero)
                else:
                    spilled = zero
                pf = pf - (take - spilled)
                budget_r = budget_r - take
                new_q.append(qq - take)
                drained = drained + take
                pool_drained = pool_drained + (take - spilled)
                fallback = fallback + spilled
            s["qos_q"] = xp.stack(new_q, -2)
            s["nic_dram"] = s["nic_dram"] + \
                xp.where(jet, fallback, drained * 2.0 * miss)
            s["mem_fb"] = s["mem_fb"] + fallback
            strag_part = pool_drained * strag_share
            parts = xp.stack([pool_drained * (1.0 - strag_share), strag_part],
                             -2)
            s["ring"] = ring_set(s["ring"], it % H, parts)
            s["resident"] = s["resident"] + pool_drained
            s["strag_res"] = s["strag_res"] + strag_part
            s["drained"] = s["drained"] + drained

            idx = (it - p["d2"]) % H                  # [.., 2, R]
            r2 = xp.take_along_axis(s["ring"], idx[..., None, :, :],
                                    -3)[..., 0, :, :]
            r2 = xp.where(it >= p["d2"], r2, zero)
            for j, is_strag in ((0, False), (1, True)):
                r = r2[..., j, :]
                void = xp.minimum(r, s["esc_debt"])
                s["esc_debt"] = s["esc_debt"] - void
                r = r - void
                repay = xp.minimum(void, s["repl_debt"])
                s["repl_debt"] = s["repl_debt"] - repay
                s["repl_mem"] = xp.maximum(zero, s["repl_mem"] - repay)
                s["resident"] = xp.maximum(zero, s["resident"] - r)
                if is_strag:
                    s["strag_res"] = xp.maximum(zero, s["strag_res"] - r)

            # Jet escape ladder (paper Algorithm 1)
            avail = xp.maximum(zero, p["pool"] - s["resident"]) / p["pool"]
            esc_on = jet & (avail < p["safe"])
            can_rep = s["repl_mem"] < p["mem_esc"]
            x_rep = xp.where(esc_on & can_rep,
                             xp.maximum(zero,
                                        xp.minimum(s["strag_res"],
                                                   p["mem_esc"]
                                                   - s["repl_mem"])),
                             zero)
            s["resident"] = s["resident"] - x_rep
            s["strag_res"] = s["strag_res"] - x_rep
            s["esc_debt"] = s["esc_debt"] + x_rep
            s["repl_debt"] = s["repl_debt"] + x_rep
            s["repl_mem"] = s["repl_mem"] + x_rep
            s["esc_dram"] = s["esc_dram"] + 0.1 * x_rep
            s["replaces"] = s["replaces"] + (x_rep > zero)
            x_cop = xp.where(esc_on & ~can_rep, s["strag_res"], zero)
            s["resident"] = s["resident"] - x_cop
            s["strag_res"] = s["strag_res"] - x_cop
            s["esc_debt"] = s["esc_debt"] + x_cop
            s["esc_dram"] = s["esc_dram"] + x_cop
            s["copies"] = s["copies"] + (x_cop > zero)
            avail2 = xp.maximum(zero, p["pool"] - s["resident"]) / p["pool"]
            in_danger = esc_on & (avail2 < p["danger"])
            s["ecn_tus"] = xp.where(in_danger, s["ecn_tus"] + fdt,
                                    s["ecn_tus"])
            esc_fire = in_danger & (s["ecn_tus"] >= p["cnp_iv"])
            s["ecn_tus"] = xp.where(esc_fire, zero, s["ecn_tus"])
            s["cnps"] = s["cnps"] + esc_fire
            s["ecns"] = s["ecns"] + esc_fire
            s["pool_sum"] = s["pool_sum"] + xp.where(jet, s["resident"], zero)
            s["pool_peak"] = xp.maximum(s["pool_peak"],
                                        xp.where(jet, s["resident"], zero))

            # receiver congestion signalling
            q_frac = s["qos_q"].sum(-2) / p["rnic_buf"]
            if host_tc:
                frac_c = s["qos_q"] / (p["rnic_buf"] / f(N_QOS))[..., None, :]
                sel = xp.where(hpfc_b, frac_c, q_frac[..., None, :])
                s["pfc"] = rx_pfc_tc & xp.where(s["pfc"], sel >= xonQ,
                                                sel > xoffQ)
                pfc_any = s["pfc"].any(-2)
            else:
                s["pfc"] = rx_pfc_en & xp.where(s["pfc"], q_frac >= p["xon"],
                                                q_frac > p["xoff"])
                pfc_any = s["pfc"]
            s["pfc_us"] = s["pfc_us"] + xp.where(pfc_any, fdt, zero)
            cnp_tus = s["cnp_tus"] + fdt
            wm_fire = wm_en & (q_frac > p["ecn_th"]) \
                & (cnp_tus >= p["cnp_iv"])
            s["cnp_tus"] = xp.where(wm_fire, zero, cnp_tus)
            s["cnps"] = s["cnps"] + wm_fire

        # ---- 4. feedback routes back to the senders ----------------------- #
        share_cr = xp.where(arr_cr > zero,
                            acc_cr / xp.maximum(arr_cr, tiny), zero)
        deliv = arr_b * share_cr[..., st["cls_of"], st["recv_of"]]
        s["deliv_lo"] = s["deliv_lo"] + deliv
        s["inj_lo"] = s["inj_lo"] - (arr_b - deliv)
        s["completion"] = xp.where(
            xp.isinf(s["completion"])
            & (s["delivered"] + s["deliv_lo"] >= p["burst_done"]),
            now, s["completion"])

        with _scope(xp, RECV_SCOPE):
            has_arr = arr_tot > zero
            heavy_new = xp.argmax(arr_rb, -1).astype(xp.int32)
            s["heavy"] = xp.where(has_arr, heavy_new, s["heavy"])
        is_heavy = arangeF == s["heavy"][..., st["recv_of"]]
        f_esc = is_heavy & esc_fire[..., st["recv_of"]]
        f_wm = is_heavy & wm_fire[..., st["recv_of"]]
        s["backlog"] = s["backlog"] + arr_m
        pace_tus = s["pace_tus"] + fdt
        pace_fire = (s["backlog"] > zero) & (pace_tus >= p["cnp_iv_f"])
        s["pace_tus"] = xp.where(pace_fire, zero, pace_tus)
        s["backlog"] = xp.where(pace_fire, zero, s["backlog"])
        fires = xp.stack([xp.where(f_esc, one, zero),
                          xp.where(f_wm, one, zero),
                          xp.where(pace_fire, one, zero)], -2)
        s["cring"] = ring_set(s["cring"], it % Hc, fires)
        cidx = (it - p["cnp_dly"]) % Hc
        due = xp.take_along_axis(s["cring"], cidx[..., None, None, :],
                                 -3)[..., 0, :, :]
        for j in range(3):
            fire_c = due[..., j, :] > half
            if any_cc:
                # timely/hpcc ignore CNPs (CongestionControl.on_cnp)
                fire_c = fire_c & is_dcqcn
            s = cut(s, fire_c)

        # ---- 5. per-priority PFC pause propagation ------------------------ #
        q0s = s["qm"][..., 0, :, :]                           # [.., S, F]
        qtcP = qtc_all(s["qm"])
        frac_occ = qtcP / buf_tc
        s["asserted"] = can_assert[..., None, :] & \
            xp.where(s["asserted"], frac_occ >= sxon, frac_occ > sxoff)
        # a slot contributes a pause iff its flow's class is asserted at
        # its own port; the pause targets the slot's ingress port on the
        # flow's class — one gather + one scatter over the S*F entries
        af = gQ(xp.where(s["asserted"], one, zero), st["qp_idx"])
        contrib = xp.where((af > half) & (q0s > zero), one, zero)
        link_paused = segQ(
            contrib.reshape(contrib.shape[:-2] + (S * F,)),
            st["pp_flat"]) > zero                             # [.., Q, P]
        if "ex_f" in st:
            # candidate-ingress semantics under failure schedules: a
            # shallow flow's last-hop (slot 5) contribution also pauses
            # its non-chosen candidate downlinks (the scalar driver's
            # OutputPort.static_ingress targeting)
            extra = contrib[..., 5, :][..., st["ex_f"]]       # [.., E]
            link_paused = link_paused | (segQ(extra, st["ex_flat"])
                                         > zero)
        link_any = link_paused.any(-2)
        s["pause_us"] = s["pause_us"] + xp.where(link_any, fdt, zero)
        s["pause_tc_us"] = s["pause_tc_us"] + \
            xp.where(link_paused, fdt, zero)
        s["ever_paused"] = s["ever_paused"] | link_any
        rx_gate = s["pfc"][..., st["owner_clamp"]] & st["owner_valid"]
        if host_tc:
            s["paused"] = link_paused | rx_gate
        else:
            s["paused"] = link_paused | rx_gate[..., None, :]
        return s

    return step


def _init_state(xp, lead, fsp: FabricSweepParams, p, dtype):
    """Zero/steady-state carry; ``lead`` is () under vmap, (G,) for numpy."""
    F, P, R, H = (fsp.n_flows, fsp.n_ports, fsp.n_recv, fsp.ring_len)
    Hc = fsp.cnp_ring
    z = lambda *sh: xp.zeros(lead + sh, dtype)       # noqa: E731
    s = {
        # flows
        "rc": p["dline"] + z(F), "rt": p["dline"] + z(F),
        "alpha": xp.ones(lead + (F,), dtype),
        "t_us": z(F), "byts": z(F), "t_stage": z(F), "b_stage": z(F),
        "a_tus": z(F), "injected": z(F), "delivered": z(F),
        "inj_lo": z(F), "deliv_lo": z(F),
        "completion": xp.full(lead + (F,), np.inf, dtype),
        "backlog": z(F),
        # immediate first paced CNP, as in the scalar driver
        "pace_tus": xp.full(lead + (F,), np.inf, dtype),
        # CNP propagation ring (slot-major, 3 notification sources)
        "cring": z(Hc, 3, F),
        # ports (axis -3: 0 = queued bytes, 1 = ECN-marked subset);
        # sparse grids queue per (stage slot, flow) instead of
        # (port, flow); PFC state stays classed [Q, P] in both layouts
        "qm": z(2, _STAGES_SP if fsp.sparse else P, F),
        "asserted": xp.zeros(lead + (N_QOS, P), bool),
        "paused": xp.zeros(lead + (N_QOS, P), bool),
        "pause_us": z(P),
        "pause_tc_us": z(N_QOS, P),
        "ever_paused": xp.zeros(lead + (P,), bool),
        # receivers ("qos_q" = HostDatapath's per-class RNIC buffer)
        "qos_q": z(N_QOS, R), "resident": z(R), "strag_res": z(R),
        "esc_debt": z(R), "repl_debt": z(R), "repl_mem": z(R),
        "rnic_drop": z(R), "drained": z(R), "nic_dram": z(R),
        "mem_fb": z(R),
        "esc_dram": z(R), "miss_sum": z(R), "pool_sum": z(R),
        "pool_peak": z(R), "cnps": z(R), "ecns": z(R), "replaces": z(R),
        "copies": z(R), "pfc_us": z(R), "ecn_tus": z(R),
        "cnp_tus": p["cnp_iv"] + z(R),   # allow an immediate first CNP
        # per-class pause state when any point runs per-TC host PFC
        # (legacy points keep every row in lockstep)
        "pfc": xp.zeros(lead + ((N_QOS, R) if fsp.host_tc else (R,)),
                        bool),
        "ring": z(H, 2, R),     # slot-major; axis -2: base / straggler
        "heavy": xp.full(lead + (R,), -1, xp.int32),
        # fleet counters
        "ecn_marked": z(), "sw_dropped": z(),
    }
    if fsp.sparse:
        # per-uplink carried bytes (fabric_uplinks utilization metrics)
        s["tx"] = z(P)
    if fsp.dyn_route:
        # routing carry: current spine choice (static hash seed), reroute
        # counts and per-uplink carried bytes
        s["route"] = xp.zeros(lead + (F,), xp.int32) \
            + xp.asarray(fsp.init_spine)
        s["reroutes"] = z(F)
        s["tx"] = z(P)
        if fsp.n_spines:
            # idle-gap flowlet state: per-flow flowlet index + last
            # active tick (far past, so the first injection opens a
            # flowlet — run_fabric's -(1 << 30) sentinel)
            s["flet_k"] = xp.zeros(lead + (F,), xp.int32)
            s["flet_last"] = xp.full(lead + (F,), -(1 << 30), xp.int32)
    if fsp.settle_ring > 1:
        s["sring"] = z(fsp.settle_ring, 2, F)
    if fsp.any_cc:
        # delay/INT controller carries (TimelyRate/HpccRate)
        s["prev_rtt"] = p["base_rtt"] + z(F)
        s["rtt_diff"] = z(F)
        s["cc_tus"] = z(F)
    if fsp.any_msg:
        # message-layer carries: started/completed counts, start-time
        # ring, latency sum and the fixed-bucket log histogram
        s["m_hw"] = xp.zeros(lead + (F,), xp.int32)
        s["m_done"] = xp.zeros(lead + (F,), xp.int32)
        s["mring"] = z(fsp.msg_ring, F)
        s["m_lat"] = z(F)
        s["m_last"] = z(F)
        s["m_hist"] = z(HIST_BUCKETS, F)
        s["m_over"] = z(F)
    if fsp.any_flt:
        # fault-layer carries: the per-flow recovery ledger (lost bytes,
        # RTO timer/backoff stage, go-back-N gap flag), retransmit and
        # fault-drop accumulators, crash-recovery stamps and the
        # switch-side link-pause mask (crash rebuilds)
        s["lost"] = z(F)
        s["rto_t"] = xp.zeros(lead + (F,), xp.int32)
        s["rto_k"] = xp.zeros(lead + (F,), xp.int32)
        s["gapped"] = xp.zeros(lead + (F,), bool)
        s["retx"] = z(F)
        s["flt_drop"] = z()
        s["crash_rec"] = xp.full(lead + (R,), np.inf, dtype)
        s["lpause"] = xp.zeros(lead + (N_QOS, P), bool)
        s["deadlock"] = z()
    return s


def _static(fsp: FabricSweepParams, xp, dtype):
    P, F = fsp.n_ports, fsp.n_flows
    owner = fsp.owner_recv
    cls_onehot = np.zeros((N_QOS, F))
    cls_onehot[fsp.qos_of, np.arange(F)] = 1.0
    out = {
        "cls_of": xp.asarray(fsp.qos_of),
        "cls_recv": xp.asarray(cls_onehot[:, None, :]
                               * fsp.recv_onehot[None, :, :], dtype),
        "stage": xp.asarray(fsp.stage_mask),
        "recv_onehot": xp.asarray(fsp.recv_onehot, dtype),
        "recv_of": xp.asarray(fsp.recv_of),
        "owner_clamp": xp.asarray(np.maximum(owner, 0)),
        "owner_valid": xp.asarray(owner >= 0),
    }
    if fsp.sparse:
        # segmented-incidence gather/scatter indices: flat
        # tc * (P + 1) + port addresses with column P the "slot unused"
        # dummy, so every per-(port, TC) reduction is one scatter over
        # the S*F slot entries and every read back one flat gather
        S = _STAGES_SP
        Ppad = P + 1
        po = fsp.port_of.astype(np.int64)                 # [S, F]
        qos = fsp.qos_of.astype(np.int64)                 # [F]
        qp = qos[None, :] * Ppad + po
        pp = qos[None, :] * Ppad + fsp.prv_port.astype(np.int64)
        cols = np.arange(F)
        dq_idx, nxt_oh = [], []
        for k in range(S - 1):
            nx = fsp.nxt_slot[k].astype(np.int64)         # [F]
            tp = po[np.minimum(nx, S - 1), cols]
            tp = np.where(nx < S, tp, P)
            dq_idx.append(xp.asarray((qos * Ppad + tp).astype(np.int32)))
            nxt_oh.append(xp.asarray(
                (nx[None, :] == np.arange(S)[:, None]).astype(np.float64),
                dtype))
        sel_inj = np.zeros((2, S, 1))
        sel_inj[0, 0, 0] = 1.0
        selm = np.zeros((2, 1))
        selm[1, 0] = 1.0
        out.update({
            "qp_idx": xp.asarray(qp.astype(np.int32)),
            "qp_flat": xp.asarray(qp.reshape(-1).astype(np.int32)),
            "pp_flat": xp.asarray(pp.reshape(-1).astype(np.int32)),
            "port_of": xp.asarray(fsp.port_of),
            "dq_idx": dq_idx,
            "nxt_oh": nxt_oh,
            "row_oh": [xp.asarray(np.eye(S)[k][:, None], dtype)
                       for k in range(S)],
            "sel_inj": xp.asarray(sel_inj, dtype),
            "selm": xp.asarray(selm, dtype),
            # trace-time skip of slots with no ports (a 2-tier sparse
            # grid leaves the super-spine slots 2-3 empty)
            "stage_any": [bool(fsp.stage_mask[k].any())
                          for k in range(S)],
        })
        if fsp.pause_extra is not None:
            # candidate-ingress pause pairs (failure schedules): gather
            # the last-hop contribution of flow ex_f, scatter it onto
            # its extra candidate downlink on the flow's class
            exf = fsp.pause_extra[0].astype(np.int64)
            exp_ = fsp.pause_extra[1].astype(np.int64)
            out["ex_f"] = xp.asarray(exf.astype(np.int32))
            out["ex_flat"] = xp.asarray(
                (qos[exf] * Ppad + exp_).astype(np.int32))
        return out
    sel = np.zeros((2, 2, 1, 1))
    sel[0, 0], sel[1, 1] = 1.0, 1.0
    out.update({
        "occ": [xp.asarray(a, dtype) for a in fsp.occ],
        "dest": [xp.asarray(a, dtype) for a in fsp.dest],
        "prev_mat": xp.asarray(fsp.prev_onehot.reshape(P * F, P), dtype),
        "sel0": xp.asarray(sel[0], dtype),
        "sel1": xp.asarray(sel[1], dtype),
    })
    if fsp.dyn_route:
        out["upP"] = xp.asarray(fsp.upP, dtype)
        out["dnP"] = xp.asarray(fsp.dnP, dtype)
        out["candS"] = xp.asarray(fsp.candS)
        out["T1"] = xp.asarray(fsp.T1, dtype)
    if fsp.any_flt:
        # deadlock-watchdog scatter: port -> flattened (u, v) node pair
        out["dl_E"] = xp.asarray(
            fused.pause_pair_onehot(fsp.port_keys), dtype)
    return out


# --------------------------------------------------------------------------- #
# Results
# --------------------------------------------------------------------------- #
def _results(s, fsp: FabricSweepParams) -> Dict[str, np.ndarray]:
    sim_us = fsp.ticks * fsp.dt_us
    per_gbps = 8.0 / (sim_us * 1e-6) / 1e9
    deliv = np.asarray(s["delivered"], np.float64) \
        + np.asarray(s["deliv_lo"], np.float64)
    goodput = deliv * per_gbps
    comp = np.asarray(s["completion"], np.float64)
    tags = np.array(fsp.flow_tags)
    inc_mask = (tags == "incast")[None, :] \
        & np.isfinite(fsp.pvals["burst"])
    inc_comp = np.where(
        inc_mask.any(-1),
        np.where(inc_mask, comp, -np.inf).max(-1), np.nan)
    vic = tags == "victim"
    G = fsp.n_points
    victim = goodput[:, vic].mean(-1) if vic.any() else np.zeros(G)
    out = {
        "flow_goodput_gbps": goodput,
        "flow_delivered_bytes": deliv,
        "flow_completion_us": comp,
        "incast_completion_us": inc_comp,
        "victim_goodput_gbps": victim,
        "has_victim": np.full(G, bool(vic.any())),
        "pause_fanout": np.asarray(s["ever_paused"]).sum(-1),
        "pause_total_us": np.asarray(s["pause_us"], np.float64).sum(-1),
        # per-priority pause budget: [G, Q] microseconds summed over
        # ingress links (matches summing FabricResult.pause_tc_us per tc)
        "pause_tc_total_us": np.asarray(s["pause_tc_us"],
                                        np.float64).sum(-1),
        # routing-aware PFC-storm metric: per-TC pause fan-out over the
        # candidate ingress sets (FabricResult.pause_tc_fanout /
        # n_pausable_links / pause_storm)
        "pause_tc_fanout": (np.asarray(s["pause_tc_us"], np.float64)
                            > 0.0).sum(-1),
        "ecn_marked_bytes": np.asarray(s["ecn_marked"], np.float64),
        "switch_dropped_bytes": np.asarray(s["sw_dropped"], np.float64),
        "recv_goodput_gbps": np.asarray(s["drained"], np.float64)
        * per_gbps,
        "recv_cnp_count": np.asarray(s["cnps"], np.float64),
        "recv_escape_ecn": np.asarray(s["ecns"], np.float64),
        "recv_pfc_pause_us": np.asarray(s["pfc_us"], np.float64),
        "recv_rnic_dropped_bytes": np.asarray(s["rnic_drop"], np.float64),
        "recv_mem_fallback_bytes": np.asarray(s["mem_fb"], np.float64),
    }
    # candidate ingress links that can ever receive a pause = ports with
    # ingress support (the scalar driver's `pausable` set exactly);
    # links down for the entire window can neither pause nor carry, so
    # they leave the storm/imbalance denominators (FabricResult's
    # zero-uptime exclusion, mirrored per grid point)
    if fsp.sparse:
        pmask = np.zeros(fsp.n_ports, bool)
        pmask[fsp.prv_port[fsp.prv_port < fsp.n_ports]] = True
        if fsp.pausable_extra is not None:
            # candidate hops of shallow flows under failure schedules
            pmask[fsp.pausable_extra] = True
    elif fsp.prev_onehot.size:
        pmask = fsp.prev_onehot.sum((0, 1)) > 0
    else:
        pmask = np.zeros(fsp.n_ports, bool)
    if "fail_at" in fsp.pvals:
        dead = (fsp.pvals["fail_at"] <= 0) \
            & (fsp.pvals["fail_until"] >= fsp.ticks)         # [G, P]
    else:
        dead = np.zeros((G, fsp.n_ports), bool)
    n_pausable = (pmask[None, :] & ~dead).sum(-1)            # [G]
    out["n_pausable_links"] = n_pausable
    out["pause_storm"] = np.where(
        n_pausable > 0,
        out["pause_tc_fanout"].max(-1) / np.maximum(n_pausable, 1), 0.0)
    if fsp.any_flt:
        out["retransmit_bytes"] = np.asarray(s["retx"],
                                             np.float64).sum(-1)
        # faults-None points packed f_mtu=inf, so their count is 0
        out["dropped_pkts"] = np.asarray(s["flt_drop"], np.float64) \
            / fsp.pvals["f_mtu"]
        out["crash_recovery_us"] = np.asarray(s["crash_rec"], np.float64)
        # vectorized PFC-deadlock watchdog (faults.has_pause_cycle)
        out["deadlock_ticks"] = np.asarray(s["deadlock"], np.float64)
    else:
        out["retransmit_bytes"] = np.zeros(G)
        out["dropped_pkts"] = np.zeros(G)
        out["deadlock_ticks"] = np.zeros(G)
    if fsp.any_msg:
        # message-layer outputs: per-flow counts, the grid-level log
        # histogram (summed over flows) and its percentile estimates —
        # zeros wherever no messages completed (the PR 2 NaN-safety
        # convention)
        mmask = np.isfinite(fsp.pvals["m_bytes"])            # [G, F]
        cnt = np.where(mmask, np.asarray(s["m_done"], np.float64), 0.0)
        tot = cnt.sum(-1)
        hist = np.asarray(s["m_hist"], np.float64).sum(-1)   # [G, B]
        lat_sum = np.asarray(s["m_lat"], np.float64).sum(-1)
        mbytes = np.where(mmask, fsp.pvals["m_bytes"], 0.0)
        # latencies above the histogram ceiling sit in the explicit
        # overflow counter; the percentile estimator returns the bucket
        # ceiling for ranks inside the overflow mass instead of a
        # silent midpoint below the true value
        ovf = np.where(mmask, np.asarray(s["m_over"], np.float64), 0.0)
        ov_tot = ovf.sum(-1)
        out["msg_count"] = cnt
        out["msg_count_total"] = tot
        out["msg_hist"] = hist
        out["msg_overflow_count"] = ov_tot
        out["msg_p50_us"] = percentile_from_counts(hist, 50.0,
                                                   overflow=ov_tot)
        out["msg_p99_us"] = percentile_from_counts(hist, 99.0,
                                                   overflow=ov_tot)
        out["msg_p999_us"] = percentile_from_counts(hist, 99.9,
                                                    overflow=ov_tot)
        out["msg_lat_mean_us"] = np.where(
            tot > 0.0, lat_sum / np.maximum(tot, 1.0), 0.0)
        out["msg_rate_mops"] = tot / sim_us
        out["msg_goodput_gbps"] = (cnt * mbytes).sum(-1) * per_gbps
        out["msg_last_done_us"] = np.where(
            mmask, np.asarray(s["m_last"], np.float64), 0.0)
        out["has_messages"] = mmask.any(-1)
    else:
        out["msg_count_total"] = np.zeros(G)
        out["has_messages"] = np.zeros(G, bool)
    if "reroutes" in s:
        rr = np.asarray(s["reroutes"], np.float64)
        out["flow_reroutes"] = rr
        out["reroute_count"] = rr.sum(-1)
    else:
        out["reroute_count"] = np.zeros(G)
    if "tx" in s:
        # per-uplink utilization (leaf->spine ports; sparse pod grids
        # add the spine->super-spine tier — fabric_uplinks' set); links
        # dead for the whole window leave the mean/max, matching
        # FabricResult.uplink_imbalance's zero-uptime exclusion
        tx = np.asarray(s["tx"], np.float64)
        cap = fsp.pvals["gbps"] * 1e9 / 8.0 * (sim_us * 1e-6)
        util = np.where(cap > 0.0, tx / np.maximum(cap, 1e-30), 0.0)
        up_mask = (fsp.stage_mask[1] | fsp.stage_mask[2]) if fsp.sparse \
            else fsp.stage_mask[1]
        alive = up_mask[None, :] & ~dead
        out["uplink_util"] = np.where(up_mask[None, :], util, 0.0)
        if up_mask.any():
            out["uplink_util_max"] = np.where(alive, util, 0.0).max(-1)
            out["uplink_util_mean"] = np.where(alive, util, 0.0).sum(-1) \
                / np.maximum(alive.sum(-1), 1)
        else:
            out["uplink_util_max"] = np.zeros(G)
            out["uplink_util_mean"] = np.zeros(G)
    return out


# --------------------------------------------------------------------------- #
# Backends
# --------------------------------------------------------------------------- #
def _np_params(fsp: FabricSweepParams, dtype) -> Dict[str, np.ndarray]:
    p = {k: (v if v.dtype == np.int32 else v.astype(dtype))
         for k, v in fsp.pvals.items()}
    # closed-flow completion threshold, shared with the scalar driver
    # (fabric.burst_done_bytes); the split injected/delivered accumulators
    # keep float32 drift to O(1) byte, well inside the threshold
    burst = fsp.pvals["burst"]
    p["burst_done"] = np.where(
        np.isfinite(burst),
        burst - np.maximum(1e-6, 1e-4 * np.where(np.isfinite(burst),
                                                 burst, 0.0)),
        np.inf).astype(dtype)
    p["d2"] = np.stack([p.pop("d_base"), p.pop("d_strag")], -2)
    return p


def _opts(fsp: FabricSweepParams, impl: str = "ref") -> dict:
    """Trace-time capability flags for :func:`_make_step`."""
    return {"dyn": fsp.dyn_route, "wrr": fsp.any_wrr,
            "host_tc": fsp.host_tc, "Hs": fsp.settle_ring,
            "Sn": fsp.n_spines, "cc": fsp.any_cc, "msg": fsp.any_msg,
            "Lm": fsp.msg_ring, "flt": fsp.any_flt, "flap": fsp.any_flap,
            "impl": impl}


def _run_numpy(fsp: FabricSweepParams, dtype=np.float64,
               adaptive: Optional[AdaptiveConfig] = None):
    p = _np_params(fsp, dtype)
    st = _static(fsp, np, dtype)

    def ring_set(ring, idx, v):
        ring[..., idx, :, :] = v
        return ring

    mk = _make_step_sparse if fsp.sparse else _make_step
    step = mk(np, ring_set, st, p, fsp.dt_us, fsp.ring_len, dtype,
              fsp.cnp_ring, _opts(fsp))
    s = _init_state(np, (fsp.n_points,), fsp, p, dtype)
    if adaptive is None:
        for t in range(fsp.ticks):
            s = step(s, t)
    else:
        # adaptive host loop: fine step, then extrapolate over the quiet
        # stride.  The delta comparison is safe on the pre-step dict
        # because every scaled/compared key is freshly allocated by the
        # step (only ring buffers mutate in place, and rings are never
        # scaled).  k == 1 leaves the carry bit-identical to a fine tick.
        stride = fused.make_stride_fn(np, fsp, p, _opts(fsp), adaptive,
                                      dtype)
        t = it = 0
        while t < fsp.ticks:
            s1 = step(s, np.int32(t), np.int32(it))
            k = int(stride(s, s1, np.int32(t)))
            if k > 1:
                s1 = fused.macro_advance(np, s, s1, dtype(k - 1))
            s = s1
            t += k
            it += 1
        res = _results(s, fsp)
        res["adaptive_iterations"] = np.full(fsp.n_points, it)
        return res
    return _results(s, fsp)


# --------------------------------------------------------------------------- #
# Host-device boundary: one buffer per dtype each way
# --------------------------------------------------------------------------- #
_F32, _I32 = np.dtype(np.float32), np.dtype(np.int32)


def _result_keys(fsp: FabricSweepParams) -> Tuple[str, ...]:
    """The carry keys :func:`_results` reads, from the flags
    :func:`_init_state` builds the carry by."""
    keys = ["delivered", "deliv_lo", "completion", "ever_paused",
            "pause_us", "pause_tc_us", "ecn_marked", "sw_dropped",
            "drained", "cnps", "ecns", "pfc_us", "rnic_drop", "mem_fb"]
    if fsp.sparse or fsp.dyn_route:
        keys.append("tx")
    if fsp.dyn_route:
        keys.append("reroutes")
    if fsp.any_msg:
        keys += ["m_done", "m_hist", "m_lat", "m_over", "m_last"]
    if fsp.any_flt:
        keys += ["retx", "flt_drop", "crash_rec", "deadlock"]
    return tuple(keys)


def _layout(arrays, lead: int) -> tuple:
    """Static layout of a dict of arrays packed one buffer per dtype:
    ``((buffer dtype, ((key, shape, dtype), ...)), ...)``, shapes without
    the ``lead`` leading axes.  int32 keys go to an int32 buffer, the
    rest (float32, and bool as 0/1) to a float32 one; a buffer no key
    goes to is left out."""
    groups: Dict[np.dtype, list] = {}
    for k, v in arrays.items():
        dt = np.dtype(v.dtype)
        groups.setdefault(_I32 if dt == _I32 else _F32, []).append(
            (k, tuple(v.shape[lead:]), dt))
    return tuple((bd, tuple(groups[bd])) for bd in (_F32, _I32)
                 if bd in groups)


def _pack(xp, layout: tuple, arrays, lead: Tuple[int, ...]) -> tuple:
    """``arrays`` (leading axes ``lead``) as the buffers of ``layout``,
    each ``lead + (width,)``."""
    return tuple(
        xp.concatenate([xp.reshape(arrays[k], lead + (-1,)).astype(bd)
                        for k, _, _ in keys], axis=-1)
        for bd, keys in layout)


def _unpack(layout: tuple, bufs, lead: Tuple[int, ...]) -> dict:
    """The inverse of :func:`_pack`: each key back at its shape and
    dtype, from static offsets."""
    out, axis = {}, len(lead)
    for (_, keys), buf in zip(layout, bufs):
        off = 0
        for k, shape, dt in keys:
            n = int(np.prod(shape, dtype=np.int64))
            piece = buf[(slice(None),) * axis + (slice(off, off + n),)]
            out[k] = piece.reshape(lead + shape).astype(dt)
            off += n
    return out


def _packed_params(fsp: FabricSweepParams) -> Tuple[tuple, tuple]:
    p = _np_params(fsp, np.float32)
    layout = _layout(p, 1)
    return layout, _pack(np, layout, p, (fsp.n_points,))


def packed_params(fsp: FabricSweepParams) -> Tuple[np.ndarray, ...]:
    """The scan program's inputs for ``fsp``: the parameters of
    :func:`_np_params` as one ``[G, n]`` float32 buffer and one
    ``[G, m]`` int32 buffer (left out when no parameter is int32).  Call
    ``_jax_program(fsp, ...)(*packed_params(fsp))``, or lower it on
    their shapes."""
    return _packed_params(fsp)[1]


# scan programs as (program, result layout); adaptive programs bare
_PROGRAMS: Dict[tuple, object] = {}
_PROGRAMS_MAX = 8          # bound compiled-executable memory, as sweep.py
# monotonic count of program-cache misses (new traces) in this process:
# the sweep farm's zero-recompile assertion reads it before/after each
# chunk — after the first chunk per canonical shape it must not move
PROGRAM_COMPILES = 0


def _program(fsp: FabricSweepParams, unroll: int, impl: str,
             p_layout: tuple) -> Tuple[Callable, tuple]:
    """The jitted scan program and the layout of the buffers it returns.

    The program takes the parameter buffers of ``p_layout``, builds the
    zero carry on the device (:func:`_init_state`, the same float32
    operations as on the host), runs the vmapped scan and returns the
    keys of :func:`_result_keys` packed one buffer per dtype."""
    global PROGRAM_COMPILES
    key = (fsp.structure_key, fsp.n_points, fsp.ticks, fsp.ring_len,
           fsp.cnp_ring, fsp.dt_us, unroll, impl, p_layout)
    hit = _PROGRAMS.get(key)
    if hit is not None:
        return hit
    PROGRAM_COMPILES += 1
    import jax
    import jax.numpy as jnp

    dtype = jnp.float32
    st = _static(fsp, jnp, dtype)
    ticks, H, Hc = fsp.ticks, fsp.ring_len, fsp.cnp_ring
    lead = (fsp.n_points,)

    def ring_set(ring, idx, v):
        return ring.at[..., idx, :, :].set(v)

    def one_point(s0, p):
        mk = _make_step_sparse if fsp.sparse else _make_step
        step = mk(jnp, ring_set, st, p, fsp.dt_us, H, dtype, Hc,
                  _opts(fsp, impl))

        def body(s, t):
            return step(s, t), None

        s, _ = jax.lax.scan(body, s0, jnp.arange(ticks, dtype=jnp.int32),
                            unroll=unroll)
        return s

    def start(bufs):
        # the whole grid's carry, as the host built it before: every key
        # batched over the grid, so the vmapped scan is unchanged
        p = _unpack(p_layout, bufs, lead)
        return _init_state(jnp, lead, fsp, p, dtype), p

    specs = [jax.ShapeDtypeStruct(
        lead + (sum(int(np.prod(sh)) for _, sh, _ in keys),), bd)
        for bd, keys in p_layout]
    s_spec, _ = jax.eval_shape(start, specs)
    r_layout = _layout({k: s_spec[k] for k in _result_keys(fsp)}, 1)

    def run(*bufs):
        s0, p = start(bufs)
        final = jax.vmap(one_point)(s0, p)
        return _pack(jnp, r_layout, final, lead)

    fn = jax.jit(run)
    while len(_PROGRAMS) >= _PROGRAMS_MAX:
        _PROGRAMS.pop(next(iter(_PROGRAMS)))
    _PROGRAMS[key] = (fn, r_layout)
    return fn, r_layout


def _jax_program(fsp: FabricSweepParams, unroll: int, impl: str = "ref"):
    """The jitted scan program of ``fsp``: :func:`packed_params` in, the
    carry keys :func:`_results` reads out, packed one buffer per dtype."""
    return _program(fsp, unroll, impl, _packed_params(fsp)[0])[0]


def _run_jax(fsp: FabricSweepParams, unroll, impl: str = "ref",
             device=None, record: Optional[dict] = None):
    """Run the scan program; ``device`` commits the inputs (and so the
    execution) to that jax device, else jax's default device.  A chunk
    crosses the boundary as one buffer per dtype each way: its packed
    parameters in, the packed carry keys :func:`_results` reads out.
    The host work is split into the ``chunk.*`` spans of :mod:`.spans`,
    timed into ``record`` when one is given."""
    import jax

    with span("chunk.params", record):
        p_layout, bufs = _packed_params(fsp)
        fn, r_layout = _program(fsp, pick_unroll(unroll), impl, p_layout)
    with transfer("chunk.h2d", record, bufs):
        bufs = jax.device_put(bufs, device)
    with span("chunk.dispatch", record):
        out = fn(*bufs)
    with span("chunk.device", record):
        jax.block_until_ready(out)
    with transfer("chunk.d2h", record, out):
        out = [np.asarray(b) for b in out]
    with span("chunk.unpack", record):
        return _results(_unpack(r_layout, out, (fsp.n_points,)), fsp)


def _jax_adaptive_program(fsp: FabricSweepParams, cfg: AdaptiveConfig,
                          impl: str):
    global PROGRAM_COMPILES
    key = ("adaptive", fsp.structure_key, fsp.n_points, fsp.ticks,
           fsp.ring_len, fsp.cnp_ring, fsp.dt_us, impl, cfg.key())
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    PROGRAM_COMPILES += 1
    import jax
    import jax.numpy as jnp

    dtype = jnp.float32
    st = _static(fsp, jnp, dtype)
    ticks, H, Hc = fsp.ticks, fsp.ring_len, fsp.cnp_ring

    def ring_set(ring, idx, v):
        return ring.at[..., idx, :, :].set(v)

    def run(s0, p):
        # unlike the scan program the adaptive loop is batched, not
        # vmapped: the stride is a whole-grid reduction, so every point
        # advances in lockstep (a per-point stride would desynchronize
        # the shared ring clock)
        step = _make_step(jnp, ring_set, st, p, fsp.dt_us, H, dtype, Hc,
                          _opts(fsp, impl))
        stride = fused.make_stride_fn(jnp, fsp, p, _opts(fsp, impl), cfg,
                                      dtype)

        def cond(carry):
            _, t, _ = carry
            return t < ticks

        def body(carry):
            s, t, it = carry
            s1 = step(s, t, it)
            k = stride(s, s1, t)
            km1 = k.astype(dtype) - dtype(1.0)
            s2 = fused.macro_advance(jnp, s, s1, km1)
            return s2, t + k, it + jnp.int32(1)

        s, _, it = jax.lax.while_loop(
            cond, body, (s0, jnp.int32(0), jnp.int32(0)))
        return s, it

    fn = jax.jit(run, donate_argnums=(0,))
    while len(_PROGRAMS) >= _PROGRAMS_MAX:
        _PROGRAMS.pop(next(iter(_PROGRAMS)))
    _PROGRAMS[key] = fn
    return fn


def _run_jax_adaptive(fsp: FabricSweepParams, cfg: AdaptiveConfig,
                      impl: str = "ref"):
    import jax.numpy as jnp

    fn = _jax_adaptive_program(fsp, cfg, impl)
    p_np = _np_params(fsp, np.float32)
    s0 = _init_state(np, (fsp.n_points,), fsp, p_np, np.float32)
    p = {k: jnp.asarray(v) for k, v in p_np.items()}
    final, iters = fn({k: jnp.asarray(v) for k, v in s0.items()}, p)
    res = _results({k: np.asarray(v) for k, v in final.items()}, fsp)
    res["adaptive_iterations"] = np.full(fsp.n_points, int(iters))
    return res


def run_fabric_sweep(scenarios: Sequence, backend: str = "jax",
                     unroll="auto", adaptive_dt: bool = False,
                     adaptive: Optional[AdaptiveConfig] = None,
                     impl: str = "auto",
                     incidence: str = "auto",
                     envelope: Optional[dict] = None
                     ) -> Dict[str, np.ndarray]:
    """Advance a grid of fabric scenarios through the full multi-host
    recurrence at once; returns ``{metric: array}`` aligned with the input
    order (arrays are ``[G]``, ``[G, F]`` or ``[G, R]`` — flow order is the
    scenario flow list, receiver order is ``sorted({flow.dst})``).

    All scenarios must share topology structure, routes and the flow set;
    receiver/switch/flow *parameters* may vary freely (see
    :class:`FabricSweepParams`).  ``backend="numpy"`` runs the same step
    function batched under float64 — the verification reference.

    ``adaptive_dt=True`` (or an explicit :class:`AdaptiveConfig` via
    ``adaptive=``) turns on macro-tick coarsening: quiet stretches of the
    whole grid advance ``k * dt`` per iteration in closed form, with fine
    ticks near every queue/watermark/timer event (see
    :mod:`repro.fabric.fused` for the quiet predicate, the event caps and
    the documented equivalence bound).  The default ``adaptive_dt=False``
    traces none of this machinery and reproduces today's results exactly.

    ``impl`` selects the fused-stage kernel tier for the jax backend
    (``"auto"`` -> Pallas on TPU, the inline reference elsewhere;
    ``"interpret"`` runs the Pallas kernels under the interpreter so CPU
    CI exercises the kernel path).  The numpy reference always runs the
    inline formulation.

    ``incidence`` picks the queue-state layout: ``"dense"`` is the
    [2, P, F] port x flow formulation, ``"sparse"`` the segmented
    [2, 6, F] slot incidence whose per-tick cost grows with
    flows x hops instead of flows x ports — required for 3-level
    (super-spine) pod fabrics and the scalable choice for any large
    static grid.  ``"auto"`` (default) selects sparse exactly when the
    topology has a super-spine tier, so existing 2-tier grids keep the
    dense engine bit-for-bit.  Sparse supports static ECMP plus
    failure/flap windows and the CC zoo (per-flow DCQCN/Timely/HPCC);
    dynamic routing, the message layer, fault injection and
    ``adaptive_dt`` stay dense-only.

    ``envelope`` is the chunk-boundary contract for the sweep farm
    (:mod:`repro.fabric.farm`): pass
    ``FabricSweepParams.from_scenarios(full_grid).envelope()`` when
    ``scenarios`` is a chunk of a larger grid, so the chunk traces the
    monolithic grid's program structure and reproduces its results
    bit-for-bit (see :meth:`FabricSweepParams.envelope`).
    """
    if incidence not in ("auto", "dense", "sparse"):
        raise ValueError(f"unknown incidence {incidence!r}")
    sparse = incidence == "sparse" or (
        incidence == "auto"
        and any(bool(s.topology.super_spines) for s in scenarios))
    fsp = FabricSweepParams.from_scenarios(scenarios, sparse=sparse,
                                           envelope=envelope)
    cfg = adaptive if adaptive is not None \
        else (AdaptiveConfig() if adaptive_dt else None)
    if fsp.sparse and cfg is not None:
        raise ValueError("adaptive_dt macro-ticking is dense-engine "
                         "only; run sparse grids at the fine tick")
    if backend == "numpy":
        return _run_numpy(fsp, adaptive=cfg)
    if backend == "jax":
        ri = fused.resolve_impl(impl)
        if cfg is not None:
            return _run_jax_adaptive(fsp, cfg, ri)
        return _run_jax(fsp, unroll, ri)
    raise ValueError(f"unknown backend {backend!r}")

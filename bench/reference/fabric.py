"""The fabric, tick by tick: DCQCN senders, switch ports with ECN and
per-class PFC on static ECMP routes, and receiving hosts behind their
RNICs.  One Python object per port, sender and receiver, in float64.

A tick: senders inject into their NIC queue; ports drain tier by tier
in path order (NIC, leaf up, spine up, super-spine, spine down, leaf
down), so an uncongested byte crosses the fabric in one tick; bytes a
full queue or RNIC refuses go back to their sender (fluid go-back-N);
receivers advance and send CNPs, switch marks turn into CNPs paced per
flow; then PFC pause state is refreshed for the next tick.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Set, Tuple

from .dcqcn import DcqcnConfig, DcqcnRate
from .receiver import N_QOS, QoS, ReceiverHost, SimConfig, testbed_100g
from .switch import OutputPort, PauseKey, SwitchConfig
from .topology import LinkKey, Topology


@dataclasses.dataclass
class Flow:
    """One sender-to-receiver transfer."""
    src: str
    dst: str
    offered_gbps: Optional[float] = None     # open-loop cap (None: saturate)
    burst_bytes: Optional[float] = None      # closed flow: stop after burst
    start_us: float = 0.0
    tag: str = ""
    qos: QoS = QoS.NORMAL                    # RNIC class and switch class


@dataclasses.dataclass
class FabricConfig:
    sim_time_s: float = 0.01
    dt_us: float = 1.0
    switch: SwitchConfig = dataclasses.field(default_factory=SwitchConfig)
    receiver_cfg: Callable[[str], SimConfig] = \
        lambda host: testbed_100g("jet")


def burst_done_bytes(burst_bytes: float) -> float:
    """A closed flow is complete at 99.99% delivered: fluid go-back-N has
    no sharp last byte."""
    return burst_bytes - max(1e-6, 1e-4 * burst_bytes)


class SenderHost:
    """One DCQCN-paced flow source."""

    def __init__(self, line_rate_gbps: float, f: Flow):
        self.line_rate_gbps = line_rate_gbps
        self.rate = DcqcnRate(DcqcnConfig(line_rate_gbps=line_rate_gbps))
        self.offered_gbps = f.offered_gbps
        self.burst_bytes = f.burst_bytes
        self.start_us = f.start_us
        self.injected = 0.0
        self.now_us = 0.0

    def offer(self, dt_us: float) -> float:
        """Bytes the flow injects into its NIC queue this tick."""
        self.now_us += dt_us
        if self.now_us <= self.start_us:
            return 0.0
        gbps = min(self.rate.advance(dt_us), self.line_rate_gbps)
        if self.offered_gbps is not None:
            gbps = min(gbps, self.offered_gbps)
        if self.burst_bytes is not None and self.injected >= self.burst_bytes:
            return 0.0
        b = gbps * 1e9 / 8.0 * dt_us * 1e-6
        if self.burst_bytes is not None:
            b = min(b, self.burst_bytes - self.injected)
        self.injected += b
        return b


@dataclasses.dataclass
class FabricResult:
    flow_delivered_bytes: List[float]
    flow_completion_us: List[float]          # inf where unfinished
    pause_link_us: Dict[LinkKey, float]      # link paused in >= 1 class
    recv_cnp_count: Dict[str, float]         # CNPs each receiver sent
    ecn_marked_bytes: float                  # marked by the switches
    sim_us: float


def run_fabric(topo: Topology, flows: List[Flow],
               fcfg: FabricConfig) -> FabricResult:
    dt = fcfg.dt_us
    ticks = int(fcfg.sim_time_s * 1e6 / dt)

    next_hop: Dict[Tuple[str, int], str] = {}      # (node, fid) -> node
    senders: List[SenderHost] = []
    for fid, f in enumerate(flows):
        nodes = topo.route(f.src, f.dst, fid)
        for a, b in zip(nodes, nodes[1:]):
            next_hop[(a, fid)] = b
        senders.append(SenderHost(topo.access_gbps(f.src), f))

    receivers: Dict[str, ReceiverHost] = {
        h: ReceiverHost(fcfg.receiver_cfg(h), sim_ticks=ticks)
        for h in sorted({f.dst for f in flows})}

    # NIC egress queues never mark ECN; only switches do
    nic_cfg = dataclasses.replace(fcfg.switch, ecn_enabled=False)
    nic_ports: Dict[str, OutputPort] = {}
    for f in flows:
        if f.src not in nic_ports:
            nic_ports[f.src] = OutputPort(
                topo.link(f.src, topo.host_leaf[f.src]), nic_cfg)
    switch_ports: Dict[str, Dict[str, OutputPort]] = {}
    for name in topo.leaves + topo.spines + topo.super_spines:
        switch_ports[name] = {l.dst: OutputPort(l, fcfg.switch)
                              for l in topo.links.values() if l.src == name}

    tc_of = [int(f.qos) if fcfg.switch.per_tc else 0 for f in flows]

    # per-flow CNP pacing at the receiver (DCQCN NP)
    cnp_accum_us = [math.inf] * len(flows)         # an immediate first CNP
    marked_backlog = [0.0] * len(flows)
    # CNPs reach their senders at the end of the tick that sent them
    pending_cnps: List[int] = []
    flows_by_dst: Dict[str, List[int]] = {}
    for fid, f in enumerate(flows):
        flows_by_dst.setdefault(f.dst, []).append(fid)
    # the heaviest recent arrival per receiver takes its CNPs while the
    # access link is paused and nothing arrives
    last_heavy: Dict[str, Optional[int]] = {}

    delivered = [0.0] * len(flows)
    completion = [math.inf] * len(flows)
    pause_link_us: Dict[LinkKey, float] = {}
    paused_by_link: Dict[LinkKey, frozenset] = {}
    _no_tcs: frozenset = frozenset()
    hosts_set = set(topo.hosts)
    Batches = Dict[Tuple[str, str], List[Tuple[int, float, float,
                                               Optional[LinkKey], int]]]

    def drain_stage(ports, arrivals, batches: Batches) -> None:
        for port in ports:
            lk = port.link.key
            dst = port.link.dst
            to_host = dst in hosts_set
            port.paused_tcs = paused_by_link.get(lk, _no_tcs)
            port.paused = False
            if to_host and dst in receivers \
                    and receivers[dst].cfg.pfc_enabled:
                port.paused = receivers[dst].pfc_paused
            for fid, b, m in port.drain(dt):
                if to_host:
                    cur = arrivals.setdefault(dst, {}) \
                        .setdefault(fid, [0.0, 0.0])
                    cur[0] += b
                    cur[1] += m
                else:
                    batches.setdefault((dst, next_hop[(dst, fid)]), []) \
                        .append((fid, b, m, lk, tc_of[fid]))

    sspine_set = set(topo.super_spines)
    stages = [
        list(nic_ports.values()),
        [p for leaf in topo.leaves for p in switch_ports[leaf].values()
         if p.link.dst not in hosts_set],
        [p for sp in topo.spines for p in switch_ports[sp].values()
         if p.link.dst in sspine_set],
        [p for ss in topo.super_spines for p in switch_ports[ss].values()],
        [p for sp in topo.spines for p in switch_ports[sp].values()
         if p.link.dst not in sspine_set],
        [p for leaf in topo.leaves for p in switch_ports[leaf].values()
         if p.link.dst in hosts_set],
    ]
    stages = [st for st in stages if st]

    for t in range(ticks):
        now_us = (t + 1) * dt
        # ---- 1. senders inject; a NIC queue takes what its class has room
        # for, pro rata, and the rest is never sent
        offers: Dict[str, List[Tuple[int, float]]] = {}
        for fid, f in enumerate(flows):
            b = senders[fid].offer(dt)
            if b > 0.0:
                offers.setdefault(f.src, []).append((fid, b))
        for host, items in offers.items():
            port = nic_ports[host]
            by_tc: Dict[int, List[Tuple[int, float]]] = {}
            for fid, b in items:
                by_tc.setdefault(tc_of[fid], []).append((fid, b))
            batch = []
            for tc, tc_items in by_tc.items():
                space = max(0.0, fcfg.switch.port_buffer_bytes
                            - port.tc_bytes(tc))
                total = sum(b for _, b in tc_items)
                scale = 1.0 if total <= space else space / total
                for fid, b in tc_items:
                    take = b if scale >= 1.0 else b * scale
                    senders[fid].injected -= b - take
                    batch.append((fid, take, 0.0, None, tc))
            port.enqueue_batch(batch)

        # ---- 2. forwarding, tier by tier
        arrivals: Dict[str, Dict[int, List[float]]] = {}
        for stage in stages:
            batches: Batches = {}
            drain_stage(stage, arrivals, batches)
            for (sw, dst), items in batches.items():
                for fid, lost in switch_ports[sw][dst] \
                        .enqueue_batch(items).items():
                    senders[fid].injected -= lost

        # ---- 3. receivers advance; CNPs go back
        for host, rx in receivers.items():
            arr = arrivals.get(host, {})
            per_class = [0.0] * N_QOS
            for fid, (b, _) in arr.items():
                per_class[flows[fid].qos] += b
            accepted, cnps = rx.step(per_class)
            if sum(per_class) > 0.0:
                share = [accepted[q] / per_class[q] if per_class[q] > 0.0
                         else 0.0 for q in range(N_QOS)]
                for fid, (b, _) in arr.items():
                    d = b * share[flows[fid].qos]
                    delivered[fid] += d
                    senders[fid].injected -= b - d   # RNIC drops resent
                    f = flows[fid]
                    if (f.burst_bytes is not None
                            and math.isinf(completion[fid])
                            and delivered[fid]
                            >= burst_done_bytes(f.burst_bytes)):
                        completion[fid] = now_us
            # receiver CNPs go to the heaviest arriving flow (lowest flow
            # id on a tie), or the last one while nothing arrives
            if arr:
                last_heavy[host] = max(sorted(arr), key=lambda i: arr[i][0])
            heavy = last_heavy.get(host)
            if heavy is not None:
                pending_cnps += [heavy] * cnps
            # switch marks become CNPs, at most one per flow per interval
            for fid, (_, m) in arr.items():
                marked_backlog[fid] += m
            interval = rx.cfg.cnp_interval_us
            for fid in flows_by_dst.get(host, ()):
                cnp_accum_us[fid] += dt
                if marked_backlog[fid] > 0.0 and \
                        cnp_accum_us[fid] >= interval:
                    cnp_accum_us[fid] = 0.0
                    marked_backlog[fid] = 0.0
                    pending_cnps.append(fid)
        for fid in pending_cnps:
            senders[fid].rate.on_cnp()
        pending_cnps.clear()

        # ---- 4. PFC state for the next tick
        paused_pairs: Set[PauseKey] = set()
        for ports in switch_ports.values():
            for p in ports.values():
                paused_pairs |= p.update_pfc()
        by_link: Dict[LinkKey, Set[int]] = {}
        for lk, tc in paused_pairs:
            by_link.setdefault(lk, set()).add(tc)
        paused_by_link = {lk: frozenset(tcs) for lk, tcs in by_link.items()}
        for lk in paused_by_link:
            pause_link_us[lk] = pause_link_us.get(lk, 0.0) + dt

    return FabricResult(
        flow_delivered_bytes=delivered,
        flow_completion_us=completion,
        pause_link_us=pause_link_us,
        recv_cnp_count={h: rx.cnp_count for h, rx in receivers.items()},
        ecn_marked_bytes=sum(sum(p.marked_bytes for p in ports.values())
                             for ports in switch_ports.values()),
        sim_us=ticks * dt,
    )

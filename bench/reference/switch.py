"""Output-queued switch port with per-traffic-class queues, ECN marking
and per-priority PFC (802.1Qbb).

* Each class owns a full ``port_buffer_bytes`` partition; a tick's
  arrivals share a class's free space in proportion to what they offer.
* A class marks ECN on arrival once its queue is past ``ecn_kmin_frac``
  of the buffer (one decision per class per tick).
* A class past ``pfc_xoff_frac`` pauses, in that class, the ingress links
  of the flows it holds, until it falls under ``pfc_xon_frac``.
* The link drains classes in strict priority (class 0 first), pro rata
  over the flows within a class.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from .receiver import N_QOS
from .topology import Link, LinkKey

N_TC = N_QOS                      # switch classes are the QoS classes

# (ingress link, traffic class): what one PFC pause frame stops
PauseKey = Tuple[LinkKey, int]


@dataclasses.dataclass
class SwitchConfig:
    port_buffer_bytes: int = 4 << 20
    ecn_enabled: bool = True
    ecn_kmin_frac: float = 0.10
    pfc_enabled: bool = False
    pfc_xoff_frac: float = 0.60
    pfc_xon_frac: float = 0.30
    # False: every flow rides class 0 (one queue per port)
    per_tc: bool = True


@dataclasses.dataclass
class _FlowQ:
    bytes: float = 0.0
    marked: float = 0.0               # ECN-marked part of ``bytes``


_NO_TCS: frozenset = frozenset()


class OutputPort:
    def __init__(self, link: Link, cfg: SwitchConfig):
        self.link = link
        self.cfg = cfg
        # class -> {flow id -> queued bytes}, in arrival (FIFO) order
        self.tcq: List[Dict[int, _FlowQ]] = [{} for _ in range(N_TC)]
        self.flow_ingress: Dict[int, Optional[LinkKey]] = {}
        self.paused = False           # whole link paused (receiver gate)
        self.paused_tcs: frozenset = _NO_TCS   # classes paused downstream
        self.tc_asserted = [False] * N_TC      # this port's xoff per class
        self.marked_bytes = 0.0
        self._tc_bytes = [0.0] * N_TC
        self._total_bytes = 0.0

    def tc_bytes(self, tc: int) -> float:
        return self._tc_bytes[tc]

    def enqueue_batch(
            self,
            items: List[Tuple[int, float, float, Optional[LinkKey], int]],
    ) -> Dict[int, float]:
        """Queue one tick's arrivals ``[(fid, bytes, marked, in_link,
        tc)]``; returns ``{fid: bytes tail-dropped}``."""
        tot_tc = [0.0] * N_TC
        for _, b, _, _, tc in items:
            if b > 0.0:
                tot_tc[tc] += b
        if not any(t > 0.0 for t in tot_tc):
            return {}
        buf = self.cfg.port_buffer_bytes
        scale_tc = [1.0] * N_TC
        for tc in range(N_TC):
            if tot_tc[tc] <= 0.0:
                continue
            space = max(0.0, buf - self._tc_bytes[tc])
            if tot_tc[tc] > space:
                scale_tc[tc] = space / tot_tc[tc]
        mark_tc = [self.cfg.ecn_enabled and
                   self._tc_bytes[tc] > self.cfg.ecn_kmin_frac * buf
                   for tc in range(N_TC)]
        dropped: Dict[int, float] = {}
        for fid, b, m, in_link, tc in items:
            if b <= 0.0:
                continue
            take = b if scale_tc[tc] >= 1.0 else b * scale_tc[tc]
            lost = b - take
            if lost > 0.0:
                dropped[fid] = dropped.get(fid, 0.0) + lost
            if take <= 0.0:
                continue
            mk = m * (take / b)
            if mark_tc[tc]:
                self.marked_bytes += take - mk
                mk = take
            fq = self.tcq[tc].setdefault(fid, _FlowQ())
            fq.bytes += take
            fq.marked += mk
            self._tc_bytes[tc] += take
            self._total_bytes += take
            self.flow_ingress[fid] = in_link
        return dropped

    def drain(self, dt_us: float) -> List[Tuple[int, float, float]]:
        """Forward up to rate x dt bytes: ``[(fid, bytes, marked)]``."""
        if self.paused:
            return []
        if self._total_bytes <= 0.0:
            return []
        budget = self.link.gbps * 1e9 / 8.0 * dt_us * 1e-6
        budget_left = budget
        out: List[Tuple[int, float, float]] = []
        for tc in range(N_TC):
            total = self._tc_bytes[tc]
            if total <= 0.0 or tc in self.paused_tcs:
                continue
            frac = min(1.0, budget_left / total)
            q = self.tcq[tc]
            for fid, fq in list(q.items()):
                b = fq.bytes * frac
                m = fq.marked * frac
                fq.bytes -= b
                fq.marked -= m
                self._tc_bytes[tc] -= b
                self._total_bytes -= b
                if fq.bytes < 1e-9:
                    self._tc_bytes[tc] -= fq.bytes
                    self._total_bytes -= fq.bytes
                    del q[fid]
                if b > 0.0:
                    out.append((fid, b, m))
            budget_left -= total * frac
            # a leftover under 1e-6 of the budget is rounding, not room
            # for the next class
            if budget_left < 1e-6 * budget:
                budget_left = 0.0
            self._tc_bytes[tc] = max(0.0, self._tc_bytes[tc])
        self._total_bytes = max(0.0, self._total_bytes)
        return out

    def update_pfc(self) -> Set[PauseKey]:
        """Refresh the xoff/xon state; returns the ``(ingress link,
        class)`` pairs to pause."""
        out: Set[PauseKey] = set()
        if not self.cfg.pfc_enabled:
            return out
        buf = self.cfg.port_buffer_bytes
        for tc in range(N_TC):
            q_frac = self._tc_bytes[tc] / buf
            if self.tc_asserted[tc]:
                if q_frac < self.cfg.pfc_xon_frac:
                    self.tc_asserted[tc] = False
            elif q_frac > self.cfg.pfc_xoff_frac:
                self.tc_asserted[tc] = True
            if self.tc_asserted[tc]:
                for fid in self.tcq[tc]:
                    lk = self.flow_ingress.get(fid)
                    if lk is not None:
                        out.add((lk, tc))
        return out

"""The receiving host behind its RNIC (paper §3-§5), one fluid tick at a
time: QoS-classed RNIC buffer, drain to the cache pool (Jet) or through
DDIO, release after the post-NIC hold, the Jet escape ladder, the RNIC's
PFC gate and watermark CNPs.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import List, Sequence

from .dcqcn import DcqcnConfig


class QoS(enum.IntEnum):
    """Transfer classes (§3.2); a lower value is served first."""
    HIGH = 0
    NORMAL = 1
    LOW = 2


N_QOS = len(QoS)


@dataclasses.dataclass
class SimConfig:
    mode: str = "ddio"                 # "ddio" (baseline) | "jet"
    pfc_enabled: bool = False
    sim_time_s: float = 0.03
    dt_us: float = 1.0

    line_rate_gbps: float = 200.0      # dual-port 100 Gbps
    num_qps: int = 32
    msg_bytes: int = 256 << 10
    incast_senders: int = 1

    pcie_gbps: float = 252.0           # PCIe 4.0 x16
    membw_total_gbps: float = 2000.0   # 250 GB/s
    cpu_membw_gbps: float = 1760.0     # CPU-side DRAM traffic
    app_gbps: float = 3200.0           # application consumption bandwidth
    consumer_latency_us: float = 60.0  # SSD/GPU/compute hand-off latency

    ddio_bytes: int = 6 << 20
    miss_knee: float = 0.5             # miss ramps over knee*ddio_bytes

    rnic_buffer_bytes: int = 2 << 20
    pfc_xoff: float = 0.80
    pfc_xon: float = 0.50
    ecn_threshold: float = 0.15
    cnp_interval_us: float = 50.0
    rnic_ecn_cnp: bool = True

    jet_pool_bytes: int = 12 << 20
    straggler_frac: float = 0.005
    straggler_mult: float = 20.0
    cache_safe: float = 0.20
    cache_danger: float = 0.05
    mem_esc_bytes: int = 2 << 20

    dcqcn: DcqcnConfig = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.dcqcn is None:
            self.dcqcn = DcqcnConfig(line_rate_gbps=self.line_rate_gbps *
                                     self.incast_senders)


def testbed_100g(mode: str = "ddio", **kw) -> SimConfig:
    """The paper's 2x100 Gbps PFC-free testbed host (§2.1)."""
    base = dict(pfc_enabled=False, line_rate_gbps=200.0, pcie_gbps=252.0,
                membw_total_gbps=2000.0, cpu_membw_gbps=1760.0,
                ddio_bytes=6 << 20)
    base.update(kw)
    return SimConfig(mode=mode, **base)


# Post-NIC hold (§4.2.2).  Jet cuts a message into 4 KB slices that pass
# three stages, land -> process -> release, each on its own; a slice's
# slot is free again three slice-times after it landed.  The per-byte
# costs are those of the fully optimised receiver: landing at PCIe pace,
# CRC on the NIC, in-place (struct) deserialisation and the
# application's touch, processed by 4 threads.
SLICE_BYTES = 4096
LAND_NS_PER_BYTE = 0.012
DESERIALISE_NS_PER_BYTE = 0.02
APP_NS_PER_BYTE = 0.10
PROCESS_THREADS = 4


def hold_us(c: SimConfig) -> float:
    """Microseconds a drained byte stays resident before the consumer
    frees it: the consumer's hand-off latency, plus three slice-times
    (Jet) or the time the application takes to consume one whole message
    (the baseline frees a buffer only when its message is consumed)."""
    if c.mode == "jet":
        process = (DESERIALISE_NS_PER_BYTE + APP_NS_PER_BYTE) \
            / PROCESS_THREADS
        slice_ns = SLICE_BYTES * (LAND_NS_PER_BYTE + process)
        return c.consumer_latency_us + 3.0 * slice_ns / 1000.0
    return c.consumer_latency_us + c.msg_bytes * 8.0 / (c.app_gbps * 1000.0)


class ReceiverHost:
    """One receiving host.  ``step`` takes the bytes that arrived on its
    access link this tick, per QoS class, and returns what the RNIC
    accepted per class and how many CNPs it sends."""

    def __init__(self, c: SimConfig, sim_ticks: int):
        self.cfg = c
        self.dt = float(c.dt_us)
        # release buckets, 1 s of slack past the end for late releases
        self.horizon = sim_ticks + int(1e6 / self.dt)
        self.rel_base = [0.0] * self.horizon
        self.rel_strag = [0.0] * self.horizon
        self.qos_q = [0.0] * N_QOS        # RNIC buffer, by class
        self.resident = 0.0               # drained bytes not yet consumed
        self.strag_resident = 0.0
        self.escape_debt = 0.0            # escaped bytes whose release is void
        self.replace_debt = 0.0
        self.pool_cap = float(c.jet_pool_bytes)
        self.replace_mem = 0.0
        self.ecn_escape_accum_us = 0.0
        h = hold_us(c)
        self.d_base = max(1, int(h / self.dt))
        self.d_strag = max(1, int(h * c.straggler_mult / self.dt))

        self.pfc_paused = False
        self.cnp_count = 0.0
        self.cnp_accum_us = c.cnp_interval_us  # an immediate first CNP
        self.t = 0

    def _admit(self, arriving: Sequence[float]) -> List[float]:
        """RNIC buffer space, granted in QoS order."""
        space = max(0.0, self.cfg.rnic_buffer_bytes - sum(self.qos_q))
        per_class = [0.0] * N_QOS
        for cls in QoS:
            take = min(float(arriving[cls]), space)
            space -= take
            self.qos_q[cls] += take
            per_class[cls] = take
        return per_class

    def _drain(self, t: int) -> int:
        """RNIC -> host, releases and the escape ladder; returns the
        escape ladder's ECN fires."""
        c, dt, q = self.cfg, self.dt, self.qos_q
        bytes_per_gbps_tick = 1e9 / 8.0 * dt * 1e-6
        if c.mode == "ddio":
            # DDIO write-allocate: past the DDIO ways each drained byte
            # costs ~2*miss bytes of DRAM bandwidth, which the CPU's own
            # traffic leaves short
            working_set = c.num_qps * c.msg_bytes + self.resident
            over = working_set - c.ddio_bytes
            miss = min(1.0, max(0.0, over / (c.miss_knee * c.ddio_bytes)))
            avail_dram = max(0.0, c.membw_total_gbps - c.cpu_membw_gbps)
            drain_bw = c.pcie_gbps
            if miss > 1e-9:
                drain_bw = min(drain_bw, avail_dram / (2.0 * miss))
            budget = drain_bw * bytes_per_gbps_tick
            drained = 0.0
            for cls in QoS:
                take = min(q[cls], budget)
                q[cls] -= take
                budget -= take
                drained += take
            pool_drained = drained
            strag_share = 0.0
        else:
            # Jet: drain into free cache-pool slots; under pool pressure
            # LOW bytes go to DRAM instead (§5)
            pool_free = max(0.0, self.pool_cap - self.resident)
            spill_low = pool_free / self.pool_cap < c.cache_safe
            budget = min(c.pcie_gbps, c.line_rate_gbps * 4.0) \
                * bytes_per_gbps_tick
            pool_drained = 0.0
            for cls in QoS:
                if cls is QoS.LOW and spill_low:
                    take = min(q[cls], budget)
                else:
                    take = min(q[cls], budget, pool_free)
                    pool_free -= take
                    pool_drained += take
                q[cls] -= take
                budget -= take
            strag_share = c.straggler_frac

        if pool_drained > 0.0:
            base_part = pool_drained * (1.0 - strag_share)
            strag_part = pool_drained * strag_share
            bt = min(self.horizon - 1, t + self.d_base)
            st = min(self.horizon - 1, t + self.d_strag)
            self.rel_base[bt] += base_part
            self.rel_strag[st] += strag_part
            self.resident += pool_drained
            self.strag_resident += strag_part

        for arr, is_strag in ((self.rel_base, False), (self.rel_strag, True)):
            r = arr[t]
            if r <= 0.0:
                continue
            if self.escape_debt > 0.0:
                void = min(r, self.escape_debt)
                self.escape_debt -= void
                r -= void
                repay = min(void, self.replace_debt)
                self.replace_debt -= repay
                self.replace_mem = max(0.0, self.replace_mem - repay)
            self.resident = max(0.0, self.resident - r)
            if is_strag:
                self.strag_resident = max(0.0, self.strag_resident - r)

        fires = 0
        if c.mode == "jet":
            # escape ladder (Algorithm 1): replace stragglers into DRAM
            # up to mem_esc_bytes, else copy them out; below the danger
            # level, ECN toward the senders
            avail_frac = max(0.0, self.pool_cap - self.resident) \
                / self.pool_cap
            if avail_frac < c.cache_safe:
                if self.replace_mem < c.mem_esc_bytes:
                    x = min(self.strag_resident,
                            c.mem_esc_bytes - self.replace_mem)
                    if x > 0.0:
                        self.resident -= x
                        self.strag_resident -= x
                        self.escape_debt += x
                        self.replace_debt += x
                        self.replace_mem += x
                else:
                    x = self.strag_resident
                    if x > 0.0:
                        self.resident -= x
                        self.strag_resident = 0.0
                        self.escape_debt += x
                avail_frac = max(0.0, self.pool_cap - self.resident) \
                    / self.pool_cap
                if avail_frac < c.cache_danger:
                    self.ecn_escape_accum_us += dt
                    if self.ecn_escape_accum_us >= c.cnp_interval_us:
                        self.ecn_escape_accum_us = 0.0
                        fires += 1
        return fires

    def step(self, arriving: Sequence[float]):
        """One tick: ``(accepted bytes per class, CNPs sent)``."""
        c, dt, t = self.cfg, self.dt, self.t
        if t >= self.horizon:
            raise RuntimeError("ReceiverHost stepped past its horizon")
        accepted = self._admit(arriving)
        cnps = self._drain(t)
        self.cnp_count += cnps

        q_frac = sum(self.qos_q) / c.rnic_buffer_bytes
        if c.pfc_enabled:
            if self.pfc_paused:
                if q_frac < c.pfc_xon:
                    self.pfc_paused = False
            elif q_frac > c.pfc_xoff:
                self.pfc_paused = True
        # RNIC-watermark CNPs (ConnectX-6 DX, §2.1)
        self.cnp_accum_us += dt
        if (c.rnic_ecn_cnp and q_frac > c.ecn_threshold
                and self.cnp_accum_us >= c.cnp_interval_us):
            self.cnp_accum_us = 0.0
            self.cnp_count += 1
            cnps += 1
        self.t += 1
        return accepted, cnps

"""Vectorized parameter-sweep engine for the receiver datapath.

Packs the per-host fluid state of :class:`~repro.fabric.hosts.ReceiverHost`
(DCQCN machine, RNIC queue, DDIO/Jet drain, release rings, escape ladder,
PFC/CNP signalling) into stacked arrays and advances *all sweep points at
once*: one ``jax.vmap`` over the grid, one ``jax.lax.scan`` over ticks, one
XLA program — hundred-point sweeps run in seconds instead of minutes of
sequential ``run_sim`` python loops.

The exact same step function also runs batched under numpy (the
``backend="numpy"`` verification reference): both paths share a single
source of truth and differ only in the array namespace and the ring
scatter/gather, so their results agree to float32 round-off.  This
engine sweeps the *receiver* datapath only; op-granular message latency
lives in the fabric layer (:mod:`repro.fabric.messages`, tracked by both
``run_fabric`` and ``run_fabric_sweep`` via a log-bucket histogram) —
here the recurrence stays identical to ``run_sim`` and goodput matches
the scalar simulator point-for-point.

The release rings are circular (mod-H indexing) rather than run_sim's
full-horizon arrays: slot ``t % H`` is *written* every tick with that
tick's scheduled release and *read* ``d`` ticks later at ``(t - d) % H``.
H exceeds the largest delay, so a slot is always consumed before the ring
wraps back over it — no scatter-add and no zeroing, which keeps the hot
loop to one dynamic-update-slice + one gather per ring.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, List, Sequence, Tuple

import numpy as np

from ..core.simulator import SimConfig
from .hosts import hold_us_baseline, hold_us_jet
from ._scan import pick_unroll

_F = np.float32


# --------------------------------------------------------------------------- #
# Parameter packing
# --------------------------------------------------------------------------- #
_SCALARS = [
    # (name, extractor)
    ("jet", lambda c: 1.0 if c.mode == "jet" else 0.0),
    ("pfc_en", lambda c: 1.0 if c.pfc_enabled else 0.0),
    ("wm_cnp", lambda c: 1.0 if c.rnic_ecn_cnp else 0.0),
    ("line", lambda c: c.line_rate_gbps * c.incast_senders),
    ("line1", lambda c: c.line_rate_gbps),
    ("cap", lambda c: np.inf if c.offered_gbps is None else c.offered_gbps),
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
    # DCQCN
    ("dline", lambda c: c.dcqcn.line_rate_gbps),
    ("minr", lambda c: c.dcqcn.min_rate_gbps),
    ("g", lambda c: c.dcqcn.g),
    ("a_tmr", lambda c: c.dcqcn.alpha_timer_us),
    ("r_tmr", lambda c: c.dcqcn.rate_timer_us),
    ("bctr", lambda c: c.dcqcn.byte_counter_mb * (1 << 20)),
    ("ai", lambda c: c.dcqcn.ai_rate_gbps),
    ("hai", lambda c: c.dcqcn.hai_rate_gbps),
    ("fth", lambda c: c.dcqcn.f_threshold),
]


@dataclasses.dataclass
class SweepParams:
    """Stacked per-point parameters (all float32 arrays of shape [P])."""
    vals: Dict[str, np.ndarray]
    d_base: np.ndarray            # int32 release delays (ticks)
    d_strag: np.ndarray
    n_points: int
    ticks: int
    dt_us: float
    ring_len: int

    def envelope(self) -> dict:
        """Structure envelope for chunked execution (see the farm layer):
        passing this to :meth:`from_configs` on a slice of the grid floors
        the ring length so every chunk traces the same program shape."""
        return {"ring_len": self.ring_len}

    @classmethod
    def from_configs(cls, configs: Sequence[SimConfig],
                     envelope: dict | None = None) -> "SweepParams":
        if not configs:
            raise ValueError("empty sweep grid")
        dt = configs[0].dt_us
        ticks = int(configs[0].sim_time_s * 1e6 / dt)
        for c in configs:
            if c.dt_us != dt or int(c.sim_time_s * 1e6 / c.dt_us) != ticks:
                raise ValueError("sweep points must share dt and sim_time")
            if c.cpu_membw_schedule is not None:
                raise ValueError("cpu_membw_schedule is not sweepable; "
                                 "use run_sim for scheduled contention")
        vals = {name: np.array([fn(c) for c in configs], dtype=_F)
                for name, fn in _SCALARS}
        d_b, d_s = [], []
        for c in configs:
            hold = hold_us_jet(c) if c.mode == "jet" \
                else hold_us_baseline(c)
            d_b.append(max(1, int(hold / dt)))
            d_s.append(max(1, int(hold * c.straggler_mult / dt)))
        ring = int(max(max(d_b), max(d_s))) + 2
        if envelope:
            ring = max(ring, int(envelope.get("ring_len", 0)))
        return cls(vals=vals, d_base=np.array(d_b, np.int32),
                   d_strag=np.array(d_s, np.int32),
                   n_points=len(configs), ticks=ticks, dt_us=dt,
                   ring_len=ring)


def grid_configs(mk, mode: str = "jet", sim_time_s: float = 0.01,
                 **axes: Sequence) -> Tuple[List[SimConfig], List[dict]]:
    """Cartesian sweep grid: ``mk(mode, sim_time_s=..., **point)`` per
    combination of the ``axes`` lists.  Returns (configs, point-dicts)."""
    names = sorted(axes)
    configs, points = [], []
    for combo in itertools.product(*(axes[n] for n in names)):
        pt = dict(zip(names, combo))
        configs.append(mk(mode, sim_time_s=sim_time_s, **pt))
        points.append(pt)
    return configs, points


# --------------------------------------------------------------------------- #
# The shared per-tick step
# --------------------------------------------------------------------------- #
def _make_step(xp, ring_get, ring_set, p: Dict, dt: float,
               H: int, d_base, d_strag):
    """Build step(state, t) -> state in the given array namespace ``xp``.

    ``p`` maps parameter names to arrays (shape [] under vmap, [P] under
    numpy); the ring_* helpers hide the gather/update difference."""
    bpt = _F(1e9 / 8.0 * dt * 1e-6)      # bytes per (Gbps * tick)
    fdt = _F(dt)

    def cut(s, fire):
        """DCQCN on_cnp for points where ``fire`` holds."""
        s = dict(s)
        s["rt"] = xp.where(fire, s["rc"], s["rt"])
        s["rc"] = xp.where(fire,
                           xp.maximum(p["minr"],
                                      s["rc"] * (1.0 - s["alpha"] / 2.0)),
                           s["rc"])
        s["alpha"] = xp.where(
            fire, xp.minimum(_F(1.0), (1.0 - p["g"]) * s["alpha"] + p["g"]),
            s["alpha"])
        for k in ("t_us", "byts", "t_stage", "b_stage", "a_tus"):
            s[k] = xp.where(fire, _F(0.0), s[k])
        return s

    def step(s, t):
        s = dict(s)
        # ---- DCQCN advance ------------------------------------------------ #
        s["a_tus"] = s["a_tus"] + fdt
        a_fire = s["a_tus"] >= p["a_tmr"]
        s["alpha"] = xp.where(a_fire, (1.0 - p["g"]) * s["alpha"],
                              s["alpha"])
        s["a_tus"] = xp.where(a_fire, _F(0.0), s["a_tus"])
        s["t_us"] = s["t_us"] + fdt
        s["byts"] = s["byts"] + s["rc"] * bpt
        t_fire = s["t_us"] >= p["r_tmr"]
        s["t_stage"] = s["t_stage"] + t_fire
        s["t_us"] = xp.where(t_fire, _F(0.0), s["t_us"])
        b_fire = s["byts"] >= p["bctr"]
        s["b_stage"] = s["b_stage"] + b_fire
        s["byts"] = xp.where(b_fire, _F(0.0), s["byts"])
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

        # ---- sender -> RNIC ----------------------------------------------- #
        offered = xp.minimum(xp.minimum(s["rc"], p["line"]), p["cap"])
        arriving = xp.where(s["pfc"], _F(0.0), offered * bpt)
        space = p["rnic_buf"] - s["rnic_q"]
        accepted = xp.minimum(arriving, xp.maximum(space, _F(0.0)))
        s["dropped"] = s["dropped"] + (arriving - accepted)
        s["rnic_q"] = s["rnic_q"] + accepted

        # ---- drain RNIC -> host ------------------------------------------- #
        jet = p["jet"] > 0.5
        ws = p["qp_bytes"] + s["resident"]
        miss = xp.clip((ws - p["ddio"]) / (p["knee"] * p["ddio"]),
                       _F(0.0), _F(1.0))
        s["miss_sum"] = s["miss_sum"] + xp.where(jet, _F(0.0), miss)
        avail_dram = xp.maximum(_F(0.0), p["membw"] - p["cpu_bw"])
        ddio_bw = xp.where(miss > 1e-9,
                           xp.minimum(p["pcie"],
                                      avail_dram / (2.0 * miss + 1e-30)),
                           p["pcie"])
        ddio_drained = xp.minimum(s["rnic_q"], ddio_bw * bpt)
        pool_free = xp.maximum(_F(0.0), p["pool"] - s["resident"])
        jet_bw = xp.minimum(p["pcie"], p["line1"] * 4.0)
        jet_drained = xp.minimum(xp.minimum(s["rnic_q"], jet_bw * bpt),
                                 pool_free)
        drained = xp.where(jet, jet_drained, ddio_drained)
        s["nic_dram"] = s["nic_dram"] + \
            xp.where(jet, _F(0.0), ddio_drained * 2.0 * miss)
        strag_share = xp.where(jet, p["sfrac"], _F(0.0))
        s["rnic_q"] = s["rnic_q"] - drained
        base_part = drained * (1.0 - strag_share)
        strag_part = drained * strag_share
        # write this tick's scheduled release at t%H; it is consumed at
        # t+d (< t+H), i.e. before the ring wraps over the slot
        s["ring_b"] = ring_set(s["ring_b"], t % H, base_part)
        s["ring_s"] = ring_set(s["ring_s"], t % H, strag_part)
        s["resident"] = s["resident"] + drained
        s["strag_res"] = s["strag_res"] + strag_part
        s["drained"] = s["drained"] + drained

        # ---- post-NIC consumption ----------------------------------------- #
        for ring_key, delay, is_strag in (("ring_b", d_base, False),
                                          ("ring_s", d_strag, True)):
            # releases scheduled ``delay`` ticks ago (zero before warm-up:
            # unwritten slots still hold their initial 0)
            r = ring_get(s[ring_key], (t - delay) % H)
            r = xp.where(t >= delay, r, _F(0.0))
            void = xp.minimum(r, s["esc_debt"])
            s["esc_debt"] = s["esc_debt"] - void
            r = r - void
            repay = xp.minimum(void, s["repl_debt"])
            s["repl_debt"] = s["repl_debt"] - repay
            s["repl_mem"] = xp.maximum(_F(0.0), s["repl_mem"] - repay)
            s["resident"] = xp.maximum(_F(0.0), s["resident"] - r)
            if is_strag:
                s["strag_res"] = xp.maximum(_F(0.0), s["strag_res"] - r)

        # ---- Jet escape ladder -------------------------------------------- #
        avail = xp.maximum(_F(0.0), p["pool"] - s["resident"]) / p["pool"]
        esc_on = jet & (avail < p["safe"])
        can_replace = s["repl_mem"] < p["mem_esc"]
        x_rep = xp.where(esc_on & can_replace,
                         xp.maximum(_F(0.0),
                                    xp.minimum(s["strag_res"],
                                               p["mem_esc"]
                                               - s["repl_mem"])),
                         _F(0.0))
        s["resident"] = s["resident"] - x_rep
        s["strag_res"] = s["strag_res"] - x_rep
        s["esc_debt"] = s["esc_debt"] + x_rep
        s["repl_debt"] = s["repl_debt"] + x_rep
        s["repl_mem"] = s["repl_mem"] + x_rep
        s["esc_dram"] = s["esc_dram"] + 0.1 * x_rep
        s["replaces"] = s["replaces"] + (x_rep > 0.0)
        x_cop = xp.where(esc_on & ~can_replace, s["strag_res"], _F(0.0))
        s["resident"] = s["resident"] - x_cop
        s["strag_res"] = s["strag_res"] - x_cop
        s["esc_debt"] = s["esc_debt"] + x_cop
        s["esc_dram"] = s["esc_dram"] + x_cop
        s["copies"] = s["copies"] + (x_cop > 0.0)
        avail2 = xp.maximum(_F(0.0), p["pool"] - s["resident"]) / p["pool"]
        in_danger = esc_on & (avail2 < p["danger"])
        s["ecn_tus"] = xp.where(in_danger, s["ecn_tus"] + fdt, s["ecn_tus"])
        fire_ecn = in_danger & (s["ecn_tus"] >= p["cnp_iv"])
        s["ecn_tus"] = xp.where(fire_ecn, _F(0.0), s["ecn_tus"])
        s["cnps"] = s["cnps"] + fire_ecn
        s["ecns"] = s["ecns"] + fire_ecn
        s["pool_sum"] = s["pool_sum"] + xp.where(jet, s["resident"],
                                                 _F(0.0))
        s["pool_peak"] = xp.maximum(s["pool_peak"],
                                    xp.where(jet, s["resident"], _F(0.0)))

        # ---- congestion signalling ----------------------------------------- #
        q_frac = s["rnic_q"] / p["rnic_buf"]
        pfc_en = p["pfc_en"] > 0.5
        s["pfc"] = pfc_en & xp.where(s["pfc"], q_frac >= p["xon"],
                                     q_frac > p["xoff"])
        s["pfc_us"] = s["pfc_us"] + xp.where(s["pfc"], fdt, _F(0.0))
        s["cnp_tus"] = s["cnp_tus"] + fdt
        fire_wm = (p["wm_cnp"] > 0.5) & (q_frac > p["ecn_th"]) \
            & (s["cnp_tus"] >= p["cnp_iv"])
        s["cnp_tus"] = xp.where(fire_wm, _F(0.0), s["cnp_tus"])
        s["cnps"] = s["cnps"] + fire_wm

        # rate cuts, in the same order run_sim applies them
        s = cut(s, fire_ecn)
        s = cut(s, fire_wm)
        return s

    return step


def _init_state(xp, shape, H, p):
    z = lambda: xp.zeros(shape, _F)   # noqa: E731
    s = {k: z() for k in
         ("t_us", "byts", "t_stage", "b_stage", "a_tus", "ecn_tus",
          "rnic_q", "resident", "strag_res", "esc_debt", "repl_debt",
          "repl_mem", "dropped", "drained", "nic_dram", "esc_dram",
          "miss_sum", "pool_sum", "pool_peak", "cnps", "ecns",
          "replaces", "copies", "pfc_us")}
    s["rc"] = p["dline"] + z()
    s["rt"] = p["dline"] + z()
    s["alpha"] = xp.ones(shape, _F)
    s["cnp_tus"] = p["cnp_iv"] + z()   # allow an immediate first CNP
    s["pfc"] = xp.zeros(shape, bool)
    s["ring_b"] = xp.zeros(shape + (H,), _F)
    s["ring_s"] = xp.zeros(shape + (H,), _F)
    return s


def _results(s, sp: SweepParams) -> Dict[str, np.ndarray]:
    sim_us = sp.ticks * sp.dt_us
    drained = np.asarray(s["drained"], np.float64)
    miss_n = np.maximum(1, sp.ticks * (1.0 - sp.vals["jet"]))
    return {
        "goodput_gbps": drained * 8.0 / (sim_us * 1e-6) / 1e9,
        "cnp_count": np.asarray(s["cnps"], np.float64),
        "escape_ecn": np.asarray(s["ecns"], np.float64),
        "escape_replaces": np.asarray(s["replaces"], np.float64),
        "escape_copies": np.asarray(s["copies"], np.float64),
        "ddio_miss_rate": np.asarray(s["miss_sum"], np.float64) / miss_n,
        "pool_peak_bytes": np.asarray(s["pool_peak"], np.float64),
        "pool_avg_bytes": np.asarray(s["pool_sum"], np.float64) / sp.ticks,
        "pfc_pause_us": np.asarray(s["pfc_us"], np.float64),
        "dropped_bytes": np.asarray(s["dropped"], np.float64),
        "nic_dram_gbps": np.asarray(s["nic_dram"], np.float64) * 8.0
        / (sim_us * 1e-6) / 1e9,
        "escape_dram_gbps": np.asarray(s["esc_dram"], np.float64) * 8.0
        / (sim_us * 1e-6) / 1e9,
    }


# --------------------------------------------------------------------------- #
# Backends
# --------------------------------------------------------------------------- #
def _run_numpy(sp: SweepParams) -> Dict[str, np.ndarray]:
    P, H = sp.n_points, sp.ring_len
    rows = np.arange(P)

    def ring_get(ring, idx):            # idx: [P] int array
        return ring[rows, idx]

    def ring_set(ring, idx, v):         # idx: scalar (t % H)
        ring[:, idx] = v
        return ring

    p = sp.vals
    step = _make_step(np, ring_get, ring_set, p, sp.dt_us, H,
                      sp.d_base, sp.d_strag)
    s = _init_state(np, (P,), H, p)
    for t in range(sp.ticks):
        s = step(s, t)
    return _results(s, sp)


@functools.lru_cache(maxsize=8)
def _jax_program(n_points: int, ticks: int, ring_len: int, dt_us: float,
                 unroll: int):
    """Compiled sweep program, cached on the trace-relevant shape tuple so
    repeated sweeps over same-shaped grids skip compilation.

    The initial scan carry is an argument (built cheaply in numpy per
    call) rather than a traced constant, so ``donate_argnums`` lets XLA
    reuse its buffers — the [P, H] release rings dominate the state —
    instead of holding the zero-init copy alive next to the running
    carry.  The unroll factor is the caller's, or 1
    (:func:`repro.fabric._scan.pick_unroll`).
    """
    import jax
    import jax.numpy as jnp

    H = ring_len

    def ring_get(ring, idx):
        return ring[idx]

    def ring_set(ring, idx, v):
        return ring.at[idx].set(v)

    def one_point(s0, pvals, d_b, d_s):
        step = _make_step(jnp, ring_get, ring_set, pvals,
                          dt_us, H, d_b, d_s)

        def body(s, t):
            return step(s, t), None

        s, _ = jax.lax.scan(body, s0, jnp.arange(ticks), unroll=unroll)
        return s

    return jax.jit(jax.vmap(one_point), donate_argnums=(0,))


def _run_jax(sp: SweepParams, unroll="auto") -> Dict[str, np.ndarray]:
    import jax.numpy as jnp

    u = pick_unroll(unroll)
    fn = _jax_program(sp.n_points, sp.ticks, sp.ring_len, sp.dt_us, u)
    s0 = _init_state(np, (sp.n_points,), sp.ring_len, sp.vals)
    pv = {k: jnp.asarray(v) for k, v in sp.vals.items()}
    final = fn({k: jnp.asarray(v) for k, v in s0.items()}, pv,
               jnp.asarray(sp.d_base), jnp.asarray(sp.d_strag))
    final = {k: np.asarray(v) for k, v in final.items()}
    return _results(final, sp)


def run_sweep(configs: Sequence[SimConfig], backend: str = "jax",
              unroll="auto",
              envelope: dict | None = None) -> Dict[str, np.ndarray]:
    """Advance every config in ``configs`` through the full fluid recurrence
    at once; returns {metric: array[P]} aligned with the input order.

    ``envelope`` (from :meth:`SweepParams.envelope` of the full grid) floors
    the ring length so chunked runs of a larger grid share one compiled
    program shape; per-point results are unchanged (release slots past a
    point's own delay are never read)."""
    sp = SweepParams.from_configs(configs, envelope=envelope)
    if backend == "numpy":
        out = _run_numpy(sp)
    elif backend == "jax":
        out = _run_jax(sp, unroll)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return out

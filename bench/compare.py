"""The comparison that decides ``correct``: the program's answers for a
sample of grid points against the plain reference's.

Each compared number is the widest gap over the sampled points, and
each has a limit of its own (``bench/checks/<cell>.json``):

* ``delivered_rel``  per-flow delivered bytes, relative gap (floor 1 kB);
* ``completion_us``  per-flow completion time, absolute gap in us, an
  unfinished flow counting as finishing at the end of the window;
* ``pause_rel``      microseconds of PFC pause summed over the links,
  relative gap (floor 1 us);
* ``cnp_rel``        CNPs sent by each receiver, relative gap (floor 1);
* ``ecn_rel``        bytes ECN-marked by the switches, relative gap
  (floor 1 kB).

The reference is :func:`bench.reference.fabric.run_fabric`; its
results are read into the program's layout (flow order as built,
receivers in sorted host order).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

NUMBERS = ("delivered_rel", "completion_us", "pause_rel", "cnp_rel",
           "ecn_rel")


def program_answer(results: Dict[str, np.ndarray], i: int) -> dict:
    """Point ``i`` of a program result table (``run_farm``'s merge)."""
    return {
        "delivered": np.asarray(results["flow_delivered_bytes"][i], float),
        "completion": np.asarray(results["flow_completion_us"][i], float),
        "pause": float(results["pause_total_us"][i]),
        "cnp": np.asarray(results["recv_cnp_count"][i], float),
        "ecn": float(results["ecn_marked_bytes"][i]),
    }


def reference_point(config: dict, traffic: dict, point: dict) -> dict:
    """Build one point with the reference's classes and run it (a pool
    worker's job: the arguments are plain data)."""
    from bench import traffic as T
    return reference_answer(T.build_point(config, traffic, point,
                                          T.reference_namespace()))


def reference_answer(scenario: dict) -> dict:
    """Run one reference scenario (a dict from the reference namespace)
    and read it into the program's layout."""
    from bench.reference.fabric import run_fabric
    r = run_fabric(scenario["topology"], scenario["flows"],
                   scenario["fabric"])
    return {
        "delivered": np.array(r.flow_delivered_bytes, float),
        "completion": np.array(r.flow_completion_us, float),
        "pause": float(sum(r.pause_link_us.values())),
        "cnp": np.array([r.recv_cnp_count[h]
                         for h in sorted(r.recv_cnp_count)], float),
        "ecn": float(r.ecn_marked_bytes),
        "sim_us": float(r.sim_us),
    }


def _rel(a, b, floor: float) -> float:
    a, b = np.asarray(a, float), np.asarray(b, float)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return float("inf")
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), floor),
                        initial=0.0))


def point_gaps(got: dict, ref: dict) -> Dict[str, float]:
    """The compared numbers for one point."""
    end = ref["sim_us"]
    cg = np.nan_to_num(got["completion"], nan=np.inf, posinf=end)
    cr = np.nan_to_num(ref["completion"], nan=np.inf, posinf=end)
    return {
        "delivered_rel": _rel(got["delivered"], ref["delivered"], 1e3),
        "completion_us": float(np.max(np.abs(np.minimum(cg, end)
                                             - np.minimum(cr, end)),
                                      initial=0.0)),
        "pause_rel": _rel(got["pause"], ref["pause"], 1.0),
        "cnp_rel": _rel(got["cnp"], ref["cnp"], 1.0),
        "ecn_rel": _rel(got["ecn"], ref["ecn"], 1e3),
    }


def widest(gaps: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """The widest gap of each number over the sampled points."""
    return {k: max((g[k] for g in gaps), default=0.0) for k in NUMBERS}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """``[{"name", "value", "limit", "ok"}]`` for every limited number.
    A number that is not finite fails its limit."""
    out = []
    for k in sorted(limits):
        v = float(numbers[k])
        out.append({"name": k, "value": v, "limit": float(limits[k]),
                    "ok": bool(np.isfinite(v) and v <= limits[k])})
    return out

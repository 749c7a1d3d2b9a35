"""The one traffic generator: a configuration file and a traffic file
(both JSON data) plus ``(seed, grid index)`` give one grid of scenarios.

A grid is the Cartesian product of the traffic's ``axes``.  An axis
either lists its values or draws ``n`` of them from the seed
(``"draw": "uniform" | "loguniform"`` between ``low`` and ``high``).
Draws depend on ``(seed, grid index, axis position)`` only, and every
grid of a cell has the same axes, flows and topology: grids differ in
numbers, never in structure, so each grid runs the same compiled
programs.

Each axis writes its value to one or more targets:

* ``receiver.<field>``            every receiver's ``SimConfig`` field;
* ``receiver[<host>].<field>``    one receiver host's field;
* ``switch.<field>``              the ``SwitchConfig`` field;
* ``flow[<tag>].<field>``         the field of every flow with that tag.

``scale`` multiplies the value before it is written, and ``int``
rounds it down to an integer (byte counts).

The scenario objects are made by a *namespace* (:class:`Namespace`):
the program's own public classes for the timed path, or the plain
reference's classes for the check.  Both read the same numbers.
"""
from __future__ import annotations

import itertools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: Fields of a traffic file's flow group besides the Flow keywords.
_GROUP_KEYS = ("src", "dst", "over")


class Namespace:
    """The classes a grid is built from: ``topology`` (module with the
    topology builders), ``Flow``, ``FabricConfig``, ``SwitchConfig``,
    ``QoS``, ``simulator`` (module with the receiver presets) and
    ``make_scenario(name, topology, flows, fabric)``."""

    def __init__(self, topology, Flow, FabricConfig, SwitchConfig, QoS,
                 simulator, make_scenario):
        self.topology = topology
        self.Flow = Flow
        self.FabricConfig = FabricConfig
        self.SwitchConfig = SwitchConfig
        self.QoS = QoS
        self.simulator = simulator
        self.make_scenario = make_scenario


def program_namespace() -> Namespace:
    """The program's public scenario classes (the timed path)."""
    from repro.core import simulator
    from repro.core.datapath import QoS
    from repro.fabric import topology
    from repro.fabric.fabric import FabricConfig, Flow
    from repro.fabric.scenarios import Scenario
    from repro.fabric.switch import SwitchConfig
    return Namespace(topology, Flow, FabricConfig, SwitchConfig, QoS,
                     simulator, Scenario)


def reference_namespace() -> Namespace:
    """The plain reference's classes (``bench/reference``)."""
    from bench.reference import receiver, topology
    from bench.reference.fabric import FabricConfig, Flow
    from bench.reference.switch import SwitchConfig

    def make(name, topo, flows, fabric):
        return {"name": name, "topology": topo, "flows": flows,
                "fabric": fabric}
    return Namespace(topology, Flow, FabricConfig, SwitchConfig,
                     receiver.QoS, receiver, make)


# --------------------------------------------------------------------------- #
# Axis values and grid points
# --------------------------------------------------------------------------- #
def _rng(seed: int, grid: int, axis: int) -> np.random.Generator:
    # SeedSequence takes non-negative integers of any size
    return np.random.default_rng(
        [int(seed) % (1 << 64), int(grid), int(axis)])


def axis_values(axis: dict, seed: int, grid: int, pos: int) -> list:
    """The values one axis takes in grid ``grid`` of seed ``seed``."""
    if "values" in axis:
        return list(axis["values"])
    n, lo, hi = int(axis["n"]), float(axis["low"]), float(axis["high"])
    u = _rng(seed, grid, pos).uniform(size=n)
    if axis["draw"] == "uniform":
        v = lo + (hi - lo) * u
    elif axis["draw"] == "loguniform":
        v = np.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * u)
    else:
        raise ValueError(f"unknown draw {axis['draw']!r}")
    return [float(x) for x in v]


def grid_points(traffic: dict, seed: int, grid: int) -> List[dict]:
    """Point dicts ``{axis name: value}`` of one grid, in product order."""
    axes = traffic["axes"]
    names = [a["name"] for a in axes]
    vals = [axis_values(a, seed, grid, i) for i, a in enumerate(axes)]
    return [dict(zip(names, combo)) for combo in itertools.product(*vals)]


def grid_size(traffic: dict) -> int:
    n = 1
    for a in traffic["axes"]:
        n *= len(a["values"]) if "values" in a else int(a["n"])
    return n


# --------------------------------------------------------------------------- #
# Scenario construction
# --------------------------------------------------------------------------- #
def _expand_group(group: dict, config: dict) -> List[dict]:
    """One flow group -> the flows it stands for: each ``over`` range
    ``[lo, hi)`` is expanded into the ``src``/``dst`` patterns; a bound
    given as a string is that key of the configuration (``"pods"``)."""
    over = group.get("over", {})
    names = sorted(over)
    ranges = [range(*(config[b] if isinstance(b, str) else b
                      for b in over[n])) for n in names]
    kw = {k: v for k, v in group.items() if k not in _GROUP_KEYS}
    out = []
    for combo in itertools.product(*ranges):
        sub = dict(zip(names, combo))
        out.append(dict(kw, src=group["src"].format(**sub),
                        dst=group["dst"].format(**sub)))
    return out


def _target_value(axis: dict, value):
    if "scale" in axis:
        value = value * float(axis["scale"])
    if axis.get("int"):
        value = int(value)
    return value


def _apply(point: dict, axes: Sequence[dict]):
    """Split a point into receiver / per-host / switch / per-tag writes."""
    recv: Dict[str, object] = {}
    host: Dict[str, Dict[str, object]] = {}
    switch: Dict[str, object] = {}
    flow: Dict[str, Dict[str, object]] = {}
    for a in axes:
        v = _target_value(a, point[a["name"]])
        for t in a["to"]:
            head, field = t.rsplit(".", 1)
            if head == "receiver":
                recv[field] = v
            elif head.startswith("receiver[") and head.endswith("]"):
                host.setdefault(head[9:-1], {})[field] = v
            elif head == "switch":
                switch[field] = v
            elif head.startswith("flow[") and head.endswith("]"):
                flow.setdefault(head[5:-1], {})[field] = v
            else:
                raise ValueError(f"unknown axis target {t!r}")
    return recv, host, switch, flow


def _receiver_factory(ns: Namespace, preset: str, base: dict,
                      per_host: Dict[str, dict]):
    make = getattr(ns.simulator, preset)

    def cfg(h: str):
        kw = dict(base)
        kw.update(per_host.get("*", {}))
        kw.update(per_host.get(h, {}))
        mode = kw.pop("mode")
        return make(mode, **kw)
    return cfg


def _flow(ns: Namespace, spec: dict):
    kw = dict(spec)
    if "qos" in kw:
        kw["qos"] = ns.QoS[kw["qos"]]
    if "on_off_us" in kw:
        kw["on_off_us"] = tuple(kw["on_off_us"])
    return ns.Flow(**kw)


def build_point(config: dict, traffic: dict, point: dict, ns: Namespace):
    """One scenario: the configuration's fabric and receivers under the
    traffic's flows, with the point's values written in."""
    recv_w, host_w, sw_w, flow_w = _apply(point, traffic["axes"])
    tspec = config["topology"]
    topo = getattr(ns.topology, tspec["builder"])(
        **{k: config[k] for k in tspec["args"]})
    flows = []
    for group in traffic["flows"]:
        for f in _expand_group(group, config):
            f.update(flow_w.get(f.get("tag", ""), {}))
            flows.append(_flow(ns, f))
    rspec = config["receiver"]
    base = dict(rspec["args"])
    base.update(recv_w)
    per_host = {h: dict(v) for h, v in traffic.get("receivers", {}).items()}
    for h, v in host_w.items():
        per_host.setdefault(h, {}).update(v)
    sw = dict(config.get("switch", {}))
    sw.update(traffic.get("switch", {}))
    sw.update(sw_w)
    fabric = ns.FabricConfig(
        sim_time_s=float(traffic["sim_time_s"]),
        switch=ns.SwitchConfig(**sw),
        receiver_cfg=_receiver_factory(ns, rspec["preset"], base,
                                       per_host))
    name = traffic["name"] + "".join(
        f"_{k}={point[k]}" for k in sorted(point))
    return ns.make_scenario(name, topo, flows, fabric)


def build_grid(config: dict, traffic: dict, seed: int, grid: int,
               ns: Optional[Namespace] = None) -> Tuple[list, List[dict]]:
    """Grid ``grid`` of seed ``seed``: ``(scenarios, point dicts)``."""
    ns = ns or program_namespace()
    points = grid_points(traffic, seed, grid)
    return [build_point(config, traffic, p, ns) for p in points], points

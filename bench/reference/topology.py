"""Leaf-spine and pod-scale Clos fabrics, and their static ECMP routes.

A topology is hosts, leaf switches, spine switches and, pod-scale,
super-spine switches, joined by one-way links with a rate.  A flow
takes one of the wired candidate paths of its host pair, chosen by its
flow id (static ECMP).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

LinkKey = Tuple[str, str]                  # (src node, dst node)


@dataclasses.dataclass(frozen=True)
class Link:
    src: str
    dst: str
    gbps: float

    @property
    def key(self) -> LinkKey:
        return (self.src, self.dst)


@dataclasses.dataclass
class Topology:
    hosts: List[str]
    leaves: List[str]
    spines: List[str]
    links: Dict[LinkKey, Link]             # both directions present
    host_leaf: Dict[str, str]              # host -> its leaf
    super_spines: List[str] = dataclasses.field(default_factory=list)

    def link(self, src: str, dst: str) -> Link:
        return self.links[(src, dst)]

    def access_gbps(self, host: str) -> float:
        return self.links[(host, self.host_leaf[host])].gbps

    def candidate_paths(self, src_host: str, dst_host: str) \
            -> List[List[str]]:
        """Leaf-to-leaf node paths over wired links: ``[sl, spine, dl]``
        through a common spine, else ``[sl, spineA, ss, spineB, dl]``
        through a super-spine."""
        sl, dl = self.host_leaf[src_host], self.host_leaf[dst_host]
        common = [s for s in self.spines
                  if (sl, s) in self.links and (s, dl) in self.links]
        if common:
            return [[sl, s, dl] for s in common]
        out: List[List[str]] = []
        for ss in self.super_spines:
            ups = [s for s in self.spines
                   if (sl, s) in self.links and (s, ss) in self.links]
            dns = [s for s in self.spines
                   if (ss, s) in self.links and (s, dl) in self.links]
            out += [[sl, sa, ss, sb, dl] for sa in ups for sb in dns]
        if not out:
            raise ValueError(f"{src_host}->{dst_host} is unroutable")
        return out

    def route(self, src_host: str, dst_host: str, flow_id: int) -> List[str]:
        """Node path of a flow: candidate path ``flow_id mod n``."""
        if src_host == dst_host:
            raise ValueError("flow endpoints must differ")
        sl = self.host_leaf[src_host]
        if sl == self.host_leaf[dst_host]:
            return [src_host, sl, dst_host]
        paths = self.candidate_paths(src_host, dst_host)
        return [src_host] + paths[flow_id % len(paths)] + [dst_host]


def _bidi(links: Dict[LinkKey, Link], a: str, b: str, gbps: float) -> None:
    links[(a, b)] = Link(a, b, gbps)
    links[(b, a)] = Link(b, a, gbps)


def clos(n_leaves: int, hosts_per_leaf: int, n_spines: int,
         host_gbps: float, uplink_gbps: float) -> Topology:
    """Two-tier Clos: ``n_leaves`` leaves of ``hosts_per_leaf`` hosts
    (``h{leaf}_{i}``), every leaf wired to every spine."""
    hosts, leaves, spines = [], [], []
    links: Dict[LinkKey, Link] = {}
    host_leaf: Dict[str, str] = {}
    for li in range(n_leaves):
        leaf = f"leaf{li}"
        leaves.append(leaf)
        for hi in range(hosts_per_leaf):
            h = f"h{li}_{hi}"
            hosts.append(h)
            host_leaf[h] = leaf
            _bidi(links, h, leaf, host_gbps)
    for si in range(n_spines):
        spine = f"spine{si}"
        spines.append(spine)
        for leaf in leaves:
            _bidi(links, leaf, spine, uplink_gbps)
    return Topology(hosts, leaves, spines, links, host_leaf)


def incast_fabric(n_senders: int, host_gbps: float = 200.0,
                  uplink_gbps: float = 800.0,
                  extra_receivers: int = 1) -> Topology:
    """Senders on one leaf, the receiver and its neighbours on the other,
    two spines."""
    return clos(n_leaves=2, hosts_per_leaf=max(n_senders,
                                               1 + extra_receivers),
                n_spines=2, host_gbps=host_gbps, uplink_gbps=uplink_gbps)


def make_pod_clos(pods: int, leaves_per_pod: int, hosts_per_leaf: int,
                  spines_per_pod: int = 2, sspines_per_plane: int = 1,
                  host_gbps: float = 100.0, leaf_spine_gbps: float = 200.0,
                  spine_sspine_gbps: float = 400.0) -> Topology:
    """Three-level Clos: each pod a two-tier Clos of ``leaves_per_pod``
    leaves and ``spines_per_pod`` spines; pod spine ``i`` of every pod
    wires to the ``sspines_per_plane`` super-spines of plane ``i``.

    Names: host ``p{pod}h{leaf}_{i}``, leaf ``p{pod}l{leaf}``, spine
    ``p{pod}s{i}``, super-spine ``ss{plane}`` (``ss{plane}_{k}`` with
    more than one per plane)."""
    hosts, leaves, spines, sspines = [], [], [], []
    links: Dict[LinkKey, Link] = {}
    host_leaf: Dict[str, str] = {}
    for pi in range(pods):
        pod_leaves = []
        for li in range(leaves_per_pod):
            leaf = f"p{pi}l{li}"
            leaves.append(leaf)
            pod_leaves.append(leaf)
            for hi in range(hosts_per_leaf):
                h = f"p{pi}h{li}_{hi}"
                hosts.append(h)
                host_leaf[h] = leaf
                _bidi(links, h, leaf, host_gbps)
        for si in range(spines_per_pod):
            spine = f"p{pi}s{si}"
            spines.append(spine)
            for leaf in pod_leaves:
                _bidi(links, leaf, spine, leaf_spine_gbps)
    if pods > 1:
        for plane in range(spines_per_pod):
            for k in range(sspines_per_plane):
                ss = f"ss{plane}" if sspines_per_plane == 1 \
                    else f"ss{plane}_{k}"
                sspines.append(ss)
                for pi in range(pods):
                    _bidi(links, f"p{pi}s{plane}", ss, spine_sspine_gbps)
    return Topology(hosts, leaves, spines, links, host_leaf,
                    super_spines=sspines)

"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

* A chip is a plane named ``/device:TPU:<n>``; its operations are the
  events of its ``XLA Ops`` line.
* Busy time is the union of a chip's operation intervals inside the
  window; idle is the rest of the window.
* Kernel time is the union of the intervals of Pallas kernels, which
  the TPU runs as custom calls (``custom-call`` in the operation's
  name or its HLO category).
* The window is the host span named ``window`` (the benchmark's own
  ``TraceAnnotation``).  An idle gap of the chips (no chip busy) is
  split by the host span it falls in, ``build``, ``farm`` or
  ``results``, and ``other`` outside them.

Everything is in seconds in the output.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"
_LABELS = ("build", "farm", "results")
TOP = 10


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace dir."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    """Merge intervals ``(start, end)`` into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi)`` around disjoint ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _is_kernel(event) -> bool:
    if "custom-call" in event.name or "custom_call" in event.name:
        return True
    for key, val in event.stats:
        if key == "hlo_category" and "custom" in str(val).lower():
            return True
    return False


def _read(pd):
    """Chips' op events and the host's labelled spans, in ns."""
    kernel_names: Dict[str, bool] = {}
    chips: Dict[int, dict] = {}
    spans: Dict[str, List[Tuple[float, float]]] = {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops, kern, names = [], [], {}
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    ops.append(iv)
                    short = ev.name.split(" = ", 1)[0]
                    names[short] = names.get(short, 0.0) + ev.duration_ns
                    k = kernel_names.get(ev.name)
                    if k is None:
                        k = kernel_names[ev.name] = _is_kernel(ev)
                    if k:
                        kern.append(iv)
            chips[int(m.group(1))] = {"ops": ops, "kernels": kern,
                                      "names": names}
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in _LABELS or ev.name == "window":
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return chips, spans


def _by_host_span(idle, spans) -> List[Tuple[str, float, float]]:
    """Split idle intervals by the host span they fall in: ``(label,
    start, end)``, ``other`` where the host was in none of them."""
    out = []
    for lo, hi in idle:
        inside = []
        for name in _LABELS:
            for s, e in clip(spans.get(name, ()), lo, hi):
                out.append((name, s, e))
                inside.append((s, e))
        out.extend(("other", s, e) for s, e in gaps(union(inside), lo, hi))
    return out


def reduce(path: str, chips_used: Optional[int] = None) -> dict:
    """:func:`reduce_profile` of the ``.xplane.pb`` file at ``path``."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), chips_used)


def reduce_profile(pd, chips_used: Optional[int] = None) -> dict:
    """Device numbers of one trace (a ``jax.profiler.ProfileData``): per
    chip busy and kernel seconds, the window, the union of busy time
    across chips, and a breakdown (top device operations, longest idle
    gaps by the host's span)."""
    chips, spans = _read(pd)
    if "window" in spans:
        lo = min(s for s, _ in spans["window"])
        hi = max(e for _, e in spans["window"])
    else:
        every = [iv for c in chips.values() for iv in c["ops"]]
        lo = min((s for s, _ in every), default=0.0)
        hi = max((e for _, e in every), default=0.0)
    ids = sorted(chips)
    if chips_used is not None:
        ids = ids[:chips_used]
    per_chip, all_busy, names = [], [], {}
    for i in ids:
        busy = union(clip(chips[i]["ops"], lo, hi))
        kern = union(clip(chips[i]["kernels"], lo, hi))
        per_chip.append({"chip": i, "busy_s": length(busy) * 1e-9,
                         "custom_call_s": length(kern) * 1e-9,
                         "busy": busy})
        all_busy.extend(busy)
        for n, d in chips[i]["names"].items():
            names[n] = names.get(n, 0.0) + d
    any_busy = union(all_busy)
    idle = sorted(_by_host_span(gaps(any_busy, lo, hi), spans),
                  key=lambda g: g[1] - g[2])
    window_s = (hi - lo) * 1e-9
    top_ops = sorted(names.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": (sum(c["busy_s"] for c in per_chip) / len(per_chip)
                   if per_chip else 0.0),
        "union_busy_s": length(any_busy) * 1e-9,
        "chips": [{k: v for k, v in c.items() if k != "busy"}
                  for c in per_chip],
        "breakdown": {
            "device_ops": [[n, d * 1e-9] for n, d in top_ops],
            "idle_gaps": [[name, (e - s) * 1e-9]
                          for name, s, e in idle[:TOP]],
        },
    }

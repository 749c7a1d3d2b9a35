"""Put the chips' idle time inside the farm down to the program's own
host spans.

The sweep farm marks its host work with ``farm.*`` and ``chunk.*``
annotations (``repro.fabric.spans``) on the profiler's host plane, on
the clock of the device trace.  Over the window of one trace:

* The main thread is the host line that holds the benchmark's
  ``window`` span.  Only its spans take idle time: the farm packs the
  next chunk on a prefetch thread (``farm.pack``, another line), and
  what holds the chip is the main thread's ``farm.pack_wait``.
* Each idle gap of the chips that ``bench/tracing.py`` puts in the
  benchmark's ``farm`` span is cut by the innermost program span on the
  main thread.  The gap keeps its interval and length and is labelled
  ``farm:<span>`` after the span that covers most of it; a gap that no
  program span covers stays ``farm``.
* The seconds of each main-thread span and the ``arrays`` argument of
  the transfer spans (``chunk.h2d``, ``chunk.d2h``) are summed, with
  the number of grids (the benchmark's ``farm`` spans) they came from.

A trace of a program without these spans reduces to ``None``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import tracing

PROGRAM = ("farm.", "chunk.")
TRANSFERS = ("chunk.h2d", "chunk.d2h")


def _main_line(pd):
    """The host line holding ``window``, or ``None``."""
    for plane in pd.planes:
        if plane.name != tracing._HOST_PLANE:
            continue
        for line in plane.lines:
            if any(ev.name == "window" for ev in line.events):
                return line
    return None


def program_spans(line) -> List[Tuple[str, float, float, dict]]:
    """``(name, start, end, args)`` of the program spans on ``line``, ns."""
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for ev in line.events if ev.name.startswith(PROGRAM)]


def innermost(spans) -> List[Tuple[float, float, str]]:
    """Disjoint sorted ``(start, end, name)`` pieces of the union of
    ``spans`` (``(name, start, end, ...)``), each named after the
    innermost span over it: of the spans covering it, the one that
    started last (the shorter on a tie)."""
    cuts = sorted({t for sp in spans for t in sp[1:3]})
    out = []
    for s, e in zip(cuts, cuts[1:]):
        over = [sp for sp in spans if sp[1] <= s and sp[2] >= e]
        if over:
            name = max(over, key=lambda sp: (sp[1], -sp[2]))[0]
            if out and out[-1][1] == s and out[-1][2] == name:
                out[-1] = (out[-1][0], e, name)
            else:
                out.append((s, e, name))
    return out


def _cover(lo: float, hi: float, pieces, j: int):
    """Nanoseconds of ``[lo, hi)`` under each name of the sorted disjoint
    ``pieces``, from index ``j`` on; and the index to start the next,
    later, interval from."""
    while j < len(pieces) and pieces[j][1] <= lo:
        j += 1
    got: Dict[str, float] = {}
    k = j
    while k < len(pieces) and pieces[k][0] < hi:
        s, e, name = pieces[k]
        got[name] = got.get(name, 0.0) + min(e, hi) - max(s, lo)
        k += 1
    return got, j


def labelled_idle(pd, chips_used: Optional[int] = None):
    """The window ``(lo, hi)`` ns, the number of chips, the chips' idle
    gaps ``(label, start, end)`` as :func:`bench.tracing.reduce_profile`
    labels and orders them (longest first), and the benchmark's spans."""
    chips, spans = tracing._read(pd)
    lo = min(s for s, _ in spans["window"])
    hi = max(e for _, e in spans["window"])
    ids = sorted(chips)
    if chips_used is not None:
        ids = ids[:chips_used]
    busy = []
    for i in ids:
        busy.extend(tracing.union(tracing.clip(chips[i]["ops"], lo, hi)))
    idle = sorted(tracing._by_host_span(
        tracing.gaps(tracing.union(busy), lo, hi), spans),
        key=lambda g: g[1] - g[2])
    return lo, hi, len(ids), idle, spans


def reduce_spans(pd, chips_used: Optional[int] = None) -> Optional[dict]:
    """The program-span split of one trace (a ``ProfileData``), in
    seconds: idle inside ``farm`` by main-thread span, the idle gaps of
    :func:`bench.tracing.reduce_profile` with their labels refined, and
    the main thread's span seconds and transfer counts in the window."""
    line = _main_line(pd)
    if line is None:
        return None
    progs = program_spans(line)
    if not progs:
        return None
    lo, hi, n_chips, idle, spans = labelled_idle(pd, chips_used)
    inside = [(n, max(s, lo), min(e, hi), a) for n, s, e, a in progs
              if e > lo and s < hi]
    pieces = innermost(inside)
    labels = {}
    by_span: Dict[str, float] = {}
    farm_idle = 0.0
    j = 0
    for g in sorted((g for g in idle if g[0] == "farm"),
                    key=lambda g: g[1]):
        got, j = _cover(g[1], g[2], pieces, j)
        farm_idle += g[2] - g[1]
        for n, d in got.items():
            by_span[n] = by_span.get(n, 0.0) + d
        if got:
            labels[g[1:]] = "farm:" + max(got, key=got.get)
    span_s: Dict[str, float] = {}
    for n, s, e, _ in inside:
        span_s[n] = span_s.get(n, 0.0) + (e - s) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "chips": n_chips,
        "grids": len(tracing.clip(spans.get("farm", ()), lo, hi)),
        "farm_idle_s": farm_idle * 1e-9,
        "named_idle_s": sum(by_span.values()) * 1e-9,
        "idle_by_span": {n: d * 1e-9 for n, d in sorted(by_span.items())},
        "idle_gaps": [[labels.get((s, e), name), (e - s) * 1e-9]
                      for name, s, e in idle[:tracing.TOP]],
        "span_s": span_s,
        "transfers": sum(int(a.get("arrays", 0)) for n, s, _, a in progs
                         if n in TRANSFERS and lo <= s < hi),
    }


def _reduce_trace_of(run) -> Optional[dict]:
    from jax.profiler import ProfileData
    from bench import harness, registry
    try:
        path = tracing.find_xplane(harness._trace_dir(registry.Bench(),
                                                      run.cell["name"]))
    except FileNotFoundError:
        return None
    return reduce_spans(ProfileData.from_file(path), run.chips)


def of_run(run) -> Optional[dict]:
    """:func:`reduce_spans` of a ``--trace 1`` run's trace, read once per
    run for all readers; ``None`` without a trace, or where the file on
    disk is not the one the run reduced (another window length)."""
    if getattr(run, "trace", None) is None:
        return None
    if not hasattr(run, "_program_spans"):
        run._program_spans = _reduce_trace_of(run)
    got = run._program_spans
    if got is None or abs(got["window_s"] - run.trace["window_s"]) > 1e-9:
        return None
    return got

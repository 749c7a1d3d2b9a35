"""Cut one chunk hand-over, with the program's host spans, out of a
profiler trace, as a text proto that
``jax.profiler.ProfileData.from_text_proto`` reads back:

    python3 bench/excerpt_spans.py <trace.xplane.pb> <out.pbtxt> [--margin-ms 0.5]

The excerpt is the longest idle gap of the chips inside the benchmark's
``farm`` span, with ``--margin-ms`` milliseconds on each side.  It keeps
each chip's ``XLA Ops`` events, with their ``hlo_category``, and, each
on its own host line, the benchmark's spans and the program's
``farm.*`` / ``chunk.*`` spans with their arguments; all cut to the
excerpt.  The tests of ``bench/spans.py`` read such
excerpts of traces recorded on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

from bench import spans, tracing  # noqa: E402

_KEEP = tracing._LABELS + ("window",)
_CATEGORY = "hlo_category"


def longest_farm_gap(pd):
    """``(start, end)`` ns of the longest chip-idle gap inside ``farm``."""
    idle = spans.labelled_idle(pd)[3]
    return next((s, e) for n, s, e in idle if n == "farm")


def _events(pd, lo: float, hi: float):
    """``[(plane, [(line, [(name, start, duration, args)])])]`` of the
    excerpt ``[lo, hi)`` (ns)."""
    out = []
    for plane in pd.planes:
        lines = []
        if tracing._DEVICE_PLANE.match(plane.name):
            for ln in plane.lines:
                if ln.name != tracing._OPS_LINE:
                    continue
                evs = []
                for ev in ln.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e > lo and s < hi:
                        s, e = max(s, lo), min(e, hi)
                        args = {k: str(v) for k, v in ev.stats
                                if k == _CATEGORY}
                        evs.append((ev.name, s, e - s, args))
                lines.append((ln.name, evs))
        elif plane.name == tracing._HOST_PLANE:
            for ln in plane.lines:
                evs = []
                for ev in ln.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if (ev.name in _KEEP or ev.name.startswith(
                            spans.PROGRAM)) and e > lo and s < hi:
                        s, e = max(s, lo), min(e, hi)
                        evs.append((ev.name, s, e - s, dict(ev.stats)))
                if evs:
                    lines.append((ln.name, evs))
        if lines:
            out.append((plane.name, lines))
    return out


def _stat(mid: int, v) -> str:
    if isinstance(v, int):
        val = f"int64_value: {v}"
    elif isinstance(v, float):
        val = f"double_value: {v!r}"
    else:
        val = f"str_value: {json.dumps(str(v))}"
    return f"stats {{ metadata_id: {mid} {val} }}"


def to_text_proto(planes, t0: float) -> str:
    """An XSpace text proto of ``planes`` (from :func:`_events`), times
    relative to ``t0`` ns; lines keep their order and their names."""
    parts = []
    for pid, (pname, lines) in enumerate(planes, 1):
        names, stats, body = {}, {}, []
        for lid, (lname, evs) in enumerate(lines, 1):
            ev_txt = []
            for name, s, d, args in evs:
                mid = names.setdefault(name, len(names) + 1)
                st = "".join(" " + _stat(stats.setdefault(
                    k, len(stats) + 1), v) for k, v in args.items())
                ev_txt.append(
                    f"events {{ metadata_id: {mid} "
                    f"offset_ps: {int(round((s - t0) * 1000))} "
                    f"duration_ps: {int(round(d * 1000))}{st} }}")
            body.append(f"lines {{ id: {lid} name: {json.dumps(lname)} "
                        f"timestamp_ns: 0\n  " + "\n  ".join(ev_txt)
                        + "\n}")
        meta = [f"event_metadata {{ key: {m} value {{ id: {m} "
                f"name: {json.dumps(n)} }} }}" for n, m in names.items()]
        meta += [f"stat_metadata {{ key: {m} value {{ id: {m} "
                 f"name: {json.dumps(n)} }} }}" for n, m in stats.items()]
        parts.append(f"planes {{ id: {pid} name: {json.dumps(pname)}\n"
                     + "\n".join(body + meta) + "\n}")
    return "\n".join(parts) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--margin-ms", type=float, default=0.5)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(args.trace)
    s, e = longest_farm_gap(pd)
    lo, hi = s - args.margin_ms * 1e6, e + args.margin_ms * 1e6
    with open(args.out, "w") as f:
        f.write(to_text_proto(_events(pd, lo, hi), lo))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

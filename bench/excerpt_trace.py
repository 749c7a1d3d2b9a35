"""Cut a short excerpt out of a profiler trace, as a text proto that
``jax.profiler.ProfileData.from_text_proto`` reads back:

    python3 bench/excerpt_trace.py <trace.xplane.pb> <out.pbtxt> [--ms 1]

It keeps what ``bench/tracing.py`` reads: the events of each chip's
``XLA Ops`` line with their ``hlo_category``, and the host's benchmark
spans (``window``, ``build``, ``farm``, ``results``), for ``--ms``
milliseconds from 0.1 ms before the first chip operation of the
``window`` span.  Spans are cut to the
excerpt; chip events that start in it are kept whole.  The tests of the
trace reduction read such excerpts of traces recorded on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

from bench import tracing  # noqa: E402

_SPANS = tracing._LABELS + ("window",)
_STAT = "hlo_category"


def _events(pd, lo: float, hi: float):
    """``{plane: {line: [(name, start, duration, category)]}}`` of the
    excerpt ``[lo, hi)`` (ns)."""
    out = {}
    for plane in pd.planes:
        if tracing._DEVICE_PLANE.match(plane.name):
            keep = [ln for ln in plane.lines if ln.name == tracing._OPS_LINE]
            for ln in keep:
                evs = []
                for ev in ln.events:
                    if lo <= ev.start_ns < hi:
                        cat = next((str(v) for k, v in ev.stats
                                    if k == _STAT), None)
                        evs.append((ev.name, ev.start_ns, ev.duration_ns,
                                    cat))
                out.setdefault(plane.name, {})[ln.name] = evs
        elif plane.name == tracing._HOST_PLANE:
            for ln in plane.lines:
                evs = []
                for ev in ln.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name in _SPANS and e > lo and s < hi:
                        s, e = max(s, lo), min(e, hi)
                        evs.append((ev.name, s, e - s, None))
                if evs:
                    out.setdefault(plane.name, {})[ln.name] = evs
    return out


def to_text_proto(planes: dict, t0: float) -> str:
    """An XSpace text proto of ``planes`` (from :func:`_events`), times
    relative to ``t0`` ns."""
    parts = []
    for pid, (pname, lines) in enumerate(sorted(planes.items()), 1):
        names, body = {}, []
        for lid, (lname, evs) in enumerate(sorted(lines.items()), 1):
            ev_txt = []
            for name, s, d, cat in evs:
                mid = names.setdefault(name, len(names) + 1)
                stat = (f" stats {{ metadata_id: 1 str_value: "
                        f"{json.dumps(cat)} }}" if cat is not None else "")
                ev_txt.append(
                    f"events {{ metadata_id: {mid} "
                    f"offset_ps: {int(round((s - t0) * 1000))} "
                    f"duration_ps: {int(round(d * 1000))}{stat} }}")
            body.append(f"lines {{ id: {lid} name: {json.dumps(lname)} "
                        f"timestamp_ns: 0\n  " + "\n  ".join(ev_txt)
                        + "\n}")
        meta = [f"event_metadata {{ key: {m} value {{ id: {m} "
                f"name: {json.dumps(n)} }} }}" for n, m in names.items()]
        meta.append(f'stat_metadata {{ key: 1 value {{ id: 1 '
                    f'name: "{_STAT}" }} }}')
        parts.append(f"planes {{ id: {pid} name: {json.dumps(pname)}\n"
                     + "\n".join(body + meta) + "\n}")
    return "\n".join(parts) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=1.0)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(args.trace)
    w0 = min(ev.start_ns for plane in pd.planes
             if plane.name == tracing._HOST_PLANE
             for ln in plane.lines for ev in ln.events
             if ev.name == "window")
    lo = min(ev.start_ns for plane in pd.planes
             if tracing._DEVICE_PLANE.match(plane.name)
             for ln in plane.lines if ln.name == tracing._OPS_LINE
             for ev in ln.events if ev.start_ns >= w0) - 1e5
    text = to_text_proto(_events(pd, lo, lo + args.ms * 1e6), lo)
    with open(args.out, "w") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Put the chips' busy time on a name scope of the program.

The program names the operations of a stage with ``jax.named_scope``
(``fabric.recv``: the receive stage).  XLA keeps the scope in each
operation's op-name metadata, and the TPU profiler hands that name to
the operation in the trace as the ``tf_op`` statistic of the event's
metadata (``jit(run)/vmap()/while/body/.../fabric.recv/mul``).
``ProfileData`` gives an event's own statistics only, so the metadata's
are read from the trace file directly (:func:`metadata_stats`, a reader
of the protobuf wire format that skips the events).

An operation is in scope ``name`` when ``name`` is one of the
``/``-separated parts of its op name.  A fusion carries the op name of
its root: operations of the stage fused under another stage's root count
with that stage, and the other way round.

Over the window of one trace (the benchmark's ``window`` span), per
chip: busy is the union of the ``XLA Ops`` intervals (as
``bench/tracing.py`` counts it), the scope's time the union of the
intervals of the operations in the scope.  A trace in which no operation
carries the scope reduces to ``None``: the program has no such scope.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple

from bench import tracing

#: The event-metadata statistic that holds an operation's op name.
OP_NAME = "tf_op"


# --------------------------------------------------------------------------- #
# Event metadata statistics, read from the file's wire format
# --------------------------------------------------------------------------- #
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of the message ``b[i:end]``: an int for
    a varint, ``(start, stop)`` for anything length-delimited or fixed."""
    while i < end:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield num, val


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _plane_metadata(b: bytes, lo: int, hi: int):
    """An ``XPlane``'s name and ``{event name: [(stat id, str value or
    ref id)]}`` of its event metadata, with ``{stat id: name}``."""
    name, events, stat_names = "", {}, {}
    for num, val in _fields(b, lo, hi):
        if num == 2:
            name = _text(b, val)
        elif num == 4:                      # map<int64, XEventMetadata>
            for k, v in _fields(b, *val):
                if k != 2:
                    continue
                ename, stats = "", []
                for f, x in _fields(b, *v):
                    if f == 2:              # name
                        ename = _text(b, x)
                    elif f == 5:            # XStat
                        sid, sval = 0, None
                        for g, y in _fields(b, *x):
                            if g == 1:
                                sid = y
                            elif g == 5:
                                sval = _text(b, y)
                            elif g == 7:
                                sval = int(y)
                        if sval is not None:
                            stats.append((sid, sval))
                events.setdefault(ename, []).extend(stats)
        elif num == 5:                      # map<int64, XStatMetadata>
            for k, v in _fields(b, *val):
                if k != 2:
                    continue
                sid, sname = 0, ""
                for f, x in _fields(b, *v):
                    if f == 1:
                        sid = x
                    elif f == 2:
                        sname = _text(b, x)
                stat_names[sid] = sname
    return name, events, stat_names


def metadata_stats(data: bytes) -> Dict[str, Dict[str, Dict[str, str]]]:
    """``{plane name: {event name: {stat name: string value}}}`` of the
    event metadata of the device planes of a serialized ``XSpace``; a
    reference value is read as the name it refers to."""
    out = {}
    for num, val in _fields(data, 0, len(data)):
        if num != 1:
            continue
        name, events, stat_names = _plane_metadata(data, *val)
        if not tracing._DEVICE_PLANE.match(name):
            continue
        out[name] = {
            ev: {stat_names.get(sid, ""): (stat_names.get(v, "")
                                           if isinstance(v, int) else v)
                 for sid, v in stats}
            for ev, stats in events.items()}
    return out


# --------------------------------------------------------------------------- #
# The reduction
# --------------------------------------------------------------------------- #
def in_scope(op_name: str, scope: str) -> bool:
    return scope in op_name.split("/")


def reduce_scope(pd, scope: str, meta: dict,
                 chips_used: Optional[int] = None) -> Optional[dict]:
    """Seconds of one trace (a ``ProfileData``) in ``scope``: the window,
    and the chips' busy and in-scope time summed over chips; ``meta`` is
    :func:`metadata_stats` of the same trace.  ``None`` where no
    operation is in the scope."""
    chips: Dict[int, Tuple[list, list]] = {}
    window: List[Tuple[float, float]] = []
    for plane in pd.planes:
        m = tracing._DEVICE_PLANE.match(plane.name)
        if m:
            pmeta = meta.get(plane.name, {})
            ops, inside, seen = [], [], {}
            for line in plane.lines:
                if line.name != tracing._OPS_LINE:
                    continue
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    ops.append(iv)
                    hit = seen.get(ev.name)
                    if hit is None:
                        hit = seen[ev.name] = in_scope(
                            pmeta.get(ev.name, {}).get(OP_NAME, ""), scope)
                    if hit:
                        inside.append(iv)
            chips[int(m.group(1))] = (ops, inside)
        elif plane.name == tracing._HOST_PLANE:
            window.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                          for line in plane.lines for ev in line.events
                          if ev.name == "window")
    if not window or not any(inside for _, inside in chips.values()):
        return None
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    ids = sorted(chips)
    if chips_used is not None:
        ids = ids[:chips_used]
    busy = scoped = 0.0
    for i in ids:
        ops, inside = chips[i]
        busy += tracing.length(tracing.union(tracing.clip(ops, lo, hi)))
        scoped += tracing.length(tracing.union(tracing.clip(inside, lo,
                                                            hi)))
    return {"window_s": (hi - lo) * 1e-9, "chips": len(ids),
            "busy_s": busy * 1e-9, "scope_s": scoped * 1e-9}


def _reduce_trace_of(run, scope: str) -> Optional[dict]:
    from jax.profiler import ProfileData
    from bench import harness, registry
    try:
        path = tracing.find_xplane(harness._trace_dir(registry.Bench(),
                                                      run.cell["name"]))
    except FileNotFoundError:
        return None
    with open(path, "rb") as f:
        data = f.read()
    try:
        meta = metadata_stats(data)
    except (ValueError, IndexError):
        return None     # a file this reader cannot walk
    return reduce_scope(ProfileData.from_serialized_xspace(data), scope,
                        meta, run.chips)


def of_run(run, scope: str) -> Optional[dict]:
    """:func:`reduce_scope` of a ``--trace 1`` run's trace, read once per
    run and scope; ``None`` without a trace, where no operation is in the
    scope, or where the file on disk is not the one the run reduced."""
    if getattr(run, "trace", None) is None:
        return None
    cache = run.__dict__.setdefault("_op_scopes", {})
    if scope not in cache:
        cache[scope] = _reduce_trace_of(run, scope)
    got = cache[scope]
    if got is None or abs(got["window_s"] - run.trace["window_s"]) > 1e-9:
        return None
    return got

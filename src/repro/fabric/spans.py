"""Host spans of the sweep farm's chunk path.

:func:`span` marks one stretch of host work twice, always:

* as a ``jax.profiler.TraceAnnotation`` named after the span, so under
  the profiler it lands on the host plane, on the clock the device trace
  uses (a record's ``chunk`` and ``device`` go along as its arguments);
* as seconds added to a plain dict, the chunk's manifest record or the
  run's, under the span's field: ``farm.pack_wait`` adds to
  ``pack_wait_s``, ``chunk.h2d`` to ``h2d_s``.

:func:`transfer` is a span over a host-device transfer that also counts
the arrays and bytes it moves (``h2d_arrays``, ``h2d_bytes``, ...).
A record also counts the chunk's receiving hosts, flows and the distinct
wirings whose routes its pack derived (``recv_hosts``, ``flows``,
``wirings``).
Outside the profiler a span costs about a microsecond.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Optional

#: Spans of one chunk, in the order the main thread meets them
#: (``farm.pack`` runs on the prefetch thread while the previous chunk
#: executes).
CHUNK_SPANS = ("farm.pack", "farm.pack_wait", "chunk.params", "chunk.h2d",
               "chunk.dispatch", "chunk.device", "chunk.d2h",
               "chunk.unpack")
#: Spans of the whole run, recorded in the manifest.
RUN_SPANS = ("farm.envelope", "farm.plan", "farm.merge")
#: Counters of a chunk's transfers.
TRANSFER_COUNTERS = ("h2d_arrays", "h2d_bytes", "d2h_arrays", "d2h_bytes")
#: Counters of a chunk's shape, set when it is packed: receiving hosts
#: (R) and flows (F), to read per-chunk numbers per receiver or per flow,
#: and the distinct topology wirings whose routes the pack derived.
SHAPE_COUNTERS = ("recv_hosts", "flows", "wirings")


def field(name: str) -> str:
    """The record field of span ``name``: ``chunk.h2d`` -> ``h2d_s``."""
    return name.split(".", 1)[-1] + "_s"


def new_record(**keys) -> dict:
    """A chunk record with every span field and counter at zero."""
    rec = dict(keys)
    rec.update({field(n): 0.0 for n in CHUNK_SPANS})
    rec.update({c: 0 for c in TRANSFER_COUNTERS + SHAPE_COUNTERS})
    return rec


@contextmanager
def span(name: str, record: Optional[dict] = None, **args):
    """Annotate the body as span ``name`` and add its seconds to
    ``record[field(name)]`` (when a record is given)."""
    from jax.profiler import TraceAnnotation
    if record is not None:
        args.update({k: record[k] for k in ("chunk", "device")
                     if k in record})
    t0 = time.perf_counter()
    with TraceAnnotation(name, **args):
        yield
    if record is not None:
        key = field(name)
        record[key] = record.get(key, 0.0) + time.perf_counter() - t0


@contextmanager
def transfer(name: str, record: Optional[dict], tree):
    """:func:`span` of a transfer of ``tree``'s array leaves; their count
    and bytes go into the span's arguments (``arrays``, ``bytes``) and
    into the record (``h2d_arrays``, ``h2d_bytes`` for ``chunk.h2d``)."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    n, nbytes = len(leaves), sum(int(x.nbytes) for x in leaves)
    if record is not None:
        short = name.split(".", 1)[-1]
        record[short + "_arrays"] = record.get(short + "_arrays", 0) + n
        record[short + "_bytes"] = record.get(short + "_bytes", 0) + nbytes
    with span(name, record, arrays=n, bytes=nbytes):
        yield

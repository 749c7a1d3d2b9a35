"""The reduction from a profiler trace to the benchmark's device
numbers, on a synthetic trace with known answers laid out as the TPU
profiler lays out its planes and lines."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import tracing  # noqa: E402

def test_interval_arithmetic():
    u = tracing.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert tracing.length(u) == 6
    assert tracing.clip(u, 1, 6) == [(1, 3), (5, 6)]
    assert tracing.gaps(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tracing.gaps([], 0, 4) == [(0, 4)]


def _event(mid, start_ns, dur_ns):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _plane(pid, name, line, events, names):
    evs = "\n".join(_event(*e) for e in events)
    meta = "\n".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                     f'name: "{n}" }} }}' for k, n in names.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: {pid} '
            f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}')


def _synthetic():
    from jax.profiler import ProfileData
    # window [0, 100) ns; chip 0 busy [10, 40) with a kernel [30, 40);
    # chip 1 busy [20, 50) and [90, 95); host builds in [50, 60),
    # farms in [60, 100)
    txt = "\n".join([
        _plane(1, "/device:TPU:0", "XLA Ops",
               [(1, 10, 20), (1, 15, 10), (2, 30, 10)],
               {1: "fusion.1", 2: "custom-call.7"}),
        _plane(2, "/device:TPU:1", "XLA Ops",
               [(1, 20, 30), (1, 90, 5)], {1: "fusion.1"}),
        _plane(3, "/host:CPU", "python",
               [(1, 0, 100), (2, 50, 10), (3, 60, 40)],
               {1: "window", 2: "build", 3: "farm"}),
    ])
    return ProfileData.from_text_proto(txt)


def test_reduce_synthetic_two_chips():
    t = tracing.reduce_profile(_synthetic())
    assert t["window_s"] == pytest.approx(100e-9)
    busy = {c["chip"]: c["busy_s"] for c in t["chips"]}
    assert busy == pytest.approx({0: 30e-9, 1: 35e-9})
    assert t["busy_s"] == pytest.approx(32.5e-9)
    assert [c["custom_call_s"] for c in t["chips"]] == \
        pytest.approx([10e-9, 0.0])
    # any chip busy in [10, 50) and [90, 95)
    assert t["union_busy_s"] == pytest.approx(45e-9)
    # no chip busy in [0, 10) (other), [50, 60) (build), [60, 90) and
    # [95, 100) (farm)
    gaps = t["breakdown"]["idle_gaps"]
    assert gaps[0] == ["farm", pytest.approx(30e-9)]
    assert sorted(gaps[1:3]) == [["build", pytest.approx(10e-9)],
                                 ["other", pytest.approx(10e-9)]]
    assert gaps[3] == ["farm", pytest.approx(5e-9)] and len(gaps) == 4
    ops = dict(t["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(65e-9)
    one = tracing.reduce_profile(_synthetic(), chips_used=1)
    assert [c["chip"] for c in one["chips"]] == [0]


DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = sorted(f for f in os.listdir(DATA) if f.endswith(".pbtxt")) \
    if os.path.isdir(DATA) else []


def _recorded(name):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, name)) as f:
        return ProfileData.from_text_proto(f.read())


def _raster(intervals, lo, hi):
    """Busy nanoseconds of ``[lo, hi)`` counted one nanosecond at a
    time: the reduction's interval arithmetic done another way."""
    import numpy as np
    busy = np.zeros(int(hi - lo), bool)
    for s, e in intervals:
        busy[max(0, int(s - lo)):max(0, int(min(e, hi) - lo))] = True
    return int(busy.sum())


@pytest.mark.parametrize("name", RECORDED)
def test_reduce_recorded_chip_trace(name):
    """1 ms excerpts of ``--trace 1`` runs on one v5e
    (``bench/excerpt_trace.py``): the planes and lines are where the
    reduction looks, and its busy, kernel and idle numbers agree with a
    count made nanosecond by nanosecond."""
    pd = _recorded(name)
    t = tracing.reduce_profile(pd)
    assert [c["chip"] for c in t["chips"]] == [0]
    chips, spans = tracing._read(pd)
    lo = min(s for s, _ in spans["window"])
    hi = max(e for _, e in spans["window"])
    assert t["window_s"] == pytest.approx((hi - lo) * 1e-9)
    ops, kern = chips[0]["ops"], chips[0]["kernels"]
    assert len(ops) > 100
    busy_ns = _raster(ops, lo, hi)
    assert 0 < busy_ns < hi - lo
    assert t["busy_s"] == pytest.approx(busy_ns * 1e-9, abs=2e-9 * len(ops))
    # the Pallas water-fill stages run as custom calls on the chip
    assert kern
    kern_ns = _raster(kern, lo, hi)
    assert t["chips"][0]["custom_call_s"] == pytest.approx(
        kern_ns * 1e-9, abs=2e-9 * len(kern))
    gaps = t["breakdown"]["idle_gaps"]
    assert {g[0] for g in gaps} <= {"build", "farm", "results", "other"}
    assert sum(g[1] for g in gaps) <= t["window_s"] - t["busy_s"] + 1e-9
    assert all(" = " not in n for n, _ in t["breakdown"]["device_ops"])

"""The share of the chips' busy time in one name scope of the program
(``bench/opscope.py``, read by ``recv_stage_share``): op names on the
events' metadata as the TPU profiler puts them, a trace without the
scope, a run without a trace."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import opscope, registry  # noqa: E402

RECV = "jit(run)/vmap()/while/body/closed_call/fabric.recv"


def _trace():
    """Window [0, 100) ns on two chips.  Chip 0: the loop [5, 95) holds
    fusion.1 [10, 30) of the receive stage, fusion.2 [30, 40) of another
    stage, add.3 [40, 50) of the receive stage and, outside the window,
    another add.3 at [120, 130).  Chip 1: fusion.1 [0, 20).  The op names
    sit on the events' metadata as interned references; an event's own
    statistics carry other things."""
    from jax.profiler import ProfileData
    names = {1: ("while.4", "jit(run)/vmap()/while"),
             2: ("fusion.1", RECV + "/mul"),
             3: ("fusion.2", "jit(run)/vmap()/while/body/closed_call/"
                             "fabric.send/mul"),
             4: ("add.3", RECV + "/add")}
    refs = {op: 10 + k for k, (_, op) in names.items()}

    def events(evs):
        out = []
        for mid, s, e in evs:
            out.append(f"events {{ metadata_id: {mid} offset_ps: {s * 1000}"
                       f" duration_ps: {(e - s) * 1000} stats {{ "
                       f'metadata_id: 1 str_value: "loop fusion" }} }}')
        return "\n".join(out)

    def meta():
        out = []
        for mid, (n, op) in names.items():
            out.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                       f'name: "%{n} = f32[16,2] op(...)" stats {{ '
                       f'metadata_id: 2 ref_value: {refs[op]} }} }} }}')
        out.append('stat_metadata { key: 1 value { id: 1 '
                   'name: "hlo_category" } }')
        out.append('stat_metadata { key: 2 value { id: 2 name: "tf_op" } }')
        out.extend(f'stat_metadata {{ key: {r} value {{ id: {r} '
                   f'name: "{op}" }} }}' for op, r in refs.items())
        return "\n".join(out)

    chip0 = events([(1, 5, 95), (2, 10, 30), (3, 30, 40), (4, 40, 50),
                    (4, 120, 130)])
    txt = "\n".join([
        f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 '
        f'name: "XLA Ops" timestamp_ns: 0 {chip0} }} {meta()} }}',
        f'planes {{ id: 2 name: "/device:TPU:1" lines {{ id: 1 '
        f'name: "XLA Ops" timestamp_ns: 0 {events([(2, 0, 20)])} }} '
        f'{meta()} }}',
        'planes { id: 3 name: "/host:CPU" lines { id: 1 name: "python3" '
        'timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 '
        'duration_ps: 100000 } } event_metadata { key: 1 value { id: 1 '
        'name: "window" } } }'])
    return ProfileData.text_proto_to_serialized_xspace(txt)


def test_scope_share_of_a_synthetic_trace():
    from jax.profiler import ProfileData
    data = _trace()
    meta = opscope.metadata_stats(data)
    assert set(meta) == {"/device:TPU:0", "/device:TPU:1"}
    got = opscope.reduce_scope(ProfileData.from_serialized_xspace(data),
                               "fabric.recv", meta)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["chips"] == 2
    # chip 0 busy [5, 95), chip 1 [0, 20)
    assert got["busy_s"] == pytest.approx(110e-9)
    # chip 0: [10, 30) + [40, 50); chip 1: [0, 20)
    assert got["scope_s"] == pytest.approx(50e-9)
    one = opscope.reduce_scope(ProfileData.from_serialized_xspace(data),
                               "fabric.recv", meta, 1)
    assert one["busy_s"] == pytest.approx(90e-9)
    assert one["scope_s"] == pytest.approx(30e-9)


def test_metadata_refs_read_as_names():
    meta = opscope.metadata_stats(_trace())["/device:TPU:0"]
    assert meta["%add.3 = f32[16,2] op(...)"] == {"tf_op": RECV + "/add"}
    assert meta["%while.4 = f32[16,2] op(...)"] == {
        "tf_op": "jit(run)/vmap()/while"}


def test_scope_is_a_whole_part_of_the_op_name():
    assert opscope.in_scope(RECV + "/add", "fabric.recv")
    assert not opscope.in_scope(RECV + "x/add", "fabric.recv")
    assert not opscope.in_scope("fabric.receive/add", "fabric.recv")
    assert not opscope.in_scope("", "fabric.recv")


def test_no_scope_in_the_trace_reads_nothing():
    """A program without the scope (the commit before it) reads None, so
    the metric is left out of the line."""
    from jax.profiler import ProfileData
    data = _trace()
    pd = ProfileData.from_serialized_xspace(data)
    meta = opscope.metadata_stats(data)
    assert opscope.reduce_scope(pd, "fabric.drain", meta) is None
    # nor does a trace whose metadata holds no op names
    assert opscope.reduce_scope(pd, "fabric.recv", {}) is None


class _Run:
    def __init__(self, trace=None, cell="no-such-cell"):
        self.trace, self.cell, self.chips = trace, {"name": cell}, 1


def test_recv_stage_share_reader(tmp_path, monkeypatch):
    from bench import harness
    read = registry.Bench().reader("recv_stage_share")
    assert read(_Run()) is None                       # no trace
    assert read(_Run(trace={"window_s": 1e-7})) is None   # no file
    tdir = tmp_path / "plugins" / "profile" / "run1"
    tdir.mkdir(parents=True)
    (tdir / "host.xplane.pb").write_bytes(_trace())
    monkeypatch.setattr(harness, "_trace_dir", lambda bench, cell:
                        str(tmp_path))
    run = _Run(trace={"window_s": 100e-9})
    assert read(run) == pytest.approx(30.0 / 90.0 * 100.0)
    # a file that is not the run's own (another window) reads nothing
    assert read(_Run(trace={"window_s": 250e-9})) is None

"""The receive stage of both engines' tick runs under the
``fabric.recv`` name scope: its operations carry the scope in their
op-name metadata, lowered and compiled, and the scope changes nothing
but metadata."""
from __future__ import annotations

import contextlib
import re

import pytest

from repro.fabric import scenarios as SC
from repro.fabric import vector as V


def _fsp(sparse: bool):
    scens = [SC.incast(n_senders=4, mode="ddio", pfc=pfc, burst_mb=0.5,
                       sim_time_s=0.0001) for pfc in (False, True)]
    return V.FabricSweepParams.from_scenarios(scens, sparse=sparse)


def _lowered(fsp):
    _, bufs = V._packed_params(fsp)
    return V._jax_program(fsp, 1, "ref").lower(*bufs)


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_receive_stage_ops_carry_the_scope(sparse):
    low = _lowered(_fsp(sparse))
    assert V.RECV_SCOPE == "fabric.recv"
    assert re.search(r'loc\("[^"]*fabric\.recv[/"]',
                     low.as_text(debug_info=True))
    names = re.findall(r'op_name="([^"]*)"', low.compile().as_text())
    inside = [n for n in names if "fabric.recv" in n.split("/")]
    # the stage's own operations, inside the scan's loop body
    assert any("/while/body/" in n for n in inside)
    ops = {n.rsplit("/", 1)[-1] for n in inside}
    assert {"mul", "reduce_sum", "min"} <= ops
    # and the scope is not everything: the other stages stay outside it
    assert len(inside) < len(names) / 2


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_scope_changes_metadata_only(sparse, monkeypatch):
    fsp = _fsp(sparse)
    with_scope = _lowered(fsp).as_text()
    monkeypatch.setattr(V, "_scope",
                        lambda xp, name: contextlib.nullcontext())
    monkeypatch.setattr(V, "_PROGRAMS", {})
    assert _lowered(fsp).as_text() == with_scope

"""The scan program's host-device boundary: one buffer per dtype each way.

The jax backend puts a chunk's parameters on the device as at most two
packed buffers (float32, int32), builds the zero carry there, and pulls
back only the carry keys ``_results`` reads, packed the same way.  These
tests hold the packing to the unpacked parameters and carry it stands
for, and the packed program to the vmapped scan of the host-made carry.
"""
import math

import numpy as np
import pytest

from repro.fabric import scenarios as SC
from repro.fabric import vector as V

SIM_S = 0.0005


def _fsp(scens):
    sparse = any(bool(s.topology.super_spines) for s in scens)
    return V.FabricSweepParams.from_scenarios(scens, sparse=sparse)


def _dense():
    return SC.incast_grid(burst_mb=(0.25, 0.5), n_senders=4,
                          sim_time_s=SIM_S)[0]


def _sparse():
    return SC.pod_incast_grid(pods=2, leaves_per_pod=2, hosts_per_leaf=2,
                              burst_mb=0.2, sim_time_s=SIM_S)[0]


def _messages():
    return SC.message_sweep_grid(msg_kb=(64.0,), window=(1, 16),
                                 verb=("write",), algo=("dcqcn", "timely"),
                                 sim_time_s=SIM_S)[0]


def _faults():
    return SC.lossy_incast_grid(loss_rate=(0.01, 0.05), n_senders=4,
                                sim_time_s=SIM_S)[0]


def _routing():
    return SC.routing_grid(sim_time_s=SIM_S, burst_mb=0.5,
                           fail_at_us=(math.inf, 150.0))[0]


GRIDS = {"dense": _dense, "sparse": _sparse, "messages": _messages,
         "faults": _faults, "routing": _routing}


@pytest.fixture(scope="module", params=sorted(GRIDS))
def grid(request):
    fsp = _fsp(GRIDS[request.param]())
    want = {"dense": (), "sparse": ("sparse",), "messages": ("any_msg",),
            "faults": ("any_flt",), "routing": ("dyn_route",)}
    assert all(getattr(fsp, f) for f in want[request.param])
    return request.param, fsp


def _same(a, b):
    return a.dtype == b.dtype and a.shape == b.shape \
        and np.array_equal(a, b, equal_nan=True)


def test_params_round_trip_bit_exact(grid):
    """The parameter buffers unpack to :func:`_np_params` bit for bit,
    inf and int32 values included, in at most two buffers."""
    _, fsp = grid
    p = V._np_params(fsp, np.float32)
    layout, bufs = V._packed_params(fsp)
    assert 1 <= len(bufs) <= 2
    assert [b.dtype for b in bufs] == [bd for bd, _ in layout]
    assert all(b.shape[0] == fsp.n_points for b in bufs)
    assert all(_same(a, b) for a, b in zip(bufs, V.packed_params(fsp)))
    back = V._unpack(layout, bufs, (fsp.n_points,))
    assert set(back) == set(p)
    for k in p:
        assert _same(back[k], p[k]), k
    assert any(np.isinf(v).any() for v in p.values()
               if v.dtype == np.float32)
    ints = [k for k, v in p.items() if v.dtype == np.int32]
    assert ints and len(bufs) == 2 and bufs[1].dtype == np.int32


def _np_carry(fsp, ticks=200):
    """The full float32 numpy carry after ``ticks`` steps."""
    p = V._np_params(fsp, np.float32)
    st = V._static(fsp, np, np.float32)

    def ring_set(ring, idx, v):
        ring[..., idx, :, :] = v
        return ring

    mk = V._make_step_sparse if fsp.sparse else V._make_step
    step = mk(np, ring_set, st, p, fsp.dt_us, fsp.ring_len, np.float32,
              fsp.cnp_ring, V._opts(fsp))
    s = V._init_state(np, (fsp.n_points,), fsp, p, np.float32)
    for t in range(min(ticks, fsp.ticks)):
        s = step(s, t)
    return s


def _results_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]),
                              equal_nan=True), k


def test_pruned_results_match_full_carry(grid):
    """``_results`` over the packed and unpacked result keys equals
    ``_results`` over the whole carry, and every key of the list is
    read: the list is what ``_results`` needs, no more."""
    _, fsp = grid
    full = _np_carry(fsp)
    keys = V._result_keys(fsp)
    assert set(keys) <= set(full)
    layout = V._layout({k: full[k] for k in keys}, 1)
    assert 1 <= len(layout) <= 2
    bufs = V._pack(np, layout, full, (fsp.n_points,))
    back = V._unpack(layout, bufs, (fsp.n_points,))
    for k in keys:
        assert _same(back[k], np.asarray(full[k])), k
    want = V._results(full, fsp)
    _results_equal(V._results(back, fsp), want)
    for k in keys:
        less = {j: v for j, v in back.items() if j != k}
        try:
            got = V._results(less, fsp)
        except KeyError:
            continue
        assert set(got) != set(want), f"{k} is never read"


def _unpacked_run(fsp):
    """The vmapped scan of the host-made carry, pulled whole: the
    program as it ran before the boundary was packed."""
    import jax
    import jax.numpy as jnp
    from repro.fabric import fused

    impl = fused.resolve_impl("auto")
    st = V._static(fsp, jnp, jnp.float32)

    def ring_set(ring, idx, v):
        return ring.at[..., idx, :, :].set(v)

    def one_point(s0, p):
        mk = V._make_step_sparse if fsp.sparse else V._make_step
        step = mk(jnp, ring_set, st, p, fsp.dt_us, fsp.ring_len,
                  jnp.float32, fsp.cnp_ring, V._opts(fsp, impl))
        s, _ = jax.lax.scan(lambda s, t: (step(s, t), None), s0,
                            jnp.arange(fsp.ticks, dtype=jnp.int32),
                            unroll=V.pick_unroll("auto"))
        return s

    p = V._np_params(fsp, np.float32)
    s0 = V._init_state(np, (fsp.n_points,), fsp, p, np.float32)
    final = jax.jit(jax.vmap(one_point))(s0, p)
    return V._results({k: np.asarray(v) for k, v in final.items()}, fsp)


@pytest.mark.parametrize("name", ["dense", "sparse"])
def test_packed_boundary_bit_identical(name):
    """``run_fabric_sweep(backend="jax")`` through the packed boundary
    gives the unpacked program's results bit for bit."""
    scens = GRIDS[name]()
    got = V.run_fabric_sweep(scens, backend="jax")
    _results_equal(got, _unpacked_run(_fsp(scens)))
    assert np.asarray(got["flow_delivered_bytes"]).sum() > 0

"""Compile-only tests for one described TPU v5e chip (no chip attached).

XLA's TPU compiler is installed wherever libtpu is, and compiles for a
chip that is described rather than present.  These tests compile the
main path's kernels and programs at real widths for one v5e chip, so a
Pallas kernel the chip's compiler refuses (unaligned slices, too much
VMEM) or a program that does not fit the device fails here, at no chip
time:

* the fused fabric water-fills (``priority_grants`` / ``priority_admit``,
  ``impl="pallas"``) at the 256-host pod grid's widths;
* the whole sparse-engine scan program over that grid, with the Pallas
  stages inside;
* the flash-attention kernel and the whole bf16 prefill step of
  ``h2o-danube-1.8b`` at published widths, the latter within 16 GB.

The topology is described inside a fixture, never at import: only one
process may load libtpu at a time, and under pytest-xdist only the
worker given this file may try.  Nothing here runs a program.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_disk_cache():
    """A compile for a described chip cannot be read back from jax's
    persistent cache without the chip; keep these compiles off it."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture(scope="module")
def pod_fsp():
    """The 256-host cross-pod incast grid of ``chip_smoke.py`` phase 2,
    packed for the sparse engine (host-side numpy only)."""
    from repro.fabric import scenarios as SC
    from repro.fabric import vector as V
    scens, _ = SC.pod_incast_grid(mode=("jet", "ddio"), pfc=(False, True),
                                  pods=4, leaves_per_pod=4,
                                  hosts_per_leaf=16, burst_mb=0.2,
                                  sim_time_s=0.004)
    fsp = V.FabricSweepParams.from_scenarios(scens, sparse=True)
    assert fsp.sparse and fsp.n_points == 4 and fsp.ticks == 4000
    return fsp


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), \
        "Pallas kernel missing from the compiled program"


def test_priority_grants_pallas(one_chip, no_disk_cache, pod_fsp):
    from repro.core.datapath import N_QOS
    from repro.fabric import fused
    g, p = pod_fsp.n_points, pod_fsp.n_ports
    qp = _sds((g, N_QOS, p), jnp.float32, one_chip)
    pp = _sds((g, p), jnp.float32, one_chip)

    def grants(demand, can, budget, crumb):
        return fused.priority_grants(jnp, demand, can, budget, crumb,
                                     jnp.float32(1.0), jnp.float32(0.0),
                                     impl="pallas")
    _assert_kernel(jax.jit(grants).lower(qp, qp, pp, pp).compile())


def test_priority_admit_pallas(one_chip, no_disk_cache, pod_fsp):
    from repro.core.datapath import N_QOS
    from repro.fabric import fused
    g, r = pod_fsp.n_points, pod_fsp.n_recv
    qr = _sds((g, N_QOS, r), jnp.float32, one_chip)
    rr = _sds((g, r), jnp.float32, one_chip)

    def admit(demand, space):
        return fused.priority_admit(jnp, demand, space, impl="pallas")
    _assert_kernel(jax.jit(admit).lower(qr, rr).compile())


def test_sparse_scan_program_pallas(one_chip, no_disk_cache, pod_fsp):
    from repro.fabric import vector as V
    fsp = pod_fsp
    fn = V._jax_program(fsp, 1, "pallas")
    compiled = fn.lower(*[_sds(b.shape, b.dtype, one_chip)
                          for b in V.packed_params(fsp)]).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_HBM_BYTES


def test_flash_attention_danube_prefill(one_chip, no_disk_cache):
    from repro.configs import get_arch
    from repro.kernels import ops
    cfg = get_arch("h2o-danube-1.8b")
    q = _sds((1, cfg.num_heads, 512, cfg.hd), jnp.bfloat16, one_chip)
    kv = _sds((1, cfg.num_kv_heads, 512, cfg.hd), jnp.bfloat16, one_chip)

    def attn(q, k, v):
        return ops.flash_attention(q, k, v, causal=True,
                                   window=cfg.sliding_window,
                                   impl="pallas")
    _assert_kernel(jax.jit(attn).lower(q, kv, kv).compile())


def test_danube_bf16_prefill_fits_one_chip(one_chip, no_disk_cache,
                                           monkeypatch):
    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.models import api as model_api
    from repro.parallel.sharding import single_device_ctx
    # the kernels pick Pallas from jax's default backend, which is the
    # CPU here; steer them to the chip's tier for this compile
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_arch("h2o-danube-1.8b")
    ctx = single_device_ctx()
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        model_api.abstract_params(cfg, jnp.bfloat16))
    tokens = _sds((1, 512), jnp.int32, one_chip)

    def prefill(params, tokens):
        return model_api.prefill(params, cfg, ctx, tokens, max_len=1024,
                                 compute_dtype=jnp.bfloat16)
    compiled = jax.jit(prefill).lower(params, tokens).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total

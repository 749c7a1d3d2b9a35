"""Multi-device checks, run in a subprocess with 8 host CPU devices.
Each check prints PASS/FAIL; exits nonzero on any failure."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, ShapeConfig, tiny_config
from repro.launch.mesh import ctx_for_mesh, make_mesh
from repro.models import api
from repro.models.moe import moe_dense_ref, moe_ep, moe_init
from repro.optim import adamw
from repro.parallel import collectives as coll
from repro.parallel.compression import compressed_psum, dequantize_int8, \
    quantize_int8
from repro.parallel.sharding import single_device_ctx
from repro.train import steps as steps_mod

FAILED = []


def check(name):
    def deco(fn):
        try:
            fn()
            print(f"PASS {name}", flush=True)
        except Exception:
            FAILED.append(name)
            print(f"FAIL {name}", flush=True)
            traceback.print_exc()
    return deco


MESH = make_mesh((4, 2), ("data", "model"))
MESH8 = make_mesh((2, 4), ("data", "model"))


@check("moe_ep_equals_dense_ref")
def _():
    """EP shard_map MoE == dense oracle when capacity is ample."""
    cfg = dataclasses.replace(tiny_config(ARCHS["llama4-scout-17b-a16e"]),
                              num_experts=4)
    key = jax.random.key(0)
    params = moe_init(key, cfg)
    x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model))
    ctx = ctx_for_mesh(MESH8, moe_capacity_factor=16.0, fsdp=False)
    with MESH8:
        y_ep, aux_ep = jax.jit(lambda p, xx: moe_ep(p, xx, cfg, ctx))(
            params, x)
    y_ref, aux_ref = moe_dense_ref(params, x, cfg, cap_factor=16.0)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    assert float(aux_ep["overflow"]) == 0.0


@check("moe_ep_jet_staged_matches_dense_ref")
def _():
    """RDCA staged expert FFN (ppermute ring) == dense oracle."""
    cfg = dataclasses.replace(tiny_config(ARCHS["llama4-scout-17b-a16e"]),
                              num_experts=4)
    params = moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model))
    ctx = ctx_for_mesh(MESH, moe_capacity_factor=16.0, fsdp=True,
                       jet_collectives=True)
    with MESH:
        y, aux = jax.jit(lambda p, xx: moe_ep(p, xx, cfg, ctx))(params, x)
    y_ref, _ = moe_dense_ref(params, x, cfg, cap_factor=16.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


@check("accum_microbatching_matches_full_batch")
def _():
    """accum=4 grad accumulation == single full-batch step (same data)."""
    cfg = dataclasses.replace(tiny_config(ARCHS["gemma-7b"]), num_layers=2)
    opt_cfg = adamw.OptConfig(lr=1e-3)
    key = jax.random.key(0)
    shape = ShapeConfig("t", "train", 16, 8)
    batch = api.synthetic_inputs(cfg, shape, key, dtype=jnp.float32)
    ctx = ctx_for_mesh(MESH8)
    with MESH8:
        s1, m1 = jax.jit(steps_mod.make_train_step(
            cfg, ctx, opt_cfg, jnp.float32))(
            steps_mod.init_state(cfg, opt_cfg, key), batch)
        micro = {k: v.reshape((4, 2) + v.shape[1:])
                 for k, v in batch.items()}
        s2, m2 = jax.jit(steps_mod.make_train_step(
            cfg, ctx, opt_cfg, jnp.float32, accum_steps=4))(
            steps_mod.init_state(cfg, opt_cfg, key), micro)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-3, \
        (float(m1["loss"]), float(m2["loss"]))
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@check("moe_ep_capacity_escape")
def _():
    """Tokens above capacity take the escape path (zero update, counted)."""
    cfg = dataclasses.replace(tiny_config(ARCHS["llama4-scout-17b-a16e"]),
                              num_experts=4)
    params = moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model))
    ctx = ctx_for_mesh(MESH8, moe_capacity_factor=0.3, fsdp=False)
    with MESH8:
        _, aux = jax.jit(lambda p, xx: moe_ep(p, xx, cfg, ctx))(params, x)
    assert float(aux["overflow"]) > 0.0


@check("ring_allgather_matmul")
def _():
    m = 8
    mesh = make_mesh((m,), ("model",))
    x = jax.random.normal(jax.random.key(0), (16, 64))
    w = jax.random.normal(jax.random.key(1), (64, 32))
    want = x @ w

    def body(x_blk, w_blk):
        return coll.ring_allgather_matmul(x_blk, w_blk, "model", m,
                                          frags=2)
    got = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(), P("model", None)),
        out_specs=P(), check_vma=False))(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@check("ring_reduce_scatter")
def _():
    m = 8
    mesh = make_mesh((m,), ("model",))
    y = jax.random.normal(jax.random.key(0), (m, 16, 64))  # per-rank partials

    def body(y_blk):
        return coll.ring_reduce_scatter(y_blk[0], "model", m)
    got = jax.jit(shard_map(body, mesh=mesh,
                                in_specs=(P("model", None, None),),
                                out_specs=P("model"),
                                check_vma=False))(y)
    # rank r's shard is columns [r*8, (r+1)*8) of the full sum; stacking
    # along axis 0 per out_specs groups rows by rank
    want = np.asarray(y.sum(axis=0))
    want_stack = np.concatenate([want[:, r * 8:(r + 1) * 8]
                                 for r in range(m)], axis=0)
    np.testing.assert_allclose(np.asarray(got), want_stack,
                               rtol=1e-4, atol=1e-4)


@check("windowed_allgather")
def _():
    m = 8
    mesh = make_mesh((m,), ("model",))
    x = jax.random.normal(jax.random.key(0), (64, 8))

    def body(x_blk):
        return coll.windowed_allgather(x_blk, "model", m, window=4)
    got = jax.jit(shard_map(body, mesh=mesh,
                                in_specs=(P("model", None),),
                                out_specs=P(None, None) if False else P(),
                                check_vma=False))(x)
    # every rank assembles the full tensor; out_specs=P() takes rank 0's
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), rtol=1e-5,
                               atol=1e-5)


@check("srq_combine_distributed_decode")
def _():
    from repro.kernels import ref as kref
    m = 4
    mesh = make_mesh((m,), ("model",))
    b, h, d, s = 2, 2, 8, 32
    q = jax.random.normal(jax.random.key(0), (b, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, h, d))
    v = jax.random.normal(jax.random.key(2), (b, s, h, d))
    want, _ = kref.decode_attention_naive(q, k, v,
                                          jnp.full((b,), s, jnp.int32))

    def body(q_full, k_blk, v_blk):
        o, lse = kref.decode_attention_naive(
            q_full, k_blk, v_blk,
            jnp.full((q_full.shape[0],), k_blk.shape[1], jnp.int32))
        return coll.srq_combine(o, lse, "model")
    got = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "model", None, None),
                  P(None, "model", None, None)),
        out_specs=P(), check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@check("gpipe_two_stage_matches_sequential")
def _():
    """2-stage GPipe over a 'pod' axis == sequential layer stack, for both
    the forward values and the parameter gradients."""
    from repro.parallel import pipeline as pp
    s, layers_per, d, m_micro, b = 2, 3, 16, 4, 8
    mesh = make_mesh((s,), ("pod",))
    key = jax.random.key(0)
    w = jax.random.normal(key, (s * layers_per, d, d)) * (d ** -0.5)
    x = jax.random.normal(jax.random.key(1), (m_micro, b, d))

    def seq_apply(w_all, xm):
        def layer(h, wi):
            return jnp.tanh(h @ wi), None
        out, _ = jax.lax.scan(layer, xm.reshape(-1, d), w_all)
        return out.reshape(xm.shape)

    def piped(w_all, x_micro):
        w_stages = pp.stack_stages(w_all, s)          # [S, L/S, d, d]

        def body(w_stage, xm):
            def stage_fn(h):
                def layer(hh, wi):
                    return jnp.tanh(hh @ wi), None
                out, _ = jax.lax.scan(layer, h.reshape(-1, d), w_stage[0])
                return out.reshape(h.shape)
            y = pp.gpipe(stage_fn, xm, "pod", s)
            return pp.broadcast_from_last(y, "pod", s)
        from jax.sharding import PartitionSpec as P
        return shard_map(body, mesh=mesh,
                             in_specs=(P("pod"), P()), out_specs=P(),
                             check_vma=False)(w_stages, x_micro)

    want = jax.vmap(lambda xm: seq_apply(w, xm))(x)
    got = jax.jit(piped)(w, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # gradients flow through the ppermute schedule
    g_seq = jax.grad(lambda ww: jax.vmap(
        lambda xm: seq_apply(ww, xm))(x).sum())(w)
    g_pipe = jax.grad(lambda ww: piped(ww, x).sum())(w)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                               rtol=5e-4, atol=5e-4)


@check("compressed_psum_error_feedback")
def _():
    m = 4
    mesh = make_mesh((m,), ("pod",))
    g = jax.random.normal(jax.random.key(0), (m, 512))

    def body(g_blk, err):
        mean, new_err = compressed_psum(g_blk[0], err[0], "pod")
        return mean, new_err[None]
    mean, err = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("pod", None), P("pod", None)),
        out_specs=(P(), P("pod", None)), check_vma=False))(
        g, jnp.zeros_like(g))
    want = np.asarray(g).mean(axis=0)
    got = np.asarray(mean)
    # int8 quantization error is bounded by scale/2 per block
    assert np.abs(got - want).max() < np.abs(g).max() / 127 + 1e-3
    # error feedback: residual equals what was lost
    q, s = quantize_int8(g[0] + 0)
    assert np.isfinite(np.asarray(err)).all()


@check("compressed_pod_grads_train_step")
def _():
    """Hierarchical int8+EF cross-pod grad sync: one step stays close to
    the exact (uncompressed) step; the EF residual is populated."""
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = dataclasses.replace(tiny_config(ARCHS["chatglm3-6b"]),
                              num_layers=2)
    key = jax.random.key(0)
    shape = ShapeConfig("t", "train", 16, 4)
    batch = api.synthetic_inputs(cfg, shape, key, dtype=jnp.float32)
    from repro.launch.mesh import ctx_for_mesh as cfm
    ctx = cfm(mesh3)
    assert ctx.data_axes == ("pod", "data")

    exact_cfg = adamw.OptConfig(lr=1e-3)
    comp_cfg = adamw.OptConfig(lr=1e-3, compressed_pod_grads=True)
    with mesh3:
        s1, m1 = jax.jit(steps_mod.make_train_step(
            cfg, ctx, exact_cfg, jnp.float32))(
            steps_mod.init_state(cfg, exact_cfg, key), batch)
        s2, m2 = jax.jit(steps_mod.make_train_step(
            cfg, ctx, comp_cfg, jnp.float32))(
            steps_mod.init_state(cfg, comp_cfg, key), batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2
    # int8 quantization error is bounded; params stay close after 1 step
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.1, atol=2e-3)
    # error feedback captured the quantization residual
    err_mag = max(float(jnp.abs(e).max())
                  for e in jax.tree.leaves(s2["err"]))
    assert np.isfinite(err_mag) and err_mag > 0.0


@check("distributed_train_step_matches_single_device")
def _():
    cfg = tiny_config(ARCHS["chatglm3-6b"])
    cfg = dataclasses.replace(cfg, num_layers=2)
    opt_cfg = adamw.OptConfig(lr=1e-3)
    key = jax.random.key(0)
    shape = ShapeConfig("t", "train", 16, 4)
    batch = api.synthetic_inputs(cfg, shape, key, dtype=jnp.float32)

    # single device
    ctx1 = single_device_ctx()
    state1 = steps_mod.init_state(cfg, opt_cfg, key)
    step1 = jax.jit(steps_mod.make_train_step(cfg, ctx1, opt_cfg,
                                              jnp.float32))
    s1, m1 = step1(state1, batch)

    # 4x2 mesh
    ctx2 = ctx_for_mesh(MESH)
    state2 = steps_mod.init_state(cfg, opt_cfg, key)
    with MESH:
        step2 = jax.jit(steps_mod.make_train_step(cfg, ctx2, opt_cfg,
                                                  jnp.float32))
        s2, m2 = step2(state2, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
    # parameters after one step agree
    l1 = jax.tree.leaves(s1["params"])
    l2 = jax.tree.leaves(s2["params"])
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


@check("moe_arch_distributed_train_step")
def _():
    cfg = tiny_config(ARCHS["llama4-scout-17b-a16e"])
    cfg = dataclasses.replace(cfg, num_layers=2)
    opt_cfg = adamw.OptConfig(lr=1e-3)
    key = jax.random.key(0)
    shape = ShapeConfig("t", "train", 16, 4)
    batch = api.synthetic_inputs(cfg, shape, key, dtype=jnp.float32)
    ctx = ctx_for_mesh(MESH, moe_capacity_factor=8.0)
    state = steps_mod.init_state(cfg, opt_cfg, key)
    with MESH:
        step = jax.jit(steps_mod.make_train_step(cfg, ctx, opt_cfg,
                                                 jnp.float32))
        s, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


@check("elastic_reshard_roundtrip")
def _():
    import tempfile
    from repro.checkpoint import ckpt
    cfg = dataclasses.replace(tiny_config(ARCHS["gemma-7b"]), num_layers=2)
    opt_cfg = adamw.OptConfig()
    state = steps_mod.init_state(cfg, opt_cfg, jax.random.key(0))
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(state, d, step=7, extra={"step": 7})
        # restore onto a 2x4 mesh with shardings (elastic: 1 dev -> 8 dev)
        ctx = ctx_for_mesh(MESH8)
        like = steps_mod.abstract_state(cfg, opt_cfg)
        specs = steps_mod.state_specs(like, ctx)
        shardings = jax.tree.map(
            lambda s: ctx.sharding(s),
            specs, is_leaf=lambda x: isinstance(x, P))
        with MESH8:
            restored, extra = ckpt.restore(d, like, shardings=shardings)
        assert extra["step"] == 7
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))


@check("farm_round_robin_over_devices")
def _():
    """Sweep-farm chunks round-robin over the local devices: 4 chunks
    land on 4 distinct devices, bit-identical to the monolithic run."""
    from repro.fabric.farm import run_farm
    from repro.fabric.scenarios import incast_grid
    from repro.fabric.vector import run_fabric_sweep
    scens, _ = incast_grid(burst_mb=(0.25, 0.5), n_senders=4,
                           sim_time_s=0.001)
    assert len(scens) == 8
    mono = run_fabric_sweep(scens, backend="jax")
    farm = run_farm(scens, workers=0, chunk_size=2, backend="jax",
                    artifacts=False)
    recs = farm["manifest"]["records"]
    assert len({r["device"] for r in recs}) == 4, [r["device"] for r in recs]
    from repro.fabric import spans as S
    for r in recs:
        for name in S.CHUNK_SPANS:
            assert r[S.field(name)] >= 0.0, (r["device"], name)
        assert r["device_s"] > 0
        assert 1 <= r["h2d_arrays"] <= 2 and 1 <= r["d2h_arrays"] <= 2
    for k in mono:
        assert np.array_equal(np.asarray(mono[k]),
                              np.asarray(farm["results"][k]),
                              equal_nan=True), k


print(f"{len(FAILED)} failures: {FAILED}", flush=True)
raise SystemExit(1 if FAILED else 0)

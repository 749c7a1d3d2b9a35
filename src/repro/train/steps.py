"""Train / eval step construction: loss + grad + AdamW, with param-sharding
rules applied (FSDP/TP/EP), ready for jit/pjit under a mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..models import api as model_api
from ..optim import adamw
from ..parallel.sharding import ParallelCtx

# (tp_dim, fsdp_dim) by leaf name, negative indices from the end
_RULES = {
    "wq": (-1, -2), "wk": (-1, -2), "wv": (-1, -2),
    "w_in": (-1, -2), "w_gate": (-1, -2), "w_x": (-1, -2),
    "w_xbc": (-1, -2), "w_z": (-1, -2), "w_dt": (-1, -2),
    "w_if": (-1, -2),
    "wo": (-2, -1), "w_out": (-2, -1),
    "e_in": (-3, -2), "e_gate": (-3, -2), "e_out": (-3, -1),
    "embed": (-2, -1), "unembed": (-1, -2),
}


def param_spec(path, leaf, ctx: ParallelCtx) -> P:
    name = None
    for p in reversed(path):
        k = getattr(p, "key", None)
        if isinstance(k, str):
            name = k
            break
    rule = _RULES.get(name)
    if rule is None or not ctx.have_mesh:
        return P()
    tp, fs = rule
    nd = leaf.ndim
    parts: list = [None] * nd
    tp_i, fs_i = tp % nd, fs % nd
    if leaf.shape[tp_i] % ctx.model_size == 0 and leaf.shape[tp_i] > 1:
        parts[tp_i] = ctx.model_axis
    if (ctx.fsdp and fs_i != tp_i and "data" in ctx.mesh.axis_names
            and leaf.shape[fs_i] % ctx.mesh.shape["data"] == 0
            and leaf.shape[fs_i] > 1):
        parts[fs_i] = "data"
    return P(*parts)


def param_specs(params, ctx: ParallelCtx):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec(path, leaf, ctx), params)


def param_shardings(params, ctx: ParallelCtx):
    return jax.tree.map(lambda s: ctx.sharding(s),
                        param_specs(params, ctx))


def opt_state_specs(opt_state, params_specs, ctx: ParallelCtx):
    """Moments inherit their parameter's spec (ZeRO).  Row-wise int8
    moments: ``q`` keeps the parameter's exact shape (same spec); ``s``
    drops the last dim (same spec truncated) — sharding-preserving, no
    reshape (see parallel.compression.quantize_int8_rowwise)."""
    def one(moment_tree):
        def match(path, leaf):
            is_scale = getattr(path[-1], "key", None) == "s"
            trimmed = [p for p in path
                       if getattr(p, "key", None) not in ("q", "s")]
            if leaf.ndim == 0:
                return P()
            if is_scale:
                # spec of the parent parameter, truncated to scale's dims
                parent = jax.ShapeDtypeStruct(tuple(leaf.shape) + (1,),
                                              leaf.dtype)
                spec = param_spec(trimmed, parent, ctx)
                return P(*tuple(spec)[:leaf.ndim])
            return param_spec(trimmed, leaf, ctx)
        return jax.tree_util.tree_map_with_path(match, moment_tree)
    return {"m": one(opt_state["m"]), "v": one(opt_state["v"]),
            "count": P()}


def batch_specs(batch, ctx: ParallelCtx):
    def one(x):
        ax = ctx.batch_axes_for(x.shape[0])
        return P(ax if ax else None, *([None] * (x.ndim - 1)))
    return jax.tree.map(one, batch)


# --------------------------------------------------------------------------- #
def make_train_step(cfg: ArchConfig, ctx: ParallelCtx,
                    opt_cfg: adamw.OptConfig,
                    compute_dtype=jnp.bfloat16, accum_steps: int = 1):
    """Returns train_step(state, batch) -> (state, metrics).

    ``accum_steps > 1`` enables gradient-accumulation microbatching: the
    batch arrives pre-split as [A, B/A, ...] (leading accum dim
    *unsharded*, micro dim data-sharded) and a lax.scan accumulates f32
    grads — activation live range (and temp HBM) divides by A, which is
    what fits the 400B train cells on 16 GB v5e chips.
    """
    grad_fn = jax.value_and_grad(model_api.loss_fn, has_aux=True)

    def compute_grads(params, batch, gctx=ctx):
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, cfg, gctx, batch,
                                             compute_dtype)
            return grads, loss, metrics
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                          params)

        def micro(carry, mb):
            g_acc, l_acc, a_acc = carry
            (l, m), g = grad_fn(params, cfg, gctx, mb, compute_dtype)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            a_new = {k: a_acc[k] + m[k] for k in a_acc}
            return (g_acc, l_acc + l, a_new), None

        aux0 = {k: jnp.zeros((), jnp.float32)
                for k in ("loss", "lb_loss", "overflow")}
        (grads, loss, asum), _ = jax.lax.scan(
            micro, (g0, jnp.zeros((), jnp.float32), aux0), batch)
        inv = 1.0 / accum_steps
        grads = jax.tree.map(lambda g: g * inv, grads)
        return grads, loss * inv, {k: v * inv for k, v in asum.items()}

    def plain_step(state: Dict[str, Any], batch: Dict[str, Any]):
        params = state["params"]
        grads, loss, metrics = compute_grads(params, batch)
        new_params, new_opt, stats = adamw.update(grads, state["opt"],
                                                  params, opt_cfg)
        out = {"params": new_params, "opt": new_opt,
               "step": state["step"] + 1}
        if "err" in state:
            out["err"] = state["err"]
        return out, {**metrics, **stats}

    use_pod = (opt_cfg.compressed_pod_grads and ctx.have_mesh
               and "pod" in ctx.mesh.axis_names)
    if not use_pod:
        return plain_step

    # --- hierarchical compressed cross-pod sync --------------------------- #
    # shard_map manual over 'pod' only: inside the body the batch is the
    # pod-local shard (loss/grads reduce over data/model via the auto
    # axes); the pod-axis gradient mean rides int8 + error feedback.
    from ..parallel.compression import compressed_psum
    import dataclasses as _dc

    # constraints inside the manual-'pod' region may only use auto axes
    inner_ctx = _dc.replace(
        ctx, data_axes=tuple(a for a in ctx.data_axes if a != "pod"))

    def pod_body(state, batch):
        params = state["params"]
        grads, loss, metrics = compute_grads(params, batch, inner_ctx)

        def one(g, e):
            mean, new_e = compressed_psum(g.astype(jnp.float32),
                                          e.astype(jnp.float32), "pod")
            return mean, new_e.astype(jnp.bfloat16)
        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = tdef.flatten_up_to(state["err"])
        pairs = [one(g, e) for g, e in zip(flat_g, flat_e)]
        grads = tdef.unflatten([p[0] for p in pairs])
        new_err = tdef.unflatten([p[1] for p in pairs])
        new_params, new_opt, stats = adamw.update(grads, state["opt"],
                                                  params, opt_cfg)
        metrics = {**metrics, **stats,
                   "loss": jax.lax.pmean(metrics["loss"]
                                         if "loss" in metrics else loss,
                                         "pod")}
        return ({"params": new_params, "opt": new_opt, "err": new_err,
                 "step": state["step"] + 1}, metrics)

    def pod_step(state, batch):
        bdim = 1 if accum_steps > 1 else 0
        bspec = jax.tree.map(
            lambda x: P(*([None] * bdim + ["pod"] +
                          [None] * (x.ndim - bdim - 1))), batch)
        return shard_map(
            pod_body, mesh=ctx.mesh,
            in_specs=(jax.tree.map(lambda _: P(), state), bspec),
            out_specs=(jax.tree.map(lambda _: P(), state),
                       jax.tree.map(lambda _: P(),
                                    {"loss": 0, "lb_loss": 0,
                                     "overflow": 0, "lr": 0,
                                     "grad_norm": 0})),
            check_vma=False, axis_names={"pod"})(state, batch)

    return pod_step


def make_eval_step(cfg: ArchConfig, ctx: ParallelCtx,
                   compute_dtype=jnp.bfloat16):
    def eval_step(params, batch):
        loss, metrics = model_api.loss_fn(params, cfg, ctx, batch,
                                          compute_dtype)
        return metrics
    return eval_step


def init_state(cfg: ArchConfig, opt_cfg: adamw.OptConfig, key,
               dtype=jnp.float32) -> Dict[str, Any]:
    params = model_api.init_params(cfg, key, dtype)
    state = {"params": params, "opt": adamw.init(params, opt_cfg),
             "step": jnp.zeros((), jnp.int32)}
    if opt_cfg.compressed_pod_grads:
        # bf16 error-feedback residuals for the int8 cross-pod grad mean
        state["err"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)
    return state


def abstract_state(cfg: ArchConfig, opt_cfg: adamw.OptConfig,
                   dtype=jnp.float32):
    """ShapeDtypeStructs of the full train state (no allocation)."""
    return jax.eval_shape(
        functools.partial(init_state, cfg, opt_cfg, dtype=dtype),
        jax.random.key(0))


def state_specs(state, ctx: ParallelCtx):
    p_specs = param_specs(state["params"], ctx)
    specs = {"params": p_specs,
             "opt": opt_state_specs(state["opt"], p_specs, ctx),
             "step": P()}
    if "err" in state:
        specs["err"] = p_specs       # residuals mirror the param sharding
    return specs

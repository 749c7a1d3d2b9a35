"""Mixture-of-Experts with SRQ-style capacity dispatch + escape (paper §4.1).

The token-dispatch path is the paper's small/large message design mapped to
EP: each expert owns a fixed-capacity slab buffer (the SRQ's pre-posted
WQEs); tokens are scattered into slots, all-to-all'd to their expert shard
(the READ large-message move, fixed fragment size = capacity slab), processed,
and combined.  Tokens beyond capacity take the *escape* path: they bypass the
expert (residual pass-through) and are counted — the MoE image of
"copy to memory / mark ECN".

Two implementations:
  * ``moe_dense_ref`` — all-experts-for-all-tokens oracle (tiny configs/tests)
  * ``moe_ep``        — shard_map expert parallelism over the model axis
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..parallel.sharding import ParallelCtx
from .layers import mlp_apply, mlp_init


def moe_init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    gated = cfg.mlp in ("swiglu", "geglu")
    def stack(k, shape, scale):
        return jax.random.normal(k, shape, dtype) * scale
    p = {
        "router": stack(ks[0], (d, e), d ** -0.5),
        "e_in": stack(ks[1], (e, d, f), d ** -0.5),
        "e_out": stack(ks[2], (e, f, d), f ** -0.5),
    }
    if gated:
        p["e_gate"] = stack(ks[3], (e, d, f), d ** -0.5)
    if cfg.shared_expert:
        p["shared"] = mlp_init(ks[4], d, f, cfg.mlp, dtype)
    return p


def _expert_ffn(p: dict, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    """x: [E, C, D] through per-expert stacked weights."""
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else \
            (lambda v: jax.nn.gelu(v, approximate=True))
        h = act(jnp.einsum("ecd,edf->ecf", x, p["e_gate"])) * \
            jnp.einsum("ecd,edf->ecf", x, p["e_in"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, p["e_in"]),
                        approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, p["e_out"])


def _route_top1(logits: jnp.ndarray):
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    return idx, gate, probs


def _aux_losses(probs: jnp.ndarray, idx: jnp.ndarray, e: int) -> jnp.ndarray:
    """Switch-style load-balance loss."""
    frac = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    return e * jnp.sum(frac * mean_p)


def capacity(cf: float, n_tokens: int, e: int) -> int:
    return max(1, int(cf * n_tokens / e))


# --------------------------------------------------------------------------- #
def moe_dense_ref(params: dict, x: jnp.ndarray, cfg: ArchConfig,
                  cap_factor: float) -> Tuple[jnp.ndarray, Dict]:
    """Oracle: compute every expert on every token, mask by routing+capacity.
    x: [B, T, D]."""
    b, t, d = x.shape
    e = cfg.num_experts
    xt = x.reshape(b * t, d)
    idx, gate, probs = _route_top1(xt @ params["router"])
    c = capacity(cap_factor, b * t, e)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) * onehot          # 1-based within expert
    keep = jnp.take_along_axis(rank, idx[:, None], axis=1)[:, 0] <= c
    y_all = _expert_ffn(params,
                        jnp.broadcast_to(xt, (e, b * t, d)), cfg.mlp)
    sel = jax.nn.one_hot(idx, e, dtype=y_all.dtype)     # [n, E]
    y = jnp.einsum("ne,end->nd", sel, y_all)
    y = y * (gate * keep)[:, None].astype(y.dtype)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], xt, cfg.mlp)
    aux = {"lb_loss": _aux_losses(probs, idx, e),
           "overflow": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return y.reshape(b, t, d), aux


# --------------------------------------------------------------------------- #
def _ep_body_decode(wr, w_gate, w_in, w_out, x_blk, *, cfg: ArchConfig,
                    cap_factor: float, model_axis: str, model_size: int,
                    fsdp_gather: bool):
    """Decode-path EP: too few tokens to split across model ranks, so every
    rank routes all (replicated) tokens, serves only its local experts, and
    the combine is a psum — the SRQ small-message path (no all-to-all
    latency on the decode critical path)."""
    if fsdp_gather:
        w_in = jax.lax.all_gather(w_in, "data", axis=1, tiled=True)
        w_out = jax.lax.all_gather(w_out, "data", axis=2, tiled=True)
        w_gate = jax.lax.all_gather(w_gate, "data", axis=1, tiled=True)
    b_loc, t, d = x_blk.shape
    e = cfg.num_experts
    e_loc = e // model_size
    r = jax.lax.axis_index(model_axis)
    n = b_loc * t
    xt = x_blk.reshape(n, d)
    idx, gate, probs = _route_top1(xt @ wr)
    c = capacity(cap_factor, n, e)
    local_idx = idx - r * e_loc
    is_local = (local_idx >= 0) & (local_idx < e_loc)
    order = jnp.argsort(jnp.where(is_local, local_idx, e_loc))
    se = jnp.where(is_local, local_idx, e_loc)[order]
    starts = jnp.searchsorted(se, jnp.arange(e_loc))
    rank = jnp.arange(n) - starts[jnp.minimum(se, e_loc - 1)]
    keep = (se < e_loc) & (rank < c)
    dest = jnp.where(keep, se * c + rank, e_loc * c)
    buf = jnp.zeros((e_loc * c + 1, d), xt.dtype).at[dest].set(xt[order])
    out = _expert_ffn({"e_gate": w_gate, "e_in": w_in, "e_out": w_out},
                      buf[:-1].reshape(e_loc, c, d), cfg.mlp)
    flat = jnp.concatenate([out.reshape(e_loc * c, d),
                            jnp.zeros((1, d), out.dtype)], axis=0)
    y_sorted = flat[dest] * keep[:, None].astype(out.dtype)
    y = jnp.zeros_like(xt).at[order].set(y_sorted)
    y = y * gate[:, None].astype(y.dtype)
    y = jax.lax.psum(y, model_axis)           # SRQ combine
    lb = _aux_losses(probs, idx, e)
    dropped = jax.lax.psum(jnp.sum(keep.astype(jnp.float32)), model_axis)
    overflow = 1.0 - dropped / n
    return y.reshape(b_loc, t, d), lb, overflow


def _staged_expert_ffn(w_gate, w_in, w_out, x, kind: str, data_size: int):
    """RDCA in-graph (paper §4.1.2): the expert weights' FSDP shards ride a
    ring over the ``data`` axis and the MXU consumes each fragment the hop
    it arrives — the gathered [E, D, F] weight never exists in HBM.  The
    two live ring slots are the cache-resident buffer pool; the ring depth
    is the in-flight window (1 fragment in flight per tensor).

    x: [E, C, D] tokens (full D locally); w_gate/w_in: [E, D/m, F] shards;
    w_out: [E, F, D/m] shards.  Same collective bytes as all-gather, no
    materialization, compute/comm overlapped by construction.

    VMEM sizing: a llama4 hop fragment is [8, 320, 8192] bf16 = 42 MB; on
    TPU the per-hop einsum runs through kernels/jet_staged_matmul, whose
    BlockSpec tiling sub-fragments the hop into <=256 KB VMEM tiles (the
    paper's READ fragment size) so the staging pool stays well under the
    128 MB VMEM budget with double buffering.
    """
    m = data_size
    r = jax.lax.axis_index("data")
    perm = [(i, (i + 1) % m) for i in range(m)]
    e, c, d = x.shape
    f = w_in.shape[-1]
    dk = d // m
    act = jax.nn.silu if kind == "swiglu" else \
        (lambda v: jax.nn.gelu(v, approximate=True))

    # phase A: h = act(x @ Wg) * (x @ Wi), contraction over D fragments
    def step_a(carry, i):
        hg, hi, wg, wi = carry
        src = (r - i) % m                      # owner of the held fragment
        xs = jax.lax.dynamic_slice_in_dim(x, src * dk, dk, axis=2)
        hg = hg + jnp.einsum("ecd,edf->ecf", xs, wg)
        hi = hi + jnp.einsum("ecd,edf->ecf", xs, wi)
        return (hg, hi, jax.lax.ppermute(wg, "data", perm),
                jax.lax.ppermute(wi, "data", perm)), None

    h0 = jnp.zeros((e, c, f), x.dtype)
    (hg, hi, _, _), _ = jax.lax.scan(step_a, (h0, h0, w_gate, w_in),
                                     jnp.arange(m))
    h = act(hg) * hi

    # phase B: out[:, :, D_src] = h @ Wo_src as Wo shards ride the ring
    def step_b(carry, i):
        out, wo = carry
        src = (r - i) % m
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.einsum("ecf,efd->ecd", h, wo), src * dk, axis=2)
        return (out, jax.lax.ppermute(wo, "data", perm)), None

    (out, _), _ = jax.lax.scan(step_b,
                               (jnp.zeros((e, c, d), x.dtype), w_out),
                               jnp.arange(m))
    return out


def _ep_body(wr, w_gate, w_in, w_out, x_blk, *, cfg: ArchConfig,
             cap_factor: float, model_axis: str, model_size: int,
             fsdp_gather: bool, jet_staged: bool = False):
    """Per-device body under shard_map.  x_blk: [B_loc, T, D] (replicated
    across the model axis); expert weights sharded on E."""
    if x_blk.shape[0] * x_blk.shape[1] % model_size != 0:
        return _ep_body_decode(wr, w_gate, w_in, w_out, x_blk, cfg=cfg,
                               cap_factor=cap_factor, model_axis=model_axis,
                               model_size=model_size,
                               fsdp_gather=fsdp_gather)
    staged = fsdp_gather and jet_staged
    if fsdp_gather and not staged:
        # ZeRO-3: expert weights arrive sharded on D over 'data'; gather
        # (this all-gather is the jet staged-collective hillclimb target)
        w_in = jax.lax.all_gather(w_in, "data", axis=1, tiled=True)
        w_out = jax.lax.all_gather(w_out, "data", axis=2, tiled=True)
        w_gate = jax.lax.all_gather(w_gate, "data", axis=1, tiled=True)
    b_loc, t, d = x_blk.shape
    e = cfg.num_experts
    r = jax.lax.axis_index(model_axis)
    n_all = b_loc * t
    n = n_all // model_size
    xt = x_blk.reshape(n_all, d)
    mine = jax.lax.dynamic_slice_in_dim(xt, r * n, n, 0)

    idx, gate, probs = _route_top1(mine @ wr)
    c = capacity(cap_factor, n, e)
    order = jnp.argsort(idx)
    se = idx[order]                                  # sorted expert ids
    starts = jnp.searchsorted(se, jnp.arange(e))     # first pos per expert
    rank = jnp.arange(n) - starts[se]
    keep = rank < c
    dest = jnp.where(keep, se * c + rank, e * c)     # overflow -> trash slot
    buf = jnp.zeros((e * c + 1, d), xt.dtype).at[dest].set(mine[order])
    buf = buf[:-1].reshape(e, c, d)

    # ---- large-message path: all-to-all to expert shards ----------------- #
    recv = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                              concat_axis=1, tiled=True)   # [E_loc, m*C, D]
    if staged:
        # data-axis size from the shard shape: w_in is [E_loc, D/m, F]
        out = _staged_expert_ffn(w_gate, w_in, w_out, recv, cfg.mlp,
                                 data_size=d // w_in.shape[1])
    else:
        out = _expert_ffn({"e_gate": w_gate, "e_in": w_in, "e_out": w_out},
                          recv, cfg.mlp)
    back = jax.lax.all_to_all(out, model_axis, split_axis=1,
                              concat_axis=0, tiled=True)   # [E, C, D]
    flat = jnp.concatenate([back.reshape(e * c, d),
                            jnp.zeros((1, d), back.dtype)], axis=0)
    y_sorted = flat[dest] * (keep[:, None].astype(back.dtype))
    y_mine = jnp.zeros_like(mine).at[order].set(y_sorted)
    y_mine = y_mine * gate[:, None].astype(y_mine.dtype)

    # ---- small-message path: combine across model ranks (SRQ) ------------ #
    y_all = jax.lax.all_gather(y_mine, model_axis, axis=0, tiled=True)
    lb = jax.lax.pmean(_aux_losses(probs, idx, e), model_axis)
    overflow = jax.lax.pmean(1.0 - jnp.mean(keep.astype(jnp.float32)),
                             model_axis)
    return y_all.reshape(b_loc, t, d), lb, overflow


def moe_ep(params: dict, x: jnp.ndarray, cfg: ArchConfig,
           ctx: ParallelCtx) -> Tuple[jnp.ndarray, Dict]:
    """shard_map expert-parallel MoE. x: [B, T, D]."""
    cf = ctx.moe_capacity_factor or cfg.capacity_factor
    mesh = ctx.mesh
    ax = ctx.model_axis
    assert "e_gate" in params, "EP path expects gated experts (llama4)"
    fsdp_gather = (ctx.fsdp and "data" in mesh.axis_names and
                   params["e_in"].shape[1] % mesh.shape["data"] == 0)
    wspec_in = P(ax, "data" if fsdp_gather else None, None)
    wspec_out = P(ax, None, "data" if fsdp_gather else None)
    xspec = P(ctx.batch_axes_for(x.shape[0]) or None, None, None)

    body = functools.partial(
        _ep_body, cfg=cfg, cap_factor=cf, model_axis=ax,
        model_size=mesh.shape[ax], fsdp_gather=fsdp_gather,
        jet_staged=ctx.jet_collectives)
    # when already inside a manual region (e.g. the compressed-pod-grads
    # shard_map), nested shard_map must target the context's abstract mesh
    try:
        cur = jax.sharding.get_abstract_mesh()
        if cur.shape_tuple and any(
                t == jax.sharding.AxisType.Manual for t in cur.axis_types):
            mesh = cur
    except Exception:  # noqa: BLE001 — fall back to the concrete mesh
        pass
    y, lb, overflow = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), wspec_in, wspec_in, wspec_out, xspec),
        out_specs=(xspec, P(), P()),
        check_vma=False,
    )(params["router"], params["e_gate"], params["e_in"],
      params["e_out"], x)
    if "shared" in params:
        b, t, d = x.shape
        y = y + mlp_apply(params["shared"], x.reshape(b * t, d),
                          cfg.mlp).reshape(b, t, d)
    return y, {"lb_loss": lb, "overflow": overflow}


def moe_apply(params: dict, x: jnp.ndarray, cfg: ArchConfig,
              ctx: ParallelCtx) -> Tuple[jnp.ndarray, Dict]:
    cf = ctx.moe_capacity_factor or cfg.capacity_factor
    if ctx.have_mesh and ctx.use_ep:
        return moe_ep(params, x, cfg, ctx)
    return moe_dense_ref(params, x, cfg, cf)

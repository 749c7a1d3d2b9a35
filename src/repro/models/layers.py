"""Shared neural layers: norms, RoPE (full / partial "2d"), MLP variants."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * (1.0 + scale)).astype(x.dtype)


def init_rms(d: int, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.zeros((d,), dtype)


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #
def rope_freqs(hd: int, fraction: float, theta: float) -> jnp.ndarray:
    """Inverse frequencies for the rotary fraction of the head dim."""
    rot = int(hd * fraction) // 2 * 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, hd: int,
               fraction: float = 1.0,
               theta: float = 10_000.0) -> jnp.ndarray:
    """x: [..., T, H, hd]; positions: broadcastable to [..., T].

    ``fraction < 1`` applies rotary to the leading ``fraction*hd`` dims and
    passes the rest through (ChatGLM's 2d/partial rotary)."""
    rot = int(hd * fraction) // 2 * 2
    if rot == 0:
        return x
    inv = rope_freqs(hd, fraction, theta)                      # [rot/2]
    ang = positions[..., None].astype(jnp.float32) * inv        # [...,T,rot/2]
    cos = jnp.cos(ang)[..., None, :]                            # [...,T,1,r/2]
    sin = jnp.sin(ang)[..., None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2].astype(jnp.float32), \
        xr[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if rot < hd else out


# --------------------------------------------------------------------------- #
# MLP
# --------------------------------------------------------------------------- #
def mlp_apply(params: dict, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else \
            (lambda v: jax.nn.gelu(v, approximate=True))
        h = act(x @ params["w_gate"]) * (x @ params["w_in"])
        return h @ params["w_out"]
    # plain gelu
    return jax.nn.gelu(x @ params["w_in"], approximate=True) @ params["w_out"]


def mlp_init(key, d: int, f: int, kind: str, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d ** -0.5
    s_out = f ** -0.5
    p = {"w_in": jax.random.normal(k1, (d, f), dtype) * s_in,
         "w_out": jax.random.normal(k2, (f, d), dtype) * s_out}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = jax.random.normal(k3, (d, f), dtype) * s_in
    return p


def embed_init(key, v: int, d: int, dtype=jnp.float32) -> jnp.ndarray:
    return jax.random.normal(key, (v, d), dtype) * (d ** -0.5)


def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray,
                  z_loss: float = 1e-4) -> jnp.ndarray:
    """Mean next-token CE with optional z-loss; logits [..., V] fp32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # select-and-sum rather than a gather: with vocab-sharded logits
    # inside a partial-manual shard_map (compressed cross-pod grads)
    # XLA's SPMD partitioner aborts on the gather; the masked sum
    # partitions as a local reduce + all-reduce and is exact (one
    # nonzero term per row)
    hit = jnp.arange(logits.shape[-1]) == targets[..., None]
    ll = jnp.where(hit, logits, 0.0).sum(-1)
    loss = jnp.mean(lse - ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(lse ** 2)
    return loss

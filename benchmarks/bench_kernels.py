"""Pallas kernel microbench: correctness (vs ref oracle) + structural
roofline terms per kernel.

The correctness check runs each kernel compiled (``impl="pallas"``) when
jax's backend is a TPU and under the Pallas interpreter elsewhere; the
``impl`` column records which ran.  Wall-clock off the chip is meaningless
for TPU kernels, so alongside the allclose check we report each kernel's
*arithmetic intensity*
(FLOPs / HBM bytes) at production shapes and its implied roofline bound on a
v5e chip (197 TFLOP/s bf16, 819 GB/s HBM) — the number the BlockSpec tiling
is designed against.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import emit

NAME = "kernels"
PAPER_REF = "kernel tier (DESIGN.md §2)"

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def _ai_row(name: str, flops: float, bytes_: float) -> Dict:
    ai = flops / bytes_
    knee = PEAK_FLOPS / HBM_BW           # FLOP/byte at the roofline ridge
    bound = "compute" if ai > knee else "memory"
    t_c = flops / PEAK_FLOPS
    t_m = bytes_ / HBM_BW
    return {"kernel": name, "gflops": flops / 1e9,
            "mbytes": bytes_ / 1e6, "arith_intensity": ai,
            "ridge": knee, "bound": bound,
            "roofline_us": max(t_c, t_m) * 1e6,
            "mxu_frac": t_c / max(t_c, t_m)}


def intensity() -> List[Dict]:
    rows = []
    # staged matmul at a production FFN tile: [4096 x 5120] @ [5120 x 8192]
    m, k, n = 4096, 5120, 8192
    fl = 2.0 * m * k * n
    by = 2.0 * (m * k + k * n + m * n)
    rows.append(_ai_row("jet_staged_matmul(ffn tile)", fl, by))
    # flash attention: B=1 H=40 T=4096 hd=128
    b, h, t, hd = 1, 40, 4096, 128
    fl = 4.0 * b * h * t * t * hd * 0.5          # causal half
    by = 2.0 * (3 * b * h * t * hd + b * h * t * hd)
    rows.append(_ai_row("jet_flash_attention(train 4k)", fl, by))
    # decode attention: one token against 32k KV, B=128
    b, t = 128, 32_768
    h, hd, hkv = 40, 128, 8
    fl = 4.0 * b * h * t * hd
    by = 2.0 * (2 * b * t * hkv * hd)            # stream K,V once
    rows.append(_ai_row("jet_decode_attention(32k)", fl, by))
    # mamba2 SSD chunk: B=1 T=4096 d_in=4096 N=64, chunk 256
    b, t, d, n = 1, 4096, 4096, 64
    fl = 6.0 * b * t * d * n
    by = 2.0 * (2 * b * t * d + 2 * b * t * n)
    rows.append(_ai_row("mamba2_ssd(4k)", fl, by))
    return rows


def correctness() -> List[Dict]:
    rows = []
    key = jax.random.key(0)
    impl = "pallas" if jax.default_backend() == "tpu" else "interpret"

    def timed(fn, *a):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        return out, (time.perf_counter() - t0) * 1e3

    # staged matmul
    a = jax.random.normal(key, (256, 512), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (512, 256), jnp.float32)
    got, ms_k = timed(lambda x, y: ops.staged_matmul(x, y, impl=impl), a, b)
    want, ms_r = timed(lambda x, y: ops.staged_matmul(x, y, impl="ref"),
                       a, b)
    err = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    rows.append({"kernel": "staged_matmul", "shape": "256x512x256",
                 "impl": impl, "kernel_ms": ms_k, "ref_ms": ms_r,
                 "max_err": err,
                 "ok": int(err < 1e-3)})

    # flash attention
    q = jax.random.normal(key, (1, 2, 256, 64), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (1, 2, 256, 64), jnp.float32)
    got, ms_k = timed(lambda *t: ops.flash_attention(*t, impl=impl),
                      q, k, v)
    want, ms_r = timed(lambda *t: ops.flash_attention(*t, impl="ref"),
                       q, k, v)
    err = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    rows.append({"kernel": "flash_attention", "shape": "1x2x256x64",
                 "impl": impl, "kernel_ms": ms_k, "ref_ms": ms_r,
                 "max_err": err,
                 "ok": int(err < 2e-3)})

    # ssd scan
    bsz, t, h, p, n = 1, 512, 4, 32, 16
    x = jax.random.normal(key, (bsz, t, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(4), (bsz, t, h)))
    a_ = -jnp.exp(jax.random.normal(jax.random.key(5), (h,)))
    b_ = jax.random.normal(jax.random.key(6), (bsz, t, 1, n))
    c_ = jax.random.normal(jax.random.key(7), (bsz, t, 1, n))
    (got, _), ms_k = timed(lambda *ts: ops.ssd(*ts, chunk=128,
                                               impl=impl),
                           x, dt, a_, b_, c_)
    (want, _), ms_r = timed(lambda *ts: ops.ssd(*ts, chunk=128, impl="ref"),
                            x, dt, a_, b_, c_)
    err = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    rows.append({"kernel": "mamba2_ssd", "shape": f"{bsz}x{t}x{h}x{p}",
                 "impl": impl, "kernel_ms": ms_k, "ref_ms": ms_r,
                 "max_err": err,
                 "ok": int(err < 2e-2)})
    return rows


def main() -> None:
    rows = correctness()
    emit(NAME + "_correctness", rows)
    assert all(r["ok"] for r in rows), "kernel mismatch vs oracle"
    emit(NAME + "_intensity", intensity())


if __name__ == "__main__":
    main()

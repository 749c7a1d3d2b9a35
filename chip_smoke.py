#!/usr/bin/env python3
"""Smoke run of the repo's main path on TPU chips, checked against references.

  python3 chip_smoke.py             # phases 1-4 on one chip
  python3 chip_smoke.py --chips 4   # phase 5 only, over four chips

Phases (each prints its result lines and its wall time):

1. Device check: the platform must be ``tpu``; there is no CPU fallback.
2. Pod-scale fabric: the 256-host cross-pod incast grid (4 points, 192
   senders each, 4,000 ticks) through ``run_fabric_sweep`` on the sparse
   engine with the Pallas water-fill stages, against the float64 numpy
   reference.
3. Paper-testbed fabric through the farm: the 64-point 2-tier ``incast``
   grid as 4 chunks of 16, bit-identical to one monolithic program, no
   recompiles after the first chunk, and 4 points against numpy.
4. Serving at published widths: ``h2o-danube-1.8b`` (bf16, random weights
   from a seed) answers 8 requests of 512 prompt tokens, 32 new tokens
   each, through the Jet-admitted ``ServingEngine``; the Pallas flash
   kernel is checked against the reference at the prefill shapes.
5. ``--chips 4``: the farm's device round-robin over four chips (8 points
   of the pod grid in 4 chunks of 2) must use 4 distinct devices and be
   bit-identical to the monolithic run on device 0.

A failed check raises, so the script exits non-zero.  The last line of
standard output is one JSON object naming the device, printed only when
every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the jax engine's equivalence bound against the float64 numpy reference
# (tests/test_topology_pods.py, TestPodEquivalence)
FABRIC_REL_BOUND = 5e-4
# bf16 flash attention vs the reference tier (tests/test_kernels.py)
BF16_TOL = 2e-2

POD = dict(pods=4, leaves_per_pod=4, hosts_per_leaf=16, sim_time_s=0.004)


def _require(ok, what) -> None:
    """A failed check raises (``assert`` would vanish under ``-O``)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _maxrel(a, b) -> float:
    """Max relative deviation of ``a`` from ``b``; the finite/inf pattern
    must agree."""
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    _require((np.isfinite(a) == np.isfinite(b)).all(),
             "finite/inf pattern mismatch")
    m = np.isfinite(b)
    if not m.any():
        return 0.0
    return float(np.max(np.abs(a[m] - b[m])
                        / np.maximum(np.abs(b[m]), 1e-9)))


def _check_close(label: str, got: dict, ref: dict, keys) -> None:
    for k in keys:
        dev = _maxrel(got[k], ref[k])
        print(f"  {label} {k}: max rel dev {dev!r} "
              f"(bound {FABRIC_REL_BOUND})")
        _require(dev <= FABRIC_REL_BOUND, (label, k, dev))


def _require_identical(a: dict, b: dict, label: str) -> None:
    import numpy as np
    _require(set(a) == set(b), label)
    for k in a:
        _require(np.array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                equal_nan=True), f"{label}: {k} differs")
    print(f"  {label}: bit-identical over {len(a)} metrics")


def _kernel_in_program(jitted, *args) -> bool:
    """Does the compiled program of ``jitted(*args)`` hold a Pallas TPU
    kernel?  (The compile is found in the in-process or disk cache.)"""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def _timed(name: str, fn):
    print(f"== {name}", flush=True)
    t0 = time.perf_counter()
    out = fn()
    print(f"== {name}: ok, wall {time.perf_counter() - t0!r} s",
          flush=True)
    return out


# --------------------------------------------------------------------------- #
def phase_device(want_chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"  platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}")
    if d0.platform != "tpu":
        raise SystemExit(f"no TPU: jax found {d0.platform!r} devices")
    if len(devs) < want_chips:
        raise SystemExit(f"need {want_chips} chips, jax found {len(devs)}")
    from repro.fabric._scan import configure_persistent_cache
    print(f"  compile cache: {configure_persistent_cache()}")
    return d0, len(devs)


def phase_pod_fabric() -> None:
    import numpy as np
    from repro.fabric import fused
    from repro.fabric import scenarios as SC
    from repro.fabric import vector as V

    _require(fused.resolve_impl("auto") == "pallas",
             f"fused impl {fused.resolve_impl('auto')!r} on the chip")
    scens, _ = SC.pod_incast_grid(mode=("jet", "ddio"), pfc=(False, True),
                                  burst_mb=0.2, **POD)
    t0 = time.perf_counter()
    jx = V.run_fabric_sweep(scens, backend="jax")
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    jx = V.run_fabric_sweep(scens, backend="jax")
    t_warm = time.perf_counter() - t0
    fsp = V.FabricSweepParams.from_scenarios(scens, sparse=True)
    print(f"  grid: {fsp.n_points} points, {fsp.n_flows} flows, "
          f"{fsp.n_ports} ports, {fsp.ticks} ticks, sparse engine")
    print(f"  jax: first call {t_cold!r} s (with compile), "
          f"second {t_warm!r} s")
    _require(_kernel_in_program(V._jax_program(fsp, 1, "pallas"),
                                *V.packed_params(fsp)),
             "Pallas stages in the compiled fabric program")
    print("  compiled program holds tpu_custom_call (Pallas stages)")
    t0 = time.perf_counter()
    ref = V.run_fabric_sweep(scens, backend="numpy")
    print(f"  numpy float64 reference: {time.perf_counter() - t0!r} s")
    # with PFC the 192-sender incast completes inside the window; without
    # it the switch drops and go-back-N recovery leaves it unfinished at
    # 4 ms in both engines (the finite pattern must agree; _maxrel checks)
    pfc = np.array([sc.fabric.switch.pfc_enabled for sc in scens])
    for name, out in (("jax", jx), ("numpy", ref)):
        inc = out["incast_completion_us"]
        print(f"  {name} incast completion us: {inc.tolist()} "
              f"(pfc {pfc.tolist()})")
        _require(np.isfinite(inc[pfc]).all(), (name, inc))
    _check_close("jax vs numpy", jx, ref,
                 ("flow_delivered_bytes", "incast_completion_us"))


def phase_farm_incast() -> None:
    import numpy as np
    from repro.fabric import vector as V
    from repro.fabric.farm import run_farm
    from repro.fabric.scenarios import build_grid

    scens, _ = build_grid("incast")
    _require(len(scens) == 64, len(scens))
    c0 = V.PROGRAM_COMPILES
    t0 = time.perf_counter()
    farm = run_farm("incast", workers=0, chunk_size=16, artifacts=False)
    print(f"  farm: {time.perf_counter() - t0!r} s, "
          f"{V.PROGRAM_COMPILES - c0} program compiles")
    recs = farm["manifest"]["records"]
    for r in recs:
        print(f"  chunk {r['chunk']}: points [{r['start']}, {r['stop']}) "
              f"wall {r['wall_s']!r} s compiles {r['compiles']} "
              f"device {r['device']}")
    _require(len(recs) == 4, len(recs))
    _require(all(r["compiles"] == 0 for r in recs[1:]),
             "no recompiles after the farm's first chunk")
    t0 = time.perf_counter()
    mono = V.run_fabric_sweep(scens, backend="jax")
    print(f"  monolithic: {time.perf_counter() - t0!r} s")
    _require_identical(mono, farm["results"], "farm vs monolithic")
    pick = [0, 21, 42, 63]
    ref = V.run_fabric_sweep([scens[i] for i in pick], backend="numpy")
    got = {k: np.asarray(v)[pick] for k, v in farm["results"].items()}
    _check_close(f"points {pick} vs numpy", got, ref,
                 ("flow_delivered_bytes", "incast_completion_us"))


def phase_serving() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.launch.serve import serve

    cfg = get_arch("h2o-danube-1.8b")
    print(f"  {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads} x {cfg.hd}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"window {cfg.sliding_window}")
    n_req, prompt_len, max_new = 8, 512, 32
    out = serve(cfg, requests=n_req, prompt_len=prompt_len,
                max_new=max_new, lanes=4, max_len=1024, seed=0,
                eos_token=-1)
    eng = out["engine"]
    n_params = sum(x.size for x in jax.tree.leaves(eng.params))
    print(f"  {n_params} bf16 parameters")
    print(f"  served {len(eng.done)}/{n_req} requests, {out['tokens']} "
          f"tokens in {out['wall_s']!r} s "
          f"({out['tokens'] / out['wall_s']!r} tok/s incl. compile; "
          f"information only)")
    _require(sorted(eng.done) == list(range(n_req)), sorted(eng.done))
    for rid, req in sorted(eng.done.items()):
        toks = np.asarray(req.generated)
        _require(toks.shape == (max_new,), (rid, toks.shape))
        _require(((toks >= 0) & (toks < cfg.vocab_size)).all(), rid)
    print(f"  request 0 tokens: {eng.done[0].generated}")
    # finite logits from the engine's own compiled prefill and decode
    prompt = jnp.asarray(eng.done[0].prompt)[None, :]
    logits, _, _ = eng._prefill(eng.params, prompt)
    _require(logits.shape == (1, cfg.vocab_size), logits.shape)
    _require(bool(jnp.isfinite(logits).all()), "finite prefill logits")
    dlogits, _ = eng._decode(eng.params, eng.state, eng.tokens,
                             eng.lengths)
    _require(dlogits.shape == (4, cfg.vocab_size), dlogits.shape)
    _require(bool(jnp.isfinite(dlogits).all()), "finite decode logits")
    print("  prefill and decode logits finite")
    _require(_kernel_in_program(eng._prefill, eng.params, prompt),
             "Pallas flash kernel in the compiled prefill")
    print("  compiled prefill holds tpu_custom_call (flash kernel)")

    # the Pallas flash kernel against the reference at prefill shapes
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, cfg.num_heads, prompt_len, cfg.hd),
                          jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, cfg.num_kv_heads, prompt_len, cfg.hd),
                          jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, cfg.num_kv_heads, prompt_len, cfg.hd),
                          jnp.bfloat16)

    def attn(impl):
        return jax.jit(lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, window=cfg.sliding_window, impl=impl))
    _require(_kernel_in_program(attn("pallas"), q, k, v),
             "Pallas flash kernel compiled")
    got = np.asarray(attn("pallas")(q, k, v), np.float32)
    want = np.asarray(attn("ref")(q, k, v), np.float32)
    err = float(np.abs(got - want).max())
    print(f"  flash pallas vs ref: max abs err {err!r} "
          f"(tolerance {BF16_TOL} + {BF16_TOL} x |ref|)")
    np.testing.assert_allclose(got, want, rtol=BF16_TOL, atol=BF16_TOL)


def phase_farm_devices(n_chips: int) -> None:
    import jax
    from repro.fabric import scenarios as SC
    from repro.fabric import vector as V
    from repro.fabric.farm import run_farm

    scens, _ = SC.fabric_grid(
        lambda mode, pfc, burst_mb: SC.pod_incast(
            mode=mode, pfc=pfc, burst_mb=burst_mb, **POD),
        mode=["jet", "ddio"], pfc=[False, True], burst_mb=[0.2, 1.0])
    _require(len(scens) == 8, len(scens))
    t0 = time.perf_counter()
    mono = V.run_fabric_sweep(scens, backend="jax")
    print(f"  monolithic on {jax.devices()[0]}: "
          f"{time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    farm = run_farm(scens, workers=0, chunk_size=2, artifacts=False)
    print(f"  farm: {time.perf_counter() - t0!r} s")
    recs = farm["manifest"]["records"]
    for r in recs:
        print(f"  chunk {r['chunk']}: points [{r['start']}, {r['stop']}) "
              f"wall {r['wall_s']!r} s compiles {r['compiles']} "
              f"device {r['device']}")
    devices = {r["device"] for r in recs}
    _require(len(recs) == 4 and len(devices) == n_chips, devices)
    _require_identical(mono, farm["results"], "farm vs monolithic")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the farm's four-chip round-robin")
    args = ap.parse_args(argv)

    d0, count = _timed("phase 1: device check",
                       lambda: phase_device(args.chips))
    if args.chips == 4:
        _timed("phase 5: farm round-robin over 4 chips",
               lambda: phase_farm_devices(4))
    else:
        _timed("phase 2: pod-scale fabric, sparse engine, Pallas stages",
               phase_pod_fabric)
        _timed("phase 3: 64-point incast grid through the farm",
               phase_farm_incast)
        _timed("phase 4: h2o-danube-1.8b serving at published widths",
               phase_serving)
    print(json.dumps({"ok": True,
                      "device": {"platform": d0.platform,
                                 "kind": d0.device_kind,
                                 "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Serving launcher: batched requests through the Jet-admitted engine.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-7b --tiny \
      --requests 12 --prompt-len 24 --max-new 16

:func:`serve` is the same path as a callable (``chip_smoke.py`` runs it at
published widths).  Weights and compute are bf16, the model API's default.
"""
from __future__ import annotations

import argparse
import time


def serve(cfg, *, requests: int = 8, prompt_len: int = 16,
          max_new: int = 12, lanes: int = 4, max_len: int = 128,
          seed: int = 0, eos_token: int = 1) -> dict:
    """Serve ``requests`` random prompts (``np.random.default_rng(seed)``)
    with random bf16 weights (``jax.random.key(seed)``) through a
    :class:`~repro.serving.engine.ServingEngine`.  ``eos_token=-1`` turns
    early stopping off, so every request generates ``max_new`` tokens.

    Returns ``{"engine", "wall_s", "tokens"}``; ``wall_s`` runs from the
    first submission to the last completion and includes compilation.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import api as model_api
    from ..parallel.sharding import single_device_ctx
    from ..serving.engine import EngineConfig, Request, ServingEngine

    ctx = single_device_ctx(moe_capacity_factor=2.0)
    params = model_api.init_params(cfg, jax.random.key(seed), jnp.bfloat16)
    engine = ServingEngine(cfg, EngineConfig(max_lanes=lanes,
                                             max_len=max_len,
                                             eos_token=eos_token),
                           params, ctx, compute_dtype=jnp.bfloat16)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    for i in range(requests):
        prompt = rng.integers(2, cfg.vocab_size,
                              size=prompt_len).astype(np.int32)
        engine.submit(Request(i, prompt, max_new))
    engine.run_until_done(max_ticks=requests * (max_new + 4))
    wall = time.perf_counter() - t0
    tokens = sum(len(r.generated) for r in engine.done.values())
    return {"engine": engine, "wall_s": wall, "tokens": tokens}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    args = ap.parse_args()

    from ..configs import get_arch, tiny_config
    from ..fabric._scan import configure_persistent_cache

    configure_persistent_cache()
    cfg = get_arch(args.arch)
    if args.tiny:
        cfg = tiny_config(cfg)
    out = serve(cfg, requests=args.requests, prompt_len=args.prompt_len,
                max_new=args.max_new, lanes=args.lanes,
                max_len=args.max_len)
    engine, dt = out["engine"], out["wall_s"]
    print(f"served {len(engine.done)}/{args.requests} requests, "
          f"{out['tokens']} tokens in {dt:.1f}s "
          f"({out['tokens']/max(dt,1e-9):.1f} tok/s)")
    print("jet:", engine.jet.stats())


if __name__ == "__main__":
    main()

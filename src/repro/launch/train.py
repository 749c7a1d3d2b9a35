"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
      --steps 50 --batch 8 --seq 512 [--mesh 1x1|2x4|single] [--tiny]

``--mesh single`` targets the production 16x16 mesh (requires 256 devices —
use the dry-run on CPU).  On CPU the default is a 1x1 mesh with the reduced
config unless ``--full`` is given.
"""
from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM host mesh (e.g. 2x4) or 'single'/'multi'")
    ap.add_argument("--tiny", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--int8-moments", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "layer_out", "none"])
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host devices (set before jax init)")
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.host_devices}")

    import jax

    from ..configs import get_arch, tiny_config
    from ..data import pipeline
    from ..fabric._scan import configure_persistent_cache
    from ..configs.base import ShapeConfig
    from ..optim import adamw
    from ..parallel.sharding import single_device_ctx
    from ..train import loop as loop_mod
    from .mesh import ctx_for_mesh, make_mesh, make_production_mesh

    configure_persistent_cache()
    cfg = get_arch(args.arch)
    if args.tiny:
        cfg = tiny_config(cfg)

    if args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        ctx = ctx_for_mesh(mesh, remat=args.remat)
    elif args.mesh == "1x1":
        mesh = None
        ctx = single_device_ctx(remat=args.remat, moe_capacity_factor=2.0)
    else:
        d, m = (int(v) for v in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
        ctx = ctx_for_mesh(mesh, remat=args.remat)

    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    data = pipeline.for_arch(cfg, shape)
    opt_cfg = adamw.OptConfig(lr=args.lr, int8_moments=args.int8_moments,
                              total_steps=args.steps)
    loop_cfg = loop_mod.LoopConfig(total_steps=args.steps,
                                   ckpt_every=args.ckpt_every,
                                   ckpt_dir=args.ckpt_dir)

    def run():
        out = loop_mod.run(cfg, ctx, opt_cfg, loop_cfg, data,
                           jax.random.key(0), accum_steps=args.accum)
        for h in out["history"]:
            print(f"step {h['step']:5d} loss {h['loss']:.4f} "
                  f"dt {h['dt']*1e3:.0f}ms"
                  + (" [straggler]" if h["straggler"] else ""))
        print(f"final step {out['final_step']}, "
              f"straggler flags: {out['straggler_flags']}")

    if mesh is not None:
        with mesh:
            run()
    else:
        run()


if __name__ == "__main__":
    main()

"""Elastic scaling + crash recovery, end to end.

  PYTHONPATH=src python examples/elastic_restart.py

Trains on an 8-device (4x2) host mesh, "loses half the fleet" (simulated
preemption mid-run), restores the checkpoint onto a 4-device (2x2) mesh
with different shardings, finishes training there, and verifies the loss
trajectory continued — the elastic-rescale path a 1000-node deployment
needs when a pod drops out.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import shutil                                          # noqa: E402

import jax                                             # noqa: E402
import numpy as np                                     # noqa: E402

from repro.configs import ARCHS, ShapeConfig, tiny_config  # noqa: E402
from repro.data import pipeline                        # noqa: E402
from repro.launch.mesh import ctx_for_mesh, make_mesh  # noqa: E402
from repro.optim import adamw                          # noqa: E402
from repro.train import loop as loop_mod               # noqa: E402

CKPT = "/tmp/repro_elastic"


def main() -> None:
    shutil.rmtree(CKPT, ignore_errors=True)
    cfg = tiny_config(ARCHS["gemma-7b"])
    shape = ShapeConfig("e", "train", 64, 8)
    opt_cfg = adamw.OptConfig(lr=3e-3, total_steps=60)

    # ---- phase 1: 8 devices (4 data x 2 model), preempt at step 25 ----
    devs = jax.devices()
    mesh8 = make_mesh((4, 2), ("data", "model"), devices=devs[:8])
    ctx8 = ctx_for_mesh(mesh8)

    def preempt(step):
        if step == 25:
            raise KeyboardInterrupt("simulated pod loss")

    print("phase 1: training on 8 devices (4x2)")
    try:
        with mesh8:
            loop_mod.run(cfg, ctx8, opt_cfg,
                         loop_mod.LoopConfig(total_steps=60, ckpt_every=10,
                                             ckpt_dir=CKPT, log_every=10),
                         pipeline.for_arch(cfg, shape), jax.random.key(0),
                         fault_injector=preempt)
    except KeyboardInterrupt:
        print(">>> preempted at step 25; checkpoint committed")

    # ---- phase 2: resume on 4 devices (2x2) — half the fleet ----------
    mesh4 = make_mesh((2, 2), ("data", "model"), devices=devs[:4])
    ctx4 = ctx_for_mesh(mesh4)
    print("phase 2: resuming on 4 devices (2x2)")
    with mesh4:
        out = loop_mod.run(cfg, ctx4, opt_cfg,
                           loop_mod.LoopConfig(total_steps=60,
                                               ckpt_every=20,
                                               ckpt_dir=CKPT,
                                               log_every=10),
                           pipeline.for_arch(cfg, shape),
                           jax.random.key(0))
    for h in out["history"]:
        print(f"  step {h['step']:3d} loss {h['loss']:.4f}")
    assert out["final_step"] == 60
    losses = [h["loss"] for h in out["history"]]
    print(f"resumed at step >25 and finished at 60; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0], "loss did not keep improving"
    print("elastic restart OK: 8 -> 4 devices, training continued")


if __name__ == "__main__":
    main()

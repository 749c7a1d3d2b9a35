import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512")
                           ).strip()
"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against ShapeDtypeStruct inputs on 512 placeholder host devices.

Proves: the sharding config is coherent (no mismatch), the program fits
(memory analysis), and yields the HLO FLOP/byte/collective numbers the
roofline analysis (benchmarks/roofline.py) consumes.

Usage:
  python -m repro.launch.dryrun --arch llama4-scout-17b-a16e --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--force]
Outputs one JSON per cell under experiments/dryrun/.
"""
import argparse
import functools
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs import ARCHS, SHAPES, eligible, get_arch, get_shape
from ..models import api as model_api
from ..optim import adamw
from ..parallel.sharding import ParallelCtx
from ..train import steps as steps_mod
from . import hlo_analysis
from .mesh import ctx_for_mesh, make_mesh, make_production_mesh

_DTSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
           "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
           "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"(\(?[a-z0-9]+\[[0-9,]*\][^)]*\)?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DTSIZE:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTSIZE[dt]
    return total


def collective_bytes(hlo: str) -> dict:
    """Per-device ICI bytes by collective type, ring-algorithm accounting."""
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts = dict.fromkeys(out, 0)
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done(" in line:
            continue
        shapes_str, op = m.group(1), m.group(2)
        size = _shape_bytes(shapes_str)
        g = _GROUPS_RE.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            g2 = _GROUPS2_RE.search(line)
            n = int(g2.group(2)) if g2 else 2
        n = max(n, 2)
        if op == "all-gather":
            # result holds the gathered tensor; each device receives
            # (n-1)/n of it over the ring
            b = size * (n - 1) / n
        elif op == "all-reduce":
            b = 2.0 * size * (n - 1) / n
        elif op == "reduce-scatter":
            b = size * (n - 1)   # result is the scattered shard; ring moves
            #                      (n-1)/n of the n-x-larger input
        elif op == "all-to-all":
            b = size * (n - 1) / n
        else:  # collective-permute
            b = size
        out[op] += b
        counts[op] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


def sharded_arg_bytes(tree, specs, mesh) -> int:
    """Per-device bytes of inputs given their PartitionSpecs."""
    total = 0
    flat_t = jax.tree.leaves(tree)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(flat_t, flat_s):
        shards = 1
        for axes in spec:
            if axes is None:
                continue
            for ax in (axes if isinstance(axes, tuple) else (axes,)):
                shards *= mesh.shape[ax]
        total += leaf.size * leaf.dtype.itemsize // max(1, shards)
    return total


# --------------------------------------------------------------------------- #
def build_cell(arch_name: str, shape_name: str, mesh, variant: dict):
    """Returns (fn, args, in_shardings, arg_specs) ready to lower."""
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    ctx = ctx_for_mesh(
        mesh,
        remat=variant.get("remat", "full"),
        fsdp=variant.get("fsdp", True),
        use_ep=variant.get("use_ep", True),
        seq_parallel_decode=variant.get("seq_parallel_decode", True),
        bf16_weight_gather=variant.get("bf16_weight_gather", False),
        jet_collectives=variant.get("jet_collectives", False),
        jet_window=variant.get("jet_window", 4),
    )
    big = cfg.param_counts()[0] > 50e9
    opt_cfg = adamw.OptConfig(
        int8_moments=variant.get("int8_moments", big),
        compressed_pod_grads=variant.get("compressed_pod_grads", False))
    compute_dtype = jnp.bfloat16

    inputs = model_api.input_specs(cfg, shape, compute_dtype)
    accum = int(variant.get("accum", 1))
    if shape.kind == "train":
        state = steps_mod.abstract_state(cfg, opt_cfg)
        state_specs = steps_mod.state_specs(state, ctx)
        if accum > 1:
            # microbatched layout: [A, B/A, ...] — accum dim unsharded,
            # micro batch dim data-sharded (see steps.make_train_step)
            inputs = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (accum, s.shape[0] // accum) + s.shape[1:], s.dtype),
                inputs)
            micro_specs = steps_mod.batch_specs(
                jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                    s.shape[1:], s.dtype), inputs), ctx)
            batch_specs = jax.tree.map(
                lambda sp: P(None, *tuple(sp)),
                micro_specs, is_leaf=lambda x: isinstance(x, P))
        else:
            batch_specs = steps_mod.batch_specs(inputs, ctx)
        fn = steps_mod.make_train_step(cfg, ctx, opt_cfg, compute_dtype,
                                       accum_steps=accum)
        args = (state, inputs)
        shardings = (jax.tree.map(ctx.sharding, state_specs,
                                  is_leaf=lambda x: isinstance(x, P)),
                     jax.tree.map(ctx.sharding, batch_specs,
                                  is_leaf=lambda x: isinstance(x, P)))
        specs = (state_specs, batch_specs)
        donate = (0,)
    elif shape.kind == "prefill":
        params = model_api.abstract_params(
            cfg, jnp.bfloat16 if variant.get("serve_bf16") else jnp.float32)
        p_specs = steps_mod.param_specs(params, ctx)
        i_specs = steps_mod.batch_specs(inputs, ctx)

        def fn(params, batch):
            return model_api.prefill(params, cfg, ctx, batch["tokens"],
                                     batch.get("patches"),
                                     max_len=shape.seq_len,
                                     compute_dtype=compute_dtype)
        args = (params, inputs)
        shardings = (jax.tree.map(ctx.sharding, p_specs,
                                  is_leaf=lambda x: isinstance(x, P)),
                     jax.tree.map(ctx.sharding, i_specs,
                                  is_leaf=lambda x: isinstance(x, P)))
        specs = (p_specs, i_specs)
        donate = ()
    else:  # decode
        params = model_api.abstract_params(
            cfg, jnp.bfloat16 if variant.get("serve_bf16") else jnp.float32)
        p_specs = steps_mod.param_specs(params, ctx)
        b = shape.global_batch
        state = inputs["state"]

        def kv_spec(leaf):
            # KV caches [.., B, S, Hkv, hd] (stacked: n_units leading);
            # ssm states and small tensors: batch-shard only.
            if leaf.ndim >= 4 and leaf.shape[-3] % 16 == 0 and \
                    leaf.shape[-3] >= 4096:
                lead = [None] * (leaf.ndim - 4)
                ax = ctx.batch_axes_for(leaf.shape[-4])
                return P(*lead, ax if ax else None, ctx.model_axis, None,
                         None)
            # batch axis is first (remainder) or second (pattern-stacked)
            for bdim in range(min(2, leaf.ndim)):
                if leaf.shape[bdim] == b:
                    ax = ctx.batch_axes_for(b)
                    parts = [None] * leaf.ndim
                    if ax:
                        parts[bdim] = ax
                    return P(*parts)
            return P()
        s_specs = jax.tree.map(kv_spec, state)
        tok_spec = P(ctx.batch_axes_for(b) or None)
        len_spec = P(ctx.batch_axes_for(b) or None)

        def fn(params, state, tokens, lengths):
            return model_api.decode_step(params, cfg, ctx, state, tokens,
                                         lengths,
                                         compute_dtype=compute_dtype)
        args = (params, state, inputs["tokens"], inputs["lengths"])
        tok_sp = P(*([ctx.batch_axes_for(b) or None] +
                     [None] * (inputs["tokens"].ndim - 1)))
        shardings = (jax.tree.map(ctx.sharding, p_specs,
                                  is_leaf=lambda x: isinstance(x, P)),
                     jax.tree.map(ctx.sharding, s_specs,
                                  is_leaf=lambda x: isinstance(x, P)),
                     ctx.sharding(tok_sp), ctx.sharding(len_spec))
        specs = (p_specs, s_specs, tok_sp, len_spec)
        donate = (1,)
    return fn, args, shardings, specs, donate


def run_cell(arch_name: str, shape_name: str, mesh_kind: str,
             out_dir: str, variant=None, force: bool = False) -> dict:
    variant = variant or {}
    vtag = ("__" + variant["tag"]) if variant.get("tag") else ""
    out_path = os.path.join(
        out_dir, f"{arch_name}__{shape_name}__{mesh_kind}{vtag}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)
    os.makedirs(out_dir, exist_ok=True)
    if variant.get("mesh_shape"):
        # custom mesh (e.g. a dedicated serving mesh (data=4, model=64)
        # for 400B-class decode — see EXPERIMENTS.md §Perf cell C)
        shape = tuple(int(v) for v in variant["mesh_shape"])
        axes = ("pod", "data", "model")[-len(shape):]
        mesh = make_mesh(shape, axes)
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
           "mesh_shape": dict(zip(mesh.axis_names,
                                  [int(s) for s in mesh.devices.shape])),
           "variant": {k: v for k, v in variant.items() if k != "tag"},
           "tag": variant.get("tag", "")}
    t0 = time.time()
    try:
        with mesh:
            fn, args, shardings, specs, donate = build_cell(
                arch_name, shape_name, mesh, variant)
            lowered = jax.jit(fn, in_shardings=shardings,
                              donate_argnums=donate).lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            cost = compiled.cost_analysis() or {}
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
            coll = collective_bytes(hlo)           # raw (loop-unaware)
            deep = hlo_analysis.analyze(hlo)       # trip-count-corrected
            rec.update({
                "ok": True,
                "lower_s": round(t1 - t0, 2),
                "compile_s": round(t2 - t1, 2),
                # XLA numbers (NOTE: while-loop bodies counted once)
                "xla_flops_per_device": float(cost.get("flops", -1.0)),
                "xla_bytes_per_device": float(cost.get("bytes accessed",
                                                       -1.0)),
                # trip-count-corrected numbers (launch.hlo_analysis)
                "flops_per_device": deep["dot_flops"],
                "dot_bytes_per_device": deep["dot_bytes"],
                "collective_bytes_per_device": deep["coll"],
                "collective_total_per_device": deep["coll_total"],
                "collective_counts": deep["coll_counts"],
                "trip_counts": deep["trip_counts"],
                "collective_bytes_raw": coll,
                "arg_bytes_per_device": _safe_arg_bytes(args, specs, mesh),
                "hlo_lines": hlo.count("\n"),
            })
            if mem is not None:
                for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                             "output_size_in_bytes",
                             "generated_code_size_in_bytes"):
                    v = getattr(mem, attr, None)
                    if v is not None:
                        rec[attr] = int(v)
    except Exception as e:  # noqa: BLE001 — record the failure verbatim
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
    rec["total_s"] = round(time.time() - t0, 2)
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def _safe_arg_bytes(args, specs, mesh) -> int:
    try:
        return sharded_arg_bytes(args, specs, mesh)
    except Exception:  # noqa: BLE001
        return -1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default=None,
                    help="JSON dict of ParallelCtx overrides + 'tag'")
    args = ap.parse_args()
    variant = json.loads(args.variant) if args.variant else {}

    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            if not eligible(get_arch(a), get_shape(s)):
                continue
            for m in meshes:
                cells.append((a, s, m))

    n_ok = 0
    for i, (a, s, m) in enumerate(cells):
        rec = run_cell(a, s, m, args.out, variant, args.force)
        ok = rec.get("ok")
        n_ok += bool(ok)
        gf = rec.get("flops_per_device", 0) / 1e9 if ok else 0
        print(f"[{i+1}/{len(cells)}] {a} x {s} x {m}: "
              f"{'OK' if ok else 'FAIL'} "
              f"({rec['total_s']}s, {gf:.1f} GF/dev)"
              + ("" if ok else f"  {rec.get('error','')[:200]}"),
              flush=True)
    print(f"dry-run complete: {n_ok}/{len(cells)} cells OK")
    if n_ok < len(cells):
        raise SystemExit(1)


if __name__ == "__main__":
    main()

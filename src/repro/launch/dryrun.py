import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real jitted step (train_step for train shapes,
prefill/serve steps for inference shapes) with production shardings, runs
``.lower(**ShapeDtypeStructs).compile()`` — no parameter allocation — and
records ``memory_analysis()`` / ``cost_analysis()`` / the collective schedule
parsed from the optimized HLO into ``artifacts/dryrun/<cell>.json``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import flops as flops_mod
from repro.analysis import hlo as hlo_mod
from repro.analysis import roofline as rl
from repro.configs import (
    SHAPES,
    cache_alloc_len,
    get_config,
    input_specs,
    list_archs,
    shape_applicable,
)
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.serve.engine import cache_shape
from repro.train import optimizer as opt_mod
from repro.train.train_step import make_train_step, opt_config_for


def _opt_shardings(params_sh, mesh):
    return {
        "m": params_sh,
        "v": params_sh,
        "step": NamedSharding(mesh, P()),
    }


def build_lowered(arch: str, shape_name: str, mesh, *, overrides=None):
    """Returns (lowered, meta) for one cell."""
    cfg = get_config(arch)
    if overrides:
        import dataclasses

        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise SkipCell(why)
    model = build_model(cfg)
    values_sds, axes = model.abstract_params()
    profile = cfg.sharding_profile if shape.kind == "train" else cfg.serve_profile
    params_sh = shd.param_shardings(values_sds, axes, mesh,
                                    rules=shd.rules_for(profile))
    specs = input_specs(cfg, shape)
    batch_sh = shd.batch_shardings(specs, mesh, profile=cfg.sharding_profile)
    meta = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "params": model.param_count(),
        "active_params": model.active_param_count(),
    }

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = opt_config_for(cfg)
            opt_sds = jax.eval_shape(lambda p: opt_mod.init(p, opt_cfg), values_sds)
            opt_sh = _opt_shardings(params_sh, mesh)
            grad_specs = jax.tree.map(lambda sh: sh.spec, params_sh)
            step_fn = make_train_step(model, opt_cfg, n_micro=cfg.microbatches,
                                      grad_specs=grad_specs)
            scalar = NamedSharding(mesh, P())
            jitted = jax.jit(
                step_fn,
                in_shardings=(params_sh, opt_sh, batch_sh),
                out_shardings=(
                    params_sh,
                    opt_sh,
                    {"loss": scalar, "grad_norm": scalar, "lr": scalar, "step": scalar},
                ),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(values_sds, opt_sds, specs)

        elif shape.kind == "prefill":
            s_alloc = cache_alloc_len(shape.seq_len)
            cache_dtype = jnp.bfloat16

            def prefill_fn(params, inputs):
                return model.prefill(params, inputs, s_alloc=s_alloc,
                                     cache_dtype=cache_dtype)

            cache_sds = jax.eval_shape(prefill_fn, values_sds, specs)[1]
            cache_sh = shd.cache_shardings(cache_sds, mesh,
                                           batch_size=shape.global_batch)
            logits_sh = NamedSharding(
                mesh, shd.batch_spec(mesh, 2, batch_size=shape.global_batch)
            )
            jitted = jax.jit(
                prefill_fn,
                in_shardings=(params_sh, batch_sh),
                out_shardings=(logits_sh, cache_sh),
            )
            lowered = jitted.lower(values_sds, specs)

        else:  # decode
            s_alloc = cache_alloc_len(shape.seq_len)
            cache_dtype = jnp.bfloat16
            s_cross = 4096 if cfg.family == "encdec" else 0
            cache_sds = cache_shape(model, shape.global_batch, s_alloc,
                                    s_cross=s_cross, cache_dtype=cache_dtype)
            cache_sh = shd.cache_shardings(cache_sds, mesh,
                                           batch_size=shape.global_batch)
            tok_sh = NamedSharding(
                mesh, shd.batch_spec(mesh, 1, batch_size=shape.global_batch)
            )
            logits_sh = NamedSharding(
                mesh, shd.batch_spec(mesh, 2, batch_size=shape.global_batch)
            )
            scalar = NamedSharding(mesh, P())

            def decode_fn(params, cache, tokens, cur_index):
                return model.decode(params, cache, tokens, cur_index)

            tok_sds = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            idx_sds = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                decode_fn,
                in_shardings=(params_sh, cache_sh, tok_sh, scalar),
                out_shardings=(logits_sh, cache_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(values_sds, cache_sds, tok_sds, idx_sds)

    return lowered, meta


class SkipCell(Exception):
    pass


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             *, overrides=None, hlo_dir: str | None = None,
             suffix: str = "") -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    t0 = time.time()
    lowered, meta = build_lowered(arch, shape_name, mesh, overrides=overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = hlo_mod.collective_bytes(hlo)   # trip-count-scaled (analysis.hlo)
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, f"{arch}_{shape_name}_{mesh_kind}{suffix}.hlo"), "w") as f:
            f.write(hlo)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    # compute/memory terms: analytic model (XLA prices while bodies once —
    # see analysis.flops docstring; cross-validated in tests)
    est = flops_mod.estimate(cfg, shape, meta["params"], meta["active_params"])
    mf = rl.model_flops(cfg, shape, meta["active_params"])
    roof = rl.Roofline(
        arch=arch,
        shape=shape_name,
        mesh=mesh_kind,
        device_flops=est.flops_global / n_dev,
        device_bytes=est.hbm_bytes_global / n_dev,
        collective_bytes=float(coll["total"]),
        model_flops_global=mf,
        n_devices=n_dev,
        device_kind=rl.V5E,
        collectives={
            "bytes": coll["bytes"],
            "counts": coll["counts"],
        },
        memory_per_device_gb=(
            (getattr(mem, "argument_size_in_bytes", 0)
             + getattr(mem, "output_size_in_bytes", 0)
             + getattr(mem, "temp_size_in_bytes", 0)) / 1e9
        ),
        notes=f"flops breakdown: { {k: f'{v:.3e}' for k, v in est.breakdown.items()} }",
    ).finalize()

    record = {
        **meta,
        "mesh": mesh_kind,
        "n_devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "roofline": roof.to_json(),
    }
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_kind}{suffix}.json")
    with open(fn, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--hlo-dir", default=None, help="also dump optimized HLO text")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (perf iterations)")
    ap.add_argument("--suffix", default="", help="artifact filename suffix")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v in ("true", "True"):
            v = True
        elif v in ("false", "False"):
            v = False
        else:
            try:
                v = int(v)
            except ValueError:
                pass
        overrides[k] = v

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            cfg = get_config(arch)
            ok, why = shape_applicable(cfg, SHAPES[shape_name])
            for mesh_kind in meshes:
                tag = f"{arch} x {shape_name} x {mesh_kind}"
                out_fn = os.path.join(
                    args.out,
                    f"{arch}_{shape_name}_{mesh_kind}{args.suffix}.json")
                if args.skip_existing and os.path.exists(out_fn):
                    print(f"[skip-existing] {tag}")
                    continue
                if not ok:
                    print(f"[skipped] {tag}: {why}")
                    os.makedirs(args.out, exist_ok=True)
                    with open(out_fn, "w") as f:
                        json.dump({"arch": arch, "shape": shape_name,
                                   "mesh": mesh_kind, "skipped": why}, f)
                    continue
                try:
                    rec = run_cell(arch, shape_name, mesh_kind, args.out,
                                   hlo_dir=args.hlo_dir,
                                   overrides=overrides or None,
                                   suffix=args.suffix)
                    r = rec["roofline"]
                    print(
                        f"[ok] {tag}: compile={rec['compile_s']}s "
                        f"flops/dev={r['device_flops']:.3e} "
                        f"coll/dev={r['collective_bytes']:.3e}B "
                        f"dominant={r['dominant']} "
                        f"roofline_frac={r['roofline_frac']:.3f}"
                    )
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for tag, err in failures:
            print(f"  {tag}: {err}")
        raise SystemExit(1)
    print("\nall requested dry-run cells compiled OK")


if __name__ == "__main__":
    main()

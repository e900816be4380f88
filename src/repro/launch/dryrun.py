import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402  (the XLA device-count flag MUST precede any jax import)
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we build the production mesh (16x16 single-pod or 2x16x16
multi-pod), shard params/optimizer/batch/cache with the 2D FSDP x TP rules,
and run ``jit(step).lower(**ShapeDtypeStructs).compile()``. Success proves
the distribution config is coherent; the compiled artifact yields:

  * memory_analysis  -- per-device bytes (args/temp/output): does it fit HBM;
  * cost_analysis    -- per-device HLO FLOPs and bytes accessed;
  * as_text          -- post-SPMD collective schedule (parsed by analysis.hlo).

Results append to a JSONL consumed by EXPERIMENTS.md SecDry-run/SecRoofline.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all [--multipod] [--mode analog_train]
"""

import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.analysis import hlo as hlo_lib
from repro.analysis import hlo_cost
from repro.analysis import roofline as roof_lib
from repro.configs import shapes as shapes_lib
from repro.core.analog import AnalogConfig
from repro.launch import sharding as shd
from repro.launch.sharding import build_opt_shardings
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models.common import set_logical_rules
from repro.models.lm import lm_init
from repro.training import optim as optim_lib

# >=40B models use Adafactor so optimizer state fits 16 GB/chip (DESIGN Sec 5)
ADAFACTOR_ARCHS = {"qwen2-72b", "llama4-maverick-400b-a17b"}


def analog_config(mode: str) -> AnalogConfig:
    if mode == "digital":
        return AnalogConfig()
    if mode == "analog_train":
        return AnalogConfig().train(eta=0.1, b_adc=8)
    if mode == "analog_infer":
        return AnalogConfig().infer(b_adc=8, t_seconds=86400.0)
    raise ValueError(mode)


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    mode: str = "digital",
    verbose: bool = True,
    override_cfg=None,
    layout: str = "2d",
    accum_steps: int = 1,
) -> dict:
    cell = shapes_lib.SHAPES[shape_name]
    cfg = override_cfg or configs.get(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    set_logical_rules(shd.logical_rules(mesh, cfg, layout))
    acfg = analog_config(mode)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "mode": mode,
        "layout": layout,
        "accum_steps": accum_steps,
        "chips": int(np.prod(mesh.devices.shape)),
        "status": "start",
    }
    t0 = time.time()
    try:
        key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params_shape = jax.eval_shape(functools.partial(lm_init, cfg=cfg), key_spec)
        n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params_shape))
        if cell.kind != "train":
            # serving: bf16 weights (fp32 masters are a training artifact)
            params_shape = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
                if x.dtype == jnp.float32 and len(x.shape) >= 2 else x,
                params_shape,
            )
        # data-axis weight replication removes per-step FSDP gathers but only
        # fits HBM for small models; >=8B models keep the 2D sharding when
        # serving (the gathers are the price of fitting).
        inference_replicate = cell.kind != "train" and n_params < 8e9
        param_shards = shd.param_shardings(
            params_shape, mesh, cfg, inference=inference_replicate,
            layout=layout,
        )
        specs = shapes_lib.input_specs(cfg, shape_name)
        batch_shards = shd.batch_shardings(specs["batch"], mesh, layout)
        rng_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

        if cell.kind == "train":
            opt_cfg = optim_lib.OptimizerConfig(
                kind="adafactor" if arch in ADAFACTOR_ARCHS else "adamw"
            )
            opt_shape = jax.eval_shape(
                functools.partial(optim_lib.init, opt_cfg), params_shape
            )
            opt_shards = build_opt_shardings(opt_shape, params_shape, param_shards, mesh)
            step_fn = make_train_step(cfg, acfg, opt_cfg, accum_steps)
            in_sh = (param_shards, opt_shards, batch_shards, rep)
            out_sh = (param_shards, opt_shards, rep)
            args = (params_shape, opt_shape, specs["batch"], rng_spec)
            jitted = jax.jit(
                step_fn, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=(0, 1),
            )
        else:
            cache_shards = shd.cache_shardings(
                specs["cache"], mesh, cell.global_batch
            )
            if cell.kind == "prefill":
                step_fn = make_prefill_step(cfg, acfg)
                model_n = mesh.shape.get("model", 1)
                v_ax = "model" if cfg.vocab % model_n == 0 else None
                spec = [shd.batch_axis(mesh, cell.global_batch), None]
                if cfg.n_codebooks:
                    spec.append(None)  # (B, 1, codebooks, V)
                spec.append(v_ax)
                out_logits = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(*spec)
                )
                out_sh = (out_logits, cache_shards)
            else:
                step_fn = make_serve_step(cfg, acfg)
                out_tokens = jax.sharding.NamedSharding(
                    mesh,
                    jax.sharding.PartitionSpec(
                        shd.batch_axis(mesh, cell.global_batch)
                    ),
                )
                out_sh = (out_tokens, cache_shards)
            in_sh = (param_shards, batch_shards, cache_shards, rep)
            args = (params_shape, specs["batch"], specs["cache"], rng_spec)
            jitted = jax.jit(
                step_fn, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=(2,),
            )

        with jax.set_mesh(mesh):
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        print(mem)  # proves it fits (per-device bytes)
        ca = compiled.cost_analysis()
        print({k: ca.get(k) for k in ("flops", "bytes accessed")})
        hlo_text = compiled.as_text()
        colls = hlo_lib.collective_stats(hlo_text)
        # loop-aware per-device costs: compiled.cost_analysis() counts while
        # bodies ONCE (verified); the walker scales by known_trip_count.
        lc = hlo_cost.analyze(hlo_text)

        n_active = roof_lib.active_params(cfg, n_params)
        mf = roof_lib.model_flops(cfg, n_params, n_active, cell)
        param_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(params_shape)
        )
        cache_bytes = 0.0
        if cell.kind != "train":
            cache_bytes = sum(
                int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(specs["cache"])
            )
        mb = roof_lib.model_bytes(cell, cache_bytes, param_bytes, n_params, n_active)
        roof = roof_lib.Roofline(
            arch=arch,
            shape=shape_name,
            mesh=mesh_name,
            chips=rec["chips"],
            flops_per_dev=lc.flops,
            bytes_per_dev=lc.bytes,
            # the SPMD program is per-device: its collective instructions
            # already describe one device's traffic
            wire_bytes_per_dev=lc.wire_bytes,
            model_flops_total=mf,
            collective_counts={k: int(v) for k, v in lc.coll_counts.items()},
            model_bytes_total=mb,
        )

        rec.update(
            status="ok",
            mode_mesh=mesh_name,
            n_params=n_params,
            n_active_params=n_active,
            t_lower_s=round(t_lower, 2),
            t_compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "total_nonaliased_gib": round(
                    (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
                    / 2**30, 3,
                ),
            },
            cost={
                "xla_flops_body_once": float(ca.get("flops", 0.0)),
                "xla_bytes_body_once": float(ca.get("bytes accessed", 0.0)),
                "loop_aware_flops": lc.flops,
                "loop_aware_bytes": lc.bytes,
                "loop_aware_wire_bytes": lc.wire_bytes,
            },
            collectives={
                "counts": colls.counts,
                "operand_bytes": colls.operand_bytes,
                "wire_bytes": colls.wire_bytes,
            },
            roofline=roof.row(),
        )
        if verbose:
            print(
                f"[ok] {arch} {shape_name} {mesh_name} {mode}: "
                f"compile {t_compile:.1f}s, "
                f"{rec['memory']['total_nonaliased_gib']:.2f} GiB/dev, "
                f"bottleneck={roof.bottleneck}, "
                f"roofline_frac={roof.roofline_fraction:.3f}"
            )
    except Exception as e:  # noqa: BLE001 -- record failures as data
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[FAIL] {arch} {shape_name} {mesh_name} {mode}: {e}")
    rec["wall_s"] = round(time.time() - t0, 2)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(shapes_lib.SHAPES) + [None])
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default="digital",
                    choices=["digital", "analog_train", "analog_infer"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(configs.LM_ARCHS)
    shape_names = [args.shape] if args.shape else list(shapes_lib.SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multipod]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        for arch in archs:
            for shape_name in shape_names:
                if not shapes_lib.applicable(arch, shape_name):
                    rec = {
                        "arch": arch, "shape": shape_name,
                        "status": "skip", "reason": "full-attention arch; "
                        "long_500k requires sub-quadratic mixing (DESIGN.md)",
                    }
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    print(f"[skip] {arch} {shape_name}")
                    continue
                for mp in meshes:
                    rec = run_cell(arch, shape_name, mp, args.mode)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()


if __name__ == "__main__":
    main()

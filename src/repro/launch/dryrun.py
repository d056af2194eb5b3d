import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any jax import — jax locks
# the device count at first init. Everything below may import jax.

import argparse  # noqa: E402
import json  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.obs import now  # noqa: E402
from repro.configs import (ARCH_IDS, SHAPES, get_config,  # noqa: E402
                           shape_applicable)
from repro.launch import analytic  # noqa: E402
from repro.launch import roofline as roof  # noqa: E402
from repro.launch import sharding as shard_lib  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as model_mod  # noqa: E402
from repro.models import params as params_mod  # noqa: E402
from repro.train import optimizer as opt_mod  # noqa: E402
from repro.train.train_step import build_train_step  # noqa: E402

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape) cell this lowers + compiles the
real entry point (train_step / prefill / decode_step) against the
production mesh with ShapeDtypeStruct stand-ins (zero allocation),
prints memory_analysis / cost_analysis, and writes the roofline report
consumed by EXPERIMENTS.md §Dry-run/§Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi \
        --arch llama3-405b --shape train_4k
"""

# per-arch microbatching for the train shape: keeps the remat carry
# (num_blocks x microbatch x seq x d_model) within HBM (DESIGN.md §5.4)
GRAD_ACCUM = {
    "llama3-405b": 8,
    "qwen1.5-110b": 8,
    "chameleon-34b": 8,
    "dbrx-132b": 8,
    "jamba-v0.1-52b": 4,
    "minitron-8b": 4,
    "deepseek-moe-16b": 4,
    "gemma2-2b": 4,
    "seamless-m4t-medium": 1,
    "mamba2-370m": 8,
}

# optimizer-state dtype: bf16 halves moments for the giants (§Dry-run
# memory table discusses the f32 alternative)
OPT_DTYPE = {
    "llama3-405b": jnp.bfloat16,
    "qwen1.5-110b": jnp.bfloat16,
    "dbrx-132b": jnp.bfloat16,
}


def _opt_cfg(arch: str) -> opt_mod.OptConfig:
    return opt_mod.OptConfig(state_dtype=OPT_DTYPE.get(arch, jnp.float32))


def lower_cell(
    arch: str, shape_name: str, mesh, *, rules_overrides=None,
    grad_accum: Optional[int] = None, donate: bool = True,
    arch_overrides=None, parallelism: str = "tp",
) -> Dict[str, Any]:
    """parallelism: 'tp' = tensor parallel over 'model' + fsdp over
    data axes (baseline); 'fsdp' = pure ZeRO-3 — every mesh axis is a
    data axis, weights gathered at use. The right choice is
    size-dependent: TP wire scales with tokens*d_model*layers, FSDP
    wire with grad_accum*params (§Perf B2)."""
    cfg = get_config(arch)
    if arch_overrides:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, **arch_overrides)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": reason}
    act_ctx = None
    if parallelism == "fsdp":
        from repro.models import sharding_utils as su

        all_axes = tuple(mesh.axis_names)
        rules_overrides = dict(rules_overrides or {})
        rules_overrides.update({
            "batch": all_axes, "fsdp": all_axes, "heads": None,
            "kv_heads": None, "head_dim": None, "mlp": None,
            "vocab": None, "experts": None, "ssm_inner": None,
        })
        act_ctx = su.use_act_map({
            "batch": all_axes, "heads": (), "kv_heads": (),
            "head_dim": (), "mlp": (), "experts": (), "ssm_inner": (),
            "vocab": (), "seq_model": (),
        })
        act_ctx.__enter__()
    rules = shard_lib.mesh_rules(mesh, rules_overrides)
    world = mesh.devices.size

    p_abs = shard_lib.abstract_params(cfg)
    p_sh = params_mod.shardings(model_mod.model_specs(cfg), rules, mesh)
    in_specs = model_mod.input_specs(cfg, shape)
    in_abs = params_mod.abstract(in_specs)
    in_sh = params_mod.shardings(in_specs, rules, mesh)

    t0 = now()
    if shape.kind == "train":
        ocfg = _opt_cfg(arch)
        accum = grad_accum if grad_accum is not None \
            else GRAD_ACCUM.get(arch, 1)
        step_fn = build_train_step(cfg, ocfg, grad_accum=accum)
        o_abs = shard_lib.abstract_opt_state(cfg, ocfg)
        o_sh = shard_lib.opt_shardings(cfg, ocfg, mesh, rules)
        fn = jax.jit(
            step_fn,
            in_shardings=(p_sh, o_sh, in_sh),
            donate_argnums=(0, 1) if donate else (),
        )
        lowered = fn.lower(p_abs, o_abs, in_abs)
    elif shape.kind == "prefill":
        fn = jax.jit(
            lambda params, batch: model_mod.prefill(params, batch, cfg),
            in_shardings=(p_sh, in_sh),
        )
        lowered = fn.lower(p_abs, in_abs)
    else:  # decode
        fn = jax.jit(
            lambda params, tokens, cache, pos: model_mod.decode_step(
                params, tokens, cache, pos, cfg),
            in_shardings=(p_sh, in_sh["tokens"], in_sh["cache"],
                          NamedSharding(mesh, P())),
            donate_argnums=(2,) if donate else (),
        )
        lowered = fn.lower(p_abs, in_abs["tokens"], in_abs["cache"],
                           in_abs["pos"])
    t_lower = now() - t0
    if act_ctx is not None:
        act_ctx.__exit__()

    t0 = now()
    compiled = lowered.compile()
    t_compile = now() - t0

    mf = roof.model_flops(cfg, shape, cfg.active_param_count())
    accum = (grad_accum if grad_accum is not None
             else GRAD_ACCUM.get(arch, 1))
    remat = (shape.kind == "train"
             and cfg.remat_policy == "nothing_saveable")
    af = analytic.flops_model(cfg, shape, grad_accum=accum, remat=remat)
    ocfg_b = _opt_cfg(arch)
    opt_bpp = 2 * jnp.dtype(ocfg_b.state_dtype).itemsize
    ab = analytic.bytes_model(
        cfg, shape, param_count=cfg.param_count(), grad_accum=accum,
        opt_bytes_per_param=opt_bpp, remat=remat)
    report = roof.roofline_report(
        compiled, world=world, model_flops_global=mf,
        analytic_flops_global=af["flops_global"],
        analytic_bytes_global=ab["bytes_global"],
        steps_hint=f"grad_accum={accum}"
        if shape.kind == "train" else shape.kind,
    )
    report.update({
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": list(mesh.devices.shape),
        "mesh_axes": list(mesh.axis_names),
        "lower_seconds": round(t_lower, 1),
        "compile_seconds": round(t_compile, 1),
        "total_params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    })
    # the two required printouts
    print(compiled.memory_analysis())
    ca = compiled.cost_analysis() or {}
    print({k: ca[k] for k in ("flops", "bytes accessed")
           if k in ca})
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES),
                    help="one shape (default all)")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--grad-accum", type=int, default=None)
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(
            ("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for mesh_name, mesh in meshes:
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}__{shape}"
                print(f"=== {mesh_name} :: {tag} ===", flush=True)
                try:
                    with mesh:
                        rep = lower_cell(arch, shape, mesh,
                                         grad_accum=args.grad_accum)
                except Exception as e:  # noqa: BLE001 — sweep must survive any one cell's lowering failure; the error lands in its report JSON
                    failures += 1
                    rep = {"arch": arch, "shape": shape,
                           "status": "failed", "error": str(e)[-2000:],
                           "traceback": traceback.format_exc()[-4000:]}
                    print(f"FAILED: {e}", flush=True)
                with open(os.path.join(outdir, tag + ".json"), "w") as f:
                    json.dump(rep, f, indent=2, default=str)
                if rep.get("status") == "ok":
                    t = rep["terms_seconds"]
                    print(
                        f"ok lower={rep['lower_seconds']}s "
                        f"compile={rep['compile_seconds']}s "
                        f"compute={t['compute']:.4f}s "
                        f"memory={t['memory']:.4f}s "
                        f"coll={t['collective']:.4f}s "
                        f"bottleneck={rep['bottleneck']} "
                        f"useful={rep['useful_flops_ratio']:.2f}",
                        flush=True)
                elif rep.get("status") == "skipped":
                    print(f"skipped: {rep['reason']}", flush=True)
    print(f"done, failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()

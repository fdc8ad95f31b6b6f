import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init). 512 placeholder host devices back both the
# single-pod (16,16) and multi-pod (2,16,16) production meshes.

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination against the production mesh, and extract the roofline terms.

Per combination this produces
  * a FULL-depth compile — proves the sharding config is coherent, yields
    ``memory_analysis()`` (per-device bytes) and compile wall time;
  * two COUNTING compiles at 1 and 2 pattern-cycles (attention inner loops
    physically unrolled) — XLA's cost_analysis does not multiply while-body
    costs by trip count, so full-depth FLOPs / HBM bytes / collective wire
    bytes are derived by linear extrapolation:
        total = base(1 cycle) + (num_cycles - 1) × [cost(2 cycles) - cost(1)]
    (everything outside the layer scan — embedding, LM head, loss, optimizer
    scalars — lives in the base term; per-cycle costs, including remat
    recompute and FSDP all-gathers, live in the delta).

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k \
      --mesh single --out results/dryrun [--skip-full] [--skip-count]
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.obs.trace import monotonic


def _planner_defaults(cfg, shape):
    """Runtime knobs for the baseline dry-run (full planner in repro.core)."""
    from repro.optim.adamw import OptConfig
    param_bytes = None  # filled lazily
    big = cfg.name in (
        "qwen2-72b", "jamba-1.5-large-398b", "arctic-480b",
        "deepseek-v2-236b", "llava-next-34b",
    )
    fsdp = big
    opt_kind = "momentum" if cfg.name == "arctic-480b" else "adamw"
    return fsdp, OptConfig(kind=opt_kind)


def variant_config(cfg, shape):
    """Arch variant actually lowered for this input shape (long-context SWA
    override for full-attention archs, per DESIGN.md §long_500k policy)."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return cfg.replace(attn_window_override=8192), "swa8192-variant"
    return cfg, "native"


def _reduced_cycles(cfg, n_cycles):
    return cfg.replace(num_layers=cfg.first_k_dense + n_cycles * len(cfg.pattern))


def build_step_and_args(cfg, shape, mesh, run, *, counting=False,
                        optimized=False):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import steps as S
    from repro.launch import mesh as mesh_lib
    from repro.models.blocks import RunConfig

    fsdp, opt = _planner_defaults(cfg, shape)
    rules = mesh_lib.sharding_rules(mesh, cfg, shape, fsdp=fsdp)

    donate = ()
    if shape.kind in ("train", "prefill"):
        runc = RunConfig(
            attn_impl="counting" if counting else "chunked",
            remat="block",  # kept in counting mode so recompute FLOPs show up
            act_sharding=mesh_lib.act_sharding(mesh, shape, seq_parallel=True),
            unroll_layers=counting,
        )
        if optimized:
            # §Perf levers: seq-sharded CE path, shard_map expert parallelism,
            # buffer donation (params/opt aliasing)
            dp = mesh_lib.dp_axes(mesh)
            runc.logit_sharding = NamedSharding(mesh, P(dp, "model", None))
            if cfg.has_moe:
                runc.moe_mesh = mesh
            if shape.kind == "train":
                from repro.models import model as M
                from repro.models.common import partition_specs
                zrules = dict(rules)
                zrules["embed"] = dp
                pspecs = partition_specs(M.model_specs(cfg), zrules)
                runc.grad_shardings = jax.tree_util.tree_map(
                    lambda ps: NamedSharding(mesh, ps), pspecs)
                runc.bf16_grads = True
                donate = (0, 1)
    else:
        runc = RunConfig(attn_impl="dense", remat="none", act_sharding=None,
                         unroll_layers=counting)
        if optimized:
            runc.cache_scatter = True
            donate = (3,)  # caches updated in place

    inputs = S.input_specs(cfg, shape, mesh, rules,
                           kv_quant=(optimized and shape.kind == "decode"))
    if shape.kind == "train":
        params = S.abstract_params(cfg, mesh, rules)
        opt_state = S.abstract_opt_state(cfg, mesh, rules, opt)
        step = S.build_train_step(cfg, runc, opt)
        args = (params, opt_state, inputs)
        fn = lambda p, o, b: step(p, o, b)
    elif shape.kind == "prefill":
        params = S.abstract_params(cfg, mesh, rules, dtype="bfloat16")
        step = S.build_prefill_step(cfg, runc)
        args = (params, inputs)
        fn = step
    else:  # decode
        params = S.abstract_params(cfg, mesh, rules, dtype="bfloat16")
        step = S.build_decode_step(cfg, runc)
        args = (params, inputs["tokens"], inputs["pos"], inputs["caches"])
        fn = step
    return fn, args, donate


def lower_compile(fn, args, mesh, donate=()):
    t0 = monotonic()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t_lower = monotonic() - t0
        t0 = monotonic()
        compiled = lowered.compile()
        t_compile = monotonic() - t0
    return lowered, compiled, t_lower, t_compile


def analyze(compiled, mesh):
    from repro.launch import hlo as hlo_lib

    cost = compiled.cost_analysis() or {}
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
    try:
        ma = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        out["memory"] = {"error": str(e)}
    try:
        txt = compiled.as_text()
        stats = hlo_lib.collective_bytes(txt)
        out["collectives"] = stats
        out["wire_bytes"] = hlo_lib.total_wire_bytes(stats)
    except Exception as e:  # pragma: no cover
        out["collectives"] = {"error": str(e)}
        out["wire_bytes"] = 0.0
    return out


def run_one(arch, shape_name, mesh_kind, outdir, skip_full=False,
            skip_count=False, optimized=False, mesh_shape=None):
    from repro.configs.base import get_config, get_shape
    from repro.launch.mesh import make_production_mesh

    cfg0 = get_config(arch)
    shape = get_shape(shape_name)
    cfg, variant = variant_config(cfg0, shape)
    if mesh_shape:  # §Perf lever: reinterpret the 256 chips, e.g. 32x8
        from jax.sharding import AxisType
        dp_sz, tp_sz = mesh_shape
        mesh = jax.make_mesh((dp_sz, tp_sz), ("data", "model"),
                             (AxisType.Auto,) * 2,
                             devices=jax.devices()[: dp_sz * tp_sz])
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": variant, "optimized": optimized,
        "num_devices": int(np.prod(list(mesh.shape.values()))),
        "pattern_cycles": cfg.num_cycles if not cfg.first_k_dense else
        (cfg.num_layers - cfg.first_k_dense) // len(cfg.pattern),
        "ok": False,
    }
    try:
        if not skip_full:
            fn, args, donate = build_step_and_args(cfg, shape, mesh, None,
                                                   optimized=optimized)
            lowered, compiled, t_lo, t_co = lower_compile(fn, args, mesh, donate)
            rec["full"] = analyze(compiled, mesh)
            rec["full"]["lower_s"] = round(t_lo, 2)
            rec["full"]["compile_s"] = round(t_co, 2)
            del lowered, compiled

        if not skip_count:
            n_cycles = rec["pattern_cycles"]
            counts = {}
            for nc in (1, 2):
                cfg_r = _reduced_cycles(cfg, nc)
                fn, args, donate = build_step_and_args(cfg_r, shape, mesh, None,
                                                       counting=True,
                                                       optimized=optimized)
                _, compiled, _, _ = lower_compile(fn, args, mesh, donate)
                counts[nc] = analyze(compiled, mesh)
                del compiled
            extra = {}
            for key in ("flops", "bytes_accessed", "wire_bytes"):
                base, two = counts[1][key], counts[2][key]
                delta = max(two - base, 0.0)
                extra[key] = base + (n_cycles - 1) * delta
                extra[key + "_per_cycle"] = delta
                extra[key + "_base"] = base
            rec["derived"] = extra
            rec["count_details"] = counts
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    outdir = Path(outdir)
    outdir.mkdir(parents=True, exist_ok=True)
    path = outdir / f"{arch}__{shape_name}__{mesh_kind}.json"
    path.write_text(json.dumps(rec, indent=1, default=float))
    status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '?')[:120]})"
    print(f"[dryrun] {arch} × {shape_name} × {mesh_kind}: {status}", flush=True)
    return rec["ok"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-full", action="store_true")
    ap.add_argument("--skip-count", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="enable the beyond-paper optimizations (§Perf): "
                         "seq-sharded CE, shard_map MoE, buffer donation")
    ap.add_argument("--mesh-shape", default="",
                    help="override single-pod mesh as DPxTP, e.g. 32x8")
    args = ap.parse_args()

    from repro.configs.base import ARCH_IDS, SHAPES

    archs = ARCH_IDS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                p = Path(args.out) / f"{arch}__{shape}__{mesh_kind}.json"
                if args.skip_existing and p.exists():
                    if json.loads(p.read_text()).get("ok"):
                        continue
                ms = None
                if args.mesh_shape:
                    ms = tuple(int(x) for x in args.mesh_shape.split("x"))
                ok = run_one(arch, shape, mesh_kind, args.out,
                             args.skip_full, args.skip_count,
                             optimized=args.opt, mesh_shape=ms)
                n_fail += (not ok)
    print(f"[dryrun] done, {n_fail} failures", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

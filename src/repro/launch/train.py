"""Training launcher — a thin CLI over the ``repro.api`` facade.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        [--reduced | --full] [--steps 100] [--batch 8] [--seq 128] [--plan] \
        [--dp 8 [--sync all_reduce|reduce_scatter_all_gather|parameter_server|auto]
               [--compress none|bf16|int8|topk]] [--report-out PATH]

Flags map 1:1 onto a :class:`repro.api.JobSpec`; the actual procedure
(planner resolution, strategy sizing, the loop) lives in
:class:`repro.api.Session`.  ``--reduced`` (the smoke-scale family member,
the default) is the setting for a CPU; ``--full`` (or ``--no-reduced``)
trains the published config, which needs an accelerator.  With ``--plan`` the session adopts
the planner's runtime knobs (microbatch / attention impl / remat /
optimizer).  ``--dp N`` switches to the explicit data-parallel trainer: set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on the CPU so the
data axis has simulated devices; ``--sync auto`` resolves the planner's
``Plan.sync_schedule`` to a runnable strategy.  ``--autotune`` runs the
closed-loop autotuner first (``Session.tune``: measured kernel-variant
choice + hardware calibration, see ``docs/tuning_guide.md``) and adopts its
knobs; the calibration persists in ``--tune-cache``.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

from repro.api import JobSpec, Session
from repro.launch.device import enable_compile_cache


def build_spec(args) -> JobSpec:
    return JobSpec(
        arch=args.arch, reduced=args.reduced, steps=args.steps,
        batch=args.batch, seq=args.seq, lr=args.lr,
        use_planner=args.plan, dp=args.dp, pipe=args.pipe,
        n_microbatch=args.microbatch, sync=args.sync,
        compress=args.compress, topology=args.topology,
        sync_overlap=args.overlap, bucket_mb=args.bucket_mb,
        staleness=args.staleness, backup_workers=args.backup_workers,
        tune=args.autotune, tune_cache=args.tune_cache,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every or (50 if args.ckpt_dir else 0),
        trace_dir=getattr(args, "trace_dir", ""))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the reduced family member (default); "
                         "--full / --no-reduced for the full config")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="alias for --no-reduced")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--plan", action="store_true",
                    help="consult the paper-planner for runtime knobs")
    ap.add_argument("--ckpt-dir", default="",
                    help="elastic checkpoint directory: async atomic saves "
                         "every --ckpt-every steps, auto-resume from the "
                         "latest complete step on restart")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint period in steps (0 = 50 when "
                         "--ckpt-dir is set)")
    ap.add_argument("--staleness", type=int, default=0,
                    help="bounded-staleness async PS: max worker params age "
                         "in steps (0 = synchronous; needs --dp)")
    ap.add_argument("--backup-workers", type=int, default=0,
                    help="drop the slowest k of dp gradients per step "
                         "(0 = wait for every worker; needs --dp)")
    ap.add_argument("--dp", type=int, default=0,
                    help="run the explicit data-parallel trainer on this many "
                         "devices (0 = single-process GSPMD loop)")
    ap.add_argument("--pipe", type=int, default=0,
                    help="1F1B pipeline stages (devices split pipe x data; "
                         "0/1 = no pipelining). With --dp N, N is the total "
                         "device count of the (pipe, data) grid")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="1F1B microbatches per step (>= --pipe; 0 = pipe)")
    ap.add_argument("--sync", default="auto",
                    help="gradient-sync strategy, or 'auto' to resolve the "
                         "planner's sync_schedule")
    ap.add_argument("--compress", default="none",
                    help="gradient compression: none|bf16|int8|topk")
    ap.add_argument("--overlap", dest="overlap",
                    action=argparse.BooleanOptionalAction, default=False,
                    help="bucketed comm/compute overlap: hide gradient sync "
                         "under the backward pass (repro.distributed.overlap)"
                         " and price the plan with the overlap-aware model")
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="sync-bucket size target in MiB for --overlap "
                         "(0 = default)")
    ap.add_argument("--topology", default="",
                    help="named cluster topology (repro.core.hardware."
                         "CLUSTERS, e.g. 2x4); empty = flat mesh")
    ap.add_argument("--autotune", action="store_true",
                    help="run the closed-loop autotuner first (measure "
                         "kernel variants + calibrate the hardware "
                         "constants) and adopt its knobs for the run")
    ap.add_argument("--tune-cache", default="results/calibration_cache.json",
                    help="calibration-cache JSON for --autotune "
                         "('' disables persistence)")
    ap.add_argument("--report-out", default="",
                    help="write the unified Report JSON here")
    ap.add_argument("--trace-dir", default="",
                    help="write a Chrome-trace JSON of the run here "
                         "(open in chrome://tracing or Perfetto)")
    ap.add_argument("--metrics-json", default="",
                    help="write the run's metrics/v1 section (repro.obs) "
                         "to this path")
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    sess = Session(build_spec(args))
    if args.plan:
        print("planner:", sess.resolved_plan)
    cfg = sess.cfg
    print(f"training {cfg.name} ({'reduced' if args.reduced else 'FULL'}) "
          f"batch={args.batch} seq={args.seq} steps={args.steps}")
    if args.dp and args.sync == "auto":
        print(f"sync resolved from planner: "
              f"{sess.resolved_plan.sync_schedule}")

    if args.autotune:
        t = sess.tuned
        r = t.replan
        print(f"autotune: minibatch*={t.chosen_minibatch} (m_bound), "
              f"microbatch*={t.chosen_microbatch}, attn={t.attn_impl()}; "
              f"step predicted {r['est_step_time_calibrated_s']*1e3:.1f}ms "
              f"calibrated vs {r['est_step_time_uncalibrated_s']*1e3:.3g}ms "
              f"datasheet (measured {r['measured_step_s']*1e3:.1f}ms)")

    rep = sess.train()
    if "sync" in rep.measured:
        print("sync report:", json.dumps(rep.measured["sync"], indent=2,
                                         default=str))
        s = rep.measured["sync"]
        if s.get("sync_overlap"):
            print(f"overlap: {s['n_buckets']} buckets hide "
                  f"{s['overlap_fraction']:.0%} of sync "
                  f"(exposed {s['exposed_comm_time']*1e3:.1f}ms of "
                  f"{s['measured_comm_s']*1e3:.1f}ms serial)")
    if "async_ps" in rep.measured:
        a = rep.measured["async_ps"]
        print(f"async PS: staleness={a['staleness']} "
              f"(age mean {a['mean_age']:.2f} / max {a['max_age']}), "
              f"backup_workers={a['backup_workers']} "
              f"({a['drops']} grads dropped), "
              f"pull amortized 1/{a['staleness'] + 1}; model wall step "
              f"{a['t_step_model']['wall_step']*1e3:.3g}ms at "
              f"{a['t_step_model']['efficiency']:.0%} statistical "
              f"efficiency")
    if "pipeline" in rep.measured:
        pr = rep.measured["pipeline"]
        print(f"pipeline: {pr['pipe']} stages x {pr['n_microbatch']} "
              f"microbatches, bubble measured {pr['bubble_measured']:.3f} "
              f"vs model {pr['bubble_model']:.3f} "
              f"(serial {pr['bubble_serial']:.3f})")
    m = rep.measured
    losses = m["losses"]
    print(f"loss {np.mean(losses[:5]):.4f} -> {np.mean(losses[-5:]):.4f}; "
          f"{m['tokens_per_s']:,.0f} tok/s; R_O={m['r_o']:.4f}")
    if args.metrics_json:
        p = Path(args.metrics_json)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(m["metrics"], indent=2))
        print(f"wrote metrics {p}")
    if "trace_file" in rep.meta:
        print(f"wrote trace {rep.meta['trace_file']} "
              f"({rep.meta['trace_events']} events)")
    if args.report_out:
        path = rep.save(args.report_out)
        print(f"wrote {path}")
    # machine-parseable summary line (tools/bench_trajectory.py reads it)
    summary = {
        "kind": "train",
        "loss_first": float(np.mean(losses[:5])),
        "loss_last": float(np.mean(losses[-5:])),
        "tokens_per_s": m["tokens_per_s"],
        "r_o": m["r_o"],
        "step_time_s": m["step_times_mean"].get("compute", 0.0)
        + m["step_times_mean"].get("dist_update", 0.0)
        + m["step_times_mean"].get("param_update", 0.0),
    }
    if "sync" in m and m["sync"].get("sync_overlap"):
        summary["overlap_fraction"] = m["sync"]["overlap_fraction"]
    if "async_ps" in m:
        summary["staleness"] = m["async_ps"]["staleness"]
        summary["backup_workers"] = m["async_ps"]["backup_workers"]
        summary["mean_age"] = m["async_ps"]["mean_age"]
    print(json.dumps(summary))


if __name__ == "__main__":
    main()

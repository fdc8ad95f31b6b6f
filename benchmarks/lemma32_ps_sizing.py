"""Paper Lemma 3.2: parameter-server sizing across the assigned archs —
now priced against the *tiered* cluster model: the PS-count curve splits
into an in-node regime (servers colocated with their workers, B_ps = the
fast intra-node tier) and a cross-node regime (the paper's dedicated PS
deployment, B_ps = the narrowest spanning tier).  Plus the TPU mapping
(grad-sync schedule masked behind compute) on both the flat pod and the
hierarchical 2-pod DCN topology."""
from __future__ import annotations

from repro.configs.base import ARCH_IDS, get_config, get_shape
from repro.core import memory_model as mm, ps
from repro.core.hardware import MULTI_POD, SINGLE_POD, get_cluster
from repro.core.planner import estimate_step_time


def run(csv_rows):
    shape = get_shape("train_4k")

    print("\n== Lemma 3.2: N_ps regimes on the tiered cluster "
          "(paper-era 2x8-GPU P2 deployment, N_w=16) ==")
    p2 = get_cluster("p2-2x8")
    print(f"{'arch':24s} {'S_p(GB)':>8s} {'in-node':>8s} {'cross':>6s} "
          f"{'rec':>11s}")
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        s_p = 4.0 * mm.n_params(cfg)  # fp32 params, the PS payload
        t_c = estimate_step_time(cfg, shape, SINGLE_POD, "block", 1)["compute"]
        placement = ps.ps_placement_plan(s_p, 16, p2, max(t_c, 1e-3))
        n_in = placement["in_node"]["n_ps"]
        n_x = placement["cross_node"]["n_ps"]
        print(f"{arch:24s} {s_p/2**30:8.1f} {n_in:8d} {n_x:6d} "
              f"{placement['recommended']:>11s}")
        csv_rows.append((f"lemma32/{arch}/nps_in_node", n_in,
                         f"b_ps={placement['in_node']['b_ps']:.2e}"))
        csv_rows.append((f"lemma32/{arch}/nps_cross_node", n_x,
                         f"b_ps={placement['cross_node']['b_ps']:.2e}"))

    print("\n== PS-count curve vs B_ps (granite-3-2b, the two regimes) ==")
    cfg = get_config("granite-3-2b")
    s_p = 4.0 * mm.n_params(cfg)
    t_c = max(estimate_step_time(cfg, shape, SINGLE_POD, "block", 1)["compute"],
              1e-3)
    print(f"{'B_ps':>12s} {'N_ps':>6s}  regime")
    for bw, regime in ((1e9 / 8, "cross-node 1GbE"),
                       (10e9 / 8, "cross-node 10GbE"),
                       (100e9 / 8, "cross-node 100Gb IB"),
                       (10e9, "in-node PCIe3"),
                       (50e9, "in-node ICI/NVLink")):
        n = ps.n_parameter_servers(s_p, 16, bw, t_c)
        print(f"{bw:12.2e} {n:6d}  {regime}")
        csv_rows.append((f"lemma32_curve/{regime.replace(' ', '_')}/nps", n,
                         f"b_ps={bw:.2e}"))

    print("\n== TPU mapping: grad-sync schedule per topology ==")
    print(f"{'arch':24s} {'mesh':8s} {'sched':26s} {'comm(s)':>8s} "
          f"{'T_C(s)':>7s} {'masked':>7s} {'bottleneck':>10s}")
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for mesh, label in ((SINGLE_POD, "pod"), (MULTI_POD, "2pod")):
            t_c = estimate_step_time(cfg, shape, mesh, "block", 1)["compute"]
            plan = ps.grad_sync_plan(2.0 * mm.n_params(cfg) / mesh.tp,
                                     mesh.cluster.dp_view(mesh.dp, mesh.tp),
                                     t_c=max(t_c, 1e-9))
            print(f"{arch:24s} {label:8s} {plan.schedule:26s} "
                  f"{plan.comm_time:8.3f} {t_c:7.3f} {str(plan.masked):>7s} "
                  f"{plan.bottleneck_tier:>10s}")
            csv_rows.append((f"lemma32_tpu/{arch}/{label}/masked",
                             float(plan.masked),
                             f"{plan.schedule}@{plan.bottleneck_tier}"))

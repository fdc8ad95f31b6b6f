"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table2,fig2,...] [--fast]

Each benchmark prints its own table and appends (name, value, derived) rows;
the run ends with the consolidated ``name,value,derived`` CSV.
"""
from __future__ import annotations

import argparse
import sys
import time

ALL = ("table2", "fig2", "fig3", "fig4", "lemma32", "sync", "sweep",
       "autotune", "ilp", "telemetry", "serve_continuous")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(ALL))
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow measured benchmarks (fig2-4)")
    args = ap.parse_args()
    which = [w.strip() for w in args.only.split(",") if w.strip()]
    if args.fast:
        which = [w for w in which if w not in ("fig2", "fig3", "fig4", "sync",
                                               "autotune", "telemetry",
                                               "serve_continuous")]

    csv_rows = []
    t0 = time.time()
    for name in which:
        if name == "table2":
            from benchmarks import table2_conv_memory as m
        elif name == "fig2":
            from benchmarks import fig2_throughput_vs_batch as m
        elif name == "fig3":
            from benchmarks import fig3_convergence as m
        elif name == "fig4":
            from benchmarks import fig4_speedup as m
        elif name == "lemma32":
            from benchmarks import lemma32_ps_sizing as m
        elif name == "sync":
            from benchmarks import sync_strategies as m
        elif name == "sweep":
            from benchmarks import sweep as m
        elif name == "autotune":
            from benchmarks import autotune as m
        elif name == "ilp":
            from benchmarks import ilp_planner as m
        elif name == "telemetry":
            from benchmarks import telemetry as m
        elif name == "serve_continuous":
            from benchmarks import serve_continuous as m
        else:
            print(f"unknown benchmark {name!r}", file=sys.stderr)
            continue
        m.run(csv_rows)

    print(f"\n== consolidated CSV ({time.time()-t0:.0f}s total) ==")
    print("name,value,derived")
    for name, value, derived in csv_rows:
        print(f"{name},{value},{derived}")


if __name__ == "__main__":
    main()

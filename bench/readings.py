"""Readings from which a training cell's limits are set (not part of a run).

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 3] [--fault-seeds 3] [--faults half_batch,...]
    [--out <file.json>]

In one process, for each seed: the timed path's readings at the cell's own
size (set-up and the first steps, then a short window), the plain
reference's, and the gaps between them (the program's readings, the lower
end of each limit).  On the first ``--control-seeds`` seeds also the
control, the reference computed in float8 and put in the program's place,
and on the first ``--fault-seeds`` the program with each of ``--faults``
planted (the upper ends).  A step that returns its state unchanged reads 1 on
``grad_gap`` and ``change_gap`` by construction and needs no run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def diagnose(prog: dict, ref: dict, sizes: dict) -> dict:
    """Every number a cell may compare, with what lies behind them: the
    per-step loss gaps, the global gradient norm's gap, and the leaves with
    the widest gaps (each with its reference gradient's root mean square
    per element)."""
    import numpy as np

    import compare

    d = compare.train_gaps(prog, ref)
    d["loss_steps"] = [a - b for a, b in zip(prog["loss"], ref["loss"])]
    if prog.get("grad_norm"):
        d["grad_norm_gap"] = abs(prog["grad_norm"] - ref["grad_norm"]) / \
            ref["grad_norm"]
    for what in ("grad", "change"):
        med = float(np.median(list(ref[what].values())))
        gaps = {k: abs(prog[what][k] - v) / max(v, med)
                for k, v in ref[what].items()}
        top = sorted(gaps, key=gaps.get, reverse=True)[:6]
        d[f"{what}_top"] = [
            [k, gaps[k], ref["grad_raw"][k] / np.sqrt(sizes[k])] for k in top]
    return d


def leaf_sizes(shapes: dict) -> dict:
    import numpy as np

    out = {}
    for path, (shape, _, _) in shapes.items():
        if path.startswith("slots/") and len(shape) > 1:
            out.update({f"{path}[{i}]": int(np.prod(shape[1:]))
                        for i in range(shape[0])})
        else:
            out[path] = int(np.prod(shape))
    return out


def readings(cell, seeds, control_seeds: int, fault_seeds: int,
             log=print, faults=("half_batch",)) -> list:
    import harness

    driver = harness.module("drivers", cell.traffic["driver"])
    fam = harness.module("reference", cell.config["family"])
    sizes = leaf_sizes(fam.leaf_shapes(cell.config))
    out = []
    for i, seed in enumerate(seeds):
        row = {"seed": seed}
        t = time.perf_counter()
        outcome = driver.measure(cell, seed=seed, seconds=0.5, trace=False,
                                 t_start=t)
        ref = driver.reference_readings(cell, seed)
        row["program"] = diagnose(outcome.readings, ref, sizes)
        if i < control_seeds:
            ctl = driver.reference_readings(cell, seed, "float8_e4m3")
            row["control"] = diagnose(ctl, ref, sizes)
        for fault in faults if i < fault_seeds else ():
            faulty = driver.measure(cell, seed=seed, seconds=0.5,
                                    trace=False, t_start=t, fault=fault)
            row[fault] = diagnose(faulty.readings, ref, sizes)
        row["seconds"] = time.perf_counter() - t
        log(json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import harness

    cell = harness.Cell.load(args.workload)
    dev = harness.device_info(cell.chips)
    if dev["platform"] != "tpu":
        print("readings: JAX found no TPU", file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = readings(cell, seeds, args.control_seeds, args.fault_seeds,
                    log=lambda s: print(s, flush=True),
                    faults=args.faults.split(","))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"workload": cell.name,
                                              "device": dev, "rows": rows},
                                             indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

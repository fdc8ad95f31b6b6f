"""The on-chip benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` and the cell's files under ``bench/``, measures the
cell on the chips of this machine, compares what the timed path produced
with the plain reference, and prints one JSON line as the last line of
standard output.  ``--trace 1`` reports the per-layer metrics, read partly
from a profiled window of its own; ``--trace 0`` the end-to-end metrics.
Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
# the TPU runtime's logs go under $TMPDIR, not to a fixed path
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.Cell.load(args.workload)
    import jax

    dev = harness.device_info(cell.chips)
    if dev["platform"] != "tpu" or len(jax.devices()) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(jax.devices())} {dev['platform']} device(s)",
              file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    line = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), t_start=T_START, dev=dev)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A run with its timed path broken underneath comes out not correct: the
harness's look for a chip skipped, everything else as a run does it, at a
size the CPU holds (``bench_tiny``: the program in float32, limits tight)."""
import pytest

import bench_tiny
import harness

SEED = 2**31 + 7
CASES = [(w["name"], fault)
         for w in harness.benchmark()["workloads"]
         for fault in harness.module(
             "drivers", harness.Cell.load(w["name"]).traffic["driver"]
         ).FAULTS]


def run(cell_name, fault=None):
    import time

    cell = bench_tiny.tiny_cell(cell_name)
    return harness.run_cell(cell, seed=SEED, seconds=0.2, trace=False,
                            t_start=time.perf_counter(),
                            dev=bench_tiny.cpu_device(), fault=fault)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  harness.benchmark()["workloads"]])
def test_sound_run_is_correct(cell):
    line = run(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {
        m["name"] for m in harness.Cell.load(cell).end_to_end}


@pytest.mark.parametrize("cell,fault", CASES)
def test_planted_fault_is_not_correct(cell, fault):
    line = run(cell, fault)
    assert not line["correct"], line["checks"]

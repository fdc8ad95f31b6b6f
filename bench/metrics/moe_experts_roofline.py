"""The held experts' grouped matmuls as a share of the bf16 peak, in
percent: the FLOPs they executed over the profiled steps (``bench/flops``'s
``expert_flops`` of the program's ``moe_held_rows``: 6 x hidden x expert
width per row and pass, over the forward, recompute and both backward
products) over the device time under the ``moe_experts`` scope times the
peak.  Compute bounds them: at the cell's 1536 rows an expert a
product moves about 16 MB (weights, rows in and out, bf16) for 8.9
GFLOP, 540 FLOP a byte against the chip's 240.  Nothing without the
count, the scope or a peak for the device."""

import harness


def read(outcome, cell, peak):
    rows = outcome.counters.get("moe_held_rows_profiled")
    t = outcome.trace.get("scopes", {}).get("moe_experts")
    if not (peak and rows and t):
        return None
    flops = harness.module("flops", cell.config["flops"]).expert_flops(
        cell.config, rows)
    return 100.0 * flops / (sum(t.values()) * peak["bf16_flops_per_s"])

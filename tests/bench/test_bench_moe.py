"""What the MoE and data-parallel cells add: DeepSeek-V2's FLOP count by
hand at a small size, the per-layer readers on outcomes with and without
what they read, and the counts the MoE driver keeps."""
import time

import pytest

import bench_tiny
import harness


def test_deepseek_v2_hand_count():
    cfg = dict(hidden_size=4, num_attention_heads=1, qk_nope_head_dim=2,
               qk_rope_head_dim=1, v_head_dim=2, kv_lora_rank=2,
               num_hidden_layers=2, first_k_dense_replace=1,
               intermediate_size=8, moe_intermediate_size=3,
               n_routed_experts=2, router_experts=4, num_experts_per_tok=2,
               n_shared_experts=1, vocab_size=10)
    # per token, 2 FLOPs per multiply-add; MLA per layer:
    #   q 4x(1x3)=12, latent 4x(2+1)=12, k/v up 2x(1x4)=8, out (1x2)x4=8
    #   -> 40 MACs; 2 layers -> 80 MACs -> 160
    # dense layer SwiGLU 3 x 4x8 = 96 MACs -> 192
    # MoE layer: router 4x4=16 MACs -> 32; shared 3 x 4x3 = 36 MACs -> 72;
    #   routed 2 x 2/4 = 1 row a token of 36 MACs -> 72; together 176
    # head 4x10 = 40 MACs -> 80
    # 3 tokens -> 3 x (160 + 192 + 176 + 80) = 1824
    # attention: 6 causal pairs, 1 head, q.k 3 + p.v 2 MACs, 2 layers
    #   -> 6 x 5 x 2 = 60 MACs -> 120
    flops = harness.module("flops", "deepseek_v2")
    assert flops.forward_flops(cfg, 1, 3) == 1824 + 120
    assert flops.train_step_flops(cfg, 2, 3) == 3 * 2 * (1824 + 120)
    # each held row: gate, up, down 3 x 4x3 = 36 MACs, in four passes
    assert flops.expert_flops(cfg, 5) == 5 * 72 * 4


def test_cell_size_model_flops():
    cfg = harness.config("deepseek-v2-lite.l5")
    got = harness.module("flops", "deepseek_v2").train_step_flops(
        cfg, 2, 8192)
    assert abs(got - 35.667e12) < 0.01e12


class Outcome:
    def __init__(self, trace=None, counters=None):
        self.trace = trace or {}
        self.counters = counters or {}


@pytest.mark.parametrize("name", ["train.moe_dispatch_ms",
                                  "train.moe_experts_ms",
                                  "moe_experts_roofline",
                                  "train.collective_exposed_share"])
def test_readers_find_nothing_without_their_inputs(name):
    cell = harness.Cell.load("dsv2lite-train-s8k")
    read = harness.module("metrics", name).read
    peak = harness.peaks("TPU v5 lite")
    assert read(Outcome(), cell, peak) is None


@pytest.mark.parametrize("name", ["train.moe_dispatch_ms",
                                  "train.moe_experts_ms",
                                  "moe_experts_roofline"])
def test_moe_readers_find_nothing_in_a_program_without_moe_scopes(name):
    cell = harness.Cell.load("dsv2lite-train-s8k")
    read = harness.module("metrics", name).read
    out = Outcome(trace={"window_s": 1.0, "collective_exposed_s": 0.0,
                         "scopes": {"mlp": {"forward": 1.0}}},
                  counters={"model_flops": 1.0})
    assert read(out, cell, harness.peaks("TPU v5 lite")) is None


def test_readers_on_a_made_up_profile():
    cell = harness.Cell.load("dsv2lite-train-s8k")
    peak = harness.peaks("TPU v5 lite")
    steps = cell.traffic["profile_steps"]
    trace = {"window_s": 2.0, "collective_exposed_s": 0.1,
             "scopes": {"moe_dispatch": {"forward": 0.03, "backward": 0.06},
                        "moe_experts": {"forward": 0.1, "recompute": 0.1,
                                        "backward": 0.2}}}
    rows = 3 * 4 * 12288
    out = Outcome(trace=trace, counters={"moe_held_rows_profiled": rows})

    def read(name):
        return harness.module("metrics", name).read(out, cell, peak)

    assert read("train.moe_dispatch_ms") == pytest.approx(90.0 / steps)
    assert read("train.moe_experts_ms") == pytest.approx(400.0 / steps)
    flops = 4 * 6 * 2048 * 1408 * rows
    assert read("moe_experts_roofline") == pytest.approx(
        100 * flops / (0.4 * 197e12))
    assert read("train.collective_exposed_share") == pytest.approx(5.0)


def test_moe_driver_counts_held_rows():
    cell = bench_tiny.tiny_cell("dsv2lite-train-s8k")
    driver = harness.module("drivers", cell.traffic["driver"])
    outcome = driver.measure(cell, seed=2**31 + 3, seconds=0.2, trace=False,
                             t_start=time.perf_counter())
    # 2 MoE layers, 4 x 64 tokens, top-4 of 16 experts, 4 held: each step
    # computes between none and all of the 2 x 256 x 4 assignments
    per_step = outcome.counters["moe_held_rows"] / outcome.attempted
    assert 0 < per_step <= 2 * 256 * 4


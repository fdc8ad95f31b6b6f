"""The whole training step's share of the chips' bf16 peak over the timed
window, in percent: model FLOPs of the window's steps (``bench/flops``,
forward and backward, recomputation not counted) over window x chips x
peak FLOP/s.  Nothing without a peak for the device."""


def read(outcome, cell, peak):
    if not peak:
        return None
    return 100.0 * outcome.counters["model_flops"] / (
        outcome.window_s * outcome.chips * peak["bf16_flops_per_s"])

"""Model FLOPs of a Mamba-2 step, from the configuration's widths.

Counted, two FLOPs per multiply-add: the input and output projections,
the depthwise convolution, and the state-space model as the chunked SSD
algorithm (arXiv:2405.21060, section 6) computes it at the configuration's
chunk size ``Q``: within a chunk ``C B^T`` and its product with ``x`` over
the causal pairs (``Q (Q + 1) / 2`` per chunk), each position's
contribution to the chunk's state (``B^T x``), and each position's read of
the state entering its chunk (``C h``); then the tied head over the real
vocabulary.  The backward pass costs twice the forward.  Not counted:
recomputation, norms, gates, the state passing between chunks, the loss
and the optimizer.
"""
from __future__ import annotations


def forward_flops(cfg: dict, batch: int, seq: int) -> float:
    D, L, V = cfg["d_model"], cfg["n_layer"], cfg["vocab_size"]
    N, W, P = cfg["d_state"], cfg["d_conv"], cfg["headdim"]
    DI = cfg["expand"] * D
    H = DI // P
    C = DI + 2 * N
    Q = min(cfg["chunk_size"], seq)
    proj = 2 * D * (DI + C + H) + 2 * DI * D
    conv = 2 * W * C
    pairs_per_token = (Q + 1) / 2  # Q (Q + 1) / 2 pairs over Q positions
    ssd = pairs_per_token * (2 * N + 2 * H * P) + 2 * (2 * N * H * P)
    per_token = L * (proj + conv + ssd) + 2 * D * V
    return float(batch * seq * per_token)


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    return 3.0 * forward_flops(cfg, batch, seq)

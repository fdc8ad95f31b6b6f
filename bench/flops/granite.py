"""Model FLOPs of a GQA decoder step (granite and its kin), from the
configuration's widths.

Counted: every matrix product of the forward pass (two FLOPs per
multiply-add) over the causal pairs attention needs (``seq (seq + 1) / 2``
query-key pairs per head), and the tied head over the real vocabulary.
The backward pass costs twice the forward.  Not counted: recomputation
(rematerialisation), norms, softmax, the loss and the optimizer, and
attention over masked (future) positions.
"""
from __future__ import annotations


def forward_flops(cfg: dict, batch: int, seq: int) -> float:
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, F, V = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    proj = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    per_token = 2 * proj * L + 2 * D * V
    pairs = seq * (seq + 1) / 2
    attention = L * 2 * (2 * H * hd) * pairs  # q.k and p.v per pair
    return float(batch * (seq * per_token + attention))


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    return 3.0 * forward_flops(cfg, batch, seq)

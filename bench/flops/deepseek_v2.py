"""Model FLOPs of a DeepSeek-V2 training step (one chip's share), from the
configuration's widths, and the FLOPs the held experts' grouped matmuls
execute.

Counted, two FLOPs per multiply-add: every matrix product of the forward
pass per token (MLA's q, latent, per-head key/value and output
projections; the dense layers' SwiGLU; each MoE layer's router, its shared
experts, and the routed experts at the expected share of assignments that
a held expert takes, ``num_experts_per_tok * n_routed_experts /
router_experts`` rows a token, 0.75 at 6 of 64 with 8 held), attention
over the causal pairs (``seq (seq + 1) / 2`` query-key pairs per head,
q.k at qk_nope + qk_rope and p.v at v_head_dim), and the untied head over
the vocabulary's slice.  The backward pass costs twice the forward.  Not
counted: recomputation, norms, softmax, the router's top-k, dispatch, the
loss and the optimizer.
"""
from __future__ import annotations

# forward, recompute (block remat) and the two products of the backward
# pass (input and weight gradients) each run every grouped matmul once
EXPERT_PASSES = 4


def expert_row_flops(cfg: dict) -> float:
    """One (token, expert) row through the SwiGLU expert: gate, up, down."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops(cfg: dict, batch: int, seq: int) -> float:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rdim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vdim, kvlr = cfg["v_head_dim"], cfg["kv_lora_rank"]
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    mla = 2 * (D * H * (nope + rdim) + D * (kvlr + rdim)
               + kvlr * H * (nope + vdim) + H * vdim * D)
    dense_mlp = 6 * D * cfg["intermediate_size"]
    held_rows = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                 / cfg["router_experts"])
    moe = (2 * D * cfg["router_experts"]
           + 6 * D * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
           + held_rows * expert_row_flops(cfg))
    per_token = (L * mla + dense * dense_mlp + (L - dense) * moe
                 + 2 * D * cfg["vocab_size"])
    pairs = seq * (seq + 1) / 2
    attention = L * 2 * H * (nope + rdim + vdim) * pairs
    return float(batch * (seq * per_token + attention))


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    return 3.0 * forward_flops(cfg, batch, seq)


def expert_flops(cfg: dict, held_rows: float) -> float:
    """FLOPs the grouped matmuls execute for ``held_rows`` (token, held
    expert) rows over every pass of a training step."""
    return EXPERT_PASSES * expert_row_flops(cfg) * held_rows

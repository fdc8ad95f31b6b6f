"""Tiny widths of the configurations that cells added after
``bench_tiny`` brought (``bench_tiny.TINY``), for the tests that run every
cell at a size the CPU holds.  ``tests/conftest.py`` imports this module
before the tests are collected."""
import bench_tiny

bench_tiny.TINY.setdefault("deepseek-v2-lite.l5", (
    dict(hidden_size=128, num_attention_heads=4, kv_lora_rank=32,
         qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
         intermediate_size=256, moe_intermediate_size=64, n_routed_experts=4,
         router_experts=16, num_experts_per_tok=4, num_hidden_layers=3,
         vocab_size=512),
    dict(num_layers=3, d_model=128, num_heads=4, num_kv_heads=4,
         head_dim=32, kv_lora_rank=32, qk_nope_head_dim=16,
         qk_rope_head_dim=16, v_head_dim=16, d_ff=256, moe_d_ff=64,
         num_experts=16, experts_held=4, expert_offset=0, top_k=4,
         vocab_size=512)))

"""DeepSeek-V2-Lite — MLA + fine-grained MoE. [arXiv:2405.04434,
hf:deepseek-ai/DeepSeek-V2-Lite]

27L d_model=2048, 16 heads MLA without q-LoRA (kv_lora_rank=512,
qk_nope=128, qk_rope=64, v=128), YaRN rope (factor 40 over 4096 positions,
beta 32/1, mscale = mscale_all_dim = 0.707); layer 0 dense with d_ff=10944,
layers 1-26 MoE: 64 routed experts (d_ff=1408) top-6 by softmax, weights
not renormalised, plus 2 shared experts; sequence-wise load-balance loss;
untied vocabulary of 102400 (model card).
"""
from repro.configs.base import ModelConfig, SlotSpec

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,  # MLA: per-head KV reconstructed from the latent
    head_dim=192,  # qk_nope + qk_rope
    d_ff=10944,  # dense d_ff (first_k_dense layers)
    vocab_size=102400,
    pattern=(SlotSpec("mla", "moe"),),
    first_k_dense=1,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    seq_aux=True,
    aux_loss_alpha=0.001,
    kv_lora_rank=512,
    q_lora_rank=0,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
)

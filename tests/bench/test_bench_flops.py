"""Model FLOPs against counts made by hand at a small size."""
import bench_tiny  # noqa: F401
import harness


def test_granite_hand_count():
    cfg = dict(hidden_size=4, num_hidden_layers=1, num_attention_heads=2,
               num_key_value_heads=1, head_dim=2, intermediate_size=8,
               vocab_size=10)
    # per token, 2 FLOPs per multiply-add:
    #   q 4x(2x2)=16, k and v 4x(1x2)=8 each, o (2x2)x4=16,
    #   gate, up 4x8=32 each, down 8x4=32 -> 144 MACs -> 288
    #   tied head 4x10=40 MACs -> 80
    # 3 tokens -> 3 x 368 = 1104
    # attention, causal pairs of 3 positions = 6; per pair and head q.k and
    # p.v are 2 MACs each -> 6 x 2 heads x 4 MACs = 48 MACs -> 96
    flops = harness.module("flops", "granite")
    assert flops.forward_flops(cfg, 1, 3) == 1104 + 96
    assert flops.train_step_flops(cfg, 2, 3) == 3 * 2 * (1104 + 96)


def test_mamba2_hand_count():
    cfg = dict(d_model=4, n_layer=1, vocab_size=10, d_state=2, d_conv=2,
               headdim=2, expand=2, chunk_size=2)
    # d_inner 8, heads 4, conv channels 8 + 2 x 2 = 12; per token:
    #   in-projections 4 x (8 + 12 + 4) = 96 MACs, out 8 x 4 = 32 -> 256
    #   conv 2 taps x 12 channels = 24 MACs -> 48
    #   tied head 40 MACs -> 80
    # SSD per chunk of 2 positions (3 causal pairs): C.B 3 x 2 = 6 MACs,
    #   weights . x 3 x 4 heads x 2 = 24, chunk state 2 x 2 x 4 x 2 = 32,
    #   read-out 32 -> 94 MACs = 188 FLOPs per chunk, 94 per token
    # 4 tokens -> 4 x (256 + 48 + 94 + 80) = 1912
    flops = harness.module("flops", "mamba2")
    assert flops.forward_flops(cfg, 1, 4) == 1912
    assert flops.train_step_flops(cfg, 1, 4) == 3 * 1912

"""DeepSeek-V2's pieces against plain computations on the CPU: MLA without
q-LoRA under YaRN rope, the sequence-wise balance loss, the grouped matmul
(megablox ``gmm`` in interpret mode) against XLA's ``ragged_dot``, the
sized row buffer and its full-size fallback against the plain layer, and
the training step's count of the rows its held experts computed."""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import attention as A
from repro.models import moe as moe_lib
from repro.models.common import materialize, yarn_mscale, yarn_ramp

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _reference():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from reference import common, deepseek_v2

    return common, deepseek_v2


def test_yarn_ramp_and_scale_at_published_widths():
    """DeepSeek-V2-Lite: the ramp runs over frequency indices 10..23 of the
    64-wide rotary part, the softmax scale is 192^-1/2 * mscale^2 with
    mscale = 0.1 * 0.707 * ln 40 + 1, and the cos/sin factor is 1."""
    cfg = get_config("deepseek-v2-lite")
    assert yarn_ramp(cfg.qk_rope_head_dim, cfg.rope_theta,
                     cfg.yarn_original_max_position, cfg.yarn_beta_fast,
                     cfg.yarn_beta_slow) == (10, 23)
    assert abs(yarn_mscale(40.0, 0.707) - 1.26080) < 1e-5
    assert abs(A.mla_scale(cfg) - 0.11472) < 1e-5
    freqs, m = A.mla_rope(cfg)
    assert m == 1.0
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(np.asarray(freqs)[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(freqs)[23:], plain[23:] / 40,
                               rtol=1e-6)


def test_mla_without_q_lora_matches_reference():
    """The program's full-sequence MLA (direct q projection, YaRN rope,
    mscale^2 softmax scale) against the benchmark's plain reference, in
    float32, on seeded random weights."""
    common, ref = _reference()
    cfg = get_config("deepseek-v2-lite").reduced()
    assert cfg.q_lora_rank == 0 and cfg.yarn_factor == 40.0
    rcfg = {"qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "kv_lora_rank": cfg.kv_lora_rank, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps,
            "rope_scaling": {"factor": 40, "beta_fast": 32, "beta_slow": 1,
                             "mscale": 0.707, "mscale_all_dim": 0.707,
                             "original_max_position_embeddings": 4096}}
    p = materialize(A.mla_specs(cfg, 1), jax.random.PRNGKey(3))
    p = jax.tree_util.tree_map(lambda a: a[0] * 0.3, p)
    S = 64
    x = jax.random.normal(jax.random.PRNGKey(4), (1, S, cfg.d_model))
    pos = jnp.arange(S)[None]
    with jax.default_matmul_precision("highest"):
        got, _ = A.mla_forward(p, x, pos, cfg, "mla", impl="dense")
        want = ref.attention(rcfg, common.POLICIES["float32"], x[0], p)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_sequence_wise_balance_loss():
    """sum_i f_i P_i per sequence, averaged over the sequences, with
    f_i = E / (k S) * #{t: i in topk(t)} and P_i the mean probability;
    the batch-wise form pools the sequences."""
    rng = np.random.default_rng(0)
    B, S, E, K = 3, 20, 8, 2
    probs = rng.dirichlet(np.ones(E), size=B * S).astype(np.float32)
    idx = np.argsort(-probs, axis=-1)[:, :K].astype(np.int32)

    def direct(p, i, S):
        counts = np.bincount(i.reshape(-1), minlength=E)
        return float(np.sum(counts * E / (K * S) * p.mean(0)))

    want = np.mean([direct(probs[b * S:(b + 1) * S], idx[b * S:(b + 1) * S],
                           S) for b in range(B)])
    got = moe_lib.load_balance_loss(jnp.asarray(probs), jnp.asarray(idx),
                                    E, B)
    assert abs(float(got) - want) < 1e-5
    pooled = moe_lib.load_balance_loss(jnp.asarray(probs), jnp.asarray(idx),
                                       E, 1)
    assert abs(float(pooled) - direct(probs, idx, B * S)) < 1e-5


def test_router_weights_follow_the_configuration():
    """DeepSeek-V2-Lite: raw top-k softmax probabilities (no
    renormalisation) times routed_scaling_factor; a renormalising
    configuration's weights sum to the factor."""
    cfg = get_config("deepseek-v2-lite").reduced().replace(
        num_experts=8, top_k=3, d_model=32, routed_scaling_factor=2.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (10, 32))
    r = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    w, idx, _ = moe_lib.route(x, r, cfg, 1)
    probs = jax.nn.softmax(x @ r, axis=-1)
    np.testing.assert_allclose(
        np.asarray(w),
        2.0 * np.take_along_axis(np.asarray(probs), np.asarray(idx), -1),
        rtol=1e-5)
    w, _, _ = moe_lib.route(x, r, cfg.replace(norm_topk_prob=True), 1)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.0, rtol=1e-5)


@pytest.mark.parametrize("held,offset", [(4, 0), (2, 3), (8, 0)])
def test_gmm_matches_ragged_dot_with_gradients(held, offset):
    """The held-expert layer through the Pallas grouped matmul (interpret
    mode) equals it through ragged_dot, output and every gradient."""
    cfg = get_config("deepseek-v2-lite").reduced().replace(
        num_experts=8, top_k=3, moe_d_ff=32, d_model=32, experts_held=held,
        expert_offset=offset)
    p = materialize(moe_lib.moe_specs(cfg, 1), jax.random.PRNGKey(5))
    p = jax.tree_util.tree_map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 32))

    def run(impl):
        def f(p, x):
            out, stats = moe_lib.moe_mlp(p, x, cfg, impl=impl)
            return jnp.sum(out * out), (out, stats["moe_held_rows"])
        return jax.grad(f, argnums=(0, 1), has_aux=True)(p, x)

    (gp, gx), (out, rows) = run("gmm")
    (rp, rx), (want, want_rows) = run("ragged_dot")
    assert int(rows) == int(want_rows) > 0
    for a, b in zip(jax.tree_util.tree_leaves((out, gp, gx)),
                    jax.tree_util.tree_leaves((want, rp, rx))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def _held_share_layer(held):
    """A 16-expert top-4 layer holding ``held`` experts (from expert 5 where
    it holds a share), its parameters and 2 x 32 tokens."""
    cfg = get_config("deepseek-v2-lite").reduced().replace(
        num_experts=16, top_k=4, moe_d_ff=32, d_model=32, experts_held=held,
        expert_offset=0 if held == 16 else 5)
    p = materialize(moe_lib.moe_specs(cfg, 1), jax.random.PRNGKey(5))
    p = jax.tree_util.tree_map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 32))
    return cfg, p, x


def _value_and_grads(layer, p, x):
    def f(p, x):
        out, stats = layer(p, x)
        return jnp.sum(out * out), (out, stats)
    (gp, gx), (out, stats) = jax.grad(f, argnums=(0, 1), has_aux=True)(p, x)
    return (out, gp, gx), stats


@pytest.mark.parametrize("routing", ["ordinary", "all_to_held", "all_held"])
def test_sized_buffer_matches_reference_and_full_buffer(routing,
                                                         monkeypatch):
    """2 of 16 experts at top-4 over 64 tokens: the sized buffer holds 64
    rows against the full 128.  Ordinary routing fits it; a router that
    sends every token to the held experts (128 assignments) takes the full
    buffer, and drops nothing; with all 16 held the sized buffer is not
    smaller and the layer has one path.  Output and every gradient equal
    the plain layer's and the full buffer's alone."""
    from repro.obs import TRACE_COUNTS

    cfg, p, x = _held_share_layer(16 if routing == "all_held" else 2)
    if routing == "all_to_held":
        x = jnp.abs(x)
        p["router"] = p["router"].at[:, 5:7].add(1.0)
    sized = TRACE_COUNTS.counter("moe/sized_buffer")
    before = sized.value
    got, stats = _value_and_grads(
        lambda p, x: moe_lib.moe_mlp(p, x, cfg, impl="ragged_dot"), p, x)
    assert (sized.value > before) == (routing != "all_held")
    assert stats["moe_overflow_layers"].dtype == jnp.int32
    assert int(stats["moe_overflow_layers"]) == (routing == "all_to_held")
    if routing == "all_to_held":
        assert int(stats["moe_held_rows"]) == 64 * 2
    ref, _ = _value_and_grads(
        lambda p, x: (moe_lib.moe_mlp_ref(p, x, cfg), None), p, x)
    monkeypatch.setattr(moe_lib, "FIT_FACTOR", 10 ** 6)
    before = sized.value
    full, full_stats = _value_and_grads(
        lambda p, x: moe_lib.moe_mlp(p, x, cfg, impl="ragged_dot"), p, x)
    assert sized.value == before
    assert int(full_stats["moe_held_rows"]) == int(stats["moe_held_rows"])
    for want in (ref, full):
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("factor,full_rows_saved", [(2, False),
                                                    (10 ** 6, True)])
def test_sized_path_saves_no_full_size_rows(factor, full_rows_saved,
                                            monkeypatch, capsys):
    """The residuals the backward pass keeps of the routed part: on the
    sized path the layer's operands and index vectors, no float array of
    the full buffer's 128 rows with a feature dimension (a differentiated
    ``lax.cond`` would keep both branches' buffers); the full buffer alone
    keeps its gathered rows."""
    import jax.ad_checkpoint

    cfg, p, x = _held_share_layer(2)
    monkeypatch.setattr(moe_lib, "FIT_FACTOR", factor)
    xf = x.reshape(-1, cfg.d_model)
    w, idx, _ = moe_lib.route(xf, p["router"], cfg, 1)

    def routed(xf, w, wg, wu, wd):
        out, _ = moe_lib.routed_experts(
            xf, w, idx, wg, wu, wd, e_lo=cfg.expert_offset,
            num_experts=cfg.num_experts, impl="ragged_dot")
        return jnp.sum(out)

    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        routed, xf, w, p["w_gate"], p["w_up"], p["w_down"])
    saved = capsys.readouterr().out
    full = re.findall(r"(?:bf|f)\d+\[128,\d+", saved)
    assert bool(full) == full_rows_saved, saved


def test_train_step_counts_held_rows_and_names_moe_scopes():
    """A tiny DeepSeek-V2-Lite step (one chip's share: 4 of 16 experts,
    top-4): its metrics carry moe_held_rows, an int, the held experts'
    assignments over the MoE layers, and moe_overflow_layers, an int32
    scalar, the layers that outgrew the sized buffer; TRACE_COUNTS counts
    the MoE implementation and the sized buffer traced; the compiled step
    names moe_dispatch and moe_experts inside mlp, forward and backward."""
    from repro.launch.steps import build_train_step
    from repro.models import model as M
    from repro.models.blocks import RunConfig
    from repro.obs import TRACE_COUNTS
    from repro.optim.adamw import OptConfig, init_state

    cfg = get_config("deepseek-v2-lite").reduced().replace(
        num_layers=3, num_experts=16, experts_held=4, top_k=4, moe_d_ff=32,
        d_model=64, vocab_size=256)
    opt = OptConfig(lr=1e-3, warmup_steps=1, total_steps=4)
    run = RunConfig(attn_impl="dense", remat="block")
    params = materialize(M.model_specs(cfg), jax.random.PRNGKey(0))
    state = init_state(opt, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    batch = {"tokens": toks, "labels": toks}
    counts = {n: TRACE_COUNTS.counter(f"moe/{n}")
              for n in ("ragged_dot", "sized_buffer")}
    before = {n: c.value for n, c in counts.items()}
    step = jax.jit(build_train_step(cfg, run, opt))
    _, _, metrics = step(params, state, batch)
    for n, c in counts.items():
        assert c.value > before[n], n
    rows = metrics["moe_held_rows"]
    assert rows.dtype == jnp.int32 and 0 < int(rows) <= 2 * 2 * 32 * 4
    overflow = metrics["moe_overflow_layers"]
    assert overflow.dtype == jnp.int32 and overflow.shape == ()
    assert 0 <= int(overflow) <= 2
    # the same counts from the router directly
    _, _, stats = M.forward(params, batch, cfg, run)
    assert int(stats["moe_held_rows"]) == int(rows)
    assert int(stats["moe_overflow_layers"]) == int(overflow)
    hlo = step.lower(params, state, batch).compile().as_text()
    names = " ".join(set(re.findall(r'op_name="([^"]*)"', hlo)))
    for scope in ("moe_dispatch", "moe_experts"):
        assert re.search(rf"mlp/[^ ]*{scope}", names)
        assert re.search(rf"transpose\([^ ]*{scope}", names)

"""Hypothesis property tests on system invariants: ring-cache position
reconstruction, the dropless MoE layer's expert shares and quantization
bounds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed; property tests skipped")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.models.attention import _ring_positions, quantize_kv, dequantize_kv
from repro.models import moe as moe_lib
from repro.configs.base import get_config
from repro.models.common import materialize


@given(st.integers(1, 10_000), st.integers(4, 64))
@settings(max_examples=50, deadline=None)
def test_ring_positions_invariants(pos, window):
    """Every valid slot holds a position in (pos-window, pos]; the write slot
    holds exactly pos; invalid slots are negative."""
    p = jnp.array([pos], jnp.int32)
    wpos, k_pos = _ring_positions(p, window, window, 1)
    k = np.asarray(k_pos[0])
    w = int(wpos[0])
    assert w == pos % window
    assert k[w] == pos  # the just-written slot
    valid = k[k >= 0]
    assert np.all(valid <= pos)
    assert np.all(pos - valid < window)
    # all valid positions distinct (no aliasing inside the window)
    assert len(np.unique(valid)) == len(valid)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_kv_quantization_bounded_error(seed):
    """int8 KV round-trip error is bounded by scale/2 = max|x|/254."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (2, 1, 4, 32), jnp.float32) * 3.0
    q, s = quantize_kv(x)
    back = dequantize_kv(q, s, jnp.float32)
    bound = np.asarray(jnp.max(jnp.abs(x), axis=-1) / 254.0 * 1.01)
    err = np.asarray(jnp.max(jnp.abs(back - x), axis=-1))
    assert np.all(err <= bound + 1e-6)


def _moe_layer(cfg, seed):
    p = materialize(moe_lib.moe_specs(cfg, 1), jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(lambda a: a[0], p)


@given(st.integers(0, 1000), st.sampled_from([1, 2, 4]))
@settings(max_examples=12, deadline=None)
def test_moe_expert_shares_sum_to_the_whole_layer(seed, shares):
    """The dropless layer told which experts it holds: the routed parts
    that ``shares`` devices compute from their own experts, plus the
    shared experts counted once, equal the uncut layer, and between them
    they compute every (token, expert) assignment once."""
    cfg = get_config("deepseek-v2-lite").reduced().replace(
        num_experts=8, top_k=3, moe_d_ff=32, d_model=32)
    p = _moe_layer(cfg, seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, 32))
    whole, stats = moe_lib.moe_mlp(p, x, cfg)
    n = 8 // shares
    part_cfg = cfg.replace(num_shared_experts=0, experts_held=n)
    total, rows = moe_lib.dense_mlp(p["shared"], x), 0
    for i in range(shares):
        held = {k: (v[i * n:(i + 1) * n] if k.startswith("w_") else v)
                for k, v in p.items() if k != "shared"}
        out, st_i = moe_lib.moe_mlp(held, x, part_cfg.replace(
            expert_offset=i * n))
        total, rows = total + out, rows + int(st_i["moe_held_rows"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert rows == int(stats["moe_held_rows"]) == 2 * 16 * 3


@given(st.integers(0, 1000), st.integers(0, 3))
@settings(max_examples=8, deadline=None)
def test_moe_batch_routed_to_one_held_expert_loses_no_token(seed, expert):
    """Every token routed to the same held expert (the router's column for
    it dominates): the layer computes all of them, as the every-expert
    reference does, where a capacity-factor dispatch would drop most."""
    cfg = get_config("deepseek-v2-lite").reduced().replace(
        num_experts=16, top_k=1, moe_d_ff=32, d_model=32,
        num_shared_experts=0, experts_held=4, expert_offset=4)
    p = _moe_layer(cfg, seed)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 32, 32)))
    p["router"] = p["router"].at[:, 4 + expert].set(10.0)
    out, stats = moe_lib.moe_mlp(p, x, cfg)
    assert int(stats["moe_held_rows"]) == 2 * 32
    ref = moe_lib.moe_mlp_ref(p, x, cfg)
    assert float(jnp.min(jnp.linalg.norm(out, axis=-1))) > 0.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

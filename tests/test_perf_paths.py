"""§Perf optimization paths must be numerically equivalent to baselines."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.models import model as M
from repro.models.blocks import RunConfig
from repro.models.common import materialize


def _decode_seq(cfg, run, S=12):
    key = jax.random.PRNGKey(0)
    params = materialize(M.model_specs(cfg), key)
    tokens = jax.random.randint(key, (2, S), 0, cfg.vocab_size)
    caches = jax.tree_util.tree_map(
        jnp.zeros_like, materialize(M.cache_specs(cfg, 2, s_max=S), key))
    outs = []
    step = jax.jit(lambda p, t, pos, c: M.decode_step(p, t, pos, c, cfg, run))
    for i in range(S):
        lg, caches = step(params, tokens[:, i : i + 1],
                          jnp.full((2,), i, jnp.int32), caches)
        outs.append(np.asarray(lg[:, 0], np.float32))
    return np.stack(outs, 1)


def test_cache_scatter_matches_onehot_gqa():
    cfg = get_config("granite-3-2b").reduced()
    a = _decode_seq(cfg, RunConfig(attn_impl="dense", remat="none"))
    b = _decode_seq(cfg, RunConfig(attn_impl="dense", remat="none",
                                   cache_scatter=True))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_cache_scatter_matches_onehot_swa_ring():
    cfg = get_config("gemma2-27b").reduced().replace(sliding_window=8)
    a = _decode_seq(cfg, RunConfig(attn_impl="dense", remat="none"), S=16)
    b = _decode_seq(cfg, RunConfig(attn_impl="dense", remat="none",
                                   cache_scatter=True), S=16)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_cache_scatter_matches_onehot_mla():
    cfg = get_config("minicpm3-4b").reduced()
    a = _decode_seq(cfg, RunConfig(attn_impl="dense", remat="none"))
    b = _decode_seq(cfg, RunConfig(attn_impl="dense", remat="none",
                                   cache_scatter=True))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_logit_sharding_noop_on_single_device():
    """The logit constraint must not change values (single-device: no-op
    sharding, value equality is exact)."""
    cfg = get_config("granite-3-2b").reduced()
    params = materialize(M.model_specs(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    run_a = RunConfig(attn_impl="dense", remat="none")
    la, _, _ = M.forward(params, {"tokens": toks}, cfg, run_a)
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
    run_b = RunConfig(attn_impl="dense", remat="none",
                      logit_sharding=NamedSharding(mesh, P(None, None, None)))
    lb, _, _ = M.forward(params, {"tokens": toks}, cfg, run_b)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_int8_kv_cache_close_to_bf16():
    """int8-quantized KV cache: greedy decode tokens should match and logits
    stay close to the bf16-cache path."""
    cfg = get_config("granite-3-2b").reduced()
    run = RunConfig(attn_impl="dense", remat="none", cache_scatter=True)
    key = jax.random.PRNGKey(0)
    params = materialize(M.model_specs(cfg), key)
    S = 24
    tokens = jax.random.randint(key, (2, S), 0, cfg.vocab_size)

    def roll(quant):
        caches = jax.tree_util.tree_map(
            jnp.zeros_like,
            materialize(M.cache_specs(cfg, 2, s_max=S, kv_quant=quant), key))
        step = jax.jit(lambda p, t, pos, c: M.decode_step(p, t, pos, c, cfg, run))
        outs = []
        for i in range(S):
            lg, caches = step(params, tokens[:, i:i+1],
                              jnp.full((2,), i, jnp.int32), caches)
            outs.append(np.asarray(lg[:, 0], np.float32))
        return np.stack(outs, 1)

    a, b = roll(False), roll(True)
    # greedy decisions must agree on the vast majority of steps (random-init
    # logits have near-ties, so a margin below 1.0 is expected)
    agree = np.mean(np.argmax(a, -1) == np.argmax(b, -1))
    assert agree >= 0.8, agree
    # logits close in aggregate
    assert np.mean(np.abs(a - b)) < 0.15 * np.mean(np.abs(a))

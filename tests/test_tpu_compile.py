"""Compile the main-path kernels and the training step for a described TPU
v5e chip, without the chip.

The TPU compiler refuses what Pallas interpret mode accepts (block shapes
off the 8 x 128 tiling, programs larger than the device), so these
compiles guard every change at no chip time.  The topology is described
inside a fixture, never at import time: only one process may load the TPU
library, and each test worker imports every test file.  The persistent
compilation cache is off around these compiles; entries written for a
described chip cannot be read back without one.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

GIB = 2 ** 30
# granite-3-2b attention (S=4096) and mamba2-780m SSD widths
B, H, KV, D, S = 4, 32, 8, 64, 4096
SSD_H, SSD_P, SSD_N, SSD_CHUNK = 48, 64, 128, 256
PAGE, N_PAGES = 16, S // 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash(sds):
    from repro.kernels.flash_attention import flash_attention

    fn = functools.partial(flash_attention, scale=D ** -0.5, interpret=False)
    return fn, (sds((B, H, S, D)), sds((B, KV, S, D)), sds((B, KV, S, D)))


def _decode(sds):
    from repro.kernels.decode_attention import decode_attention

    fn = functools.partial(decode_attention, scale=D ** -0.5,
                           interpret=False)
    return fn, (sds((B, H, D)), sds((B, KV, S, D)), sds((B, KV, S, D)),
                sds((B,), jnp.int32))


def _paged(sds):
    from repro.kernels.decode_attention import paged_decode_attention

    fn = functools.partial(paged_decode_attention, scale=D ** -0.5,
                           interpret=False)
    pool = (2 * B * N_PAGES, KV, PAGE, D)
    return fn, (sds((B, H, D)), sds(pool), sds(pool),
                sds((B, N_PAGES), jnp.int32), sds((B,), jnp.int32))


def _ssd(sds):
    from repro.kernels.ssd_scan import ssd_scan

    fn = functools.partial(ssd_scan, chunk=SSD_CHUNK, interpret=False)
    f32 = jnp.float32
    return fn, (sds((B, SSD_H, S, SSD_P), f32), sds((B, SSD_H, S), f32),
                sds((SSD_H,), f32), sds((B, S, SSD_N), f32),
                sds((B, S, SSD_N), f32))


def _flash_kernel(kernel):
    """One of the flash kernels at granite-3-2b's training widths."""
    def build(sds):
        from repro.kernels import flash_attention as fa

        b, s = 4, 2048
        fwd, bwd = fa.block_sizes(s, s, D)
        plan = fa._Plan(scale=D ** -0.5, window=0, cap=0.0, sk_real=s,
                        fwd=fwd, bwd=bwd, interpret=False)
        q, kv = sds((b, H, D, s)), sds((b, KV, D, s))  # (B, H, D, S)
        row = sds((b, H, 1, s), jnp.float32)
        if kernel == "forward":
            return (lambda q, k, v: fa._forward(q, k, v, plan)), (q, kv, kv)
        fn = fa._dkv if kernel == "dkv" else fa._dq
        return (lambda *a: fn(*a, plan)), (q, kv, kv, q, row, row)
    return build


FLASH_KERNELS = ("forward", "dkv", "dq")


@pytest.mark.parametrize(
    "build", [_flash, _decode, _paged, _ssd]
    + [_flash_kernel(k) for k in FLASH_KERNELS],
                         ids=["flash_attention", "decode_attention",
                              "paged_decode_attention", "ssd_scan"]
                         + [f"flash_{k}_granite" for k in FLASH_KERNELS])
def test_kernel_compiles_for_v5e(one_chip, build):
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = build(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _granite_step_memory(one_chip, attn_impl):
    """Compile the step chip_smoke.py trains (published widths, 8 of 40
    layers, batch 4 x 2048, float32 AdamW, block remat) as Session.train
    runs it; its memory analysis and optimized HLO."""
    from repro.configs.base import get_config
    from repro.launch.steps import build_train_step
    from repro.models import model as M
    from repro.models.blocks import RunConfig
    from repro.models.common import materialize
    from repro.optim.adamw import OptConfig, init_state

    cfg = get_config("granite-3-2b").replace(num_layers=8)
    opt = OptConfig(lr=1e-3, warmup_steps=1, total_steps=4)
    run = RunConfig(attn_impl=attn_impl, remat="block")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: materialize(M.model_specs(cfg), jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda p: init_state(opt, p), params))
    tok = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    step = jax.jit(build_train_step(cfg, run, opt), donate_argnums=(0, 1))
    compiled = step.lower(params, state, {"tokens": tok, "labels": tok}
                          ).compile()
    return compiled.memory_analysis(), compiled.as_text()


def test_granite_train_step_fits_one_v5e(one_chip):
    """The step chip_smoke.py trains: published widths, 8 of 40 layers,
    batch 4 x 2048, float32 AdamW, block remat — as Session.train runs
    it."""
    ma, _ = _granite_step_memory(one_chip, "auto")
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < 16 * GIB, f"{used / GIB:.2f} GiB"


def test_granite_flash_train_step_fits_one_v5e(one_chip, monkeypatch):
    """The same step through the Pallas flash kernel, as it runs on a TPU:
    kernels compiled (not interpreted), no S x S score tensor, and fewer
    temporaries than the dense step's 5.88e9 B."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    ma, text = _granite_step_memory(one_chip, "pallas")
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < 16 * GIB, f"{used / GIB:.2f} GiB"
    # forward, dK/dV and dQ: the block remat keeps the forward's output
    # and log-sum-exp, so its recompute runs no forward kernel
    assert text.count("tpu_custom_call") == 3
    assert not re.search(r"\[4,(8,4|32),2048,2048\]", text)
    assert ma.temp_size_in_bytes < 5.88e9, ma.temp_size_in_bytes


def test_deepseek_v2_lite_train_step_fits_one_v5e(one_chip, monkeypatch):
    """The step of the benchmark's dsv2lite-train-s8k cell as it runs on a
    TPU: DeepSeek-V2-Lite at published widths, one chip's share (the dense
    layer and 4 MoE layers, 8 of 64 experts, an eighth of the vocabulary),
    2 x 8192 rows, float32 AdamW, block remat; MLA through the flash
    kernels and the experts through the grouped matmul, compiled.  It fits
    90% of the chip (Eq. 5) and holds no S x S tensor."""
    from repro.configs.base import get_config
    from repro.kernels import ops
    from repro.launch.steps import build_train_step
    from repro.models import attention, moe
    from repro.models import model as M
    from repro.models.blocks import RunConfig
    from repro.models.common import materialize
    from repro.optim.adamw import OptConfig, init_state

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe, "_auto_impl", lambda: "gmm")
    cfg = get_config("deepseek-v2-lite").replace(
        num_layers=5, experts_held=8, vocab_size=12800)
    opt = OptConfig(lr=3e-4, warmup_steps=10, total_steps=100000)
    run = RunConfig(attn_impl="auto", remat="block")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: materialize(M.model_specs(cfg), jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda p: init_state(opt, p), params))
    tok = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
    compiled = jax.jit(build_train_step(cfg, run, opt),
                       donate_argnums=(0, 1)).lower(
        params, state, {"tokens": tok, "labels": tok}).compile()
    ma, text = compiled.memory_analysis(), compiled.as_text()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < 0.9 * 16 * GIB, f"{used / GIB:.2f} GiB"
    # flash forward, dQ and dK/dV in the dense layer and in the MoE layers'
    # loop, and the grouped matmuls (3 forward; 3 recomputed and 6
    # transposed in the backward pass), once for each buffer size
    assert text.count("tpu_custom_call") == 6 + 2 * 12
    assert not re.search(r"\[[0-9,]*8192,8192\]", text)

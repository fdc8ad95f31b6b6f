"""Block (slot) composition: pre-norm mixer + residual, pre-norm MLP + residual,
optional post-norms (gemma2). Dispatches on SlotSpec (mixer, mlp)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax

from repro.configs.base import ModelConfig, SlotSpec
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.common import ParamSpec, rms_norm
from repro.obs.scopes import scope


@dataclass
class RunConfig:
    """Runtime (non-architecture) knobs — what the paper's planner tunes."""

    attn_impl: str = "auto"  # dense | chunked | pallas | auto
    remat: str = "block"  # none | block
    seq_parallel: bool = False
    microbatch: int = 0  # >0: gradient-accumulation microbatch size
    # concrete NamedShardings injected by the launcher (None on single host):
    act_sharding: Any = None  # residual stream (B, S, D)
    kv_block: int = 1024
    q_block: int = 2048
    # dry-run FLOP-accounting mode: python-unroll the layer loops so that
    # cost_analysis (which ignores while-loop trip counts) sees every op
    unroll_layers: bool = False
    # --- beyond-paper optimizations (§Perf), all off by default ---
    logit_sharding: Any = None  # keep logits seq-sharded through the CE path
    moe_mesh: Any = None  # shard_map expert-parallel MoE over this mesh
    moe_axis: str = "model"  # expert axis name within moe_mesh
    pad_heads_to: int = 0  # zero-pad Q heads so TP divides them (llava/arctic)
    grad_shardings: Any = None  # pytree of NamedShardings: force reduce-scatter
    # grad sync onto the ZeRO layout instead of GSPMD's all-reduce choice
    cache_scatter: bool = False  # decode cache write via scatter, not one-hot
    bf16_grads: bool = False  # mixed precision: grads computed/synced in bf16


def constrain(x, sharding):
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def slot_specs(cfg: ModelConfig, slot: SlotSpec, layers: int) -> Dict[str, Any]:
    la = ("layers",)
    L = (layers,)
    s: Dict[str, Any] = {
        "mixer_norm": ParamSpec(L + (cfg.d_model,), la + ("embed",), init="zeros"),
    }
    if slot.mixer == "mamba":
        s["mixer"] = ssm_lib.ssm_specs(cfg, layers)
    else:
        s["mixer"] = attn.attn_specs(cfg, slot.mixer, layers)
    if cfg.use_post_norm:
        s["mixer_post_norm"] = ParamSpec(L + (cfg.d_model,), la + ("embed",), init="zeros")

    has_mlp = not (slot.mlp == "dense" and cfg.d_ff == 0)
    if has_mlp:
        s["mlp_norm"] = ParamSpec(L + (cfg.d_model,), la + ("embed",), init="zeros")
        if slot.mlp == "dense":
            s["mlp"] = moe_lib.dense_mlp_specs(cfg.d_model, cfg.d_ff, layers)
        elif slot.mlp == "moe":
            s["mlp"] = moe_lib.moe_specs(cfg, layers)
        else:  # moe_dense: arctic — parallel dense residual + MoE
            s["mlp"] = {
                "dense": moe_lib.dense_mlp_specs(cfg.d_model, cfg.d_ff, layers),
                "moe": moe_lib.moe_specs(cfg, layers),
            }
        if cfg.use_post_norm:
            s["mlp_post_norm"] = ParamSpec(L + (cfg.d_model,), la + ("embed",), init="zeros")
    return s


# ---------------------------------------------------------------------------
# Forward (full sequence: train / prefill)
# ---------------------------------------------------------------------------


def _mixer_forward(p, h, positions, cfg, slot: SlotSpec, run: RunConfig):
    if slot.mixer == "mamba":
        with scope("mamba"):
            return ssm_lib.ssm_forward(p, h, positions, cfg, impl="auto")
    with scope("attention"):
        if slot.mixer.startswith("mla"):
            return attn.mla_forward(p, h, positions, cfg, slot.mixer,
                                    impl=run.attn_impl)
        return attn.gqa_forward(p, h, positions, cfg, slot.mixer,
                                impl=run.attn_impl)


def _mlp_forward(p, h, cfg, slot: SlotSpec, run: RunConfig):
    """Returns (out, MoE statistics: ``moe.no_stats()`` for a dense MLP)."""
    with scope("mlp"):
        if slot.mlp == "dense":
            return moe_lib.dense_mlp(p, h), moe_lib.no_stats()
        moe_fn, kw = moe_lib.moe_mlp, {}
        if run.moe_mesh is not None:
            moe_fn = moe_lib.moe_mlp_sharded
            kw = dict(mesh=run.moe_mesh, axis=run.moe_axis)
        if slot.mlp == "moe":
            return moe_fn(p, h, cfg, **kw)
        y_moe, stats = moe_fn(p["moe"], h, cfg, **kw)
        return moe_lib.dense_mlp(p["dense"], h) + y_moe, stats


def slot_forward(p, h, positions, cfg: ModelConfig, slot: SlotSpec, run: RunConfig):
    """Returns (h, cache, MoE statistics keyed as ``moe.no_stats()``)."""
    with scope("block"):
        resid = h
        u = rms_norm(h, p["mixer_norm"], cfg.norm_eps)
        u, cache = _mixer_forward(p["mixer"], u, positions, cfg, slot, run)
        if cfg.use_post_norm:
            u = rms_norm(u, p["mixer_post_norm"], cfg.norm_eps)
        h = constrain(resid + u, run.act_sharding)

        stats = moe_lib.no_stats()
        if "mlp_norm" in p:
            resid = h
            u = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
            u, stats = _mlp_forward(p["mlp"], u, cfg, slot, run)
            if cfg.use_post_norm:
                u = rms_norm(u, p["mlp_post_norm"], cfg.norm_eps)
            h = constrain(resid + u, run.act_sharding)
        return h, cache, stats


# ---------------------------------------------------------------------------
# Decode (single token, cached)
# ---------------------------------------------------------------------------


def _mixer_decode(p, h, pos, cache, cfg, slot: SlotSpec, run: RunConfig):
    if slot.mixer == "mamba":
        return ssm_lib.ssm_decode(p, h, pos, cache, cfg)
    if slot.mixer.startswith("mla"):
        return attn.mla_decode(p, h, pos, cache, cfg, slot.mixer,
                               scatter=run.cache_scatter)
    return attn.gqa_decode(p, h, pos, cache, cfg, slot.mixer,
                           scatter=run.cache_scatter)


def slot_decode(p, h, pos, cache, cfg: ModelConfig, slot: SlotSpec, run: RunConfig):
    resid = h
    u = rms_norm(h, p["mixer_norm"], cfg.norm_eps)
    u, new_cache = _mixer_decode(p["mixer"], u, pos, cache, cfg, slot, run)
    if cfg.use_post_norm:
        u = rms_norm(u, p["mixer_post_norm"], cfg.norm_eps)
    h = resid + u
    if "mlp_norm" in p:
        resid = h
        u = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
        u, _ = _mlp_forward(p["mlp"], u, cfg, slot, run)
        if cfg.use_post_norm:
            u = rms_norm(u, p["mlp_post_norm"], cfg.norm_eps)
        h = resid + u
    return h, new_cache


# ---------------------------------------------------------------------------
# Extend (multi-token cache append — chunked prefill)
# ---------------------------------------------------------------------------


def _mixer_extend(p, h, pos0, cache, cfg, slot: SlotSpec, run: RunConfig):
    if slot.mixer == "mamba" or slot.mixer.startswith("mla"):
        raise NotImplementedError(
            f"chunked prefill is attention-only; {slot.mixer!r} slots use "
            f"whole-prompt prefill (model.supports_extend gates this)")
    return attn.gqa_extend(p, h, pos0, cache, cfg, slot.mixer)


def slot_extend(p, h, pos0, cache, cfg: ModelConfig, slot: SlotSpec,
                run: RunConfig):
    """slot_decode's multi-token sibling: h (B,C,D), pos0 (B,) chunk start."""
    resid = h
    u = rms_norm(h, p["mixer_norm"], cfg.norm_eps)
    u, new_cache = _mixer_extend(p["mixer"], u, pos0, cache, cfg, slot, run)
    if cfg.use_post_norm:
        u = rms_norm(u, p["mixer_post_norm"], cfg.norm_eps)
    h = resid + u
    if "mlp_norm" in p:
        resid = h
        u = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
        u, _ = _mlp_forward(p["mlp"], u, cfg, slot, run)
        if cfg.use_post_norm:
            u = rms_norm(u, p["mlp_post_norm"], cfg.norm_eps)
        h = resid + u
    return h, new_cache


def slot_cache_specs(cfg: ModelConfig, slot: SlotSpec, layers: int, batch: int,
                     s_max: int, dtype: str = "bfloat16",
                     kv_quant: bool = False):
    if slot.mixer == "mamba":
        return ssm_lib.ssm_cache_specs(cfg, layers, batch, dtype)
    window = attn._window_for(cfg, slot.mixer)
    eff = min(s_max, window) if window else s_max
    quant = kv_quant and not slot.mixer.startswith("mla")  # MLA stays bf16
    return attn.attn_cache_specs(cfg, slot.mixer, layers, batch, eff, dtype,
                                 kv_quant=quant)

"""Top-level decoder: embeddings (token / multi-codebook / VLM-prefix),
scan-over-cycles block stack, LM head, loss, and the three entry points

  * ``forward``      — full-sequence logits (+ prefill caches)
  * ``loss_fn``      — masked CE (+ MoE load-balance loss)
  * ``decode_step``  — single-token cached decoding

The stack is grouped by the config's layer-pattern *cycle*: parameters for
slot ``i`` are stacked over ``num_cycles`` and the decoder is a
``lax.scan`` over cycles, so HLO size is O(len(pattern)), not O(depth).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, SlotSpec
from repro.kernels import FLASH_RESIDUALS
from repro.models.blocks import (RunConfig, constrain, slot_cache_specs,
                                 slot_decode, slot_extend, slot_forward,
                                 slot_specs)
from repro.models.common import (ParamSpec, cross_entropy, rms_norm, softcap)
from repro.models.moe import add_stats, no_stats
from repro.obs.scopes import scope


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    V, D = cfg.padded_vocab, cfg.d_model
    s: Dict[str, Any] = {}
    if cfg.num_codebooks:
        s["embed"] = ParamSpec((cfg.num_codebooks, V, D), (None, "vocab", "embed"))
    else:
        s["embed"] = ParamSpec((V, D), ("vocab", "embed"))
    if cfg.first_k_dense:
        # prelude layers: same mixer as slot 0, dense MLP at cfg.d_ff
        pre_slot = SlotSpec(cfg.pattern[0].mixer, "dense")
        s["prelude"] = slot_specs(cfg, pre_slot, cfg.first_k_dense)
    cycles = (cfg.num_layers - cfg.first_k_dense) // len(cfg.pattern)
    s["slots"] = {
        f"slot{i}": slot_specs(cfg, slot, cycles)
        for i, slot in enumerate(cfg.pattern)
    }
    s["final_norm"] = ParamSpec((D,), ("embed",), init="zeros")
    if not cfg.tie_embeddings:
        if cfg.num_codebooks:
            s["lm_head"] = ParamSpec((cfg.num_codebooks, D, V), (None, "embed", "vocab"))
        else:
            s["lm_head"] = ParamSpec((D, V), ("embed", "vocab"))
    return s


def main_cycles(cfg: ModelConfig) -> int:
    return (cfg.num_layers - cfg.first_k_dense) // len(cfg.pattern)


def cache_specs(cfg: ModelConfig, batch: int, s_max: int,
                dtype: str = "bfloat16", kv_quant: bool = False) -> Dict[str, Any]:
    c: Dict[str, Any] = {}
    if cfg.first_k_dense:
        pre_slot = SlotSpec(cfg.pattern[0].mixer, "dense")
        c["prelude"] = slot_cache_specs(cfg, pre_slot, cfg.first_k_dense, batch,
                                        s_max, dtype, kv_quant)
    cycles = main_cycles(cfg)
    c["slots"] = {
        f"slot{i}": slot_cache_specs(cfg, slot, cycles, batch, s_max, dtype,
                                     kv_quant)
        for i, slot in enumerate(cfg.pattern)
    }
    return c


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    with scope("embed"):
        tokens = batch["tokens"]
        if cfg.num_codebooks:
            # (B,S,K) -> sum_k embed_k[token]
            parts = [
                jnp.take(params["embed"][k], tokens[..., k], axis=0)
                for k in range(cfg.num_codebooks)
            ]
            h = sum(parts)
        else:
            h = jnp.take(params["embed"], tokens, axis=0)
        if "image_embeds" in batch:
            h = jnp.concatenate([batch["image_embeds"].astype(h.dtype), h],
                                axis=1)
        if cfg.scale_embed:
            h = h * np.sqrt(cfg.d_model)
        return h.astype(jnp.dtype(cfg.dtype))


def lm_logits(params, h, cfg: ModelConfig):
    if cfg.num_codebooks:
        w = (
            jnp.transpose(params["embed"], (0, 2, 1))
            if cfg.tie_embeddings
            else params["lm_head"]
        )
        logits = jnp.einsum("bsd,kdv->bskv", h, w)
    else:
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = h @ w
    if cfg.padded_vocab != cfg.vocab_size:  # mask padding columns
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, -1e30)
    return softcap(logits, cfg.logit_softcap)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _remat(run: RunConfig, fn):
    """``fn`` rematerialised as a block when ``run.remat`` asks for it.
    The flash kernel's output and log-sum-exp are kept: the backward pass
    then recomputes the block without the attention forward."""
    if run.remat == "none":
        return fn
    return jax.checkpoint(
        fn, prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUALS))


def _scan_cycles(params, h, positions, cfg, run, with_cache: bool):
    """Scan the main pattern cycles. Returns (h, caches, MoE statistics
    summed over the layers, keyed as ``moe.no_stats()``)."""
    slot_names = [f"slot{i}" for i in range(len(cfg.pattern))]
    stacked = {n: params["slots"][n] for n in slot_names}

    def cycle(h, cycle_params):
        caches, stats = {}, no_stats()
        for n, slot in zip(slot_names, cfg.pattern):
            h, cache, s = slot_forward(cycle_params[n], h, positions, cfg, slot, run)
            caches[n] = cache
            stats = add_stats(stats, s)
        return h, (caches, stats)

    body = _remat(run, cycle)

    if run.unroll_layers:
        n = main_cycles(cfg)
        caches_list, total = [], no_stats()
        for i in range(n):
            cp = jax.tree_util.tree_map(lambda a: a[i], stacked)
            h, (c, stats) = body(h, cp)
            total = add_stats(total, stats)
            if with_cache:
                caches_list.append(c)
        caches = (
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *caches_list)
            if with_cache else None
        )
        return h, caches, total

    def scan_body(h, cycle_params):
        h, (caches, stats) = body(h, cycle_params)
        return h, (caches if with_cache else None, stats)

    h, (caches, stats) = jax.lax.scan(scan_body, h, stacked)
    return h, caches, jax.tree_util.tree_map(lambda a: jnp.sum(a, 0), stats)


def cast_params(params, cfg: ModelConfig):
    """Compute-dtype view of the (fp32 master) parameters; MoE routers stay
    float32 (the router scores in float32)."""
    dt = jnp.dtype(cfg.dtype)

    def cast(path, a):
        if a.dtype != jnp.float32 or any(
                getattr(k, "key", None) == "router" for k in path):
            return a
        return a.astype(dt)

    return jax.tree_util.tree_map_with_path(cast, params)


def forward(params, batch, cfg: ModelConfig, run: RunConfig,
            with_cache: bool = False):
    """Full-sequence forward. Returns (logits, caches, MoE statistics
    summed over the layers, keyed as ``moe.no_stats()``)."""
    params = cast_params(params, cfg)
    h = embed_tokens(params, batch, cfg)
    h = constrain(h, run.act_sharding)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    pre_caches = None
    if cfg.first_k_dense:
        pre_slot = SlotSpec(cfg.pattern[0].mixer, "dense")

        def pre_cycle(h, layer_params):
            h, cache, _ = slot_forward(layer_params, h, positions, cfg, pre_slot, run)
            return h, cache if with_cache else None

        h, pre_caches = jax.lax.scan(_remat(run, pre_cycle), h,
                                     params["prelude"])

    h, caches, stats = _scan_cycles(params, h, positions, cfg, run, with_cache)
    with scope("head_loss"):
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = lm_logits(params, h, cfg)
        # §Perf: keep logits sequence-sharded through the CE path (prevents
        # a full-vocab unsharded materialization, ~40 GB f32 for qwen2-72b
        # train)
        logits = constrain(logits, run.logit_sharding)
    all_caches = {"slots": caches}
    if cfg.first_k_dense:
        all_caches["prelude"] = pre_caches
    return logits, (all_caches if with_cache else None), stats


def loss_fn(params, batch, cfg: ModelConfig, run: RunConfig):
    """Masked next-token CE plus ``aux_loss_alpha`` times the MoE
    load-balance loss. ``labels`` < 0 are ignored. For VLM inputs the
    image-prefix positions carry no labels (mask handled via label padding).
    The metrics of an MoE configuration carry ``moe_held_rows``, the
    (token, expert) assignments its held experts computed, and
    ``moe_overflow_layers``, the MoE layer calls whose held experts outgrew
    the sized row buffer (``models.moe.routed_experts``)."""
    logits, _, stats = forward(params, batch, cfg, run)
    with scope("head_loss"):
        labels = batch["labels"]
        if "image_embeds" in batch:
            n_img = batch["image_embeds"].shape[1]
            pad = jnp.full(labels.shape[:1] + (n_img,) + labels.shape[2:],
                           -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        mask = (labels >= 0).astype(jnp.float32)
        ce = cross_entropy(logits, jnp.maximum(labels, 0), mask)
    metrics = {"ce": ce, "aux": stats["aux"]}
    if cfg.has_moe:
        metrics["moe_held_rows"] = stats["moe_held_rows"]
        metrics["moe_overflow_layers"] = stats["moe_overflow_layers"]
    return ce + cfg.aux_loss_alpha * stats["aux"], metrics


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_step(params, tokens, pos, caches, cfg: ModelConfig, run: RunConfig):
    """One decoding step.

    tokens (B,1) or (B,1,K) int32; pos (B,) int32 absolute positions;
    caches as produced by ``cache_specs``. Returns (logits, new_caches).
    """
    params = cast_params(params, cfg)
    h = embed_tokens(params, {"tokens": tokens}, cfg)
    B = h.shape[0]

    new_caches: Dict[str, Any] = {}
    if cfg.first_k_dense:
        pre_slot = SlotSpec(cfg.pattern[0].mixer, "dense")

        def pre_body(h, xs):
            layer_params, layer_cache = xs
            h, new_cache = slot_decode(layer_params, h, pos, layer_cache, cfg,
                                       pre_slot, run)
            return h, new_cache

        h, new_pre = jax.lax.scan(pre_body, h, (params["prelude"], caches["prelude"]))
        new_caches["prelude"] = new_pre

    slot_names = [f"slot{i}" for i in range(len(cfg.pattern))]
    stacked = ({n: params["slots"][n] for n in slot_names},
               {n: caches["slots"][n] for n in slot_names})

    def cycle(h, xs):
        cycle_params, cycle_cache = xs
        out_cache = {}
        for n, slot in zip(slot_names, cfg.pattern):
            h, nc = slot_decode(cycle_params[n], h, pos, cycle_cache[n], cfg,
                                slot, run)
            out_cache[n] = nc
        return h, out_cache

    if run.unroll_layers:
        outs = []
        for i in range(main_cycles(cfg)):
            xs_i = jax.tree_util.tree_map(lambda a: a[i], stacked)
            h, oc = cycle(h, xs_i)
            outs.append(oc)
        new_slot_caches = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
    else:
        h, new_slot_caches = jax.lax.scan(cycle, h, stacked)
    new_caches["slots"] = new_slot_caches

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, h, cfg)
    return logits, new_caches


def supports_extend(cfg: ModelConfig) -> bool:
    """Whether the config can run chunked prefill (``extend_step``):
    attention-only stacks.  Mamba state folds the whole prefix (no
    per-position cache to append to) and MLA decodes in absorbed-latent
    form — both fall back to whole-prompt prefill."""
    return all(s.mixer in ("attn", "swa") for s in cfg.pattern)


def extend_step(params, tokens, pos0, caches, cfg: ModelConfig,
                run: RunConfig):
    """Chunked prefill: append C prompt tokens to linear caches in one call.

    tokens (B,C) int32; pos0 (B,) absolute position of the chunk's first
    token; caches linear (non-ring) as placed by the serving engine.
    Returns (logits (B,C,V), new_caches) — logits[:, i] is the next-token
    distribution after absolute position pos0+i, identical to what a
    whole-prompt ``forward`` yields at that position.
    """
    if not supports_extend(cfg):
        raise NotImplementedError(
            f"{cfg.name}: chunked prefill needs an attention-only pattern")
    params = cast_params(params, cfg)
    h = embed_tokens(params, {"tokens": tokens}, cfg)

    slot_names = [f"slot{i}" for i in range(len(cfg.pattern))]
    stacked = ({n: params["slots"][n] for n in slot_names},
               {n: caches["slots"][n] for n in slot_names})

    def cycle(h, xs):
        cycle_params, cycle_cache = xs
        out_cache = {}
        for n, slot in zip(slot_names, cfg.pattern):
            h, nc = slot_extend(cycle_params[n], h, pos0, cycle_cache[n], cfg,
                                slot, run)
            out_cache[n] = nc
        return h, out_cache

    new_caches: Dict[str, Any] = {}
    if cfg.first_k_dense:
        pre_slot = SlotSpec(cfg.pattern[0].mixer, "dense")

        def pre_body(h, xs):
            layer_params, layer_cache = xs
            return slot_extend(layer_params, h, pos0, layer_cache, cfg,
                               pre_slot, run)

        h, new_pre = jax.lax.scan(pre_body, h,
                                  (params["prelude"], caches["prelude"]))
        new_caches["prelude"] = new_pre

    h, new_slot_caches = jax.lax.scan(cycle, h, stacked)
    new_caches["slots"] = new_slot_caches

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, h, cfg)
    return logits, new_caches

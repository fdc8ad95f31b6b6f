"""Step builders (train / prefill / decode) and abstract input specs for the
multi-pod dry-run. All functions are pure and jit-friendly; the dry-run
lowers them with ShapeDtypeStruct stand-ins (no allocation).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import mesh as mesh_lib
from repro.models import model as M
from repro.models.blocks import RunConfig
from repro.models.common import abstractify
from repro.obs.scopes import scope
from repro.optim import adamw as opt_lib


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------


def build_grad_fn(cfg: ModelConfig, run: RunConfig):
    """(params, batch) -> (loss, metrics, grads), with microbatch gradient
    accumulation under a scan when ``run.microbatch > 0`` (the paper's X_mini
    knob). Shared by :func:`build_train_step` and the explicit data-parallel
    trainer (repro.distributed.trainer), which calls it per device shard
    inside shard_map."""

    def loss(p, b):
        # inside the differentiated function, so the backward pass shows
        # as transpose(jvp(forward))/... in the ops' op_name
        with scope("forward"):
            if run.bf16_grads:
                # mixed precision: differentiate wrt the bf16 compute params
                # so the data-axis gradient sync moves half the wire bytes;
                # the optimizer still applies them to the fp32 master (cast
                # in apply_updates)
                p = M.cast_params(p, cfg)
            return M.loss_fn(p, b, cfg, run)

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def grads_of(params, batch):
        if run.microbatch:
            B = batch["tokens"].shape[0]
            n = max(B // run.microbatch, 1)

            def reshape(x):
                return x.reshape((n, B // n) + x.shape[1:])

            micro = jax.tree_util.tree_map(reshape, batch)

            def acc_body(carry, mb):
                gsum, lsum = carry
                (loss, _), g = grad_fn(params, mb)
                # accumulate g as computed on its own: without the barrier
                # XLA fuses the add into the backward pass and rounds
                # differently from the 1F1B trainer, which adds each
                # microbatch's gradient in a program of its own
                g = jax.lax.optimization_barrier(g)
                gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                return (gsum, lsum + loss), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(acc_body, (zeros, 0.0), micro)
            grads = jax.tree_util.tree_map(lambda g: g / n, gsum)
            return lsum / n, {}, grads
        (loss, metrics), grads = grad_fn(params, batch)
        return loss, metrics, grads

    return grads_of


def build_train_step(cfg: ModelConfig, run: RunConfig, opt: opt_lib.OptConfig,
                     *, grad_sync=None):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``grad_sync`` (optional) is applied to the gradient pytree between the
    backward pass and the optimizer update — the hook through which a
    resolved ``Plan.sync_schedule`` strategy (repro.distributed) runs its
    collectives when the step executes under shard_map."""

    grads_of = build_grad_fn(cfg, run)

    def train_step(params, opt_state, batch):
        loss, metrics, grads = grads_of(params, batch)
        if grad_sync is not None:
            grads = grad_sync(grads)
        if run.grad_shardings is not None:
            # land grads directly on the ZeRO-1 optimizer-state layout: the
            # data-axis gradient sum becomes a reduce-scatter (1x wire)
            # instead of an all-reduce (2x wire)
            grads = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, grads, run.grad_shardings)
        with scope("optimizer"):
            new_params, new_state, gnorm = opt_lib.apply_updates(
                opt, params, grads, opt_state)
        out_metrics = {"loss": loss, "grad_norm": gnorm}
        out_metrics.update({k: v for k, v in (metrics or {}).items()})
        return new_params, new_state, out_metrics

    return train_step


def build_prefill_step(cfg: ModelConfig, run: RunConfig):
    def prefill_step(params, batch):
        logits, caches, _ = M.forward(params, batch, cfg, run, with_cache=True)
        return logits[:, -1:], caches

    return prefill_step


def build_decode_step(cfg: ModelConfig, run: RunConfig):
    def decode_step(params, tokens, pos, caches):
        return M.decode_step(params, tokens, pos, caches, cfg, run)

    return decode_step


# ---------------------------------------------------------------------------
# Abstract input specs (dry-run)
# ---------------------------------------------------------------------------


def token_shape(cfg: ModelConfig, batch: int, seq: int):
    if cfg.num_codebooks:
        return (batch, seq, cfg.num_codebooks)
    return (batch, seq)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh,
                rules: Optional[Dict[str, Any]] = None,
                kv_quant: bool = False) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable) for every
    model input of the given (arch × input-shape) pair."""
    if rules is None:
        rules = mesh_lib.sharding_rules(mesh, cfg, shape)
    bsh = mesh_lib.batch_sharding(mesh, shape)
    bspec = bsh.spec

    def tok_struct(batch, seq):
        return jax.ShapeDtypeStruct(
            token_shape(cfg, batch, seq), jnp.int32,
            sharding=NamedSharding(mesh, P(*(tuple(bspec) + (None,) * (
                len(token_shape(cfg, batch, seq)) - 1)))),
        )

    B, S = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        text_len = S - (cfg.num_image_tokens or 0)
        specs: Dict[str, Any] = {"tokens": tok_struct(B, text_len)}
        if cfg.num_image_tokens:
            specs["image_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16,
                sharding=NamedSharding(mesh, P(*(tuple(bspec) + (None, None)))),
            )
        if shape.kind == "train":
            specs["labels"] = tok_struct(B, text_len)
        return specs

    # decode: one new token + caches of seq_len
    specs = {
        "tokens": tok_struct(B, 1),
        "pos": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=bsh),
        "caches": abstractify(M.cache_specs(cfg, B, S, kv_quant=kv_quant),
                              mesh, rules),
    }
    return specs


def abstract_params(cfg: ModelConfig, mesh, rules, dtype: Optional[str] = None):
    return abstractify(M.model_specs(cfg), mesh, rules, dtype_override=dtype)


def abstract_opt_state(cfg: ModelConfig, mesh, rules, opt: opt_lib.OptConfig):
    """Optimizer state: ZeRO-1 — always FSDP-sharded over the data axes."""
    zrules = dict(rules)
    zrules["embed"] = mesh_lib.dp_axes(mesh)
    tree = abstractify(M.model_specs(cfg), mesh, zrules)
    state: Dict[str, Any] = {
        "step": jax.ShapeDtypeStruct((), jnp.int32,
                                     sharding=NamedSharding(mesh, P())),
    }
    if opt.kind == "adamw":
        state["m"] = tree
        state["v"] = jax.tree_util.tree_map(lambda x: x, tree)
    elif opt.kind == "momentum":
        state["m"] = tree
    return state

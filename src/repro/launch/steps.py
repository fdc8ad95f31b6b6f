"""The training step's builders: the gradient function and the whole step
(gradients, optional sync, optimizer update). Both are pure and
jit-friendly; the training loop and the trainers jit them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.blocks import RunConfig
from repro.obs.scopes import scope
from repro.optim import adamw as opt_lib


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
        with scope("optimizer"):
            new_params, new_state, gnorm = opt_lib.apply_updates(
                opt, params, grads, opt_state)
        out_metrics = {"loss": loss, "grad_norm": gnorm}
        out_metrics.update({k: v for k, v in (metrics or {}).items()})
        return new_params, new_state, out_metrics

    return train_step

"""Stable names for the device work of a step.

Each name is a ``jax.named_scope`` placed in the program (``launch/steps.py``,
``models/*``).  JAX writes the scope stack into every HLO instruction's
``op_name`` metadata, and the profiler copies that ``op_name`` into each
device op of a trace (the ``tf_op`` stat), so a trace can be summed by these
names however XLA fuses or renames the ops.  Scopes change metadata only:
the compiled computation is the same with or without them.

Under ``jax.value_and_grad`` the scope ``forward`` (around the loss) shows as
``jvp(forward)`` in the forward pass and ``transpose(jvp(forward))`` in the
backward pass; a block rematerialised by ``jax.checkpoint`` is recomputed
under ``…/checkpoint/rematted_computation/…``.
"""
from __future__ import annotations

COMPONENTS = (
    "forward",         # the loss, differentiated (build_grad_fn)
    "embed",           # token embedding lookup
    "block",           # one decoder block (slot)
    "attention",       # an attention mixer: projections, RoPE, output
    "attention_core",  # the attention itself: dense, chunked or flash
    "mamba",           # a Mamba-2 mixer: projections, conv, gate, output
    "ssd_scan",        # the SSD chunked scan (jnp or Pallas)
    "mlp",             # the block's MLP (dense or MoE)
    "moe_dispatch",    # MoE routing: router, top-k, sort, gathers into
                       # expert order and the weighted scatter-add back
    "moe_experts",     # the grouped matmuls over the held experts and
                       # their activation
    "head_loss",       # final norm, LM head logits and cross-entropy
    "optimizer",       # gradient clipping and the optimizer update
)


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`COMPONENTS`."""
    if name not in COMPONENTS:
        raise ValueError(f"unknown scope {name!r}; known: {COMPONENTS}")
    import jax

    return jax.named_scope(name)

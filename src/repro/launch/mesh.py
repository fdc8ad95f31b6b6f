"""Production meshes and logical-axis sharding rules.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) > n:  # e.g. 512 placeholder devices, single-pod mesh
        devices = devices[:n]
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def sharding_rules(mesh, cfg: ModelConfig, shape: Optional[ShapeConfig] = None,
                   *, fsdp: bool = False) -> Dict[str, object]:
    """Map logical parameter/cache axes onto mesh axes.

    TP ("model"): heads / ff / experts / d_inner / vocab.  FSDP adds the
    data-parallel axes on the ``embed`` dim (per-layer all-gather under the
    layer scan).  KV caches: batch on data axes, sequence on "model" — and on
    (data+model) when the batch cannot cover the data axes (long_500k, B=1).
    """
    dp = dp_axes(mesh)
    batch_rule: object = dp
    kv_seq_rule: object = ("model",)
    if shape is not None:
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if shape.global_batch < dp_size:
            batch_rule = None
            kv_seq_rule = dp + ("model",)
    tp = mesh.shape["model"]
    # Archs whose head count is not divisible by TP (llava/arctic: 56 heads,
    # minicpm3: 40) fall back to replicated attention projections — a known
    # baseline inefficiency; the head-padding optimization in §Perf fixes it.
    heads_ok = cfg.num_heads == 0 or cfg.num_heads % tp == 0
    rules: Dict[str, object] = {
        "vocab": "model",
        "q_heads": "model" if heads_ok else None,
        "kv_heads": None,  # kv_heads (<=16) replicated; Q/O carry the TP split
        "ff": "model",
        "experts": "model",
        "inner": "model",
        "ssm_heads": "model",
        "conv_ch": "model",
        "lora": None,
        "embed": dp if fsdp else None,
        "layers": None,
        "batch": batch_rule,
        "kv_seq": kv_seq_rule,
    }
    return rules


def act_sharding(mesh, shape: Optional[ShapeConfig] = None,
                 *, seq_parallel: bool = True):
    """Residual-stream (B, S, D) sharding constraint."""
    dp = dp_axes(mesh)
    batch: object = dp
    if shape is not None:
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if shape.global_batch < dp_size:
            batch = None
    return NamedSharding(mesh, P(batch, "model" if seq_parallel else None, None))


def batch_sharding(mesh, shape: Optional[ShapeConfig] = None):
    dp = dp_axes(mesh)
    batch: object = dp
    if shape is not None:
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if shape.global_batch < dp_size:
            batch = None
    return NamedSharding(mesh, P(batch))

"""Shared model machinery: ParamSpec trees (single source of truth for shapes,
init and logical sharding axes), norms, rope, softcap.

A model's ``param_specs(config)`` returns a pytree whose leaves are
:class:`ParamSpec`; ``materialize(specs, key)`` makes real parameters from
it, and ``param_count`` / ``param_bytes`` size it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# ParamSpec
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis name per dim (None = replicated)
    dtype: str = "float32"
    init: str = "normal"  # normal | zeros | ones | small_normal | ssm_a | ssm_dt
    scale: float = 1.0  # stddev multiplier / fan-in handled by caller

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x):
    return isinstance(x, ParamSpec)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def materialize(specs, key: jax.Array, dtype_override: Optional[str] = None):
    """Randomly initialize real parameters from a ParamSpec tree."""

    def init_leaf(path, spec: ParamSpec):
        dt = jnp.dtype(dtype_override or spec.dtype)
        # zlib.crc32, NOT hash(): python string hashing is randomized per
        # process (PYTHONHASHSEED), which would make init non-reproducible
        import zlib
        k = jax.random.fold_in(key, zlib.crc32(_path_str(path).encode()) % (2**31))
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dt)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dt)
        if spec.init == "ssm_a":  # A_log init: log of uniform [1, 16]
            u = jax.random.uniform(k, spec.shape, jnp.float32, 1.0, 16.0)
            return jnp.log(u).astype(dt)
        if spec.init == "ssm_dt":  # dt_bias: softplus^-1 of uniform [1e-3, 0.1]
            u = jax.random.uniform(k, spec.shape, jnp.float32, 1e-3, 0.1)
            return (u + jnp.log(-jnp.expm1(-u))).astype(dt)
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = spec.scale / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(dt)

    return jax.tree_util.tree_map_with_path(init_leaf, specs, is_leaf=_is_spec)


def param_count(specs) -> int:
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=_is_spec)
    return int(sum(int(np.prod(s.shape)) for s in leaves))


def param_bytes(specs, bytes_per_param: int = 2) -> int:
    return param_count(specs) * bytes_per_param


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def rope_freqs(d: int, theta: float) -> jax.Array:
    """Inverse frequencies of a ``d``-wide rotary embedding (``d // 2``)."""
    half = d // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def yarn_ramp(d: int, theta: float, original_max_pos: int,
              beta_fast: float, beta_slow: float) -> Tuple[int, int]:
    """First and last frequency index of YaRN's interpolation ramp: the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
    original context, clamped to ``[0, d - 1]``."""
    def dim(rotations):
        return (d * np.log(original_max_pos / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    lo = int(np.floor(dim(beta_fast)))
    hi = int(np.ceil(dim(beta_slow)))
    return max(lo, 0), min(hi, d - 1)


def yarn_freqs(d: int, theta: float, factor: float, original_max_pos: int,
               beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN inverse frequencies (DeepSeek-V2's ``DeepseekV2YarnRotary
    Embedding``): the original frequencies above the ramp (index below its
    first), frequencies divided by ``factor`` below it, blended linearly
    along it."""
    lo, hi = yarn_ramp(d, theta, original_max_pos, beta_fast, beta_slow)
    extra = rope_freqs(d, theta)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / factor * (1.0 - keep) + extra * keep


def rope(x: jax.Array, positions: jax.Array, theta: float,
         freqs: Optional[jax.Array] = None, mscale: float = 1.0) -> jax.Array:
    """Rotary embedding (half-split). x: (..., S, H, D_rot); positions:
    (..., S).  ``freqs`` replaces the plain ``theta`` frequencies (YaRN);
    ``mscale`` multiplies cos and sin."""
    d = x.shape[-1]
    half = d // 2
    if freqs is None:
        freqs = rope_freqs(d, theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def swish(x):
    return x * jax.nn.sigmoid(x)


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: jax.Array,
                  logit_cap: float = 0.0) -> jax.Array:
    """Mean CE over mask. logits (..., V) f32-cast internally; labels int."""
    logits = softcap(logits.astype(jnp.float32), logit_cap)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)

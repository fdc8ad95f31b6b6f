"""Pieces every plain reference shares: weights from the seed, the
precision policies, and a float32 AdamW training step computed row by row.

Nothing here imports the program.  The weights are drawn the way the
configuration's parameterisation states them (one normal, uniform or
constant draw per named leaf, keyed by ``fold_in(key, crc32(path))``), so
the reference and the program start from the same numbers without one
taking them from the other.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps only
    the low 32 of a larger one)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


# A leaf: (shape, init, scale); init in normal | zeros | ones | ssm_a | ssm_dt
LeafSpec = Tuple[Tuple[int, ...], str, float]


def init_leaf(key: jax.Array, path: str, spec: LeafSpec) -> jax.Array:
    shape, init, scale = spec
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2**31))
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "ssm_a":  # log of a decay rate uniform in [1, 16]
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if init == "ssm_dt":  # inverse softplus of a step uniform in [1e-3, 0.1]
        u = jax.random.uniform(k, shape, jnp.float32, 1e-3, 0.1)
        return u + jnp.log(-jnp.expm1(-u))
    if init != "normal":
        raise ValueError(f"unknown init {init!r} for {path}")
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return jax.random.normal(k, shape, jnp.float32) * (scale / np.sqrt(fan_in))


def nest(flat: Dict[str, jax.Array]) -> dict:
    """``{"a/b/c": x}`` -> ``{"a": {"b": {"c": x}}}``."""
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def init_params(shapes: Dict[str, LeafSpec], seed: int) -> dict:
    key = seed_key(seed)
    return nest({p: init_leaf(key, p, s) for p, s in shapes.items()})


# ---------------------------------------------------------------------------
# Precision policies: every matrix product goes through ``ein``; ``q`` is
# applied to weights read outside a product (the embedding gather).
# ---------------------------------------------------------------------------


class Float32:
    """float32 operands, products at ``HIGHEST`` precision."""

    @staticmethod
    def q(x):
        return x

    def ein(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)


class Float8(Float32):
    """The control: every operand of a product rounded to float8 (e4m3, one
    scale per tensor from its largest magnitude), accumulated in float32."""

    @staticmethod
    def q(x):
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


POLICIES = {"float32": Float32(), "float8_e4m3": Float8()}


def rms_norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def silu(x):
    return x * jax.nn.sigmoid(x)


def nll_sum(h, embed, labels, vocab, pol):
    """Summed next-token negative log-likelihood of one row under the tied
    head: logits over the real vocabulary rows of ``embed``."""
    logits = pol.ein("sd,vd->sv", h, embed[:vocab])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


# ---------------------------------------------------------------------------
# Training: float32 AdamW over rows, as the configuration states it
# ---------------------------------------------------------------------------


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to 0 at ``total_steps``."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + np.cos(np.pi * frac))


def layer_norms(tree) -> Dict[str, float]:
    """Norm of every leaf, split by layer where a leaf stacks layers (the
    leaves under ``slots/``): ``{"slots/slot0/mixer/wq[3]": norm, ...}``."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    names = ["/".join(str(getattr(p, "key", p)) for p in path)
             for path, _ in flat]
    split = tuple(n.startswith("slots/") and x.ndim > 1
                  for n, (_, x) in zip(names, flat))

    def norms(xs):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                 axis=tuple(range(1 if s else 0, x.ndim))))
                for x, s in zip(xs, split)]

    out = {}
    for name, s, n in zip(names, split, jax.device_get(
            jax.jit(norms)([x for _, x in flat]))):
        if s:
            out.update({f"{name}[{i}]": float(v) for i, v in enumerate(n)})
        else:
            out[name] = float(n)
    return out


def train_readings(row_nll: Callable, shapes: Dict[str, LeafSpec], cfg: dict,
                   opt: dict, batches: List[Tuple[np.ndarray, np.ndarray]],
                   seed: int, pol) -> dict:
    """Run ``len(batches)`` AdamW steps from the seed's weights.

    ``row_nll(params, tokens, labels, cfg, pol)`` is one row's summed loss.
    The batch loss is the mean over all its tokens; its gradient is summed
    row by row.  Returns the loss of each step, the per-layer norms of the
    first step's gradient before clipping (``grad_raw``, with its global
    norm ``grad_norm``) and as the optimizer applies it (``grad``), and of
    the weights' change over all the steps (``change``)."""
    params = jax.jit(lambda: init_params(shapes, seed))()
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    m, v = zeros(params), zeros(params)
    n_tok = int(np.prod(batches[0][0].shape))

    def row_acc(p, gsum, lsum, tokens, labels):
        loss, g = jax.value_and_grad(
            lambda p_: row_nll(p_, tokens, labels, cfg, pol))(p)
        return jax.tree_util.tree_map(jnp.add, gsum, g), lsum + loss

    acc = jax.jit(row_acc, donate_argnums=(1,))

    def clip_fn(gsum):
        g = jax.tree_util.tree_map(lambda x: x / n_tok, gsum)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
        return jax.tree_util.tree_map(lambda x: x * scale, g), gn, scale

    clip = jax.jit(clip_fn, donate_argnums=(0,))

    def adamw(p, m, v, g, t, lr):
        b1, b2 = opt["b1"], opt["b2"]
        m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                                   v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree_util.tree_map(
            lambda p_, m_, v_: p_ - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                        + opt["eps"])
                                          + opt["weight_decay"] * p_),
            p, m, v)
        return p, m, v

    update = jax.jit(adamw, donate_argnums=(0, 1, 2))
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for i, (tokens, labels) in enumerate(batches):
            gsum, lsum = zeros(params), jnp.float32(0.0)
            for r in range(tokens.shape[0]):
                gsum, lsum = acc(params, gsum, lsum, jnp.asarray(tokens[r]),
                                 jnp.asarray(labels[r]))
            out["loss"].append(float(lsum) / n_tok)
            g, gn, scale = clip(gsum)
            del gsum
            if i == 0:
                out["grad"] = layer_norms(g)
                out["grad_raw"] = {k: v / float(scale)
                                   for k, v in out["grad"].items()}
                out["grad_norm"] = float(gn)
            params, m, v = update(
                params, m, v, g, jnp.float32(i + 1),
                jnp.float32(lr_at(opt, i + 1)))
            del g
    del m, v
    p0 = jax.jit(lambda: init_params(shapes, seed))()
    out["change"] = layer_norms(jax.jit(
        lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))(params, p0))
    return out

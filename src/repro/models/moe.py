"""Mixture-of-Experts MLP: a dropless expert layer told which experts it
holds, and the plain dense (SwiGLU) MLP.

The router scores every token against all ``num_experts`` experts in
float32 and picks the top-k; the layer holds the experts
``[expert_offset, expert_offset + held_experts)`` and computes only the
part of the result they give: the (token, expert) assignments to a held
expert are sorted by expert, gathered into rows, run through grouped
matmuls over the held experts' weights, weighted and scatter-added back.
The row buffer is sized from static shapes: ``FIT_FACTOR`` times the held
experts' expected share of the assignments, ``T * k * held /
num_experts``.  No assignment is dropped: a layer call whose held experts
receive more than that runs the same computation on a buffer sized for
the most they can receive, ``T * min(k, held)`` (counted in
``moe_overflow_layers``), and where the sized buffer is not smaller than
that (every expert held) the layer has the full buffer alone.  The
grouped matmul computes only the rows the group sizes cover.  The shared
experts are computed in full.

Dispatch is gather/scatter (memory ops), not a one-hot einsum, and the
grouped matmul's FLOPs are those of the rows it covers, so the compute
term of a roofline counts true active-expert FLOPs.  Also provides
arctic's parallel dense + MoE residual form (``blocks``).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec, swish
from repro.obs.metrics import TRACE_COUNTS
from repro.obs.scopes import scope

# rows of the grouped matmul's buffer are padded to a multiple of this
ROW_TILE = 512
# the sized row buffer holds this many times the held experts' expected
# share of a layer's assignments
FIT_FACTOR = 2


# ---------------------------------------------------------------------------
# Dense SwiGLU MLP
# ---------------------------------------------------------------------------


def dense_mlp_specs(d_model: int, d_ff: int, layers: int) -> Dict[str, ParamSpec]:
    L, la = (layers,), ("layers",)
    return {
        "w_gate": ParamSpec(L + (d_model, d_ff), la + ("embed", "ff")),
        "w_up": ParamSpec(L + (d_model, d_ff), la + ("embed", "ff")),
        "w_down": ParamSpec(L + (d_ff, d_model), la + ("ff", "embed")),
    }


def dense_mlp(p, x):
    return (swish(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def moe_specs(cfg: ModelConfig, layers: int) -> Dict[str, ParamSpec]:
    """The router over all ``num_experts``; the held experts' weights."""
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.held_experts
    L, la = (layers,), ("layers",)
    s = {
        "router": ParamSpec(L + (D, cfg.num_experts), la + ("embed", None),
                            scale=0.1),
        "w_gate": ParamSpec(L + (E, D, F), la + ("experts", "embed", None)),
        "w_up": ParamSpec(L + (E, D, F), la + ("experts", "embed", None)),
        "w_down": ParamSpec(L + (E, F, D), la + ("experts", None, "embed")),
    }
    if cfg.num_shared_experts:
        s["shared"] = dense_mlp_specs(D, cfg.moe_d_ff * cfg.num_shared_experts, layers)
    return s


def no_stats():
    """What an MLP without experts adds to the MoE statistics."""
    return {"aux": jnp.float32(0.0), "moe_held_rows": jnp.int32(0),
            "moe_overflow_layers": jnp.int32(0)}


def add_stats(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def load_balance_loss(probs, idx, num_experts: int, groups: int):
    """``sum_e f_e P_e`` averaged over ``groups`` equal runs of tokens (the
    sequences, for DeepSeek's sequence-wise loss, or the whole batch):
    ``f_e`` is ``E / k`` times the share of the run's assignments that go
    to expert e, ``P_e`` the run's mean router probability of e."""
    T, K = idx.shape
    E = num_experts
    counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1)
    counts = counts.reshape(groups, T // groups, E).sum(axis=1)
    f = counts * (E / (K * (T // groups)))
    P = jnp.mean(probs.reshape(groups, T // groups, E), axis=1)
    return jnp.mean(jnp.sum(f * P, axis=-1))


def route(xf, router_w, cfg: ModelConfig, groups: int):
    """Tokens xf (T, D) -> (weights (T, k) f32, experts (T, k) int32,
    load-balance loss).  Scores over all experts in float32; weights are
    the top-k probabilities, renormalised if the configuration says so,
    times ``routed_scaling_factor``."""
    logits = jnp.dot(xf.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    w = w * cfg.routed_scaling_factor
    aux = load_balance_loss(probs, idx, cfg.num_experts, groups)
    return w, idx, aux


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _buffer_rows(n: int) -> int:
    """``n`` rows padded to the grouped matmul's row tile (to 8 below it)."""
    return _round_up(n, ROW_TILE if n >= ROW_TILE else 8)


def _auto_impl() -> str:
    """The Pallas grouped matmul (megablox ``gmm``) on a TPU, XLA's
    ``ragged_dot`` elsewhere."""
    return "gmm" if jax.default_backend() == "tpu" else "ragged_dot"


def _grouped(impl: str, rows, w, sizes):
    """rows (M, K) sorted by group, w (G, K, N), sizes (G + 1,) with the
    rows past the first G groups last -> (M, N); those rows give zeros."""
    if impl == "gmm":
        from repro.kernels import ops as kops

        return kops.grouped_matmul(rows, w, sizes)
    return jax.lax.ragged_dot(rows, w, sizes[:-1],
                              preferred_element_type=rows.dtype)


def _buffered(M: int, impl: str, xf, wts, wg, wu, wd, tok, active, counts):
    """The held experts' part through an M-row buffer: the first M of the
    assignments sorted by expert (token ``tok``, router weight ``wts``,
    ``active`` where the expert is held; ``counts`` per held expert, then
    the rest) gathered, run through the experts, weighted in float32 and
    scatter-added back -> (T, D) float32.  M must hold every active row."""
    T, D = xf.shape
    G = wg.shape[0]
    with scope("moe_dispatch"):
        tok, active, wts = tok[:M], active[:M], wts[:M]
        sizes = counts.at[G].set(M - jnp.sum(counts[:G]))
        rows = jnp.where(active[:, None], xf[tok], 0).astype(xf.dtype)
    with scope("moe_experts"):
        h = (swish(_grouped(impl, rows, wg, sizes))
             * _grouped(impl, rows, wu, sizes))
        y = _grouped(impl, h.astype(xf.dtype), wd, sizes)
    with scope("moe_dispatch"):
        return jnp.zeros((T, D), jnp.float32).at[tok].add(
            jnp.where(active[:, None], y.astype(jnp.float32) * wts[:, None],
                      0.0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _two_sizes(m_fit: int, m_full: int, impl: str, fits, xf, wts, wg, wu, wd,
               tok, active, counts):
    """:func:`_buffered` on ``m_fit`` rows where ``fits``, else on
    ``m_full``.  Its residuals are its operands: differentiating a
    ``lax.cond`` would keep both branches' buffers for the backward pass,
    zeros in the branch not taken."""
    return jax.lax.cond(
        fits, functools.partial(_buffered, m_fit, impl),
        functools.partial(_buffered, m_full, impl),
        xf, wts, wg, wu, wd, tok, active, counts)


def _two_sizes_fwd(m_fit, m_full, impl, fits, *args):
    return _two_sizes(m_fit, m_full, impl, fits, *args), (fits, args)


def _two_sizes_bwd(m_fit, m_full, impl, res, g):
    fits, (xf, wts, wg, wu, wd, tok, active, counts) = res

    def pull(M):
        def f(xf, wts, wg, wu, wd):
            _, vjp = jax.vjp(
                lambda *a: _buffered(M, impl, *a, tok, active, counts),
                xf, wts, wg, wu, wd)
            return vjp(g)
        return f

    grads = jax.lax.cond(fits, pull(m_fit), pull(m_full), xf, wts, wg, wu, wd)
    return (None,) + tuple(grads) + (None, None, None)


_two_sizes.defvjp(_two_sizes_fwd, _two_sizes_bwd)


def routed_experts(xf, w, idx, wg, wu, wd, *, e_lo, num_experts: int,
                   impl: str = "auto") -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The held experts' part of the routed result.

    xf (T, D) tokens; w, idx (T, k) the router's weights and experts; wg,
    wu (G, D, F) and wd (G, F, D) the G held experts, global experts
    ``[e_lo, e_lo + G)`` of ``num_experts`` (``e_lo`` may be traced).
    Returns (out (T, D) float32, {"moe_held_rows": the assignments the held
    experts computed, "moe_overflow_layers": 1 where they outgrew the sized
    buffer and took the full one, else 0}).

    The row buffer has ``FIT_FACTOR * T * k * G / num_experts`` rows,
    padded to the row tile, where the held experts' assignments fit, else
    ``T * min(k, G)``, the most they can receive.  Both sizes run the same
    rows in the same order; only the zero rows past them differ."""
    T, D = xf.shape
    K = idx.shape[1]
    G = wg.shape[0]
    if impl == "auto":
        impl = _auto_impl()
    TRACE_COUNTS.inc("moe/" + impl)
    m_full = _buffer_rows(T * min(K, G))  # the most G experts can receive
    m_fit = _buffer_rows(FIT_FACTOR * -(-T * K * G // num_experts))
    with scope("moe_dispatch"):
        local = idx.reshape(-1) - e_lo
        held = (local >= 0) & (local < G)
        key = jnp.where(held, local, G).astype(jnp.int32)
        flat_w = w.reshape(-1)
        if m_full > T * K:  # pad with assignments to no held expert
            key = jnp.pad(key, (0, m_full - T * K), constant_values=G)
            flat_w = jnp.pad(flat_w, (0, m_full - T * K))
        order = jnp.argsort(key, stable=True)[:m_full]
        counts = jnp.zeros((G + 1,), jnp.int32).at[key].add(1)
        n_held = jnp.sum(counts[:G])
        active = key[order] < G
        tok = jnp.minimum(order // K, T - 1)
        wts = jnp.where(active, flat_w[order], 0.0)
    args = (xf, wts, wg, wu, wd, tok, active, counts)
    if m_fit >= m_full:
        out, overflow = _buffered(m_full, impl, *args), jnp.int32(0)
    else:
        TRACE_COUNTS.inc("moe/sized_buffer")
        fits = n_held <= m_fit
        out = _two_sizes(m_fit, m_full, impl, fits, *args)
        overflow = jnp.logical_not(fits).astype(jnp.int32)
    return out, {"moe_held_rows": n_held, "moe_overflow_layers": overflow}


def moe_mlp(p, x, cfg: ModelConfig, *, impl: str = "auto"):
    """x (B, S, D) -> (out (B, S, D), {"aux", "moe_held_rows",
    "moe_overflow_layers"}): routed over all experts, computed by the held
    ones, dropless, plus the shared experts."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    with scope("moe_dispatch"):
        w, idx, aux = route(xf, p["router"], cfg, B if cfg.seq_aux else 1)
    out, stats = routed_experts(
        xf, w, idx, p["w_gate"], p["w_up"], p["w_down"],
        e_lo=cfg.expert_offset, num_experts=cfg.num_experts, impl=impl)
    out = out.astype(x.dtype).reshape(B, S, D)
    if cfg.num_shared_experts:
        out = out + dense_mlp(p["shared"], x)
    return out, {"aux": aux, **stats}


def moe_mlp_ref(p, x, cfg: ModelConfig):
    """Every held expert on every token, selected by the router's top-k
    (test-only): the held experts' routed part plus the shared experts."""
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    w, idx, _ = route(xf, p["router"], cfg, 1)
    G = p["w_gate"].shape[0]
    local = idx - cfg.expert_offset
    # (T, G): the router weight of each held expert, 0 where not picked
    sel = jnp.sum(jax.nn.one_hot(local, G, dtype=jnp.float32) * w[..., None],
                  axis=1)
    all_y = jnp.einsum(
        "etf,efd->etd",
        swish(jnp.einsum("td,edf->etf", xf, p["w_gate"]))
        * jnp.einsum("td,edf->etf", xf, p["w_up"]),
        p["w_down"])
    out = jnp.einsum("te,etd->td", sel, all_y).astype(x.dtype).reshape(B, S, D)
    if cfg.num_shared_experts:
        out = out + dense_mlp(p["shared"], x)
    return out

"""Mixture-of-Experts MLP with sort-based capacity dispatch.

Dispatch is gather/scatter (memory ops), NOT one-hot einsum — a one-hot
dispatch matmul would inject O(T·E·C·D) fake FLOPs into the HLO and poison
the roofline compute term. Expert compute is a grouped einsum
``ecd,edf->ecf`` whose FLOP count equals the true active-expert FLOPs at
capacity factor 1.0.

Experts are sharded on the mesh "model" axis (expert parallelism); the
scatter/gather into the (E, C, D) buffer is GSPMD's all-to-all analogue.
Also provides the plain dense (SwiGLU) MLP and arctic's parallel
dense+MoE residual form.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec, swish


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
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    L, la = (layers,), ("layers",)
    s = {
        "router": ParamSpec(L + (D, E), la + ("embed", None), scale=0.1),
        "w_gate": ParamSpec(L + (E, D, F), la + ("experts", "embed", None)),
        "w_up": ParamSpec(L + (E, D, F), la + ("experts", "embed", None)),
        "w_down": ParamSpec(L + (E, F, D), la + ("experts", None, "embed")),
    }
    if cfg.num_shared_experts:
        s["shared"] = dense_mlp_specs(D, cfg.moe_d_ff * cfg.num_shared_experts, layers)
    return s


def _router_topk(logits: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """logits (T, E) -> (weights (T,k), experts (T,k) int32, aux_loss scalar)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss: E * sum_e f_e * p_e
    E = logits.shape[-1]
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    onehot = jax.nn.one_hot(idx[:, 0], E)  # fraction routed (top-1 proxy)
    fe = jnp.mean(onehot, axis=0)
    aux = E * jnp.sum(fe * me)
    return w, idx, aux


def moe_mlp(p, x, cfg: ModelConfig, *, capacity_factor: float = 1.25):
    """x (B, S, D) -> (B, S, D); sort-based dispatch with per-expert capacity."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, D)

    w, idx, aux = _router_topk(xf @ p["router"], K)  # (T,K)

    C = int(capacity_factor * T * K / E) + 1
    C = max(C, 4)

    # flatten (token, k) assignments and sort by expert
    flat_e = idx.reshape(-1)  # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_e)  # stable
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # position of each assignment within its expert group
    expert_start = jnp.searchsorted(se, jnp.arange(E))  # (E,)
    pos = jnp.arange(T * K) - expert_start[se]
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)  # overflow -> dropped row

    # dispatch: buffer (E*C+1, D); last row is the drop bin
    buf = jnp.zeros((E * C + 1, D), x.dtype).at[slot].set(xf[st])
    h = buf[: E * C].reshape(E, C, D)
    y = (
        jnp.einsum("ecf,efd->ecd",
                   swish(jnp.einsum("ecd,edf->ecf", h, p["w_gate"]))
                   * jnp.einsum("ecd,edf->ecf", h, p["w_up"]),
                   p["w_down"])
    )
    y = jnp.concatenate([y.reshape(E * C, D), jnp.zeros((1, D), y.dtype)], axis=0)

    # combine
    out = jnp.zeros((T, D), jnp.float32).at[st].add(
        (y[slot] * jnp.where(keep, sw, 0.0)[:, None]).astype(jnp.float32)
    )
    out = out.astype(x.dtype).reshape(B, S, D)
    if cfg.num_shared_experts:
        out = out + dense_mlp(p["shared"], x)
    return out, aux


def _local_expert_pass(xf, router_w, wg, wu, wd, cfg: ModelConfig,
                       capacity_factor: float, e_lo, e_loc: int):
    """Tokens xf (T, D) through the LOCAL experts [e_lo, e_lo + e_loc) only
    (e_lo may be a traced axis_index; e_loc is static). Returns
    (partial_out (T, D) f32, aux); the caller reduces across expert shards."""
    T, D = xf.shape
    E, K = cfg.num_experts, cfg.top_k
    E_loc = e_loc
    w, idx, aux = _router_topk(xf @ router_w, K)

    C = int(capacity_factor * T * K / E) + 1
    C = max(C, 4)

    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    expert_start = jnp.searchsorted(se, jnp.arange(E))
    pos = jnp.arange(T * K) - expert_start[se]
    local = (se >= e_lo) & (se < e_lo + E_loc) & (pos < C)
    slot = jnp.where(local, (se - e_lo) * C + pos, E_loc * C)

    buf = jnp.zeros((E_loc * C + 1, D), xf.dtype).at[slot].set(xf[st])
    h = buf[: E_loc * C].reshape(E_loc, C, D)
    y = jnp.einsum(
        "ecf,efd->ecd",
        swish(jnp.einsum("ecd,edf->ecf", h, wg))
        * jnp.einsum("ecd,edf->ecf", h, wu),
        wd)
    y = jnp.concatenate([y.reshape(E_loc * C, D),
                         jnp.zeros((1, D), y.dtype)], axis=0)
    out = jnp.zeros((T, D), jnp.float32).at[st].add(
        (y[slot] * jnp.where(local, sw, 0.0)[:, None]).astype(jnp.float32))
    return out, aux


def moe_mlp_sharded(p, x, cfg: ModelConfig, *, mesh, axis: str = "model",
                    capacity_factor: float = 1.25):
    """Expert-parallel MoE via shard_map (§Perf optimization).

    The baseline ``moe_mlp`` scatters into an expert-sharded buffer, which
    GSPMD lowers to replicated scatters + giant all-reduces. Here each
    expert shard all-gathers the (sequence-sharded) tokens once, runs ONLY
    its local experts with local scatters, and the partial outputs are
    combined with one reduce-scatter back to the sequence-sharded layout:
    exactly 2 collectives per MoE layer instead of GSPMD's emergent storm.
    """
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    tp = mesh.shape[axis]
    E = cfg.num_experts
    assert E % tp == 0, (E, tp)
    E_loc = E // tp
    dp = tuple(a for a in mesh.axis_names if a != axis)

    def body(xl, router_w, wg, wu, wd):
        # xl (B_loc, S/tp, D) -> gather full local-replica token set
        x_full = jax.lax.all_gather(xl, axis, axis=1, tiled=True)  # (B_loc,S,D)
        Bl, Sl, _ = x_full.shape
        xf = x_full.reshape(Bl * Sl, D)
        eidx = jax.lax.axis_index(axis)
        out, aux = _local_expert_pass(
            xf, router_w, wg, wu, wd, cfg, capacity_factor,
            e_lo=eidx * E_loc, e_loc=E_loc)
        out = out.reshape(Bl, Sl, D).astype(x.dtype)
        # sum partials across expert shards, landing seq-sharded again
        out = jax.lax.psum_scatter(out, axis, scatter_dimension=1, tiled=True)
        aux = jax.lax.pmean(aux, axis)
        for a in dp:
            aux = jax.lax.pmean(aux, a)
        return out, aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, axis, None), P(), P(axis, None, None),
                  P(axis, None, None), P(axis, None, None)),
        out_specs=(P(dp, axis, None), P()), check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if cfg.num_shared_experts:
        out = out + dense_mlp(p["shared"], x)
    return out, aux


def moe_mlp_ref(p, x, cfg: ModelConfig):
    """Naive per-token loop-free reference (computes ALL experts; test-only)."""
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    w, idx, _ = _router_topk(xf @ p["router"], cfg.top_k)
    all_y = jnp.einsum(
        "ecf,efd->ecd",
        swish(jnp.einsum("td,edf->etf", xf, p["w_gate"]).transpose(0, 1, 2)) *
        jnp.einsum("td,edf->etf", xf, p["w_up"]),
        p["w_down"],
    )  # careful: dims (E,T,D)
    # gather chosen experts per token
    picked = all_y[idx, jnp.arange(xf.shape[0])[:, None]]  # (T,K,D)
    out = jnp.sum(picked * w[..., None], axis=1).astype(x.dtype).reshape(B, S, D)
    if cfg.num_shared_experts:
        out = out + dense_mlp(p["shared"], x)
    return out

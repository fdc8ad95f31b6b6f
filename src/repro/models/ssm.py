"""Mamba-2 SSD (state-space duality) mixer — chunked-scan reference in pure
jnp (the Pallas kernel in ``repro.kernels.ssd_scan`` mirrors the same chunked
algorithm), plus O(1) single-token decode.

Block: in_proj -> [z | x | B | C | dt]; causal depthwise conv over (x,B,C);
SSD core y = SSD(a, dt*Bx, C) + D*x; gated RMSNorm(y * silu(z)); out_proj.
Group count G=1 (B/C shared across heads), as in Mamba-2 defaults.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec, rms_norm, swish
from repro.obs.scopes import scope


def ssm_specs(cfg: ModelConfig, layers: int) -> Dict[str, ParamSpec]:
    D, DI, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    W = cfg.ssm_conv_width
    conv_ch = DI + 2 * N  # x, B, C share the conv
    L = (layers,)
    la = ("layers",)
    return {
        "w_z": ParamSpec(L + (D, DI), la + ("embed", "inner")),
        "w_xbc": ParamSpec(L + (D, DI + 2 * N), la + ("embed", "conv_ch")),
        "w_dt": ParamSpec(L + (D, H), la + ("embed", "ssm_heads")),
        "conv_w": ParamSpec(L + (W, conv_ch), la + (None, "conv_ch"), scale=3.0),
        "conv_b": ParamSpec(L + (conv_ch,), la + ("conv_ch",), init="zeros"),
        "a_log": ParamSpec(L + (H,), la + ("ssm_heads",), init="ssm_a"),
        "dt_bias": ParamSpec(L + (H,), la + ("ssm_heads",), init="ssm_dt"),
        "d_skip": ParamSpec(L + (H,), la + ("ssm_heads",), init="ones"),
        "gate_norm": ParamSpec(L + (DI,), la + ("inner",), init="zeros"),
        "w_out": ParamSpec(L + (DI, D), la + ("inner", "embed")),
    }


# ---------------------------------------------------------------------------
# SSD core — chunked reference
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, a_neg, b_mat, c_mat, chunk: int, h0=None):
    """SSD over a full sequence, chunked.

    x      (B, L, H, P)   per-head inputs
    dt     (B, L, H)      softplus'd step sizes (>=0)
    a_neg  (H,)           negative continuous-time decay (-exp(a_log))
    b_mat  (B, L, N)      input projection onto state  (G=1, shared over heads)
    c_mat  (B, L, N)      state readout
    h0     (B, H, N, P)   optional initial state
    returns y (B, L, H, P), h_final (B, H, N, P)
    """
    B, L, H, P = x.shape
    N = b_mat.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q

    loga = dt * a_neg  # (B, L, H) log per-step decay, <= 0
    xr = x.reshape(B, nc, Q, H, P)
    dtr = dt.reshape(B, nc, Q, H)
    logar = loga.reshape(B, nc, Q, H)
    br = b_mat.reshape(B, nc, Q, N)
    cr = c_mat.reshape(B, nc, Q, N)

    cl = jnp.cumsum(logar, axis=2)  # (B,nc,Q,H) inclusive cumsum of log decay
    # intra-chunk: Lmat[h,i,j] = exp(cl_i - cl_j) for i >= j (decay j+1..i)
    diff = cl[:, :, :, None, :] - cl[:, :, None, :, :]  # (B,nc,Q(i),Q(j),H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    # mask BEFORE the exp: for i < j the exponent cl_i - cl_j is positive and
    # can overflow to inf, which the where() would drop in the forward pass
    # but turn into 0 * inf = NaN in the backward pass
    lmat = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", cr, br)  # (B,nc,Q,Q)
    w = cb[..., None] * lmat * dtr[:, :, None, :, :]  # (B,nc,i,j,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", w, xr)

    # chunk-final partial states: S_c = sum_j exp(cl_Q - cl_j) dt_j B_j x_j^T
    decay_to_end = jnp.exp(cl[:, :, -1:, :] - cl)  # (B,nc,Q,H)
    sx = xr * (decay_to_end * dtr)[..., None]  # (B,nc,Q,H,P)
    s_chunk = jnp.einsum("bcjn,bcjhp->bchnp", br, sx)  # (B,nc,H,N,P)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(cl[:, :, -1, :])  # (B,nc,H) total decay of chunk

    def scan_fn(h, inp):
        s_c, dec = inp  # (B,H,N,P), (B,H)
        h_next = h * dec[..., None, None] + s_c.astype(h.dtype)
        return h_next, h

    if h0 is None:
        h0 = jnp.zeros((B, H, N, P), jnp.float32)
    h_fin, h_prev = jax.lax.scan(
        scan_fn,
        h0.astype(jnp.float32),
        (s_chunk.transpose(1, 0, 2, 3, 4),
         chunk_decay.astype(jnp.float32).transpose(1, 0, 2)),
    )
    h_prev = h_prev.astype(x.dtype)
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)  # (B,nc,H,N,P) state at chunk start

    # inter-chunk contribution: y_i += exp(cl_i) * C_i . h_chunk_start
    decay_from_start = jnp.exp(cl)  # (B,nc,Q,H)
    y_inter = jnp.einsum("bcin,bchnp->bcihp", cr, h_prev) * decay_from_start[..., None]

    y = (y_intra + y_inter).reshape(B, L, H, P)
    return y, h_fin


def ssd_step(h, x_t, dt_t, a_neg, b_t, c_t):
    """Single-token SSD update.
    h (B,H,N,P), x_t (B,H,P), dt_t (B,H), b_t (B,N), c_t (B,N)."""
    dec = jnp.exp(dt_t * a_neg)  # (B,H)
    inject = jnp.einsum("bn,bhp->bhnp", b_t, x_t * dt_t[..., None])
    h = h * dec[..., None, None] + inject
    y = jnp.einsum("bn,bhnp->bhp", c_t, h)
    return y, h


# ---------------------------------------------------------------------------
# Mixer forward / decode
# ---------------------------------------------------------------------------


def _project(p, x):
    return x @ p["w_z"], x @ p["w_xbc"], x @ p["w_dt"]


def ssm_forward(p, x, positions, cfg: ModelConfig, *, impl="auto"):
    """Full-sequence mamba2 block. Returns (out, cache) with final state cache."""
    B, L, D = x.shape
    DI, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    W = cfg.ssm_conv_width

    z, xbc_raw, dt_raw = _project(p, x)

    # causal depthwise conv over (x,B,C) channels
    pad = jnp.pad(xbc_raw, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(
        pad[:, i : i + L] * p["conv_w"][i][None, None] for i in range(W)
    ) + p["conv_b"][None, None]
    xbc = swish(conv)

    xs = xbc[..., :DI].reshape(B, L, H, P)
    b_mat = xbc[..., DI : DI + N]
    c_mat = xbc[..., DI + N :]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"]).astype(x.dtype)
    a_neg = -jnp.exp(p["a_log"].astype(jnp.float32)).astype(x.dtype)

    with scope("ssd_scan"):
        if impl == "pallas":
            from repro.kernels import ops as kops
            y, h_fin = kops.ssd_scan(xs, dt, a_neg, b_mat, c_mat,
                                     chunk=cfg.ssm_chunk)
        else:
            y, h_fin = ssd_chunked(xs, dt, a_neg, b_mat, c_mat,
                                   cfg.ssm_chunk)
    y = y + xs * p["d_skip"][None, None, :, None]
    y = y.reshape(B, L, DI)
    y = rms_norm(y * swish(z), p["gate_norm"], cfg.norm_eps)
    out = y @ p["w_out"]
    # conv tail: last W-1 *pre-activation* (x,B,C) values, for decode continuation
    cache = {"state": h_fin, "conv": pad[:, L:]}
    return out, cache


def ssm_decode(p, x, pos, cache, cfg: ModelConfig):
    """Single-token mamba2 step. cache: state (B,H,N,P), conv (B,W-1,conv_ch)."""
    B = x.shape[0]
    DI, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    W = cfg.ssm_conv_width

    z, xbc_new, dt_raw = _project(p, x[:, 0])

    hist = jnp.concatenate([cache["conv"], xbc_new[:, None]], axis=1)  # (B,W,ch)
    conv = jnp.einsum("bwc,wc->bc", hist, p["conv_w"]) + p["conv_b"]
    xbc = swish(conv)

    x_t = xbc[..., :DI].reshape(B, H, P)
    b_t = xbc[..., DI : DI + N]
    c_t = xbc[..., DI + N :]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"]).astype(x.dtype)
    a_neg = -jnp.exp(p["a_log"].astype(jnp.float32)).astype(x.dtype)

    y, h = ssd_step(cache["state"], x_t, dt, a_neg, b_t, c_t)
    y = y + x_t * p["d_skip"][None, :, None]
    y = y.reshape(B, DI)
    y = rms_norm(y * swish(z), p["gate_norm"], cfg.norm_eps)
    out = (y @ p["w_out"])[:, None]
    return out, {"state": h, "conv": hist[:, 1:]}


def ssm_cache_specs(cfg: ModelConfig, layers: int, batch: int,
                    dtype: str = "bfloat16"):
    N, H, P = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    conv_ch = cfg.d_inner + 2 * N
    W = cfg.ssm_conv_width
    return {
        "state": ParamSpec((layers, batch, H, N, P),
                           ("layers", "batch", "ssm_heads", None, None),
                           dtype=dtype, init="zeros"),
        "conv": ParamSpec((layers, batch, W - 1, conv_ch),
                          ("layers", "batch", None, "conv_ch"),
                          dtype=dtype, init="zeros"),
    }

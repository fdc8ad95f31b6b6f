"""Plain reference of a Mamba-2 decoder (arXiv:2405.21060) with a tied head,
as the configuration file states it (keys in the published config's names).

Per layer: RMSNorm (weight ``1 + scale``), then the mixer: projections to
z, (x, B, C) and dt; a causal depthwise convolution over (x, B, C) with
SiLU; dt = softplus(dt + bias), A = -exp(A_log); the state-space model in
its quadratic (masked-matrix) form over the whole row,

    y_i = sum_{j <= i} (C_i . B_j) exp(sum_{k=j+1..i} dt_k A) dt_j x_j
          + D x_i,

one group of B and C shared by every head; then RMSNorm(y * silu(z)) and
the output projection, added to the residual.  Final RMSNorm and logits
against the embedding rows of the real vocabulary.

One row at a time, layer by layer (``lax.scan`` with each layer
rematerialised), the quadratic form in blocks of query positions.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from reference.common import LeafSpec, nll_sum, rms_norm, silu

Q_BLOCK = 512  # query positions per block of the quadratic form


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def dims(cfg: dict):
    D = cfg["d_model"]
    DI = cfg["expand"] * D
    return D, DI, cfg["d_state"], DI // cfg["headdim"], cfg["headdim"]


def leaf_shapes(cfg: dict) -> Dict[str, LeafSpec]:
    D, DI, N, H, _ = dims(cfg)
    L, W = cfg["n_layer"], cfg["d_conv"]
    C = DI + 2 * N
    s = "slots/slot0/"
    return {
        "embed": ((padded_vocab(cfg), D), "normal", 1.0),
        s + "mixer_norm": ((L, D), "zeros", 1.0),
        s + "mixer/w_z": ((L, D, DI), "normal", 1.0),
        s + "mixer/w_xbc": ((L, D, C), "normal", 1.0),
        s + "mixer/w_dt": ((L, D, H), "normal", 1.0),
        s + "mixer/conv_w": ((L, W, C), "normal", 3.0),
        s + "mixer/conv_b": ((L, C), "zeros", 1.0),
        s + "mixer/a_log": ((L, H), "ssm_a", 1.0),
        s + "mixer/dt_bias": ((L, H), "ssm_dt", 1.0),
        s + "mixer/d_skip": ((L, H), "ones", 1.0),
        s + "mixer/gate_norm": ((L, DI), "zeros", 1.0),
        s + "mixer/w_out": ((L, DI, D), "normal", 1.0),
        "final_norm": ((D,), "zeros", 1.0),
    }


def ssm(xs, dt, a, b, c, pol):
    """The quadratic form: xs (S,H,P), dt (S,H), a (H,), b/c (S,N)."""
    S = xs.shape[0]
    cum = jnp.cumsum(dt * a, axis=0)  # (S, H)
    ys = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(lo + Q_BLOCK, S)
        seg = cum[lo:hi, None, :] - cum[None, :, :]  # (q, S, H)
        causal = (jnp.arange(lo, hi)[:, None] >= jnp.arange(S)[None, :])
        # mask before exp: above the diagonal the exponent is positive
        decay = jnp.exp(jnp.where(causal[..., None], seg, -jnp.inf))
        cb = pol.ein("in,jn->ij", c[lo:hi], b)
        w = cb[..., None] * decay * dt[None]
        ys.append(pol.ein("ijh,jhp->ihp", w, xs))
    return jnp.concatenate(ys, axis=0)


def layer(cfg: dict, pol, h, p):
    D, DI, N, H, P = dims(cfg)
    eps, W = cfg["norm_eps"], cfg["d_conv"]
    S = h.shape[0]
    m = p["mixer"]
    x = rms_norm(h, p["mixer_norm"], eps)
    z = pol.ein("sd,de->se", x, m["w_z"])
    xbc = pol.ein("sd,dc->sc", x, m["w_xbc"])
    dt = jax.nn.softplus(pol.ein("sd,dh->sh", x, m["w_dt"]) + m["dt_bias"])
    pad = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), xbc.dtype), xbc])
    pad, conv_w = pol.q(pad), pol.q(m["conv_w"])
    conv = sum(pad[i:i + S] * conv_w[i] for i in range(W)) + m["conv_b"]
    xbc = silu(conv)
    xs = xbc[:, :DI].reshape(S, H, P)
    y = ssm(xs, dt, -jnp.exp(m["a_log"]), xbc[:, DI:DI + N], xbc[:, DI + N:],
            pol)
    y = (y + xs * m["d_skip"][None, :, None]).reshape(S, DI)
    y = rms_norm(y * silu(z), m["gate_norm"], eps)
    return h + pol.ein("se,ed->sd", y, m["w_out"])


def hidden(params, tokens, cfg: dict, pol):
    h = pol.q(params["embed"])[tokens]
    body = jax.checkpoint(lambda h, p: (layer(cfg, pol, h, p), None))
    h, _ = jax.lax.scan(body, h, params["slots"]["slot0"])
    return rms_norm(h, params["final_norm"], cfg["norm_eps"])


def row_nll(params, tokens, labels, cfg: dict, pol):
    h = hidden(params, tokens, cfg, pol)
    return nll_sum(h, params["embed"], labels, cfg["vocab_size"], pol)

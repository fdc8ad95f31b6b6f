"""Plain reference of a pre-norm GQA decoder with a tied head, as the
configuration file states it (keys in the published config's names).

Per layer: RMSNorm (weight ``1 + scale``), grouped-query attention with
rotary positions (half-split rotation) and a causal softmax, residual add;
RMSNorm, SwiGLU MLP, residual add.  Then a final RMSNorm and logits
against the embedding rows of the real vocabulary.  The embedding table is
held padded to a multiple of 256 rows, as the configuration runs it.

One row at a time, layer by layer (``lax.scan`` with each layer
rematerialised), so that a whole cell's batch fits beside nothing else.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from reference.common import LeafSpec, nll_sum, rms_norm, silu


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def leaf_shapes(cfg: dict) -> Dict[str, LeafSpec]:
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, F = cfg["head_dim"], cfg["intermediate_size"]
    s = "slots/slot0/"
    return {
        "embed": ((padded_vocab(cfg), D), "normal", 1.0),
        s + "mixer_norm": ((L, D), "zeros", 1.0),
        s + "mixer/wq": ((L, D, H, hd), "normal", 1.0),
        s + "mixer/wk": ((L, D, KV, hd), "normal", 1.0),
        s + "mixer/wv": ((L, D, KV, hd), "normal", 1.0),
        s + "mixer/wo": ((L, H, hd, D), "normal", 1.0),
        s + "mlp_norm": ((L, D), "zeros", 1.0),
        s + "mlp/w_gate": ((L, D, F), "normal", 1.0),
        s + "mlp/w_up": ((L, D, F), "normal", 1.0),
        s + "mlp/w_down": ((L, F, D), "normal", 1.0),
        "final_norm": ((D,), "zeros", 1.0),
    }


def rope(x, theta):
    """x (S, heads, hd): rotate the two halves of each head by position."""
    S, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(cfg: dict, pol, h, p):
    eps = cfg["rms_norm_eps"]
    S = h.shape[0]
    KV, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    G = cfg["num_attention_heads"] // KV
    x = rms_norm(h, p["mixer_norm"], eps)
    q = rope(pol.ein("sd,dhk->shk", x, p["mixer"]["wq"]), cfg["rope_theta"])
    k = rope(pol.ein("sd,dhk->shk", x, p["mixer"]["wk"]), cfg["rope_theta"])
    v = pol.ein("sd,dhk->shk", x, p["mixer"]["wv"])
    q = q.reshape(S, KV, G, hd)
    scores = pol.ein("qkgd,skd->kgqs", q, k) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = pol.ein("kgqs,skd->qkgd", probs, v).reshape(S, KV * G, hd)
    h = h + cfg["residual_multiplier"] * pol.ein("shk,hkd->sd", att,
                                                  p["mixer"]["wo"])
    x = rms_norm(h, p["mlp_norm"], eps)
    mlp = p["mlp"]
    u = silu(pol.ein("sd,df->sf", x, mlp["w_gate"])) * pol.ein(
        "sd,df->sf", x, mlp["w_up"])
    return h + cfg["residual_multiplier"] * pol.ein("sf,fd->sd", u,
                                                     mlp["w_down"])


def hidden(params, tokens, cfg: dict, pol):
    """Final hidden states (S, D) of one row of tokens (S,)."""
    h = pol.q(params["embed"])[tokens] * cfg["embedding_multiplier"]
    body = jax.checkpoint(lambda h, p: (layer(cfg, pol, h, p), None))
    h, _ = jax.lax.scan(body, h, params["slots"]["slot0"])
    return rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])


def row_nll(params, tokens, labels, cfg: dict, pol):
    h = hidden(params, tokens, cfg, pol) / cfg["logits_scaling"]
    return nll_sum(h, params["embed"], labels, cfg["vocab_size"], pol)

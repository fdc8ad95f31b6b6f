"""Plain reference of DeepSeek-V2 (arXiv:2405.04434) as the configuration
file states it (keys in the published config's names): one chip's share of
the model, in float32.

Embedding; ``first_k_dense_replace`` dense layers, then MoE layers.  Each
layer: RMSNorm (weight ``1 + scale``), multi-head latent attention, residual
add; RMSNorm, MLP, residual add.  Then a final RMSNorm and logits against
the untied head over the vocabulary's slice.

* MLA without q-LoRA: q = x Wq per head (qk_nope + qk_rope wide); the
  latent ``[c_kv, k_pe] = x W_dkv``, c_kv normalised, per-head
  ``[k_nope, v] = c_kv W_ukv`` materialised; rotary positions on q_pe and
  the one shared k_pe with YaRN's inverse frequencies and cos/sin factor
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``; a causal
  softmax at scale ``(qk_nope + qk_rope)^-1/2 * mscale(factor,
  mscale_all_dim)^2``.
* Dense layer: SwiGLU of ``intermediate_size``.
* MoE layer: softmax router over ``router_experts`` outputs, greedy top-k
  (``num_experts_per_tok``), weights the raw probabilities (no
  renormalisation when ``norm_topk_prob`` is false) times
  ``routed_scaling_factor``.  The ``n_routed_experts`` experts held here,
  ``[expert_offset, expert_offset + n_routed_experts)``, are each applied
  to every token and weighted by its router weight where the top-k picked
  it, else 0; the shared experts (SwiGLU of ``n_shared_experts *
  moe_intermediate_size``) are added for every token.  The
  sequence-wise balance loss over all router experts, ``sum_i f_i P_i``
  with ``f_i = E / (k S) * #{t: i in topk(t)}`` and ``P_i`` the mean
  probability, is summed over the MoE layers and weighted by
  ``aux_loss_alpha``.

Departures from the published model, each as the configuration's
``assumed`` and ``reduced`` state them: rope on the half-split layout
(DeepSeek rotates interleaved pairs, a fixed permutation of the weights'
columns); only the held experts' part of the routed result (the chip's
share); the vocabulary's slice.

One row at a time, layer by layer (``lax.scan`` with each layer
rematerialised), attention in query blocks (each rematerialised), so that
8192 tokens fit.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import LeafSpec, rms_norm, silu

Q_BLOCK = 1024


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def _layer_shapes(cfg: dict, n: int, moe: bool) -> Dict[str, LeafSpec]:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rdim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vdim, kvlr = cfg["v_head_dim"], cfg["kv_lora_rank"]
    s = {
        "mixer_norm": ((n, D), "zeros", 1.0),
        "mixer/wq": ((n, D, H, nope + rdim), "normal", 1.0),
        "mixer/wkv_down": ((n, D, kvlr + rdim), "normal", 1.0),
        "mixer/kv_norm": ((n, kvlr), "zeros", 1.0),
        "mixer/wkv_up": ((n, kvlr, H, nope + vdim), "normal", 1.0),
        "mixer/wo": ((n, H, vdim, D), "normal", 1.0),
        "mlp_norm": ((n, D), "zeros", 1.0),
    }
    if not moe:
        F = cfg["intermediate_size"]
        s.update({"mlp/w_gate": ((n, D, F), "normal", 1.0),
                  "mlp/w_up": ((n, D, F), "normal", 1.0),
                  "mlp/w_down": ((n, F, D), "normal", 1.0)})
        return s
    E, F = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    Fs = cfg["n_shared_experts"] * F
    s.update({
        "mlp/router": ((n, D, cfg["router_experts"]), "normal", 0.1),
        "mlp/w_gate": ((n, E, D, F), "normal", 1.0),
        "mlp/w_up": ((n, E, D, F), "normal", 1.0),
        "mlp/w_down": ((n, E, F, D), "normal", 1.0),
        "mlp/shared/w_gate": ((n, D, Fs), "normal", 1.0),
        "mlp/shared/w_up": ((n, D, Fs), "normal", 1.0),
        "mlp/shared/w_down": ((n, Fs, D), "normal", 1.0),
    })
    return s


def leaf_shapes(cfg: dict) -> Dict[str, LeafSpec]:
    D, V = cfg["hidden_size"], padded_vocab(cfg)
    dense = cfg["first_k_dense_replace"]
    out = {"embed": ((V, D), "normal", 1.0)}
    out.update({f"prelude/{k}": v
                for k, v in _layer_shapes(cfg, dense, False).items()})
    out.update({f"slots/slot0/{k}": v for k, v in _layer_shapes(
        cfg, cfg["num_hidden_layers"] - dense, True).items()})
    out["final_norm"] = ((D,), "zeros", 1.0)
    out["lm_head"] = ((D, V), "normal", 1.0)
    return out


# ---------------------------------------------------------------------------
# YaRN rotary positions
# ---------------------------------------------------------------------------


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * np.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(cfg: dict):
    """(inverse frequencies (d/2,), cos/sin factor, softmax scale)."""
    rs, d, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return d * np.log(orig / (rotations * 2 * np.pi)) / (2 * np.log(base))

    low = max(int(np.floor(correction_dim(rs["beta_fast"]))), 0)
    high = min(int(np.ceil(correction_dim(rs["beta_slow"]))), d - 1)
    extra = 1.0 / base ** (np.arange(0, d, 2) / d)
    inter = extra / factor
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    freqs = inter * (1.0 - keep) + extra * keep
    m = _mscale(factor, rs["mscale"]) / _mscale(factor, rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + d) ** -0.5 * _mscale(
        factor, rs["mscale_all_dim"]) ** 2
    return jnp.asarray(freqs, jnp.float32), float(m), float(scale)


def rope(x, freqs, m):
    """x (S, heads, d): rotate the two halves of each head by position."""
    S, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # (S, half)
    cos, sin = (jnp.cos(ang) * m)[:, None], (jnp.sin(ang) * m)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def attention(cfg: dict, pol, x, p):
    nope, kvlr = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    S = x.shape[0]
    freqs, m, scale = yarn(cfg)
    q = pol.ein("sd,dhk->shk", x, p["wq"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], freqs, m)], -1)
    latent = pol.ein("sd,dc->sc", x, p["wkv_down"])
    ckv = rms_norm(latent[:, :kvlr], p["kv_norm"], cfg["rms_norm_eps"])
    k_pe = rope(latent[:, None, kvlr:], freqs, m)  # (S, 1, rdim)
    kv = pol.ein("sc,chk->shk", ckv, p["wkv_up"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:2] + k_pe.shape[-1:])],
        -1)
    qb = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        scores = pol.ein("qhd,shd->hqs", qi, k) * scale
        causal = (jnp.arange(S)[None, :]
                  <= (i * qb + jnp.arange(qb))[:, None])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return pol.ein("hqs,shd->qhd", probs, v)

    att = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, *v.shape[1:])
    return pol.ein("shv,hvd->sd", att, p["wo"])


def swiglu(pol, x, p):
    u = silu(pol.ein("sd,df->sf", x, p["w_gate"])) * pol.ein(
        "sd,df->sf", x, p["w_up"])
    return pol.ein("sf,fd->sd", u, p["w_down"])


def moe(cfg: dict, pol, x, p):
    """(held experts' routed part + shared experts, balance loss)."""
    S = x.shape[0]
    E, K = cfg["router_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(pol.ein("sd,de->se", x, p["router"]), axis=-1)
    w, idx = jax.lax.top_k(probs, K)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    held = p["w_gate"].shape[0]
    local = idx - cfg["expert_offset"]
    sel = jnp.sum(jax.nn.one_hot(local, held) * w[..., None], axis=1)
    u = silu(pol.ein("sd,edf->esf", x, p["w_gate"])) * pol.ein(
        "sd,edf->esf", x, p["w_up"])
    y = pol.ein("esf,efd->esd", u, p["w_down"])
    routed = jnp.einsum("se,esd->sd", sel, y,
                        precision=jax.lax.Precision.HIGHEST)
    counts = jnp.sum(jax.nn.one_hot(idx, E), axis=(0, 1))
    aux = jnp.sum(counts * E / (K * S) * jnp.mean(probs, axis=0))
    return routed + swiglu(pol, x, p["shared"]), aux


def layer(cfg: dict, pol, h, p, is_moe: bool):
    eps = cfg["rms_norm_eps"]
    h = h + attention(cfg, pol, rms_norm(h, p["mixer_norm"], eps), p["mixer"])
    x = rms_norm(h, p["mlp_norm"], eps)
    if is_moe:
        y, aux = moe(cfg, pol, x, p["mlp"])
    else:
        y, aux = swiglu(pol, x, p["mlp"]), jnp.float32(0.0)
    return h + y, aux


def hidden(params, tokens, cfg: dict, pol):
    """Final hidden states (S, D) of one row of tokens (S,), and the
    balance loss summed over the MoE layers."""
    h = pol.q(params["embed"])[tokens]

    def stack(is_moe):
        return jax.checkpoint(
            lambda h, p: layer(cfg, pol, h, p, is_moe))

    h, _ = jax.lax.scan(stack(False), h, params["prelude"])
    h, aux = jax.lax.scan(stack(True), h, params["slots"]["slot0"])
    return rms_norm(h, params["final_norm"], cfg["rms_norm_eps"]), \
        jnp.sum(aux)


def row_nll(params, tokens, labels, cfg: dict, pol):
    """One row's summed next-token loss plus its share of the balance loss:
    the batch loss is the mean over tokens of the first and the mean over
    rows (sequences) of the second, so a row of S tokens carries
    ``alpha * S`` times its own."""
    h, aux = hidden(params, tokens, cfg, pol)
    logits = pol.ein("sd,dv->sv", h, params["lm_head"][:, :cfg["vocab_size"]])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold) + cfg["aux_loss_alpha"] * tokens.shape[0] * aux

"""Attention mixers: GQA (optionally sliding-window / softcapped) and MLA
(DeepSeek-V2 multi-head latent attention), each with

  * full-sequence path (train / prefill)  — ``dense``, ``chunked`` or
    ``pallas`` impl (chunked = online-softmax scan over KV blocks: the XLA
    flash-attention reference; the Pallas kernel in ``repro.kernels``
    mirrors its math and has its own backward pass; ``auto`` takes it on a
    TPU), and
  * cached single-token decode path (MLA uses the absorbed-latent form).

Shapes: x (B, S, D); caches are per-slot dicts of (B, S_max, ...) arrays.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec, rope, softcap
from repro.obs.metrics import TRACE_COUNTS
from repro.obs.scopes import scope

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def gqa_specs(cfg: ModelConfig, layers: int) -> Dict[str, ParamSpec]:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L = (layers,)
    la = ("layers",)
    s = {
        "wq": ParamSpec(L + (D, H, hd), la + ("embed", "q_heads", None)),
        "wk": ParamSpec(L + (D, KV, hd), la + ("embed", "kv_heads", None)),
        "wv": ParamSpec(L + (D, KV, hd), la + ("embed", "kv_heads", None)),
        "wo": ParamSpec(L + (H, hd, D), la + ("q_heads", None, "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec(L + (H, hd), la + ("q_heads", None), init="zeros")
        s["bk"] = ParamSpec(L + (KV, hd), la + ("kv_heads", None), init="zeros")
        s["bv"] = ParamSpec(L + (KV, hd), la + ("kv_heads", None), init="zeros")
    return s


def mla_specs(cfg: ModelConfig, layers: int) -> Dict[str, ParamSpec]:
    D, H = cfg.d_model, cfg.num_heads
    nope, rdim, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qlr, kvlr = cfg.q_lora_rank, cfg.kv_lora_rank
    L = (layers,)
    la = ("layers",)
    if qlr:
        q = {
            "wq_down": ParamSpec(L + (D, qlr), la + ("embed", "lora")),
            "q_norm": ParamSpec(L + (qlr,), la + ("lora",), init="zeros"),
            "wq_up": ParamSpec(L + (qlr, H, nope + rdim),
                               la + ("lora", "q_heads", None)),
        }
    else:  # no q-LoRA (deepseek-v2-lite): q projected directly
        q = {"wq": ParamSpec(L + (D, H, nope + rdim),
                             la + ("embed", "q_heads", None))}
    return dict(q, **{
        "wkv_down": ParamSpec(L + (D, kvlr + rdim), la + ("embed", None)),
        "kv_norm": ParamSpec(L + (kvlr,), la + (None,), init="zeros"),
        "wkv_up": ParamSpec(L + (kvlr, H, nope + vdim), la + (None, "q_heads", None)),
        "wo": ParamSpec(L + (H, vdim, D), la + ("q_heads", None, "embed")),
    })


def attn_specs(cfg: ModelConfig, mixer: str, layers: int) -> Dict[str, ParamSpec]:
    return mla_specs(cfg, layers) if mixer.startswith("mla") else gqa_specs(cfg, layers)


# ---------------------------------------------------------------------------
# Core attention math (shared by dense / chunked)
# ---------------------------------------------------------------------------


def _mask(q_pos, k_pos, window: int):
    """(..., Sq, Sk) boolean mask: causal + optional sliding window.
    Negative k_pos marks invalid (unwritten ring-buffer) slots."""
    m = (k_pos[..., None, :] <= q_pos[..., :, None]) & (k_pos[..., None, :] >= 0)
    if window:
        m &= (q_pos[..., :, None] - k_pos[..., None, :]) < window
    return m


def dense_attention(q, k, v, q_pos, k_pos, *, scale, window=0, cap=0.0):
    """q (B,Sq,H,dk), k (B,Sk,KV,dk), v (B,Sk,KV,dv); GQA via head repeat."""
    B, Sq, H, dk = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, dk)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    logits = softcap(logits, cap)
    m = _mask(q_pos, k_pos, window)[:, None, None]  # (B,1,1,Sq,Sk)
    logits = jnp.where(m, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def chunked_attention(q, k, v, q_pos, k_pos, *, scale, window=0, cap=0.0,
                      kv_block=1024, q_block=2048, unroll_kv=False):
    """Triangular blocked online-softmax attention — the XLA flash reference.

    Outer *unrolled* loop over query blocks (so each block sees a static KV
    prefix: no wasted FLOPs on fully-masked future blocks; sliding windows
    also bound the prefix from below); inner ``lax.scan`` over KV blocks with
    running (max, denom, acc). Live memory is O(q_block * kv_block * H)."""
    B, Sq, H, dk = q.shape
    Sk, KV, dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    q_pad = -Sq % q_block
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, q_pad)), constant_values=-1)
    k_pad = -Sk % kv_block
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, k_pad)), constant_values=2**30)
    Sk_p = Sk + k_pad

    def one_q_block(qi: int):
        q_lo, q_hi = qi * q_block, (qi + 1) * q_block
        qg = (q[:, q_lo:q_hi].reshape(B, q_block, KV, G, dk) * scale)
        qp = q_pos[:, q_lo:q_hi]
        # static KV range this q block can see (assumes monotone positions:
        # q_pos = offset + arange, which holds for train/prefill paths)
        kv_hi = min(-(-q_hi // kv_block) * kv_block, Sk_p)
        kv_lo = 0
        if window:
            kv_lo = max(0, (q_lo - window) // kv_block * kv_block)
        nblk = (kv_hi - kv_lo) // kv_block
        kb = k[:, kv_lo:kv_hi].reshape(B, nblk, kv_block, KV, dk).transpose(1, 0, 2, 3, 4)
        vb = v[:, kv_lo:kv_hi].reshape(B, nblk, kv_block, KV, dv).transpose(1, 0, 2, 3, 4)
        pb = k_pos[:, kv_lo:kv_hi].reshape(B, nblk, kv_block).transpose(1, 0, 2)

        def step(carry, blk):
            m_run, l_run, acc = carry
            kc, vc, pc = blk
            logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc).astype(jnp.float32)
            logits = softcap(logits, cap)
            msk = _mask(qp, pc, window)[:, None, None]
            logits = jnp.where(msk, logits, NEG_INF)
            m_blk = jnp.max(logits, axis=-1)
            m_new = jnp.maximum(m_run, m_blk)
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, KV, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_block, dv), jnp.float32)
        if unroll_kv:
            # counting mode for the dry-run FLOP accounting: XLA's
            # cost_analysis does not multiply while-body costs by trip count,
            # so the roofline lowers use a physically-unrolled KV loop.
            carry = (m0, l0, a0)
            for t in range(nblk):
                carry, _ = step(carry, (kb[t], vb[t], pb[t]))
            m_f, l_f, acc = carry
        else:
            (m_f, l_f, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4).reshape(B, q_block, H, dv)

    blocks = [one_q_block(i) for i in range((Sq + q_pad) // q_block)]
    out = jnp.concatenate(blocks, axis=1) if len(blocks) > 1 else blocks[0]
    return out[:, :Sq].astype(v.dtype)


# the name each impl is counted under in ``TRACE_COUNTS``
_COUNTED = {"pallas": "flash", "counting": "chunked"}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _auto_impl(q, k, v) -> str:
    """``impl="auto"``: the Pallas flash kernel for full-sequence
    self-attention on a TPU (the train and prefill paths, whose positions
    are ``arange(S)``; MLA's value head may be narrower than its query and
    key head), else dense up to 2048 keys and chunked above."""
    S = q.shape[1]
    if _on_tpu() and k.shape[1] == S and S % 128 == 0:
        return "pallas"
    return "chunked" if k.shape[1] > 2048 else "dense"


def attention(q, k, v, q_pos, k_pos, *, scale, window=0, cap=0.0,
              impl="auto", kv_block=1024):
    with scope("attention_core"):
        if impl == "auto":
            impl = _auto_impl(q, k, v)
        TRACE_COUNTS.inc("attention/" + _COUNTED.get(impl, impl))
        if impl == "pallas":
            # the Pallas flash kernel: compiled on a TPU, run by the Pallas
            # interpreter on any other backend (kernels/ops.py decides)
            from repro.kernels import ops as kops
            return kops.flash_attention(q, k, v, q_pos, k_pos, scale=scale,
                                        window=window, cap=cap)
        if impl == "counting":
            # dry-run FLOP-accounting mode: big unrolled blocks, no while
            # loops
            return chunked_attention(q, k, v, q_pos, k_pos, scale=scale,
                                     window=window, cap=cap, kv_block=8192,
                                     q_block=8192, unroll_kv=True)
        f = dense_attention if impl == "dense" else chunked_attention
        kw = {} if impl == "dense" else {"kv_block": kv_block}
        return f(q, k, v, q_pos, k_pos, scale=scale, window=window, cap=cap,
                 **kw)


# ---------------------------------------------------------------------------
# GQA mixer
# ---------------------------------------------------------------------------


def _window_for(cfg: ModelConfig, mixer: str) -> int:
    if mixer in ("swa", "mla_swa"):
        return cfg.sliding_window
    return cfg.attn_window_override  # 0 unless long-context SWA variant


def gqa_forward(p, x, positions, cfg: ModelConfig, mixer: str, *,
                impl="auto") -> jax.Array:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    out = attention(
        q, k, v, positions, positions,
        scale=1.0 / np.sqrt(cfg.head_dim),
        window=_window_for(cfg, mixer),
        cap=cfg.attn_softcap,
        impl=impl,
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), {"k": k, "v": v}


def quantize_kv(x):
    """Per-(token, head) int8 quantization: x (B,1,KV,hd) ->
    (int8 values, f32 scales (B,1,KV))."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(q, s, dtype):
    return (q.astype(jnp.float32) * s[..., None].astype(jnp.float32)).astype(dtype)


def gqa_decode(p, x, pos, cache, cfg: ModelConfig, mixer: str,
               scatter: bool = False):
    """x (B,1,D); pos (B,) int32 current position; cache dict k/v (B,Smax,KV,hd).
    If the cache carries ``k_scale``/``v_scale`` it is int8-quantized (§Perf:
    halves decode cache bytes vs bf16; per-token-per-head scales)."""
    B = x.shape[0]
    quant = "k_scale" in cache
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)
    window = _window_for(cfg, mixer)
    wpos, k_pos = _ring_positions(pos, cache["k"].shape[1], window, B)
    write = _cache_write_scatter if (scatter or quant) else _cache_write
    new_cache = {}
    if quant:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ckq = write(cache["k"], kq, wpos)
        cvq = write(cache["v"], vq, wpos)
        cks = write(cache["k_scale"], ks, wpos)
        cvs = write(cache["v_scale"], vs, wpos)
        ck = dequantize_kv(ckq, cks, x.dtype)
        cv = dequantize_kv(cvq, cvs, x.dtype)
        new_cache = {"k": ckq, "v": cvq, "k_scale": cks, "v_scale": cvs}
    else:
        ck = write(cache["k"], k, wpos)
        cv = write(cache["v"], v, wpos)
        new_cache = {"k": ck, "v": cv}
    out = attention(
        q, ck, cv, pos[:, None], k_pos,
        scale=1.0 / np.sqrt(cfg.head_dim),
        window=window,
        cap=cfg.attn_softcap,
        impl="dense",  # single query: dense == flash-decoding after SPMD
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def gqa_extend(p, x, pos0, cache, cfg: ModelConfig, mixer: str):
    """Chunked-prefill extension: append a chunk of C tokens to a *linear*
    cache.  x (B,C,D); pos0 (B,) absolute position of the chunk's first
    token; cache dict k/v (B,Smax,KV,hd), non-ring, bf16 (int8-quantized
    caches are a decode-path option and unsupported here).

    Equivalent to running prefill over prompt[:pos0+C] and keeping the last
    C outputs: the chunk attends causally to the cache (which holds every
    earlier position at its own slot) plus itself."""
    B, C = x.shape[:2]
    positions = pos0[:, None] + jnp.arange(C)[None]  # (B, C)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    ck = _cache_write_chunk(cache["k"], k, positions)
    cv = _cache_write_chunk(cache["v"], v, positions)
    s_cache = ck.shape[1]
    k_pos = jnp.broadcast_to(jnp.arange(s_cache)[None], (B, s_cache))
    out = attention(
        q, ck, cv, positions, k_pos,
        scale=1.0 / np.sqrt(cfg.head_dim),
        window=_window_for(cfg, mixer),
        cap=cfg.attn_softcap,
        impl="dense",
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), {"k": ck, "v": cv}


def _cache_write_chunk(cache, new, positions):
    """Write new (B,C,...) into cache (B,Smax,...) at per-example positions
    (B,C) — the multi-token scatter behind chunked prefill."""
    b_idx = jnp.arange(cache.shape[0])[:, None]
    return cache.at[b_idx, positions].set(new.astype(cache.dtype))


def _ring_positions(pos, s_cache: int, window: int, batch: int):
    """Write index + absolute positions held by each cache slot.

    If the cache is window-sized (ring buffer for SWA slots), slot j holds
    absolute position pos - ((pos - j) mod S); unwritten slots come out
    negative and are masked. Otherwise the cache is linear: slot j = pos j."""
    ring = bool(window) and s_cache <= window
    j = jnp.arange(s_cache)[None]
    if ring:
        wpos = pos % s_cache
        k_pos = pos[:, None] - jnp.mod(pos[:, None] - j, s_cache)
    else:
        wpos = pos
        k_pos = jnp.broadcast_to(j, (batch, s_cache))
    return wpos, k_pos


def _cache_write_scatter(cache, new, pos):
    """In-place-friendly scatter write (§Perf): one row per example instead
    of the one-hot blend (which reads+writes the whole cache twice)."""
    b_idx = jnp.arange(cache.shape[0])
    return cache.at[b_idx, pos].set(new[:, 0].astype(cache.dtype))


def _cache_write(cache, new, pos):
    """Write new (B,1,...) into cache (B,Smax,...) at per-example pos (B,)."""
    B = cache.shape[0]
    oh = jax.nn.one_hot(pos, cache.shape[1], dtype=cache.dtype)  # (B, Smax)
    oh = oh.reshape((B, cache.shape[1]) + (1,) * (cache.ndim - 2))
    return cache * (1 - oh) + oh * new[:, 0][:, None]


# ---------------------------------------------------------------------------
# MLA mixer
# ---------------------------------------------------------------------------


def mla_rope(cfg: ModelConfig):
    """(inverse frequencies or None, cos/sin factor) of MLA's rotary part:
    YaRN's when the configuration scales rope, else the plain ones."""
    if not cfg.yarn_factor:
        return None, 1.0
    from repro.models.common import yarn_freqs, yarn_mscale

    freqs = yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn_factor,
                       cfg.yarn_original_max_position, cfg.yarn_beta_fast,
                       cfg.yarn_beta_slow)
    mscale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
              / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return freqs, mscale


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: ``(qk_nope + qk_rope)^-1/2``, times YaRN's mscale
    squared where the configuration scales rope."""
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        from repro.models.common import yarn_mscale

        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return float(scale)


def _mla_qkv(p, x, positions, cfg: ModelConfig):
    from repro.models.common import rms_norm

    nope = cfg.qk_nope_head_dim
    if "wq" in p:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    else:
        cq = rms_norm(x @ p["wq_down"], p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsl,lhk->bshk", cq, p["wq_up"])
    freqs, mscale = mla_rope(cfg)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope(q_rope, positions, cfg.rope_theta, freqs, mscale)

    ckv_full = x @ p["wkv_down"]  # (B,S,kvlr+rdim)
    ckv, k_rope = ckv_full[..., : cfg.kv_lora_rank], ckv_full[..., cfg.kv_lora_rank:]
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    k_rope = rope(k_rope[..., None, :], positions, cfg.rope_theta, freqs,
                  mscale)[..., 0, :]
    return q_nope, q_rope, ckv, k_rope


def mla_forward(p, x, positions, cfg: ModelConfig, mixer: str, *, impl="auto"):
    """Full-sequence MLA: reconstruct per-head K/V from the latent (train/prefill)."""
    nope, vdim = cfg.qk_nope_head_dim, cfg.v_head_dim
    H = cfg.num_heads
    q_nope, q_rope, ckv, k_rope = _mla_qkv(p, x, positions, cfg)
    kv = jnp.einsum("bsl,lhk->bshk", ckv, p["wkv_up"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], k_nope.shape[:3] + (q_rope.shape[-1],))],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attention(
        q, k, v, positions, positions,
        scale=mla_scale(cfg),
        window=_window_for(cfg, mixer),
        cap=cfg.attn_softcap,
        impl=impl,
    )
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"]), {"ckv": ckv, "k_rope": k_rope}


def mla_decode(p, x, pos, cache, cfg: ModelConfig, mixer: str,
               scatter: bool = False):
    """Absorbed-latent decode: attend in the compressed kv_lora space.
    cache: ckv (B,Smax,kvlr), k_rope (B,Smax,rdim)."""
    nope = cfg.qk_nope_head_dim
    B = x.shape[0]
    q_nope, q_rope, ckv_new, k_rope_new = _mla_qkv(p, x, pos[:, None], cfg)
    window = _window_for(cfg, mixer)
    wpos, k_pos = _ring_positions(pos, cache["ckv"].shape[1], window, B)
    write = _cache_write_scatter if scatter else _cache_write
    ckv = write(cache["ckv"], ckv_new, wpos)
    krope = write(cache["k_rope"], k_rope_new, wpos)

    w_uk = p["wkv_up"][..., :nope]  # (kvlr, H, nope)
    w_uv = p["wkv_up"][..., nope:]  # (kvlr, H, vdim)
    q_abs = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk)  # absorbed query
    scale = mla_scale(cfg)
    logits = (
        jnp.einsum("bshl,bkl->bhsk", q_abs, ckv)
        + jnp.einsum("bshr,bkr->bhsk", q_rope, krope)
    ).astype(jnp.float32) * scale
    logits = softcap(logits, cfg.attn_softcap)
    m = _mask(pos[:, None], k_pos, window)[:, None]
    logits = jnp.where(m, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(ckv.dtype)
    ctx = jnp.einsum("bhsk,bkl->bshl", probs, ckv)  # latent context
    out = jnp.einsum("bshl,lhv->bshv", ctx, w_uv)
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"]), {"ckv": ckv, "k_rope": krope}


# ---------------------------------------------------------------------------
# Cache allocation
# ---------------------------------------------------------------------------


def attn_cache_specs(cfg: ModelConfig, mixer: str, layers: int, batch: int,
                     s_max: int, dtype: str = "bfloat16",
                     kv_quant: bool = False):
    """ParamSpec-style descriptors for the per-slot KV cache (stacked layers).
    ``kv_quant``: int8 values + per-(token, head) f32 scales (GQA only)."""
    L = (layers, batch)
    la = ("layers", "batch")
    if mixer.startswith("mla"):
        return {
            "ckv": ParamSpec(L + (s_max, cfg.kv_lora_rank), la + ("kv_seq", None),
                             dtype=dtype, init="zeros"),
            "k_rope": ParamSpec(L + (s_max, cfg.qk_rope_head_dim),
                                la + ("kv_seq", None), dtype=dtype, init="zeros"),
        }
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    vdt = "int8" if kv_quant else dtype
    specs = {
        "k": ParamSpec(L + (s_max, KV, hd), la + ("kv_seq", None, None),
                       dtype=vdt, init="zeros"),
        "v": ParamSpec(L + (s_max, KV, hd), la + ("kv_seq", None, None),
                       dtype=vdt, init="zeros"),
    }
    if kv_quant:
        specs["k_scale"] = ParamSpec(L + (s_max, KV), la + ("kv_seq", None),
                                     dtype="float32", init="zeros")
        specs["v_scale"] = ParamSpec(L + (s_max, KV), la + ("kv_seq", None),
                                     dtype="float32", init="zeros")
    return specs

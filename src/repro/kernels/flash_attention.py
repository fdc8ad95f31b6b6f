"""Pallas TPU flash attention (causal, GQA, sliding-window, softcap).

Grid (B, H, n_q_blocks, n_kv_blocks); the innermost kv dimension is
sequential ("arbitrary") so the online-softmax running state lives in VMEM
scratch across kv steps. Block shapes are MXU-aligned (q_block × head_dim,
head_dim a multiple of 128 where the arch allows). Fully-masked kv blocks
(above the causal diagonal / outside the sliding window) are skipped with
``pl.when`` — the same triangular saving the XLA reference gets from its
static q-block prefix.

Layout: q (B, H, Sq, D), k/v (B, KV, Sk, D) — transposed by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, cap, window, sk_real, tq, tk, nk):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * tq
    k_start = ki * tk
    # block-level relevance: causal (k_start <= q_end) and window
    relevant = k_start <= q_start + tq - 1
    if window:
        relevant &= (k_start + tk - 1) > (q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (tq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (tk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if cap:
            s = cap * jnp.tanh(s / cap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        mask = (kpos <= qpos) & (kpos < sk_real)
        if window:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, scale: float, window: int = 0,
                    cap: float = 0.0, q_block: int = 512, kv_block: int = 512,
                    interpret: bool):
    """q (B,H,Sq,D), k/v (B,KV,Sk,D) -> (B,H,Sq,D). Causal."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    tq = min(q_block, max(Sq, 8))
    tk = min(kv_block, max(Sk, 8))
    q_pad = -Sq % tq
    k_pad = -Sk % tk
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    nq = (Sq + q_pad) // tq
    nk = (Sk + k_pad) // tk

    kernel = functools.partial(
        _kernel, scale=scale, cap=cap, window=window, sk_real=Sk,
        tq=tq, tk=tk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, tq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, tk, D), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, tk, D), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + q_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, D), jnp.float32),
            pltpu.VMEM((tq,), jnp.float32),
            pltpu.VMEM((tq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]

"""Pallas TPU flash-decoding: one query token against a long KV cache.

Grid (B, H, n_kv_blocks); kv sequential with running (m, l, acc) scratch —
the single-chip analogue of the cross-shard partial-softmax combine the
SPMD decode path performs. Per-example position of the current token
arrives as a (B,) int32 scalar-prefetch operand in SMEM (cache entries
> pos masked).

Layout: q (B, H, D), k/v (B, KV, S, D).

Two entry points share one kernel body:

* :func:`decode_attention`        — linear per-request caches (B, KV, S, D)
* :func:`paged_decode_attention`  — a block-pool cache (N, KV, bs, D) plus a
  per-request block table (B, nb).  The table is a *scalar-prefetch* operand
  (``PrefetchScalarGridSpec``): the kv grid axis walks logical blocks and the
  BlockSpec index map translates them to physical pool blocks, so the kernel
  streams exactly the request's blocks with no gather materialization.
  With ``bs == kv_block`` both paths run the identical op sequence per
  block, so their outputs are bit-identical for the same cache content.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _flash_body(pos, ki, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale, cap, window, tk, nk):
    """One kv-block step of the running-softmax decode, shared by the linear
    and paged kernels. ``ki`` is the *logical* block index — masking is by
    logical position, so where the physical block came from is irrelevant."""

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = ki * tk
    relevant = k_start <= pos
    if window:
        relevant &= (k_start + tk - 1) >= pos - window + 1

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (1, D) block carries one head
        k = k_ref[0, 0].astype(jnp.float32)  # (tk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (1, tk)
        if cap:
            s = cap * jnp.tanh(s / cap)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        mask = kpos <= pos
        if window:
            mask &= (pos - kpos) < window
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


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, cap, window, tk, nk):
    _flash_body(pos_ref[pl.program_id(0)], pl.program_id(2), q_ref, k_ref,
                v_ref, o_ref, acc_ref, m_ref, l_ref, scale=scale, cap=cap,
                window=window, tk=tk, nk=nk)


def _paged_kernel(table_ref, *refs, **kw):
    # table_ref routed the k/v BlockSpecs; the body only needs the position.
    del table_ref
    _kernel(*refs, **kw)


def decode_attention(q, k, v, pos, *, scale: float, window: int = 0,
                     cap: float = 0.0, kv_block: int = 512,
                     interpret: bool):
    """q (B,H,D), k/v (B,KV,S,D), pos (B,) -> (B,H,D)."""
    B, H, D = q.shape
    KV, S = k.shape[1], k.shape[2]
    G = H // KV
    tk = min(kv_block, max(S, 8))
    k_pad = -S % tk
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    nk = (S + k_pad) // tk
    q4 = q[:, :, None, :]  # (B, H, 1, D)

    kernel = functools.partial(_kernel, scale=scale, cap=cap, window=window,
                               tk=tk, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, ki, p: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, tk, D),
                         lambda b, h, ki, p, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, tk, D),
                         lambda b, h, ki, p, g=G: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, ki, p: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32), q4, k, v)
    return out[:, :, 0, :]


def paged_decode_attention(q, k_pool, v_pool, block_table, pos, *,
                           scale: float, window: int = 0, cap: float = 0.0,
                           interpret: bool):
    """Flash-decoding over a paged KV cache.

    q (B,H,D); k_pool/v_pool (N,KV,bs,D) — N physical blocks of bs tokens;
    block_table (B,nb) int32 mapping each request's logical block ki to a
    physical pool block (entries past the request's length may repeat any
    valid id — those positions are masked by ``pos``); pos (B,) current
    position per request.  Returns (B,H,D).

    The table and positions ride in as scalar-prefetch operands so the k/v
    index maps can dereference the table per grid step — the kernel streams
    physical blocks directly, no gathered linear copy is materialized.
    """
    B, H, D = q.shape
    KV, bs = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    nb = block_table.shape[1]
    q4 = q[:, :, None, :]  # (B, H, 1, D)

    kernel = functools.partial(_paged_kernel, scale=scale, cap=cap,
                               window=window, tk=bs, nk=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, ki, tbl, p: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, ki, tbl, p, g=G: (tbl[b, ki], h // g, 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, ki, tbl, p, g=G: (tbl[b, ki], h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, ki, tbl, p: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(pos, jnp.int32),
      q4, k_pool, v_pool)
    return out[:, :, 0, :]

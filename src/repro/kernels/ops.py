"""Jit'd public wrappers for the Pallas kernels.

:func:`_interpret` is the one place that chooses by platform: on a TPU
the kernels compile natively; on any other backend they run in Pallas
interpret mode (the kernel body executes with the same block/grid
schedule).  The kernels themselves take ``interpret`` without a default.
Model code passes (B, S, H, D) layouts; these wrappers adapt to the
kernels' (B, H, S, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as dec_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import ssd_scan as ssd_k


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("scale", "window", "cap"))
def flash_attention(q, k, v, q_pos=None, k_pos=None, *, scale, window=0,
                    cap=0.0):
    """(B,S,H,D) x (B,S,KV,D) -> (B,S,H,D), causal from position 0, with
    its own backward kernels; the positions are not read."""
    out = fa_k.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        scale=scale, window=window, cap=cap, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


# Tiles of the grouped matmul: 512 rows, and a whole K or N dimension up
# to 1536 wide (DeepSeek-V2-Lite's expert width 1408 = 11 x 128 has no
# smaller 128-multiple divisor), else 512 of it.
GMM_ROWS, GMM_FULL, GMM_COLS = 512, 1536, 512


def gmm_tiling(m: int, k: int, n: int):
    """(tm, tk, tn) of megablox's ``gmm`` / ``tgmm`` for an (m, k) x (k, n)
    problem; ``m`` is a multiple of ``min(m, GMM_ROWS)``."""
    return (min(m, GMM_ROWS), k if k <= GMM_FULL else GMM_COLS,
            n if n <= GMM_FULL else GMM_COLS)


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (M, K) rows sorted by group, rhs (G, K, N), group_sizes (G',)
    int32 with G' >= G -> (M, N) in lhs's dtype: row block g of lhs times
    rhs[g] for the first G groups, zeros for the rows of later groups.
    Differentiable (megablox's ``gmm`` with its ``tgmm`` backward)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb

    return mb.gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                  gmm_tiling, None, None, False, _interpret())


@functools.partial(jax.jit, static_argnames=("scale", "window", "cap"))
def decode_attention(q, k, v, pos, *, scale, window=0, cap=0.0):
    """q (B,1,H,D), cache k/v (B,S,KV,D), pos (B,) -> (B,1,H,D)."""
    out = dec_k.decode_attention(
        q[:, 0], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), pos,
        scale=scale, window=window, cap=cap, interpret=_interpret())
    return out[:, None]


@jax.jit
def gather_kv_blocks(pool, block_table):
    """Materialize linear caches from a block pool: pool (N, bs, *tail) and
    block_table (B, nb) -> (B, nb*bs, *tail).

    The slow-path twin of :func:`paged_decode_attention` — used by the
    engine's batch-reconstruction path and as the reference the paged kernel
    is tested bit-identical against."""
    g = pool[block_table]  # (B, nb, bs, *tail)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


@functools.partial(jax.jit, static_argnames=("scale", "window", "cap"))
def paged_decode_attention(q, k_pool, v_pool, block_table, pos, *, scale,
                           window=0, cap=0.0):
    """q (B,1,H,D), pools (N,bs,KV,D) in model layout, block_table (B,nb),
    pos (B,) -> (B,1,H,D).  Streams the request's physical blocks via the
    scalar-prefetched table; no gathered linear cache is materialized."""
    out = dec_k.paged_decode_attention(
        q[:, 0], k_pool.transpose(0, 2, 1, 3), v_pool.transpose(0, 2, 1, 3),
        block_table, pos, scale=scale, window=window, cap=cap,
        interpret=_interpret())
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a_neg, b_mat, c_mat, *, chunk=256):
    """Model layout x (B,L,H,P), dt (B,L,H) -> y (B,L,H,P), h (B,H,N,P)."""
    y, h = ssd_k.ssd_scan(
        x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), a_neg, b_mat, c_mat,
        chunk=chunk, interpret=_interpret())
    return y.transpose(0, 2, 1, 3), h


# ---------------------------------------------------------------------------
# Tuning registry — the autotuner's view of this layer
# ---------------------------------------------------------------------------
# Every op the paper's "choose the computation algorithm" procedure can pick
# between is enumerable here: `tune_inputs(op)` builds representative
# kernel-layout inputs, `tune_candidates(op)` returns the named variants
# (pallas kernel vs jnp reference, and per-chunk schedules for the scan).
# `repro.core.autotune` times these and records the fastest feasible one.

TUNABLE_OPS = ("flash_attention", "decode_attention",
               "paged_decode_attention", "ssd_scan")


def tune_inputs(op: str, *, seed: int = 0, batch: int = 1, seq: int = 128,
                heads: int = 2, head_dim: int = 64, ssm_p: int = 32,
                ssm_n: int = 16):
    """Representative random inputs for ``op`` in KERNEL layout (B,H,S,D)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    if op == "flash_attention":
        q = jax.random.normal(ks[0], (batch, heads, seq, head_dim))
        k = jax.random.normal(ks[1], (batch, heads, seq, head_dim))
        v = jax.random.normal(ks[2], (batch, heads, seq, head_dim))
        return (q, k, v)
    if op == "decode_attention":
        q = jax.random.normal(ks[0], (batch, heads, head_dim))
        k = jax.random.normal(ks[1], (batch, heads, seq, head_dim))
        v = jax.random.normal(ks[2], (batch, heads, seq, head_dim))
        pos = jnp.full((batch,), seq - 1, jnp.int32)
        return (q, k, v, pos)
    if op == "paged_decode_attention":
        bs = 16
        nb = max(seq // bs, 1)
        n_pool = 2 * batch * nb  # half-occupied pool, non-contiguous tables
        q = jax.random.normal(ks[0], (batch, heads, head_dim))
        k_pool = jax.random.normal(ks[1], (n_pool, heads, bs, head_dim))
        v_pool = jax.random.normal(ks[2], (n_pool, heads, bs, head_dim))
        table = jax.random.permutation(
            ks[3], n_pool)[: batch * nb].reshape(batch, nb).astype(jnp.int32)
        pos = jnp.full((batch,), nb * bs - 1, jnp.int32)
        return (q, k_pool, v_pool, table, pos)
    if op == "ssd_scan":
        x = jax.random.normal(ks[0], (batch, heads, seq, ssm_p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, heads, seq)))
        a_neg = -jnp.exp(jax.random.normal(ks[2], (heads,)) * 0.5)
        b = jax.random.normal(ks[3], (batch, seq, ssm_n))
        c = jax.random.normal(ks[4], (batch, seq, ssm_n))
        return (x, dt, a_neg, b, c)
    raise KeyError(f"unknown tunable op {op!r}; known: {TUNABLE_OPS}")


def tune_candidates(op: str, *, ssd_chunks=(32, 64, 128)):
    """Named algorithm variants for ``op``, each a callable on the arrays
    from :func:`tune_inputs`.  ``pallas`` variants run interpreted on CPU
    and compiled on TPU (same code path as the model)."""
    if op == "flash_attention":
        def _scale(q):
            return 1.0 / (q.shape[-1] ** 0.5)
        return {
            "pallas": lambda q, k, v: fa_k.flash_attention(
                q, k, v, scale=_scale(q), interpret=_interpret()),
            "ref": lambda q, k, v: _ref().flash_attention_ref(
                q, k, v, scale=_scale(q)),
        }
    if op == "decode_attention":
        return {
            "pallas": lambda q, k, v, pos: dec_k.decode_attention(
                q, k, v, pos, scale=1.0 / (q.shape[-1] ** 0.5),
                interpret=_interpret()),
            "ref": lambda q, k, v, pos: _ref().decode_attention_ref(
                q, k, v, pos, scale=1.0 / (q.shape[-1] ** 0.5)),
        }
    if op == "paged_decode_attention":
        def _gathered(pool, table):
            # (N,KV,bs,D)[table] -> (B,nb,KV,bs,D) -> linear (B,KV,nb*bs,D)
            g = pool[table]
            b, nb, kv, bs, d = g.shape
            return g.transpose(0, 2, 1, 3, 4).reshape(b, kv, nb * bs, d)
        return {
            "pallas": lambda q, kp, vp, tbl, pos: dec_k.paged_decode_attention(
                q, kp, vp, tbl, pos, scale=1.0 / (q.shape[-1] ** 0.5),
                interpret=_interpret()),
            "gather_ref": lambda q, kp, vp, tbl, pos: _ref().decode_attention_ref(
                q, _gathered(kp, tbl), _gathered(vp, tbl), pos,
                scale=1.0 / (q.shape[-1] ** 0.5)),
        }
    if op == "ssd_scan":
        def _chunk_variant(c):
            return lambda *a: ssd_k.ssd_scan(*a, chunk=c,
                                             interpret=_interpret())
        out = {f"pallas_chunk{c}": _chunk_variant(c) for c in ssd_chunks}
        out["ref"] = lambda *a: _ref().ssd_scan_ref(*a)
        return out
    raise KeyError(f"unknown tunable op {op!r}; known: {TUNABLE_OPS}")


def _ref():
    from repro.kernels import ref
    return ref

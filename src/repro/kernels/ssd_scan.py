"""Pallas TPU Mamba-2 SSD chunked scan.

Grid (B, H, n_chunks); chunks sequential with the (N, P) inter-chunk state
in VMEM scratch. Per chunk: the quadratic intra-chunk term (the "dual"
attention-like form, MXU matmuls), the chunk-state contribution of the
carried state, and the state update — mirroring ``repro.models.ssm.
ssd_chunked`` exactly (its pure-jnp math is the oracle in ref.py).

Layouts: x (B,H,L,P), dt (B,H,L), a_neg (H,), b/c (B,L,N) (G=1: shared
across heads). Outputs y (B,H,L,P) and final state (B,H,N,P).

The per-step scalars travel as (B,H,1,L) rows: dt and its log decay
``dt * a_neg`` (formed by XLA, so the kernel needs no per-head scalar
operand). A (1, chunk) block then meets the TPU tiling rule (last two block
dims equal to the array's or divisible by 8 and 128) for chunk % 128 == 0.
Inside the kernel every vector stays 2-D: the inclusive prefix sum is a
masked lane reduction, and row <-> column moves go through the diagonal
mask, so no 1-D relayout or cumsum has to lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, la_ref, b_ref, c_ref, y_ref, hout_ref, state_ref,
            *, q, nc):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)  # (1, Q)
    la_row = la_ref[0, 0]  # (1, Q) log per-step decay dt * a
    b = b_ref[0].astype(jnp.float32)  # (Q, N)
    c = c_ref[0].astype(jnp.float32)  # (Q, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = ii >= jj
    diag = ii == jj
    # inclusive prefix sum cl[i] = sum_{j<=i} la[j], as a column, then the
    # same values as a row (diagonal transpose keeps both bit-identical)
    cl_col = jnp.sum(jnp.where(causal, la_row, 0.0), axis=1, keepdims=True)
    cl_row = jnp.sum(jnp.where(diag, cl_col, 0.0), axis=0, keepdims=True)
    dt_col = jnp.sum(jnp.where(diag, dt_row, 0.0), axis=1, keepdims=True)
    cl_last = cl_row[:, q - 1:]  # (1, 1) log decay over the whole chunk

    # intra-chunk (dual/quadratic form)
    lmat = jnp.where(causal, jnp.exp(cl_col - cl_row), 0.0)  # (Q, Q)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    w = cb * lmat * dt_row
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)

    # contribution of the carried inter-chunk state
    h = state_ref[...]  # (N, P)
    ch = jax.lax.dot_general(c, h, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, P)
    y = y + ch * jnp.exp(cl_col)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h' = exp(cl_Q) h + sum_j exp(cl_Q - cl_j) dt_j b_j x_j^T
    decay_end = jnp.exp(cl_last - cl_col) * dt_col  # (Q, 1)
    sx = x * decay_end  # (Q, P)
    s_chunk = jax.lax.dot_general(b, sx, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = h * jnp.exp(cl_last) + s_chunk

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = state_ref[...].astype(hout_ref.dtype)


def ssd_scan(x, dt, a_neg, b_mat, c_mat, *, chunk: int = 256,
             interpret: bool):
    """x (B,H,L,P), dt (B,H,L), a_neg (H,), b/c (B,L,N).
    Returns y (B,H,L,P), h_final (B,H,N,P)."""
    B, H, L, P = x.shape
    N = b_mat.shape[-1]
    q = min(chunk, L)
    assert L % q == 0, (L, q)
    nc = L // q
    la = dt.astype(jnp.float32) * a_neg.astype(jnp.float32)[None, :, None]
    row = pl.BlockSpec((1, 1, 1, q), lambda b, h, ci: (b, h, 0, ci))

    kernel = functools.partial(_kernel, q=q, nc=nc)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, P), lambda b, h, ci: (b, h, ci, 0)),
            row,
            row,
            pl.BlockSpec((1, q, N), lambda b, h, ci: (b, ci, 0)),
            pl.BlockSpec((1, q, N), lambda b, h, ci: (b, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt.reshape(B, H, 1, L), la.reshape(B, H, 1, L), b_mat, c_mat)
    return y, h_fin

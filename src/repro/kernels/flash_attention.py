"""Pallas TPU flash attention (causal, GQA, sliding-window, softcap), with
its backward pass.

Three kernels, tied together by ``jax.custom_vjp``:

- forward, grid (B, H, nq, nk), the kv axis sequential: online softmax
  with the running output, and the running max and sum replicated across
  128 lanes, in VMEM scratch; writes ``o`` and the per-row log-sum-exp
  (float32, as (B, H, 1, Sq) rows) that the backward pass reads, both
  named (``FLASH_RESIDUALS``) so a rematerialised block can keep them;
- dK/dV, grid (B, KV, nk, G, nq): the G = H / KV query heads of a group
  and the q blocks are the sequential axes, dK and dV accumulate in VMEM
  scratch.  It works on the transposed scores S^T (tk, tq), so the
  log-sum-exp and ``D = rowsum(dO * O)`` broadcast as rows;
- dQ, grid (B, H, nq, nk), the kv axis sequential.

Both backward kernels recompute P = exp(S - lse) block by block; the S x S
scores never reach HBM.  Matmul operands keep the input dtype (bf16 on
the MXU) with float32 accumulation; the softmax statistics are float32;
P and dS are cast to the value and key dtype before their matmuls.  Blocks
fully above the causal diagonal or outside the window are skipped with
``pl.when``, and their index maps repeat a block the grid fetches anyway,
so a skipped step issues no copy.  Only blocks that cross the diagonal,
the window's edge or the padded tail build a mask.

Layout: :func:`flash_attention` takes q (B, H, Sq, D), k (B, KV, Sk, D)
and v (B, KV, Sk, Dv) (ops.py transposes the model's (B, S, H, D) to it;
Dv may differ from D, as in MLA) and hands the kernels (B, heads, D, S):
the sequence in the 128-wide lanes, so a head dim of 64 fills them and
the projections around the kernel read and write the same layout as
dense attention's.  Each kernel turns its q, dO, k or v block
into (t, D) rows once per block it keeps, so every matmul is a plain or
a right-transposed product.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import FLASH_RESIDUALS

NEG_INF = -2.0e38
NT = (((1,), (1,)), ((), ()))  # a @ b.T
NN = (((1,), (0,)), ((), ()))  # a @ b


class _Plan(NamedTuple):
    """What the kernels close over (hashable: custom_vjp's static arg)."""
    scale: float
    window: int
    cap: float
    sk_real: int
    fwd: tuple  # (tq, tk) of the forward kernel
    bwd: tuple  # (tq, tk) of both backward kernels
    interpret: bool


# Positions per block, timed on a v5e at B 4, H 32, KV 8, S 2048, D 64
# (tools/flash_blocks.py; PERF.md section 6): the forward took
# 2.14 ms at (1024, 1024) against 2.77 ms at (512, 512); dK/dV 2.34 ms at
# (512, 512), no faster at (1024, 1024), where it overflows VMEM with a
# softcap; dQ 2.23 ms at (512, 512), 1.98 ms at (1024, 1024).
FWD_BLOCK = 1024
BWD_BLOCK = 512


def _fit(s: int, cap: int) -> int:
    """The largest block of at most ``cap`` rows, halving down to 128,
    that divides ``s``; ``s`` itself when shorter."""
    if s <= cap:
        return max(s, 8)
    b = cap
    while b > 128 and s % b:
        b //= 2
    return b


def _padded(s: int) -> int:
    """The sequence length the kernels run: a multiple of 128 above 128."""
    return s if s <= 128 else s + (-s % 128)


def block_sizes(sq: int, sk: int, d: int):
    """(forward (tq, tk), backward (tq, tk)) for padded lengths ``sq`` and
    ``sk`` (multiples of 128 above 128), each dividing its length."""
    del d  # timed at D = 64 only
    return ((_fit(sq, FWD_BLOCK), _fit(sk, FWD_BLOCK)),
            (_fit(sq, BWD_BLOCK), _fit(sk, BWD_BLOCK)))


# ---------------------------------------------------------------------------
# Block geometry shared by the kernels and their index maps
# ---------------------------------------------------------------------------


def _pow2(scale: float) -> bool:
    return scale > 0 and math.frexp(scale)[0] == 0.5


def _prescale(q, scale):
    """q with ``scale`` folded in where that is exact (a power of two)."""
    return (q * scale).astype(q.dtype) if _pow2(scale) else q


def _post(scale):
    """The factor :func:`_prescale` leaves to apply to the scores."""
    return 1.0 if _pow2(scale) else scale


def _relevant(q_start, k_start, tq, tk, window, sk_real):
    """Some (q, k) pair of the block pair is attended."""
    ok = (k_start <= q_start + tq - 1) & (k_start < sk_real)
    if window:
        ok &= (k_start + tk - 1) > (q_start - window)
    return ok


def _needs_mask(q_start, k_start, tq, tk, window, sk_real):
    """Some (q, k) pair of the block pair is not attended."""
    m = (k_start + tk - 1 > q_start) | (k_start + tk > sk_real)
    if window:
        m |= (q_start + tq - 1 - k_start) >= window
    return m


def _k_range(qi, tq, tk, nk, window):
    """First and last k block that q block ``qi`` attends."""
    hi = jnp.minimum((qi * tq + tq - 1) // tk, nk - 1)
    lo = jnp.maximum(qi * tq - window + 1, 0) // tk if window else 0
    return lo, hi


def _q_range(ki, tq, tk, nq, window):
    """First and last q block that attends k block ``ki``."""
    lo = jnp.minimum((ki * tk) // tq, nq - 1)
    hi = (jnp.minimum((ki * tk + tk - 1 + window - 1) // tq, nq - 1)
          if window else nq - 1)
    return lo, hi


def _mask(qpos, kpos, window, sk_real):
    m = (kpos <= qpos) & (kpos < sk_real)
    if window:
        m &= (qpos - kpos) < window
    return m


def _softcap(s, cap):
    """(capped scores, tanh) — the tanh is the backward pass's factor."""
    if not cap:
        return s, None
    t = jnp.tanh(s / cap)
    return cap * t, t


def _masked_pair(relevant, needs_mask, body):
    """Run ``body(masked)`` for a relevant block pair, with the mask only
    where the pair needs one."""
    @pl.when(relevant & needs_mask)
    def _edge():
        body(True)

    @pl.when(relevant & jnp.logical_not(needs_mask))
    def _inner():
        body(False)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic, widened to n lanes."""
    return pltpu.repeat(x, n // 128, 1) if n % 128 == 0 else x[:, :1]


def _rows(x_t):
    """A (D, t) block as (t, D) rows: the transpose goes through float32,
    which every Mosaic version transposes."""
    return x_t.astype(jnp.float32).T.astype(x_t.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, q_sc, acc_ref, m_ref,
                l_ref, *, p: _Plan, nk):
    tq, tk = p.fwd
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        q_sc[...] = _prescale(_rows(q_ref[0, 0]), p.scale)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start, k_start = qi * tq, ki * tk
    post = _post(p.scale)

    def body(masked):
        v_t = v_ref[0, 0]
        s = jax.lax.dot_general(q_sc[...], k_ref[0, 0], NN,
                                preferred_element_type=jnp.float32)
        if post != 1.0:
            s = s * post
        s, _ = _softcap(s, p.cap)
        if masked:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            s = jnp.where(_mask(qpos, kpos, p.window, p.sk_real), s, NEG_INF)
        # running max and sum: (tq, 128) with every lane equal
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        e = jnp.exp(s - _lanes(m_new, tk))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(e, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * corr[:, :1] + jax.lax.dot_general(
            e.astype(v_t.dtype), v_t, NT, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _masked_pair(_relevant(q_start, k_start, tq, tk, p.window, p.sk_real),
                 _needs_mask(q_start, k_start, tq, tk, p.window, p.sk_real),
                 body)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, :1]).T.astype(o_ref.dtype)
        lse_ref[0, 0, 0] = (m_ref[...] + jnp.log(l))[:, 0]


def _forward(q, k, v, p: _Plan):
    B, H, D, Sq = q.shape
    KV, Sk, Dv = k.shape[1], k.shape[3], v.shape[2]
    G = H // KV
    tq, tk = p.fwd
    nq, nk = Sq // tq, Sk // tk

    def q_map(b, h, qi, ki):
        return b, h, 0, qi

    def kv_map(b, h, qi, ki):
        lo, hi = _k_range(qi, tq, tk, nk, p.window)
        return b, h // G, 0, jnp.clip(ki, lo, hi)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[pl.BlockSpec((1, 1, D, tq), q_map),
                  pl.BlockSpec((1, 1, D, tk), kv_map),
                  pl.BlockSpec((1, 1, Dv, tk), kv_map)],
        out_specs=[
            pl.BlockSpec((1, 1, Dv, tq), q_map),
            pl.BlockSpec((1, 1, 1, tq), q_map),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, Dv, Sq), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((tq, D), q.dtype),
            pltpu.VMEM((tq, Dv), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=p.interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward: dK/dV
# ---------------------------------------------------------------------------


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
                k_sc, v_sc, dk_acc, dv_acc, *, p: _Plan, G, nq):
    tq, tk = p.bwd
    ki, g, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (qi == 0))
    def _init():
        k_sc[...] = _rows(k_ref[0, 0])
        v_sc[...] = _rows(v_ref[0, 0])
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = qi * tq, ki * tk

    def body(masked):
        q_t, post = _prescale(q_ref[0, 0], p.scale), _post(p.scale)
        do_t = do_ref[0, 0]
        st = jax.lax.dot_general(k_sc[...], q_t, NN,
                                 preferred_element_type=jnp.float32)
        if post != 1.0:
            st = st * post
        st, t = _softcap(st, p.cap)
        pt = jnp.exp(st - lse_ref[0, 0])  # (tk, tq); lse a (1, tq) row
        if masked:
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
            pt = jnp.where(_mask(qpos, kpos, p.window, p.sk_real), pt, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do_t.dtype), do_t, NT,
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_sc[...], do_t, NN,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - dl_ref[0, 0])
        if t is not None:
            dst = dst * (1.0 - t * t)
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q_t.dtype), q_t, NT,
            preferred_element_type=jnp.float32)

    _masked_pair(_relevant(q_start, k_start, tq, tk, p.window, p.sk_real),
                 _needs_mask(q_start, k_start, tq, tk, p.window, p.sk_real),
                 body)

    @pl.when((g == G - 1) & (qi == nq - 1))
    def _finalize():
        # S = scale * q.k: dK carries the scale unless q already did
        dk_ref[0, 0] = (dk_acc[...] * _post(p.scale)).T.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].T.astype(dv_ref.dtype)


def _dkv(q, k, v, do, lse, dl, p: _Plan):
    B, H, D, Sq = q.shape
    KV, Sk, Dv = k.shape[1], k.shape[3], v.shape[2]
    G = H // KV
    tq, tk = p.bwd
    nq, nk = Sq // tq, Sk // tk

    def clamp(ki, qi):
        lo, hi = _q_range(ki, tq, tk, nq, p.window)
        return jnp.clip(qi, lo, hi)

    def q_map(b, kv, ki, g, qi):
        return b, kv * G + g, 0, clamp(ki, qi)

    def kv_map(b, kv, ki, g, qi):
        return b, kv, 0, ki

    q_spec = pl.BlockSpec((1, 1, D, tq), q_map)
    do_spec = pl.BlockSpec((1, 1, Dv, tq), q_map)
    k_spec = pl.BlockSpec((1, 1, D, tk), kv_map)
    v_spec = pl.BlockSpec((1, 1, Dv, tk), kv_map)
    row_spec = pl.BlockSpec((1, 1, 1, tq), q_map)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, p=p, G=G, nq=nq),
        grid=(B, KV, nk, G, nq),
        in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=[k_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((tk, D), k.dtype),
                        pltpu.VMEM((tk, Dv), v.dtype),
                        pltpu.VMEM((tk, D), jnp.float32),
                        pltpu.VMEM((tk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=p.interpret,
    )(q, k, v, do, lse, dl)


# ---------------------------------------------------------------------------
# Backward: dQ
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, q_sc,
               do_sc, dq_acc, *, p: _Plan, nk):
    tq, tk = p.bwd
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        q_sc[...] = _prescale(_rows(q_ref[0, 0]), p.scale)
        do_sc[...] = _rows(do_ref[0, 0])
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start, k_start = qi * tq, ki * tk
    post = _post(p.scale)

    def body(masked):
        k_t, v_t = k_ref[0, 0], v_ref[0, 0]  # (D, tk)
        s = jax.lax.dot_general(q_sc[...], k_t, NN,
                                preferred_element_type=jnp.float32)
        if post != 1.0:
            s = s * post
        s, t = _softcap(s, p.cap)
        pm = jnp.exp(s - lse_ref[0, 0, 0][:, None])  # lse as a (tq, 1) column
        if masked:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            pm = jnp.where(_mask(qpos, kpos, p.window, p.sk_real), pm, 0.0)
        dp = jax.lax.dot_general(do_sc[...], v_t, NN,
                                 preferred_element_type=jnp.float32)
        ds = pm * (dp - dl_ref[0, 0, 0][:, None])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k_t.dtype), k_t, NT, preferred_element_type=jnp.float32)

    _masked_pair(_relevant(q_start, k_start, tq, tk, p.window, p.sk_real),
                 _needs_mask(q_start, k_start, tq, tk, p.window, p.sk_real),
                 body)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_acc[...] * p.scale).T.astype(dq_ref.dtype)


def _dq(q, k, v, do, lse, dl, p: _Plan):
    B, H, D, Sq = q.shape
    KV, Sk, Dv = k.shape[1], k.shape[3], v.shape[2]
    G = H // KV
    tq, tk = p.bwd
    nq, nk = Sq // tq, Sk // tk

    def kv_map(b, h, qi, ki):
        lo, hi = _k_range(qi, tq, tk, nk, p.window)
        return b, h // G, 0, jnp.clip(ki, lo, hi)

    def q_map(b, h, qi, ki):
        return b, h, 0, qi

    q_spec = pl.BlockSpec((1, 1, D, tq), q_map)
    return pl.pallas_call(
        functools.partial(_dq_kernel, p=p, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, pl.BlockSpec((1, 1, D, tk), kv_map),
                  pl.BlockSpec((1, 1, Dv, tk), kv_map),
                  pl.BlockSpec((1, 1, Dv, tq), q_map),
                  pl.BlockSpec((1, 1, 1, tq), q_map),
                  pl.BlockSpec((1, 1, 1, tq), q_map)],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((tq, D), q.dtype),
                        pltpu.VMEM((tq, Dv), do.dtype),
                        pltpu.VMEM((tq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=p.interpret,
    )(q, k, v, do, lse, dl)


# ---------------------------------------------------------------------------
# The differentiable op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(q, k, v, p: _Plan):
    return _forward(q, k, v, p)[0]


def _attend_fwd(q, k, v, p: _Plan):
    o, lse = _forward(q, k, v, p)
    o = checkpoint_name(o, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return o, (q, k, v, o, lse)


def _attend_bwd(p: _Plan, res, do):
    q, k, v, o, lse = res
    dl = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=2,
                 keepdims=True)  # (B, H, 1, Sq), as lse
    dq = _dq(q, k, v, do, lse, dl, p)
    dk, dv = _dkv(q, k, v, do, lse, dl, p)
    return dq, dk, dv


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention(q, k, v, *, scale: float, window: int = 0,
                    cap: float = 0.0, q_block: int | None = None,
                    kv_block: int | None = None, interpret: bool):
    """q (B,H,Sq,D), k (B,KV,Sk,D), v (B,KV,Sk,Dv) -> (B,H,Sq,Dv). Causal,
    differentiable; the value head may differ from the query and key head
    (MLA: D 192, Dv 128).

    ``q_block`` / ``kv_block`` set every kernel's blocks (tests use them to
    span several blocks at small sizes); left out, :func:`block_sizes`
    chooses them from the shapes."""
    Sq, D = q.shape[2], q.shape[3]
    Sk = k.shape[2]
    if q_block or kv_block:
        blk = (min(q_block or FWD_BLOCK, max(Sq, 8)),
               min(kv_block or FWD_BLOCK, max(Sk, 8)))
        fwd = bwd = blk
        q_pad, k_pad = -Sq % blk[0], -Sk % blk[1]
    else:
        q_pad, k_pad = _padded(Sq) - Sq, _padded(Sk) - Sk
        fwd, bwd = block_sizes(Sq + q_pad, Sk + k_pad, D)
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    plan = _Plan(scale=float(scale), window=int(window), cap=float(cap),
                 sk_real=Sk, fwd=fwd, bwd=bwd, interpret=interpret)
    # the kernels take (B, H, D, S): the sequence in the lanes
    o = _attend(q.swapaxes(2, 3), k.swapaxes(2, 3), v.swapaxes(2, 3), plan)
    return o.swapaxes(2, 3)[:, :, :Sq]

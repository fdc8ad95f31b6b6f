"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _mk_qkv(key, B, Sq, Sk, H, KV, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, Sk, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, Sk, D), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KV,D,window,cap,qb,kb",
    [
        (2, 128, 4, 4, 64, 0, 0.0, 64, 64),     # MHA
        (1, 256, 8, 2, 64, 0, 0.0, 128, 64),    # GQA, uneven blocks
        (2, 96, 4, 2, 32, 0, 0.0, 64, 64),      # padding path (96 % 64 != 0)
        (1, 256, 4, 4, 64, 64, 0.0, 64, 64),    # sliding window
        (1, 128, 4, 2, 64, 0, 50.0, 64, 64),    # softcap (gemma2)
        (1, 128, 4, 2, 128, 48, 30.0, 32, 32),  # window + cap + D=128
    ],
)
def test_flash_attention_matches_oracle(B, S, H, KV, D, window, cap, qb, kb, dtype):
    q, k, v = _mk_qkv(jax.random.PRNGKey(0), B, S, S, H, KV, D, dtype)
    scale = 1.0 / np.sqrt(D)
    out = flash_attention(q, k, v, scale=scale, window=window, cap=cap,
                          q_block=qb, kv_block=kb, interpret=True)
    want = ref.flash_attention_ref(q, k, v, scale=scale, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KV,D,window,kb",
    [
        (2, 256, 4, 4, 64, 0, 64),
        (3, 300, 8, 2, 64, 0, 128),   # padding + GQA
        (2, 256, 4, 2, 128, 96, 64),  # sliding window
    ],
)
def test_decode_attention_matches_oracle(B, S, H, KV, D, window, kb, dtype):
    key = jax.random.PRNGKey(1)
    q, k, v = _mk_qkv(key, B, 1, S, H, KV, D, dtype)
    q = q[:, :, 0]  # (B, H, D)
    pos = jax.random.randint(jax.random.fold_in(key, 7), (B,), 1, S)
    scale = 1.0 / np.sqrt(D)
    out = decode_attention(q, k, v, pos, scale=scale, window=window,
                           kv_block=kb, interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos, scale=scale, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def _paged_from_linear(k, v, bs, *, key, extra=3):
    """Scatter linear (B, KV, S, D) caches into shuffled block pools:
    returns (k_pool, v_pool, block_table) with pools (N, KV, bs, D) and a
    non-contiguous, non-monotonic table (B, S // bs)."""
    B, KV, S, D = k.shape
    nb = S // bs
    n_pool = B * nb + extra
    table = np.asarray(jax.random.permutation(key, n_pool)[:B * nb],
                       np.int32).reshape(B, nb)
    k_pool = np.asarray(
        jax.random.normal(jax.random.fold_in(key, 1),
                          (n_pool, KV, bs, D), jnp.float32), np.float32)
    v_pool = k_pool[::-1].copy()  # poison unused blocks: gathers must skip
    k_pool, v_pool = k_pool.astype(k.dtype), v_pool.astype(k.dtype)
    kn, vn = np.asarray(k), np.asarray(v)
    for b in range(B):
        for i in range(nb):
            k_pool[table[b, i]] = kn[b, :, i * bs:(i + 1) * bs]
            v_pool[table[b, i]] = vn[b, :, i * bs:(i + 1) * bs]
    return jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KV,D,window,bs",
    [
        (2, 256, 4, 4, 64, 0, 64),
        (3, 256, 8, 2, 64, 0, 32),    # GQA, non-contiguous table
        (2, 256, 4, 2, 128, 96, 64),  # sliding window over block seams
        (1, 128, 4, 4, 64, 0, 16),    # many small blocks
    ],
)
def test_paged_decode_bitwise_matches_linear(B, S, H, KV, D, window, bs,
                                             dtype):
    """With matched blocking (linear kv_block == paged block size) the two
    kernels share the accumulation order, so the paged gather must be
    BIT-identical to the linear cache — the invariant that lets the paged
    serving path claim the linear engine's numbers."""
    key = jax.random.PRNGKey(3)
    q, k, v = _mk_qkv(key, B, 1, S, H, KV, D, dtype)
    q = q[:, :, 0]
    pos = jax.random.randint(jax.random.fold_in(key, 11), (B,), 1, S)
    k_pool, v_pool, table = _paged_from_linear(k, v, bs, key=key)
    scale = 1.0 / np.sqrt(D)
    lin = decode_attention(q, k, v, pos, scale=scale, window=window,
                           kv_block=bs, interpret=True)
    paged = paged_decode_attention(q, k_pool, v_pool, table, pos,
                                   scale=scale, window=window,
                                   interpret=True)
    assert np.array_equal(np.asarray(lin), np.asarray(paged)), \
        f"max diff {np.abs(np.asarray(lin, np.float32) - np.asarray(paged, np.float32)).max()}"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_matches_oracle_ragged(dtype):
    """Paged kernel vs the pure-jnp oracle under ragged positions (every
    row at a different fill level, including block-boundary edges)."""
    B, S, H, KV, D, bs = 4, 128, 4, 2, 64, 32
    key = jax.random.PRNGKey(5)
    q, k, v = _mk_qkv(key, B, 1, S, H, KV, D, dtype)
    q = q[:, :, 0]
    pos = jnp.asarray([1, bs - 1, bs, S - 1], jnp.int32)  # edges + interior
    k_pool, v_pool, table = _paged_from_linear(k, v, bs, key=key)
    scale = 1.0 / np.sqrt(D)
    out = paged_decode_attention(q, k_pool, v_pool, table, pos, scale=scale,
                                 interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos, scale=scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_paged_ops_wrapper_matches_gathered_reference():
    """The model-layout ops wrapper: paged attention over shuffled pools
    equals the reference run on gather_kv_blocks'd linear caches."""
    from repro.kernels import ops

    B, S, H, KV, D, bs = 2, 64, 4, 2, 32, 16
    key = jax.random.PRNGKey(9)
    q, k, v = _mk_qkv(key, B, 1, S, H, KV, D, jnp.float32)
    q = q[:, :, 0]
    pos = jnp.asarray([S - 1, bs + 3], jnp.int32)
    k_pool, v_pool, table = _paged_from_linear(k, v, bs, key=key)
    scale = 1.0 / np.sqrt(D)
    # model layout: q (B,1,H,D), pools (N, bs, KV, D)
    out = ops.paged_decode_attention(
        q[:, None], k_pool.transpose(0, 2, 1, 3),
        v_pool.transpose(0, 2, 1, 3), table, pos, scale=scale)
    k_lin = ops.gather_kv_blocks(k_pool.transpose(0, 2, 1, 3), table)
    v_lin = ops.gather_kv_blocks(v_pool.transpose(0, 2, 1, 3), table)
    want = ref.decode_attention_ref(q, k_lin.transpose(0, 2, 1, 3),
                                    v_lin.transpose(0, 2, 1, 3), pos,
                                    scale=scale)
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               **TOL[jnp.float32])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,L,P,N,chunk",
    [
        (2, 4, 128, 64, 32, 32),
        (1, 8, 256, 32, 64, 64),
        (2, 3, 64, 64, 128, 16),  # odd head count, many chunks
    ],
)
def test_ssd_scan_matches_oracle(B, H, L, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, H, L, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, L), jnp.float32))
    a_neg = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.5)
    b = jax.random.normal(ks[3], (B, L, N), jnp.float32).astype(dtype)
    c = jax.random.normal(ks[4], (B, L, N), jnp.float32).astype(dtype)
    dt = dt.astype(dtype)

    y, h = ssd_scan(x, dt, a_neg, b, c, chunk=chunk, interpret=True)
    y_ref, h_ref = ref.ssd_scan_ref(x, dt, a_neg, b, c, chunk=chunk)
    # bf16: oracle computes intra-chunk einsums in bf16, kernel accumulates
    # in f32 — tolerance covers the representation gap, not an algorithmic one
    tol = dict(rtol=5e-2, atol=1e-1) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h_ref, np.float32), **tol)


def test_ssd_chunk_invariance():
    """SSD output must not depend on the chunk size (algebraic identity)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, H, L, P, N = 1, 2, 128, 32, 16
    x = jax.random.normal(ks[0], (B, H, L, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, L)))
    a_neg = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    b = jax.random.normal(ks[3], (B, L, N))
    c = jax.random.normal(ks[4], (B, L, N))
    y16, h16 = ssd_scan(x, dt, a_neg, b, c, chunk=16, interpret=True)
    y64, h64 = ssd_scan(x, dt, a_neg, b, c, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h16), np.asarray(h64), rtol=1e-4, atol=1e-4)


def test_flash_matches_chunked_model_path():
    """Kernel == the model's chunked (XLA flash) path, not just dense."""
    from repro.models.attention import chunked_attention
    B, S, H, KV, D = 1, 192, 4, 2, 64
    q, k, v = _mk_qkv(jax.random.PRNGKey(4), B, S, S, H, KV, D, jnp.float32)
    scale = 1.0 / np.sqrt(D)
    out = flash_attention(q, k, v, scale=scale, q_block=64, kv_block=64,
                          interpret=True)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    want = chunked_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), pos, pos, scale=scale, kv_block=64,
        q_block=64).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KV,D,window,cap,qb,kb",
    [
        (1, 256, 4, 4, 64, 0, 0.0, 64, 128),     # MHA, uneven blocks
        (1, 256, 8, 2, 64, 0, 0.0, 128, 64),     # H = 4 KV
        (1, 256, 8, 2, 64, 64, 0.0, 64, 64),     # sliding window
        (1, 192, 4, 4, 128, 0, 30.0, 64, 64),    # softcap, D = 128
        (1, 192, 8, 2, 128, 48, 30.0, 64, 64),   # window + cap + GQA
        (2, 96, 4, 2, 64, 0, 0.0, 64, 64),       # padded tail
    ],
)
def test_flash_attention_grads_match_oracle(B, S, H, KV, D, window, cap, qb,
                                            kb, dtype):
    """The custom VJP (dK/dV and dQ kernels) against jax.grad of the dense
    oracle: output, dq, dk and dv, over several q and k blocks."""
    q, k, v = _mk_qkv(jax.random.PRNGKey(6), B, S, S, H, KV, D, dtype)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    scale = 1.0 / np.sqrt(D)

    def loss(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)

    (dq, dk, dv), out = loss(lambda q, k, v: flash_attention(
        q, k, v, scale=scale, window=window, cap=cap, q_block=qb,
        kv_block=kb, interpret=True))(q, k, v)
    (rq, rk, rv), want = loss(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, scale=scale, window=window, cap=cap))(q, k, v)
    for got, exp in ((out, want), (dq, rq), (dk, rk), (dv, rv)):
        assert got.dtype == exp.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_mla_head_dims_grads_match_oracle(dtype):
    """MLA's heads: q and k 192 wide (qk_nope 128 + qk_rope 64), v 128, at
    DeepSeek-V2-Lite's YaRN softmax scale, over several q and k blocks: the
    forward, dK/dV and dQ kernels against jax.grad of the dense oracle."""
    B, S, H, D, Dv = 1, 256, 2, 192, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, S, Dv), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[3], (B, H, S, Dv), jnp.float32)
    scale = 0.11472

    def grads(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (dq, dk, dv), out = grads(lambda q, k, v: flash_attention(
        q, k, v, scale=scale, q_block=128, kv_block=128, interpret=True))
    (rq, rk, rv), want = grads(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, scale=scale))
    assert out.shape == (B, H, S, Dv) and dv.shape == v.shape
    for got, exp in ((out, want), (dq, rq), (dk, rk), (dv, rv)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32), **TOL[dtype])


# (Sq, Sk, dk, dv, on a TPU) -> the implementation attention(impl="auto")
# picks, as counted under repro.obs.TRACE_COUNTS
AUTO_CASES = [
    (256, 256, 64, 64, True, "flash"),      # full-sequence self-attention
    (2048, 2048, 64, 64, True, "flash"),    # granite's training shape
    (4096, 4096, 128, 128, True, "flash"),  # above 2048 too
    (192, 192, 64, 64, True, "dense"),      # S not a multiple of 128
    (128, 256, 64, 64, True, "dense"),      # Sq != Sk (a chunk on a cache)
    (1, 4096, 64, 64, True, "chunked"),     # decode-shaped, long cache
    (256, 256, 192, 128, True, "flash"),    # MLA: dk != dv
    (256, 256, 64, 64, False, "dense"),     # not a TPU
    (4096, 4096, 64, 64, False, "chunked"),
]


@pytest.mark.parametrize("sq,sk,dk,dv,tpu,impl", AUTO_CASES)
def test_auto_attention_dispatch(monkeypatch, sq, sk, dk, dv, tpu, impl):
    from repro.models import attention as A
    from repro.obs import TRACE_COUNTS

    monkeypatch.setattr(A, "_on_tpu", lambda: tpu)
    B, H, KV = 1, 4, 2
    sds = jax.ShapeDtypeStruct
    q = sds((B, sq, H, dk), jnp.bfloat16)
    k = sds((B, sk, KV, dk), jnp.bfloat16)
    v = sds((B, sk, KV, dv), jnp.bfloat16)
    qp = sds((B, sq), jnp.int32)
    kp = sds((B, sk), jnp.int32)
    names = ("flash", "dense", "chunked")
    before = {n: TRACE_COUNTS.counter(f"attention/{n}").value for n in names}
    out = jax.eval_shape(lambda *a: A.attention(*a, scale=dk ** -0.5),
                         q, k, v, qp, kp)
    assert out.shape == (B, sq, H, dv)
    after = {n: TRACE_COUNTS.counter(f"attention/{n}").value for n in names}
    assert {n: after[n] - before[n] for n in names} == \
        {n: float(n == impl) for n in names}


def test_auto_attention_on_tpu_trains_through_the_kernel(monkeypatch):
    """With the TPU probe on, impl="auto" runs the differentiable kernel
    (interpreted here): output and gradients equal the dense path's, and
    impl="pallas" reaches the same kernel."""
    from repro.models import attention as A

    B, S, H, KV, D = 1, 128, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def grads(impl):
        def f(q, k, v):
            o = A.attention(q, k, v, pos, pos, scale=D ** -0.5, impl=impl)
            return jnp.sum(o * o), o
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    want = grads("dense")
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    for impl in ("auto", "pallas"):
        got = grads(impl)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       **TOL[jnp.float32])

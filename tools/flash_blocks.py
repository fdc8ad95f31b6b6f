"""Time the Pallas flash attention kernels on a TPU for several block
sizes, and check the chosen ones against dense attention.

    PYTHONPATH=src python3 tools/flash_blocks.py [--batch 4 --heads 32
        --kv-heads 8 --seq 2048 --head-dim 64 --iters 20]

One process, on the chip (it refuses to run elsewhere).  Prints one JSON
line per timed variant: the forward kernel and the two backward kernels
(dQ then dK/dV) for each (tq, tk), and ``jax.vjp`` of dense attention and
of the differentiable kernel as :func:`block_sizes` chooses its blocks,
in ms per call (see ``_time``) after a warm-up call.  A last
line gives the largest gaps of the chosen kernel's output and gradients
from a float32 dense reference, beside those of bf16 dense attention.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

BLOCKS = ((256, 512), (512, 256), (512, 512), (512, 1024), (1024, 512),
          (1024, 1024))


def _time(fn, args, iters):
    """ms per call: ``iters`` calls queued back to back, so the device
    never waits on the host; the least of three such rounds."""
    jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(iters)])
        rounds.append((time.perf_counter() - t0) / iters)
    return min(rounds) * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=20)
    a = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("flash_blocks.py times the chip: no TPU here", file=sys.stderr)
        return 1
    from repro.kernels import flash_attention as fa
    from repro.kernels import ref

    B, H, KV, S, D = a.batch, a.heads, a.kv_heads, a.seq, a.head_dim
    scale = D ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, KV, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, KV, S, D), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, H, S, D), jnp.bfloat16)
    # causal matmul work: forward 2 matmuls, dQ 3, dK/dV 4, over half of S^2
    unit = 2 * B * H * S * S * D / 2
    shape = {"B": B, "H": H, "KV": KV, "S": S, "D": D}

    # the kernels' own layout: (B, heads, D, S)
    q_t, k_t, v_t, do_t = (x.swapaxes(2, 3) for x in (q, k, v, do))
    for blk in BLOCKS:
        if S % blk[0] or S % blk[1]:
            continue
        plan = fa._Plan(scale=scale, window=0, cap=0.0, sk_real=S, fwd=blk,
                        bwd=blk, interpret=False)
        o, lse = jax.jit(lambda q, k, v: fa._forward(q, k, v, plan))(
            q_t, k_t, v_t)
        dl = jnp.sum(do_t.astype(jnp.float32) * o.astype(jnp.float32),
                     axis=2, keepdims=True)
        bwd_args = (q_t, k_t, v_t, do_t, lse, dl)
        for name, fn, args, n_mm in (
                ("forward", lambda q, k, v: fa._forward(q, k, v, plan),
                 (q_t, k_t, v_t), 2),
                ("dq", lambda *r: fa._dq(*r, plan), bwd_args, 3),
                ("dkv", lambda *r: fa._dkv(*r, plan), bwd_args, 4)):
            try:
                ms = _time(jax.jit(fn), args, a.iters)
            except Exception as e:  # a block the compiler refuses
                print(json.dumps({"kernel": name, "blocks": blk,
                                  "error": str(e)[:300]}), flush=True)
                continue
            print(json.dumps({"kernel": name, "blocks": blk, "ms": ms,
                              "tflops": n_mm * unit / ms / 1e9, **shape}),
                  flush=True)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, scale=scale, interpret=False)

    def dense(q, k, v):
        return ref.flash_attention_ref(q, k, v, scale=scale)

    def fwd_bwd(f):
        def run(q, k, v, do):
            o, vjp = jax.vjp(f, q, k, v)
            return (o,) + vjp(do)
        return jax.jit(run)

    results = {}
    for name, f in (("flash", flash), ("dense", dense)):
        run = fwd_bwd(f)
        ms = _time(run, (q, k, v, do), a.iters)
        results[name] = run(q, k, v, do)
        print(json.dumps({"kernel": f"{name}_fwd_bwd",
                          "blocks": fa.block_sizes(S, S, D), "ms": ms,
                          "tflops": 9 * unit / ms / 1e9, **shape}),
              flush=True)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    want = fwd_bwd(dense)(*f32)
    gaps = {name: [float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
                   for g, w in zip(got, want)]
            for name, got in results.items()}
    print(json.dumps({"max_gap_vs_f32_dense": gaps,
                      "order": ["o", "dq", "dk", "dv"],
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

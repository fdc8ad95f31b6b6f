"""The synced step-0 gradient of the data-parallel trainer on N chips
against its one-chip twin.

    python3 tools/dp_grad_twin.py [--chips 4] [--rows-per-chip 6] [--seed 0]

granite-3-2b at its published widths, 8 of 40 layers (the benchmark's
``granite-3-2b.l8``), one batch of ``chips x rows-per-chip`` rows of 2048
random tokens from ``--seed``, the same weights on both sides.  The
trainer (``all_reduce``) computes each chip's gradient of its rows and
averages them; the twin, ``build_grad_fn`` at ``microbatch =
rows-per-chip`` on one chip, averages the same per-chip batches' gradients
in turn.  Prints one JSON line: the loss of each side, the relative gap
``||g_dp - g_1|| / ||g_1||`` over all leaves and the largest over leaves,
and the share of gradient elements whose sign differs, all elements and
those under 1e-3 of their leaf's largest magnitude (where AdamW's first,
sign-like update turns a rounding difference into a whole step).  Needs
``chips`` devices; on the CPU set ``XLA_FLAGS=
--xla_force_host_platform_device_count=<n>``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--rows-per-chip", type=int, default=6)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.distributed.trainer import DataParallelTrainer
    from repro.launch.steps import build_grad_fn
    from repro.models import model as M
    from repro.models.blocks import RunConfig
    from repro.models.common import materialize
    from repro.optim.adamw import OptConfig

    cfg = get_config("granite-3-2b").replace(num_layers=args.layers)
    run = RunConfig(attn_impl="auto", remat="block")
    opt = OptConfig(lr=3e-4, warmup_steps=10, total_steps=100000)
    devs = jax.devices()[:args.chips]
    rows = args.chips * args.rows_per_chip
    toks = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, (rows, args.seq + 1)).astype(np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    params = jax.jit(lambda k: materialize(M.model_specs(cfg), k))(
        jax.random.PRNGKey(args.seed))

    twin = jax.jit(build_grad_fn(
        cfg, dataclasses.replace(run, microbatch=args.rows_per_chip)))
    loss_1, _, g_1 = twin(params, batch)
    g_1 = jax.device_get(g_1)
    loss_1 = float(loss_1)

    trainer = DataParallelTrainer(cfg, run, opt, strategy="all_reduce",
                                  devices=devs)
    from jax.sharding import NamedSharding, PartitionSpec as P
    params = jax.device_put(params, NamedSharding(trainer.mesh, P()))
    losses, g_dp = trainer.grads(
        params, jax.device_put(batch, trainer.batch_sharding()))
    g_dp = jax.device_get(g_dp)

    num = den = 0.0
    worst, flips, small_flips, small = 0.0, 0, 0, 0
    n = 0
    for a, b in zip(jax.tree_util.tree_leaves(g_dp),
                    jax.tree_util.tree_leaves(g_1)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        d2, b2 = float(np.sum((a - b) ** 2)), float(np.sum(b * b))
        num, den = num + d2, den + b2
        worst = max(worst, (d2 / max(b2, 1e-300)) ** 0.5)
        differ = np.sign(a) != np.sign(b)
        tiny = np.abs(b) < 1e-3 * np.max(np.abs(b))
        flips += int(differ.sum())
        small_flips += int((differ & tiny).sum())
        small += int(tiny.sum())
        n += b.size
    print(json.dumps({
        "chips": len(devs), "rows": rows, "seq": args.seq,
        "device": devs[0].device_kind,
        "loss_dp": float(jnp.mean(losses)), "loss_one_chip": loss_1,
        "grad_rel_gap": (num / den) ** 0.5, "grad_rel_gap_worst_leaf": worst,
        "sign_flip_share": flips / n,
        "sign_flip_share_small": small_flips / max(small, 1),
        "small_share": small / n}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

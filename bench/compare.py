"""The comparison that decides ``correct``: numbers, each with its limit.

Training, over the first steps of the timed path against the plain
reference from the same seed and rows:

* ``loss_gap``: the largest ``|loss - reference loss|`` over the steps;
* ``grad_gap``: over the leaves (a stacked leaf split by layer), the
  largest gap between the norm of the program's first gradient, as its
  optimizer applied it, and the reference's, divided by the larger of that
  leaf's reference norm and the median leaf's;
* ``change_gap``: the same for the weights' change over the steps, leaving
  out leaves whose reference gradient is under ``1e-3`` of the median
  leaf's (they move by rounding and weight decay alone);
* ``grad_median_gap``: the median over leaves of the first gradient's gap,
  for a cell whose widest gap is set by a few small noisy leaves;
* ``embed_change_gap``: the change gap of the token embedding alone, the
  one leaf whose change depends on which tokens the step saw (rows of
  tokens absent from the batch get no gradient from the lookup).

A cell's limits file names the numbers compared for it, each with its
limit; ``PERF.md`` gives the readings each limit was set from.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

NEGLIGIBLE_GRAD = 1e-3  # of the median leaf's gradient norm


TOKEN_LEAF = "embed"


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], keep=None):
    """``|prog - ref|`` of each leaf's norm over the larger of its reference
    norm and the median leaf's."""
    keys = [k for k in ref if keep is None or k in keep]
    missing = [k for k in keys if k not in prog]
    if missing:
        raise KeyError(f"program readings lack {missing[:5]}")
    med = float(np.median([ref[k] for k in keys]))
    gaps = {}
    for k in keys:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        gaps[k] = g if math.isfinite(g) else math.inf
    return gaps


def train_gaps(prog: dict, ref: dict) -> dict:
    """Every number a training cell may compare (and the leaf that set the
    widest gaps)."""
    med = float(np.median(list(ref["grad_raw"].values())))
    moved = {k for k, v in ref["grad_raw"].items()
             if v >= NEGLIGIBLE_GRAD * med}
    loss = [abs(a - b) for a, b in zip(prog["loss"], ref["loss"])]
    if len(prog["loss"]) != len(ref["loss"]) or not all(
            math.isfinite(x) for x in loss):
        loss = [math.inf]
    grad = leaf_gaps(prog["grad"], ref["grad"])
    change = leaf_gaps(prog["change"], ref["change"], moved)
    grad_at = max(grad, key=grad.get)
    change_at = max(change, key=change.get)
    return {"loss_gap": max(loss),
            "grad_gap": grad[grad_at], "grad_leaf": grad_at,
            "grad_median_gap": float(np.median(list(grad.values()))),
            "change_gap": change[change_at], "change_leaf": change_at,
            "embed_change_gap": change.get(TOKEN_LEAF, math.inf)}


def train_checks(prog: dict, ref: dict, limits: dict) -> Dict[str, dict]:
    g = train_gaps(prog, ref)
    return {name: {"value": g[name], "limit": limit}
            for name, limit in limits.items()}

"""Training an MoE configuration on one chip: the ``train`` driver's set-up,
window, profile and comparison, with what the expert layer reports.

The program's step returns ``moe_held_rows``, the (token, held expert)
assignments its experts computed, summed over the layers; it is kept with
each step's loss and read back after the window: ``counters`` gets the
window's sum (``moe_held_rows``) and the profiled steps' sum
(``moe_held_rows_profiled``).  A traced run's profile is reduced by
``scope_reduce`` as well, so that ``trace["scopes"]`` holds the device time
by named scope, where the program names its scopes.

A probe beside ``train``'s planted faults: ``capacity_drop`` puts back
the dispatch this program no longer has, each expert taking at most
``CAPACITY_FACTOR * T * k / E`` assignments of a layer's T tokens in token
order and dropping the rest.  ``bench/readings.py --faults capacity_drop``
reads it; it is not in ``FAULTS``, the faults the comparison must catch,
because at this configuration it moves no reading beyond a sound run's,
not even the held experts' own gradient norms (``PERF.md``).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

import harness
import scope_reduce
import trace_reduce

train = harness.module("drivers", "train")
FAULTS = train.FAULTS
CAPACITY_FACTOR = 1.0
reference_readings = train.reference_readings
check = train.check


@contextlib.contextmanager
def patched(module, **attrs):
    """``module``'s attributes replaced for the duration of the block."""
    old = {k: getattr(module, k) for k in attrs}
    for k, v in attrs.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


trace_reduce_base = trace_reduce.reduce


def reduce_with_scopes(path: str) -> dict:
    """``trace_reduce.reduce`` with ``scopes`` added where the program names
    its scopes."""
    out = trace_reduce_base(path)
    s = scope_reduce.scopes(path)
    if s is not None:
        out["scopes"] = s
    return out


def drop_over_capacity(route):
    """``repro.models.moe.route`` with the weights of the assignments over
    an expert's capacity set to 0, as a capacity-factor dispatch drops
    them."""
    def capped(xf, router_w, cfg, groups):
        w, idx, aux = route(xf, router_w, cfg, groups)
        T, K = idx.shape
        E = cfg.num_experts
        cap = int(CAPACITY_FACTOR * T * K / E) + 1
        onehot = jax.nn.one_hot(idx.reshape(-1), E, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
        return jnp.where(pos.reshape(T, K) < cap, w, 0.0), idx, aux
    return capped


def measure(cell, *, seed, seconds, trace, t_start, fault=None):
    from repro.models import moe

    held = []
    make_step = train.make_step

    def counted_step(pcfg, run, opt, donate=True):
        inner = make_step(pcfg, run, opt, donate)

        def step(p, s, b):
            if fault == "capacity_drop":
                with patched(moe, route=drop_over_capacity(moe.route)):
                    p, s, m = inner(p, s, b)
            else:
                p, s, m = inner(p, s, b)
            held.append(m["moe_held_rows"])
            return p, s, m
        return step

    with patched(train, make_step=counted_step), \
            patched(trace_reduce, reduce=reduce_with_scopes):
        outcome = train.measure(
            cell, seed=seed, seconds=seconds, trace=trace, t_start=t_start,
            fault=None if fault == "capacity_drop" else fault)
    t = cell.traffic
    rows = [int(x) for x in jax.device_get(held)]
    window = rows[t["check_steps"]:t["check_steps"] + outcome.attempted]
    outcome.counters["moe_held_rows"] = float(sum(window))
    if trace:
        outcome.counters["moe_held_rows_profiled"] = float(
            sum(rows[-t["profile_steps"]:]))
    return outcome

"""Training on one chip: the program's jitted train step
(``repro.launch.steps.build_train_step``, parameters and optimizer state
donated) fed by its ``PrefetchLoader``, which reads the benchmark's token
stream.

Set-up builds one step and one state, and drives them through the first
``check_steps`` steps with the window's own call and feed: that compiles
everything the window runs, and gives the readings the comparison uses
(each step's loss, the first gradient as the optimizer got it, read back
from its first moment, and the weights' change over those steps).  The
window then runs whole steps until ``--seconds`` have passed, each ending
in the loss's read-back, as the program's loop does.  A traced run then
profiles ``profile_steps`` more.

After the window the program's state is freed and the plain reference
runs the same steps on the same rows from the same seed.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import jax
import numpy as np

import compare
import generator
import harness
import trace_reduce
from reference.common import POLICIES, layer_norms, seed_key, train_readings

FAULTS = ("unchanged_state", "half_batch")


def program_config(cfg: dict):
    from repro.configs.base import get_config

    p = cfg["program"]
    return get_config(p["arch"]).replace(**p["replace"])


def make_step(pcfg, run, opt, donate: bool = True):
    """The entry the window drives."""
    from repro.launch.steps import build_train_step

    return jax.jit(build_train_step(pcfg, run, opt),
                   donate_argnums=(0, 1) if donate else ())


def faulty_step(fault: str, pcfg, run, opt, batch: int):
    """The step with one planted fault, for the tests that show the
    comparison catches it."""
    if fault == "unchanged_state":
        inner = make_step(pcfg, run, opt, donate=False)
        return lambda p, s, b: (p, s, inner(p, s, b)[2])
    if fault == "half_batch":
        inner = make_step(pcfg, run, opt)
        half = jax.jit(lambda b: {k: v[:batch // 2] for k, v in b.items()})
        return lambda p, s, b: inner(p, s, half(b))
    raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")


def host_change_norms(after, before) -> dict:
    """Per-layer norms of ``after - before`` (numpy trees), split as
    :func:`reference.common.layer_norms` splits them."""
    out = {}
    flat_a = jax.tree_util.tree_flatten_with_path(after)[0]
    flat_b = jax.tree_util.tree_leaves(before)
    for (path, a), b in zip(flat_a, flat_b):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        d = np.asarray(a, np.float32) - np.asarray(b, np.float32)
        if name.startswith("slots/") and d.ndim > 1:
            d = d.reshape(d.shape[0], -1)
            for i, v in enumerate(np.sqrt(np.einsum("ij,ij->i", d, d))):
                out[f"{name}[{i}]"] = float(v)
        else:
            d = d.reshape(-1)
            out[name] = float(np.sqrt(np.dot(d, d)))
    return out


def model_flops(cfg: dict, batch: int, seq: int) -> float:
    return harness.module("flops", cfg["flops"]).train_step_flops(
        cfg, batch, seq)


def measure(cell, *, seed, seconds, trace, t_start, fault=None):
    # set-up's parts, in seconds since the process started
    phases = {"device": time.perf_counter() - t_start}
    from repro.data.pipeline import PrefetchLoader
    from repro.models import model as M
    from repro.models.blocks import RunConfig
    from repro.models.common import materialize
    from repro.optim import adamw

    t = cell.traffic
    pcfg = program_config(cell.config)
    run = RunConfig(**t["run"])
    opt = adamw.OptConfig(**t["optimizer"])
    B, S = t["batch"], t["seq"]
    clock = harness.CompileClock()
    spans = harness.Spans()

    # weights and optimizer state from the seed, one jitted call each
    params = jax.jit(lambda k: materialize(M.model_specs(pcfg), k))(
        seed_key(seed))
    opt_state = jax.jit(lambda p: adamw.init_state(opt, p))(params)
    step = (faulty_step(fault, pcfg, run, opt, B) if fault
            else make_step(pcfg, run, opt))
    loader = PrefetchLoader(pcfg, B, S, corpus=generator.TokenStream(
        t["tokens"], pcfg.vocab_size, seed))
    p0 = jax.device_get(params)
    phases["weights"] = time.perf_counter() - t_start

    def one_step():
        nonlocal params, opt_state
        with spans.span("data_wait"):
            batch, _ = next(loader)
        with spans.span("step"):
            params, opt_state, metrics = step(params, opt_state, batch)
            return float(metrics["loss"]), metrics

    try:
        readings = {"loss": []}
        for i in range(t["check_steps"]):
            loss, metrics = one_step()
            readings["loss"].append(loss)
            if i == 0:
                readings["grad"] = {k: v / (1.0 - opt.b1) for k, v in
                                    layer_norms(opt_state["m"]).items()}
                readings["grad_norm"] = float(metrics.get("grad_norm", 0.0))
        phases["first_steps"] = time.perf_counter() - t_start
        readings["change"] = host_change_norms(jax.device_get(params), p0)
        del p0

        setup_s = time.perf_counter() - t_start
        compiles = clock.count
        print("set-up (s since start): " + ", ".join(
            f"{k} {v:.3f}" for k, v in phases.items()) + f", readings "
            f"{setup_s:.3f}; {compiles} compiles took {clock.total_s:.3f}",
            file=sys.stderr, flush=True)
        steps = failed = 0
        t0 = time.perf_counter()
        while True:
            failed += not math.isfinite(one_step()[0])
            steps += 1
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        compiles_in_window = clock.count - compiles

        traced = {}
        if trace:
            with harness.profiled(trace_reduce.reduce, traced):
                for _ in range(t["profile_steps"]):
                    one_step()
        memory = harness.memory_peak_bytes(cell.chips)
    finally:
        loader.close()
    del params, opt_state, step
    gc.collect()

    window_s = t1 - t0
    outcome = harness.Outcome(
        window_s=window_s, attempted=steps, failed=failed,
        end_to_end={"setup_s": setup_s,
                    "train_tokens_per_s": steps * B * S / window_s},
        memory_peak_bytes=memory, chips=cell.chips, spans=spans,
        window=(t0, t1), trace=traced, compiles_in_window=compiles_in_window,
        counters={"model_flops": steps * model_flops(cell.config, B, S)},
        readings=readings)
    return outcome


def reference_readings(cell, seed: int, policy: str = "float32") -> dict:
    t = cell.traffic
    fam = harness.module("reference", cell.config["family"])
    stream = generator.TokenStream(t["tokens"], cell.config["vocab_size"],
                                   seed)
    rows = generator.train_rows(stream, t["batch"], t["seq"],
                                t["check_steps"])
    return train_readings(fam.row_nll, fam.leaf_shapes(cell.config),
                          cell.config, t["optimizer"], rows, seed,
                          POLICIES[policy])


def check(cell, *, seed, outcome):
    ref = reference_readings(cell, seed)
    return compare.train_checks(outcome.readings, ref, cell.limits)

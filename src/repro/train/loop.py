"""Instrumented training loop — the paper's Fig.-1 pipeline made executable.

Each iteration measures the seven steps (parameter refresh is implicit in
SPMD — the ZeRO all-gather — so it is folded into compute; data load / prep /
h2d come from the PrefetchLoader; param+distributed update are inside the
jitted train_step and are folded into compute on a single host, while their
*modeled* costs come from the planner's SyncPlan). The loop emits StepTimes
so R_O and Lemma 3.1/3.2 can be evaluated on real measurements.

Entry points should go through ``repro.api`` (JobSpec -> Session -> Report)
rather than importing :func:`train` directly; the direct import stays
supported for library composition (the Session itself uses it) but is a
deprecation candidate for scripts — see README "One API".
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.pipeline import StepTimes
from repro.data.pipeline import PrefetchLoader
from repro.models import model as M
from repro.models.blocks import RunConfig
from repro.models.common import materialize
from repro.obs.trace import Tracer, monotonic
from repro.optim import adamw as opt_lib
from repro.launch.steps import build_train_step
from repro.checkpoint import CheckpointManager, latest_step as ckpt_latest


@dataclass
class TrainResult:
    """``tokens_per_s`` is timed from the end of the first step to the end
    of the last, over the tokens of the steps after the first, so the first
    step's compile is not in it; with a single step it is that step's
    tokens over its whole wall clock, compile included."""

    losses: List[float]
    step_times: List[StepTimes]
    tokens_per_s: float
    start_step: int = 0
    params: Any = None  # the final parameters, on the device

    @property
    def mean_r_o(self) -> float:
        ros = [t.r_o() for t in self.step_times[2:]]
        return float(np.mean(ros)) if ros else 0.0

    def summary(self) -> Dict[str, Any]:
        """The measured block of a ``repro.api.Report``: loss trajectory,
        throughput, R_O, and steady-state (warmup-excluded) means of every
        Fig.-1 step."""
        from repro.core.pipeline import STEP_NAMES

        steady = self.step_times[2:] or self.step_times
        means = {name: float(np.mean([getattr(t, name) for t in steady]))
                 for name in STEP_NAMES} if steady else {}
        head, tail = self.losses[:5], self.losses[-5:]
        return {
            "steps": len(self.losses),
            "start_step": int(self.start_step),
            "loss_first": float(np.mean(head)) if head else float("nan"),
            "loss_last": float(np.mean(tail)) if tail else float("nan"),
            "losses": [float(l) for l in self.losses],
            "tokens_per_s": float(self.tokens_per_s),
            "r_o": self.mean_r_o,
            "step_times_mean": means,
        }


def train(cfg: ModelConfig, run: RunConfig, opt: opt_lib.OptConfig, *,
          batch: int, seq: int, steps: int, seed: int = 0,
          loader: Optional[PrefetchLoader] = None,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
          log_every: int = 10,
          params=None, opt_state=None,
          step_fn: Optional[Callable] = None,
          batch_sharding: Optional[Dict[str, Any]] = None,
          tracer: Optional[Tracer] = None) -> TrainResult:
    """``step_fn`` (optional) replaces the default jitted train step with a
    caller-built executor — e.g. repro.distributed.DataParallelTrainer's
    phase-split step. It may attach host-side phase timings to metrics as
    plain floats under ``t_comm`` / ``t_update``; they are split out of
    compute into StepTimes.dist_update / .param_update. ``batch_sharding``
    maps input names to shardings for the loader's h2d step.  ``tracer``
    (repro.obs) wraps every iteration in a ``step`` span (step index as a
    span arg) and the loader wait in ``data_wait``; phase-level spans come
    from the ``step_fn`` itself when it traces (the DataParallelTrainer
    does).

    The ``step`` span's wall clock IS the StepTimes compute measurement, so
    the loop needs a live clock: a missing/disabled tracer is replaced by a
    private enabled one (events go nowhere, timing still works).

    Checkpointing: when ``ckpt_dir`` is set the loop saves the full
    training state (``params`` + ``opt_state``, minus any dp-shaped ``ef``
    error-feedback leaves, which depend on the device grid and are re-
    initialized on restore) every ``ckpt_every`` steps via an async
    :class:`CheckpointManager`, and AUTO-RESUMES: if a complete checkpoint
    already exists in ``ckpt_dir``, training restarts from its step with
    the loader fast-forwarded, so the resumed loss trajectory matches an
    uninterrupted run — even onto a different ``(dp, pipe)`` grid, because
    the checkpoint stores the logical (replicated) tree and restore re-
    shards onto the live templates."""
    if tracer is None or not tracer.enabled:
        tracer = Tracer(enabled=True)
    key = jax.random.PRNGKey(seed)
    if params is None:
        params = materialize(M.model_specs(cfg), key)
    if opt_state is None:
        opt_state = opt_lib.init_state(opt, params)

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if mgr is not None and ckpt_latest(ckpt_dir) is not None:
        # "ef" has a leading dp axis (one slot per data shard) so it is
        # grid-dependent: excluded from the checkpoint, kept zero-fresh here
        ef = opt_state.get("ef") if isinstance(opt_state, dict) else None
        tmpl_state = {k: v for k, v in opt_state.items() if k != "ef"} \
            if isinstance(opt_state, dict) else opt_state
        restored, start_step = mgr.restore(
            {"params": params, "opt_state": tmpl_state})
        params = restored["params"]
        opt_state = restored["opt_state"]
        if ef is not None:
            opt_state = dict(opt_state)
            opt_state["ef"] = ef
        if start_step >= steps:
            print(f"  checkpoint at step {start_step} >= steps {steps}; "
                  f"nothing to do", flush=True)
        else:
            print(f"  resuming from checkpoint step {start_step}",
                  flush=True)

    own_loader = loader is None
    if loader is None:
        loader = PrefetchLoader(cfg, batch, seq, seed=seed,
                                sharding=batch_sharding,
                                skip_batches=start_step)

    if step_fn is None:
        step_fn = jax.jit(build_train_step(cfg, run, opt),
                          donate_argnums=(0, 1))

    losses: List[float] = []
    times: List[StepTimes] = []
    t_start = monotonic()
    t_first = None  # end of the first step: the throughput clock starts
    try:
        for i in range(start_step, steps):
            with tracer.span("data_wait", step=i):
                dev_batch, bt = next(loader)
            with tracer.span("step", step=i) as sp:
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     dev_batch)
                loss = float(metrics["loss"])  # blocks
            t_comp = sp.elapsed_s
            t_comm = float(metrics.pop("t_comm", 0.0))
            t_upd = float(metrics.pop("t_update", 0.0))
            losses.append(loss)
            times.append(StepTimes(
                data_load=bt.data_load, data_prep=bt.data_prep, h2d=bt.h2d,
                compute=max(t_comp - t_comm - t_upd, 0.0),
                param_update=t_upd, dist_update=t_comm))
            if mgr is not None and ckpt_every and (i + 1) % ckpt_every == 0:
                payload = {"params": params,
                           "opt_state": {k: v for k, v in opt_state.items()
                                         if k != "ef"}
                           if isinstance(opt_state, dict) else opt_state}
                mgr.save(i + 1, payload)
            if log_every and (i % log_every == 0 or i == steps - 1):
                print(f"  step {i:4d} loss {loss:.4f} "
                      f"compute {t_comp*1e3:.0f}ms io "
                      f"{(bt.data_load+bt.data_prep+bt.h2d)*1e3:.0f}ms",
                      flush=True)
            if t_first is None:
                t_first = monotonic()
    finally:
        if own_loader:
            loader.close()
        if mgr is not None:
            mgr.close()
    t_end = monotonic()
    n = steps - start_step
    if n > 1:
        tps = (n - 1) * batch * seq / max(t_end - t_first, 1e-9)
    else:
        tps = n * batch * seq / max(t_end - t_start, 1e-9)
    return TrainResult(losses, times, tps, start_step, params)

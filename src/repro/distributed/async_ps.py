"""AsyncPSTrainer — bounded-staleness parameter-server data parallelism.

Relaxes the synchronous-worker assumption under Lemma 3.2 (the paper's §2
taxonomy names stragglers and I/O stalls as exactly what breaks it at
scale) along the two axes the Hitchhiker's-Guide survey maps:

**Bounded staleness** (``staleness = s``): the replicated "server" copy of
the parameters advances every step, but each worker refreshes its private
copy only on its scheduled slot — worker ``w`` pulls at steps where
``(t + w) % (s + 1) == 0`` — so a worker's gradients are computed against
parameters at most ``s`` steps stale, the pull traffic in Eq. 7 amortizes
over ``s + 1`` steps, and refreshes stagger across workers instead of
thundering in the same step.  ``s = 0`` degenerates to every worker
pulling every step: the refresh is a byte-exact ``jnp.where`` copy of the
server params and the gradient graph is the same per-shard program the
synchronous trainer runs, so the run is **bit-identical** to
``DataParallelTrainer`` with the ``parameter_server`` strategy (pinned by
``tests/test_checkpoint.py``).

**Backup workers** (``backup_workers = k``): each step drops the slowest
``k`` of ``dp`` gradients (simulated per-step delays, seeded exponential —
this container has no real stragglers) and averages the survivors,
pre-scaled by ``dp / (dp - k)`` so the inherited ``psum/dp`` sync yields
the survivor mean.  ``k = 0`` multiplies by exactly 1.0 (IEEE-exact), so
the synchronous path is the same code path, not a special case.

The server update itself is the inherited 3-phase machinery — same
``parameter_server`` collective, same optimizer — which is what makes the
bit-identity claim testable rather than aspirational.  :meth:`async_report`
sets the measured refresh/drop/age counters against the cost model's
``T_step(s, k)`` (``repro.core.ps.async_step_time``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import ps as ps_lib
from repro.distributed.collectives import SyncStrategy
from repro.distributed.trainer import (DataParallelTrainer, DEFAULT_LINK_BW,
                                       _stack, _unstack)
from repro.launch.steps import build_grad_fn
from repro.models.blocks import RunConfig
from repro.optim import adamw as opt_lib
from repro.train import loop as loop_lib


@dataclass
class AsyncPSReport:
    """Measured async-PS behaviour vs the relaxed-lemma step model."""

    staleness: int
    backup_workers: int
    dp: int
    steps: int
    refreshes: int              # total worker pulls actually performed
    mean_age: float             # mean params age (steps) at grad time
    max_age: int                # never exceeds `staleness` by construction
    drops: int                  # total gradients dropped (= steps * k)
    drop_counts: Tuple[int, ...]  # per-worker drop totals
    pull_amortization: float    # 1 / (s + 1): Eq. 7 pull traffic factor
    t_step_model: Dict[str, float]  # repro.core.ps.async_step_time terms

    def as_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__dataclass_fields__}


class AsyncPSTrainer(DataParallelTrainer):
    """Bounded-staleness + backup-worker variant of the PS trainer.

    Parameters
    ----------
    staleness:
        Max age ``s`` (in steps) of the params a worker may compute
        gradients against.  0 = fully synchronous.
    backup_workers:
        Slowest ``k`` gradients dropped per step, ``0 <= k < dp``.
    mean_delay_s:
        Mean of the seeded exponential per-worker delay used to *rank*
        workers each step (and to price the straggler model); the
        simulation never sleeps.
    """

    def __init__(self, cfg: ModelConfig, run: RunConfig,
                 opt: opt_lib.OptConfig, *,
                 staleness: int = 0,
                 backup_workers: int = 0,
                 mean_delay_s: float = 0.01,
                 strategy: Union[str, SyncStrategy] = "parameter_server",
                 devices: Optional[List] = None,
                 link_bw: float = DEFAULT_LINK_BW,
                 delay_seed: int = 0,
                 **kwargs):
        if kwargs.pop("sync_overlap", False):
            raise ValueError("AsyncPSTrainer: sync_overlap is a synchronous-"
                             "schedule optimization; staleness already "
                             "amortizes the pull traffic")
        super().__init__(cfg, run, opt, strategy=strategy, devices=devices,
                         link_bw=link_bw, **kwargs)
        if self.strategy.hierarchical:
            raise ValueError("AsyncPSTrainer needs a flat strategy (the "
                             "worker refresh schedule assumes one data axis)")
        if self.compressor.stateful:
            raise ValueError("AsyncPSTrainer: error-feedback compressors "
                             "assume every gradient lands; incompatible "
                             "with backup-worker drops")
        if staleness < 0:
            raise ValueError(f"staleness must be >= 0, got {staleness}")
        if not 0 <= backup_workers < self.dp:
            raise ValueError(f"need 0 <= backup_workers < dp={self.dp}, "
                             f"got {backup_workers}")
        self.staleness = int(staleness)
        self.backup_workers = int(backup_workers)
        self.mean_delay_s = float(mean_delay_s)
        self.delay_seed = int(delay_seed)
        self._workers = None          # stacked (dp,)+shape private copies
        self._ages = np.zeros(self.dp, np.int64)
        self._refreshes = 0
        self._age_sum = 0
        self._age_max = 0
        self._drop_counts = np.zeros(self.dp, np.int64)
        self._steps_run = 0
        self._build_async_phases()

    # ------------------------------------------------------------------
    def _build_async_phases(self):
        mesh, dspec = self.mesh, self._data_spec

        def bcast(p):
            # replicated logical tree -> (dp,)+shape worker stack (each
            # shard gets its own byte-copy of the server params)
            return _stack(p)

        self._bcast_fn = jax.jit(jax.shard_map(
            bcast, mesh=mesh, in_specs=(P(),), out_specs=dspec,
            check_vma=False))

        def refresh(mask, server, workers):
            # mask shard: (1,) bool; jnp.where copies bytes exactly, so a
            # refreshed worker holds the server params bit-for-bit
            def sel(s, w):
                m = mask.reshape((1,) + (1,) * (w.ndim - 1))
                return jnp.where(m, s[None], w)
            return jax.tree_util.tree_map(sel, server, workers)

        self._refresh_fn = jax.jit(jax.shard_map(
            refresh, mesh=mesh,
            in_specs=(dspec, P(), dspec), out_specs=dspec,
            check_vma=False))

        grads_of = build_grad_fn(self.cfg, self.run)

        def wgrad(pstack, batch):
            # per-shard program identical to the synchronous grad phase —
            # the params just arrive as this worker's (1,)+shape slice
            loss, _, grads = grads_of(_unstack(pstack), batch)
            return _stack((loss, grads))

        self._wgrad_fn = jax.jit(jax.shard_map(
            wgrad, mesh=mesh, in_specs=(dspec, dspec), out_specs=dspec,
            check_vma=False))

        def weight(gstack, w):
            # w shard: (1,) float32 — 1.0 for survivors scaled dp/(dp-k),
            # 0.0 for dropped; the *1.0 path (k=0) is IEEE-exact
            def mul(x):
                return x * w.reshape((1,) + (1,) * (x.ndim - 1))
            return jax.tree_util.tree_map(mul, gstack)

        self._weight_fn = jax.jit(jax.shard_map(
            weight, mesh=mesh, in_specs=(dspec, dspec), out_specs=dspec,
            check_vma=False))

    # ------------------------------------------------------------------
    def _refresh_mask(self, t: int) -> np.ndarray:
        """Worker w pulls at steps with (t + w) % (s + 1) == 0 — every
        worker's age stays <= s and refreshes stagger across the window."""
        return ((t + np.arange(self.dp)) % (self.staleness + 1)) == 0

    def _step_weights(self, rng: np.random.Generator) -> np.ndarray:
        """Per-worker gradient weights for this step: drop the k slowest
        (by simulated seeded delay), scale survivors so psum/dp is the
        survivor mean.  k=0 -> all exactly 1.0."""
        dp, k = self.dp, self.backup_workers
        delays = rng.exponential(self.mean_delay_s, dp)
        w = np.full(dp, dp / (dp - k) if k else 1.0, np.float32)
        if k:
            dropped = np.argsort(delays)[-k:]
            w[dropped] = 0.0
            self._drop_counts[dropped] += 1
        return w

    # ------------------------------------------------------------------
    def step_fn(self):
        """Loop-compatible step: refresh scheduled workers from the server
        copy, compute per-worker grads at their (possibly stale) params,
        drop/rescale, then the inherited sync + server update."""
        counter = {"t": 0}
        rng = np.random.default_rng(self.delay_seed)
        wspec = NamedSharding(self.mesh, self._data_spec)

        def step(params, opt_state, batch):
            t = counter["t"]
            counter["t"] = t + 1
            if self._workers is None:
                self._workers = self._bcast_fn(params)
                self._ages[:] = 0
            tr = self.tracer
            mask = self._refresh_mask(t)
            with tr.span("compute") as sp_c:
                if mask.any():
                    dev_mask = jax.device_put(mask, wspec)
                    self._workers = self._refresh_fn(dev_mask, params,
                                                     self._workers)
                    self._refreshes += int(mask.sum())
                    self._ages[mask] = 0
                losses, gstack = self._wgrad_fn(self._workers, batch)
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(gstack)[0])
            self._age_sum += int(self._ages.sum())
            self._age_max = max(self._age_max, int(self._ages.max()))
            self._ages += 1
            with tr.span("dist_update") as sp_s:
                w = self._step_weights(rng)
                gstack = self._weight_fn(gstack, jax.device_put(w, wspec))
                grads, _ = self._sync_fn(gstack, None)
                jax.block_until_ready(jax.tree_util.tree_leaves(grads)[0])
            with tr.span("param_update") as sp_u:
                params, opt_state, gnorm = self._update_fn(
                    params, opt_state, grads)
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(params)[0])
            self._steps_run += 1
            self._publish_phases(sp_c.elapsed_s, sp_s.elapsed_s,
                                 sp_u.elapsed_s)
            self.metrics.observe("train/refreshes", float(mask.sum()))
            metrics = {"loss": jnp.mean(losses), "grad_norm": gnorm,
                       "t_comm": sp_s.elapsed_s, "t_update": sp_u.elapsed_s}
            return params, opt_state, metrics

        return step

    # ------------------------------------------------------------------
    def train(self, **kw) -> loop_lib.TrainResult:
        # fresh worker copies + counters per run: a resumed run rebuilds
        # the worker stack from the restored server params (the stack is
        # derived state, deliberately absent from checkpoints — all
        # workers restart fresh, ages 0)
        self._workers = None
        self._ages = np.zeros(self.dp, np.int64)
        self._refreshes = 0
        self._age_sum = 0
        self._age_max = 0
        self._drop_counts = np.zeros(self.dp, np.int64)
        self._steps_run = 0
        return super().train(**kw)

    # ------------------------------------------------------------------
    def async_report(self) -> AsyncPSReport:
        """Measured staleness/straggler counters + the T_step(s, k) model
        evaluated at this run's measured compute time."""
        steady = self._times[2:] or self._times
        t_c = (float(np.mean([t.compute for t in steady]))
               if steady else 0.0)
        n_ps = self.strategy.n_servers or self.dp
        model = ps_lib.async_step_time(
            self._grad_bytes, self.dp, n_ps, self.link_bw, t_c,
            staleness=self.staleness, backup_workers=self.backup_workers,
            mean_delay=self.mean_delay_s)
        steps = self._steps_run
        return AsyncPSReport(
            staleness=self.staleness,
            backup_workers=self.backup_workers,
            dp=self.dp,
            steps=steps,
            refreshes=self._refreshes,
            mean_age=(self._age_sum / (steps * self.dp)) if steps else 0.0,
            max_age=self._age_max,
            drops=int(self._drop_counts.sum()),
            drop_counts=tuple(int(c) for c in self._drop_counts),
            pull_amortization=1.0 / (self.staleness + 1),
            t_step_model=model,
        )

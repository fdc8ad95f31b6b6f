"""DataParallelTrainer — data parallelism that is real rather than napkin math.

Wraps the instrumented training loop (``repro.train.loop``) with an explicit
gradient-sync strategy over the mesh ``data`` axis. The step is split into
three separately-jitted, separately-timed phases so the paper's Fig.-1 steps
map onto measured wall-clock:

  1. **compute**   — per-device local gradients (shard_map, batch sharded),
  2. **dist_update** — compress + sync collectives (the Lemma 3.2 payload),
  3. **param_update** — replicated optimizer update.

The phase times land in ``StepTimes`` (compute / dist_update / param_update)
so R_O (Lemma 3.1) is evaluated on measurements, and :meth:`report` sets the
measured comm time against the Lemma 3.2 prediction for the same schedule.

With ``sync_overlap=True`` the strict 3-phase step gives way to the
bucketed overlap schedule (``repro.distributed.overlap``): the first
:data:`~DataParallelTrainer.N_CALIB_STEPS` steps run serial-bucketed (one
blocking collective per bucket — the per-bucket serial decomposition), and
every later step is ONE fused XLA program in which each bucket's
compress→sync chain is dataflow-independent from the others and from the
optimizer update, so the scheduler overlaps them (wait-free
backpropagation as XLA sees it).  Both paths are numerically identical to
the serial trainer — same collectives over the same per-leaf payloads —
and :meth:`report` adds the measured ``overlap_fraction`` /
``exposed_comm_time`` against the serial calibration.

Telemetry (``repro.obs``): every phase above is a tracer span — ``compute``
/ ``dist_update`` / ``param_update``, ``bucket_sync`` (per bucket, with the
bucket index and payload bytes as span args) and ``fused_step`` — and the
span wall clocks ARE the values that land in ``StepTimes``/``SyncReport``
(no second clock).  The same numbers stream into a ``MetricsRegistry``
(``train/compute_s`` etc. histograms, ``train/overlap_fraction`` gauges),
which ``Session.train`` renders into the Report's ``metrics/v1`` section.

Numerics: each device computes the mean loss over its batch shard; the
strategy returns the data-axis mean, so with equal shard sizes (enforced)
the synced gradient equals the full-batch gradient up to reduction order —
every strategy must match the single-device baseline within fp32 tolerance
(compression variants within their documented looser tolerance).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.hardware import ClusterSpec
from repro.core.pipeline import StepTimes
from repro.distributed.collectives import SyncStrategy, get_strategy
from repro.distributed.compression import Compressor, get_compressor
from repro.distributed.overlap import (BucketPlan, DEFAULT_BUCKET_MB,
                                       bucket_span_args, build_bucket_plan,
                                       bucket_leaves, mb_to_bytes,
                                       unbucket_leaves)
from repro.launch.steps import build_grad_fn
from repro.obs import MetricsRegistry, Tracer
from repro.models import model as M
from repro.models.blocks import RunConfig
from repro.models.common import materialize
from repro.optim import adamw as opt_lib
from repro.train import loop as loop_lib

# CPU-emulation "link" bandwidth used for the Lemma 3.2 prediction when the
# caller does not supply one (bytes/s; ~memcpy-order for host collectives).
DEFAULT_LINK_BW = 4e9


@dataclass
class SyncReport:
    """Measured-vs-predicted Lemma 3.1/3.2 numbers for one training run."""

    strategy: str
    compression: str
    dp: int
    n_servers: Optional[int]
    grad_bytes: float           # S_p: fp32 gradient payload
    wire_bytes: float           # after compression, per Lemma's worker view
    link_bw: float
    measured_comm_s: float      # mean dist_update over steady-state steps
    predicted_comm_s: float     # Lemma 3.2 for this schedule + payload
    measured_compute_s: float   # mean T_C
    measured_update_s: float
    masked_measured: bool       # comm <= T_C on the wall clock
    masked_predicted: bool      # comm <= T_C per the lemma
    r_o_measured: float         # Lemma 3.1 overhead ratio from StepTimes
    # topology view (hierarchical runs): dp-axis fan-out per tier,
    # innermost first, and the per-tier wire-byte split of `wire_bytes`
    tiers: Optional[Tuple[int, ...]] = None
    wire_bytes_by_tier: Optional[Tuple[float, ...]] = None
    # bucketed-overlap view (repro.distributed.overlap). For serial runs
    # the sync is fully exposed: exposed_comm_time == measured_comm_s and
    # overlap_fraction == 0. For overlapped runs `measured_comm_s` is the
    # *serial-equivalent* comm measured on the bucketed calibration steps,
    # `exposed_comm_time` the residual the fused (overlapped) steps still
    # pay on the wall clock, and `overlap_fraction` the hidden share.
    sync_overlap: bool = False
    bucket_mb: float = 0.0            # bucket size target [MiB] (0 = unbucketed)
    n_buckets: int = 1
    bucket_sizes_bytes: Optional[Tuple[float, ...]] = None
    per_bucket_comm_s: Optional[Tuple[float, ...]] = None  # serial calibration
    exposed_comm_time: float = 0.0    # comm left outside compute [s]
    overlap_fraction: float = 0.0     # hidden comm / serial comm, in [0, 1]
    overlapped_step_s: float = 0.0    # mean fused-step wall clock [s]

    @property
    def effective_link_bw(self) -> float:
        """Measured bytes/s the sync phase actually moved per worker —
        the autotuner's feedback path: ``repro.core.autotune`` fits the
        calibrated tier bandwidths from this instead of the datasheet
        ``link_bw`` (0.0 when nothing crossed the wire)."""
        if self.measured_comm_s <= 0:
            return 0.0
        return self.wire_bytes / self.measured_comm_s

    def as_dict(self) -> Dict[str, Any]:
        d = {k: getattr(self, k) for k in self.__dataclass_fields__}
        d["effective_link_bw"] = self.effective_link_bw
        return d


def _stack(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


def _unstack(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


class DataParallelTrainer:
    """Run ``repro.train.loop.train`` under an explicit sync strategy.

    Parameters/optimizer state are replicated; the batch is sharded over the
    ``data`` axis (all visible devices unless ``devices`` is given). The
    strategy and compressor may be names (resolved via the registries) or
    instances — ``Plan.resolve_sync()`` hands over an instance sized by
    Lemma 3.2.
    """

    # serial-bucketed calibration steps at the head of an overlapped run:
    # step 0 absorbs the per-bucket compiles, step 1 supplies the clean
    # serial decomposition (compute / per-bucket comm / update) the fused
    # steps are measured against
    N_CALIB_STEPS = 2

    def __init__(self, cfg: ModelConfig, run: RunConfig,
                 opt: opt_lib.OptConfig, *,
                 strategy: Union[str, SyncStrategy] = "all_reduce",
                 compression: Union[str, Compressor] = "none",
                 devices: Optional[List] = None,
                 link_bw: float = DEFAULT_LINK_BW,
                 topology: Optional[ClusterSpec] = None,
                 sync_overlap: bool = False,
                 bucket_mb: float = DEFAULT_BUCKET_MB,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None):
        self.cfg, self.run, self.opt = cfg, run, opt
        # the phase wall clocks that feed StepTimes/SyncReport come FROM the
        # tracer's spans, so the trainer always times against an *enabled*
        # tracer — a disabled one would zero the measurements, so it is
        # substituted by a private live clock (events then go nowhere)
        self.tracer = (tracer if tracer is not None and tracer.enabled
                       else Tracer(enabled=True))
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        if bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
        self.sync_overlap = bool(sync_overlap)
        self.bucket_mb = float(bucket_mb)
        self.strategy = (get_strategy(strategy)
                         if isinstance(strategy, str) else strategy)
        self.compressor = (get_compressor(compression)
                           if isinstance(compression, str) else compression)
        devs = list(devices if devices is not None else jax.devices())
        self.dp = len(devs)
        self.topology = topology
        self._tier_bws: Optional[Tuple[float, ...]] = None
        if self.strategy.hierarchical:
            sizes = self._resolve_tiers(topology)
            self.strategy = dataclasses.replace(self.strategy, tiers=sizes)
            if topology is not None and topology.tier_sizes == sizes:
                self._tier_bws = topology.tier_bws
            inner = sizes[0]
            if len(sizes) > 1 and self.dp // inner > 1:
                # nested axes: nodes (slow tier) x data (in-node, fast tier)
                self.mesh = Mesh(
                    np.array(devs).reshape(self.dp // inner, inner),
                    ("nodes", "data"))
                self._axes: Union[str, Tuple[str, ...]] = ("nodes", "data")
            else:
                self.mesh = Mesh(np.array(devs), ("data",))
                self._axes = "data"
        else:
            self.mesh = Mesh(np.array(devs), ("data",))
            self._axes = "data"
        self._data_spec = (P(self._axes) if isinstance(self._axes, str)
                           else P(tuple(self._axes)))
        self.link_bw = link_bw
        self._times: List[StepTimes] = []
        self._grad_bytes: float = 0.0
        self._bucket_plan: Optional[BucketPlan] = None
        self._bucket_sync_fn = None
        self._fused_fn = None
        # serial decomposition from the calibration steps (means of the
        # clean calibration step) + fused-step observations
        self._calib: Dict[str, Any] = {}
        self._fused_steps: List[Dict[str, float]] = []
        self._build_phases()

    def _resolve_tiers(self, topology: Optional[ClusterSpec]) -> Tuple[int, ...]:
        """dp-axis fan-out per tier for the hierarchical strategy: the
        strategy's own sizing when it matches this trainer's device count,
        else the topology's, else an adapted/degenerate split."""
        cands = []
        if self.strategy.tiers:
            cands.append(tuple(self.strategy.tiers))
        if topology is not None:
            cands.append(tuple(topology.tier_sizes))
        for sizes in cands:
            if math.prod(sizes) == self.dp:
                return sizes
        for sizes in cands:  # keep the in-node fan-out if it divides dp
            if sizes[0] > 1 and self.dp % sizes[0] == 0:
                return (sizes[0], self.dp // sizes[0])
        return (self.dp,)

    # ------------------------------------------------------------------
    @classmethod
    def from_plan(cls, plan, cfg: ModelConfig, run: RunConfig,
                  opt: opt_lib.OptConfig, *,
                  compression: Union[str, Compressor] = "none",
                  devices: Optional[List] = None,
                  link_bw: float = DEFAULT_LINK_BW,
                  topology: Optional[ClusterSpec] = None,
                  sync_overlap: Optional[bool] = None,
                  bucket_mb: Optional[float] = None,
                  tracer: Optional[Tracer] = None,
                  metrics: Optional[MetricsRegistry] = None
                  ) -> "DataParallelTrainer":
        """Trainer whose sync strategy comes from a planner ``Plan`` —
        ``resolve_sync()`` supplies the Lemma-3.2-sized strategy instance
        (the topology defaults to the plan's own, the overlap knobs to the
        plan's ``sync_overlap``/``bucket_mb``)."""
        if topology is None:
            topology = plan.cluster
        if sync_overlap is None:
            sync_overlap = bool(getattr(plan, "sync_overlap", False))
        if bucket_mb is None:
            bucket_mb = float(getattr(plan, "bucket_mb", 0.0)
                              or DEFAULT_BUCKET_MB)
        return cls(cfg, run, opt, strategy=plan.resolve_sync(),
                   compression=compression, devices=devices, link_bw=link_bw,
                   topology=topology, sync_overlap=sync_overlap,
                   bucket_mb=bucket_mb, tracer=tracer, metrics=metrics)

    # ------------------------------------------------------------------
    def _build_phases(self):
        grads_of = build_grad_fn(self.cfg, self.run)
        strat, comp, dp = self.strategy, self.compressor, self.dp
        axes, dspec = self._axes, self._data_spec

        def grad_phase(params, batch):
            # per-device local grads; stacked on a fresh leading data axis
            loss, _, grads = grads_of(params, batch)
            return _stack((loss, grads))

        self._grad_fn = jax.jit(jax.shard_map(
            grad_phase, mesh=self.mesh,
            in_specs=(P(), dspec), out_specs=dspec, check_vma=False))

        def sync_phase(gstack, efstack):
            grads = _unstack(gstack)
            ef = _unstack(efstack) if efstack is not None else None
            grads, ef = comp.apply(grads, ef)
            grads = strat.sync(grads, axes, dp)
            ef_out = _stack(ef) if ef is not None else None
            return grads, ef_out

        # ef may be None (stateless compressor): an empty pytree, for which
        # the data-axes prefix spec is vacuous
        self._sync_fn = jax.jit(jax.shard_map(
            sync_phase, mesh=self.mesh,
            in_specs=(dspec, dspec),
            out_specs=(P(), dspec), check_vma=False))

        self._update_fn = jax.jit(
            lambda p, s, g: opt_lib.apply_updates(self.opt, p, g, s),
            donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    # Bucketed overlap path (repro.distributed.overlap)
    # ------------------------------------------------------------------
    def _ensure_bucket_plan(self, params) -> BucketPlan:
        if self._bucket_plan is None:
            self._bucket_plan = build_bucket_plan(
                params, mb_to_bytes(self.bucket_mb))
        return self._bucket_plan

    def _build_overlap_fns(self):
        """Per-bucket sync executables (the serial calibration path, one
        blocking collective per bucket) and the fused overlapped step (one
        XLA program per step: every bucket's collective chain is dataflow-
        independent, so the scheduler overlaps bucket k+1's comm with
        bucket k's consumers — wait-free backpropagation as XLA sees it)."""
        if self._bucket_sync_fn is not None:
            return
        if self._bucket_plan is None:
            raise RuntimeError("overlap path needs a BucketPlan; call init() "
                               "(or train()) before step_fn()")
        plan = self._bucket_plan
        grads_of = build_grad_fn(self.cfg, self.run)
        strat, comp, dp = self.strategy, self.compressor, self.dp
        axes, dspec = self._axes, self._data_spec

        # one jitted sync shared by every bucket — jit's signature cache
        # specializes it per bucket's leaf shapes
        def bucket_sync(g_leaves, ef_leaves):
            g = _unstack(g_leaves)
            ef = _unstack(ef_leaves) if ef_leaves is not None else None
            g, ef = comp.apply(g, ef)
            g = strat.sync(g, axes, dp)
            ef_out = _stack(ef) if ef is not None else None
            return g, ef_out

        self._bucket_sync_fn = jax.jit(jax.shard_map(
            bucket_sync, mesh=self.mesh,
            in_specs=(dspec, dspec), out_specs=(P(), dspec),
            check_vma=False))

        def sync_all_buckets(p, b, efs):
            """shard_map body of the fused step: local grads, then one
            compress+sync chain per bucket in grad-availability order."""
            loss, _, grads = grads_of(p, b)
            g_leaves, treedef = jax.tree_util.tree_flatten(grads)
            e_leaves = (jax.tree_util.tree_leaves(_unstack(efs))
                        if efs is not None else None)
            out_g: List[Any] = []
            out_e: List[Any] = []
            for idx in plan.buckets:
                gb = [g_leaves[i] for i in idx]
                eb = [e_leaves[i] for i in idx] if e_leaves is not None else None
                gb, eb = comp.apply(gb, eb)
                gb = strat.sync(gb, axes, dp)
                out_g.append(gb)
                if eb is not None:
                    out_e.append(eb)
            synced = jax.tree_util.tree_unflatten(
                treedef, unbucket_leaves(out_g, plan))
            ef_out = None
            if e_leaves is not None:
                ef_out = _stack(jax.tree_util.tree_unflatten(
                    treedef, unbucket_leaves(out_e, plan)))
            return _stack(loss), synced, ef_out

        def fused_step(params, opt_state, batch, efstack):
            losses, grads, efs = jax.shard_map(
                sync_all_buckets, mesh=self.mesh,
                in_specs=(P(), dspec, dspec),
                out_specs=(dspec, P(), dspec),
                check_vma=False)(params, batch, efstack)
            new_p, new_s, gnorm = opt_lib.apply_updates(
                self.opt, params, grads, opt_state)
            return new_p, new_s, losses, efs, gnorm

        self._fused_fn = jax.jit(fused_step, donate_argnums=(0, 1))

    def _calib_step(self, params, opt_state, batch, ef):
        """Serial-bucketed step: identical numerics to the fused path, but
        each bucket's collective blocks, yielding the per-bucket serial
        comm decomposition the overlap measurement is set against.  Every
        phase is a tracer span; the span wall clocks ARE the measurements
        (``per_bucket_comm_s`` is the ``bucket_sync`` span durations)."""
        plan = self._bucket_plan
        tr = self.tracer
        with tr.span("compute") as sp_c:
            losses, gstack = self._grad_fn(params, batch)
            jax.block_until_ready(jax.tree_util.tree_leaves(gstack)[0])
        per_bucket: List[float] = []
        with tr.span("dist_update", n_buckets=plan.n_buckets) as sp_s:
            g_leaves, treedef = jax.tree_util.tree_flatten(gstack)
            e_leaves = (jax.tree_util.tree_leaves(ef)
                        if ef is not None else None)
            g_buckets = bucket_leaves(g_leaves, plan)
            e_buckets = (bucket_leaves(e_leaves, plan)
                         if e_leaves is not None else [None] * plan.n_buckets)
            out_g: List[Any] = []
            out_e: List[Any] = []
            for k, (gb, eb) in enumerate(zip(g_buckets, e_buckets)):
                with tr.span("bucket_sync",
                             **bucket_span_args(plan, k)) as sp_b:
                    g_syn, ef_out = self._bucket_sync_fn(gb, eb)
                    jax.block_until_ready(g_syn)
                per_bucket.append(sp_b.elapsed_s)
                out_g.append(g_syn)
                if ef_out is not None:
                    out_e.append(ef_out)
        with tr.span("param_update") as sp_u:
            grads = jax.tree_util.tree_unflatten(
                treedef, unbucket_leaves(out_g, plan))
            ef_new = (jax.tree_util.tree_unflatten(
                treedef, unbucket_leaves(out_e, plan)) if out_e else None)
            params, opt_state, gnorm = self._update_fn(
                params, opt_state, grads)
            jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
        # the last calibration step is the clean one (step 0 pays compiles)
        self._calib = {"compute": sp_c.elapsed_s, "comm": sp_s.elapsed_s,
                       "update": sp_u.elapsed_s,
                       "per_bucket": tuple(per_bucket)}
        self._publish_phases(sp_c.elapsed_s, sp_s.elapsed_s, sp_u.elapsed_s)
        for t in per_bucket:
            self.metrics.observe("train/bucket_comm_s", t)
        return params, opt_state, losses, ef_new, gnorm, {
            "t_comm": sp_s.elapsed_s, "t_update": sp_u.elapsed_s}

    def _overlap_step(self, params, opt_state, batch, ef):
        """Fused overlapped step, timed as one span; the serial
        calibration decomposition attributes the wall clock to exposed
        comm vs (hidden-under) update/compute."""
        with self.tracer.span("fused_step") as sp:
            params, opt_state, losses, ef_new, gnorm = self._fused_fn(
                params, opt_state, batch, ef)
            jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
        wall = sp.elapsed_s
        comm_s = self._calib.get("comm", 0.0)
        comp_s = self._calib.get("compute", 0.0)
        upd_s = self._calib.get("update", 0.0)
        exposed = min(max(wall - comp_s - upd_s, 0.0), comm_s)
        self._fused_steps.append(
            {"wall_s": wall, "exposed_comm_s": exposed,
             "serial_comm_s": comm_s})
        self.metrics.inc("train/steps")
        self.metrics.observe("train/step_s", wall)
        self.metrics.observe("train/fused_step_s", wall)
        self.metrics.observe("train/exposed_comm_s", exposed)
        t_update = min(upd_s, max(wall - exposed, 0.0))
        return params, opt_state, losses, ef_new, gnorm, {
            "t_comm": exposed, "t_update": t_update}

    # ------------------------------------------------------------------
    def init(self, seed: int = 0):
        """Replicated params + opt state (with per-device EF slots when the
        compressor is stateful)."""
        params = materialize(M.model_specs(self.cfg), jax.random.PRNGKey(seed))
        state = opt_lib.init_state(self.opt, params)
        rep = NamedSharding(self.mesh, P())
        params = jax.device_put(params, rep)
        state = jax.device_put(state, rep)
        if self.compressor.stateful:
            zeros = jax.tree_util.tree_map(
                lambda a: jnp.zeros((self.dp,) + a.shape, jnp.float32), params)
            state["ef"] = jax.device_put(
                zeros, NamedSharding(self.mesh, self._data_spec))
        self._grad_bytes = 4.0 * sum(
            int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(params))
        if self.sync_overlap:
            self._ensure_bucket_plan(params)
        return params, state

    def grads(self, params, batch):
        """(per-device losses, the synced gradient): the compute and sync
        phases of a step without the update (and without error feedback)."""
        losses, gstack = self._grad_fn(params, batch)
        return losses, self._sync_fn(gstack, None)[0]

    def batch_sharding(self) -> NamedSharding:
        """Where :meth:`step_fn` takes its batch: rows split over the data
        axes."""
        return NamedSharding(self.mesh, self._data_spec)

    def step_fn(self):
        """A loop-compatible step callable: (params, opt_state, batch) ->
        (params, opt_state, metrics). Phase wall-times are attached to
        ``metrics`` as plain floats (``t_comm`` / ``t_update``) after device
        sync, so the loop can split them out of compute.

        With ``sync_overlap`` the first :data:`N_CALIB_STEPS` steps run the
        serial-bucketed calibration path (numerically identical, blocking
        per bucket) and every later step runs the fused overlapped program;
        ``t_comm`` then reports the *exposed* comm only."""

        if self.sync_overlap:
            self._build_overlap_fns()
            counter = {"k": 0}

            def step(params, opt_state, batch):
                ef = opt_state.pop("ef", None)
                k = counter["k"]
                counter["k"] = k + 1
                fn = (self._calib_step if k < self.N_CALIB_STEPS
                      else self._overlap_step)
                params, opt_state, losses, ef, gnorm, phase = fn(
                    params, opt_state, batch, ef)
                if ef is not None:
                    opt_state["ef"] = ef
                metrics = {"loss": jnp.mean(losses), "grad_norm": gnorm,
                           **phase}
                return params, opt_state, metrics

            return step

        def step(params, opt_state, batch):
            ef = opt_state.pop("ef", None)
            tr = self.tracer
            with tr.span("compute") as sp_c:
                losses, gstack = self._grad_fn(params, batch)
                jax.block_until_ready(jax.tree_util.tree_leaves(gstack)[0])
            with tr.span("dist_update") as sp_s:
                grads, ef = self._sync_fn(gstack, ef)
                jax.block_until_ready(jax.tree_util.tree_leaves(grads)[0])
            with tr.span("param_update") as sp_u:
                params, opt_state, gnorm = self._update_fn(
                    params, opt_state, grads)
                jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
            if ef is not None:
                opt_state["ef"] = ef
            self._publish_phases(sp_c.elapsed_s, sp_s.elapsed_s,
                                 sp_u.elapsed_s)
            metrics = {"loss": jnp.mean(losses), "grad_norm": gnorm,
                       "t_comm": sp_s.elapsed_s, "t_update": sp_u.elapsed_s}
            return params, opt_state, metrics

        return step

    def _publish_phases(self, compute_s: float, comm_s: float,
                        update_s: float) -> None:
        """Per-step phase histograms in the shared registry (the
        metrics/v1 ``train/*`` family)."""
        m = self.metrics
        m.inc("train/steps")
        m.observe("train/compute_s", compute_s)
        m.observe("train/dist_update_s", comm_s)
        m.observe("train/param_update_s", update_s)
        m.observe("train/step_s", compute_s + comm_s + update_s)

    # ------------------------------------------------------------------
    def train(self, *, batch: int, seq: int, steps: int, seed: int = 0,
              log_every: int = 10, params=None, opt_state=None,
              ckpt_dir: Optional[str] = None,
              ckpt_every: int = 0) -> loop_lib.TrainResult:
        if batch % self.dp:
            raise ValueError(f"batch {batch} not divisible by dp={self.dp} "
                             "(equal shards are required for exact means)")
        # fresh overlap measurements per run: a second train() (e.g. with
        # carried-over params) must not mix fused-step observations or the
        # serial calibration of the previous run into its report
        self._calib = {}
        self._fused_steps = []
        if params is None or opt_state is None:
            params, opt_state = self.init(seed)
        elif self._grad_bytes == 0:
            self._grad_bytes = 4.0 * sum(
                int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(params))
        if self.sync_overlap:
            self._ensure_bucket_plan(params)
        batch_sharding = {k: self.batch_sharding()
                          for k in ("tokens", "labels", "image_embeds")}
        res = loop_lib.train(
            self.cfg, self.run, self.opt, batch=batch, seq=seq, steps=steps,
            seed=seed, log_every=log_every, params=params,
            opt_state=opt_state, step_fn=self.step_fn(),
            batch_sharding=batch_sharding,
            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, tracer=self.tracer)
        self._times = res.step_times
        return res

    # ------------------------------------------------------------------
    def report(self) -> SyncReport:
        """Close the loop: measured comm vs the Lemma 3.2 prediction.

        For an overlapped run the steady window additionally skips the
        first fused step (its compile), ``measured_comm_s`` is the
        serial-equivalent comm from the bucketed calibration step, and the
        overlap fields report how much of it the fused steps actually
        hid."""
        warmup = (self.N_CALIB_STEPS + 1) if self.sync_overlap else 2
        steady = self._times[warmup:] or self._times
        comm = float(np.mean([t.dist_update for t in steady])) if steady else 0.0
        compute = float(np.mean([t.compute for t in steady])) if steady else 0.0
        upd = float(np.mean([t.param_update for t in steady])) if steady else 0.0
        s_p = self._grad_bytes
        wire_payload = self.compressor.wire_bytes(s_p)
        predicted = self.strategy.predicted_comm_time(
            wire_payload, self.dp, self.link_bw, tier_bws=self._tier_bws)
        r_o = (float(np.mean([t.r_o() for t in steady])) if steady else 0.0)
        bplan = self._bucket_plan
        exposed, frac, fused_wall = comm, 0.0, 0.0
        if self.sync_overlap:
            comm = float(self._calib.get("comm", comm))
            fused = self._fused_steps[1:] or self._fused_steps
            if fused:
                # best-of, like autotune._timeit: host noise inflates
                # individual fused steps, it never deflates them
                exposed = float(min(f["exposed_comm_s"] for f in fused))
                fused_wall = float(min(f["wall_s"] for f in fused))
            else:  # fused path never ran (too few steps): fully exposed
                exposed = comm
            frac = (min(max(1.0 - exposed / comm, 0.0), 1.0)
                    if comm > 0 else 0.0)
        # registry view of the same numbers (the metrics/v1 train family)
        m = self.metrics
        m.set_gauge("train/measured_comm_s", comm)
        m.set_gauge("train/overlap_fraction", frac)
        m.set_gauge("train/exposed_comm_time_s", exposed)
        m.set_gauge("train/n_buckets", bplan.n_buckets if bplan else 1)
        m.set_gauge("train/effective_link_bw",
                    self.strategy.wire_bytes(wire_payload, self.dp) / comm
                    if comm > 0 else 0.0)
        return SyncReport(
            strategy=self.strategy.name, compression=self.compressor.name,
            dp=self.dp, n_servers=self.strategy.n_servers,
            grad_bytes=s_p,
            wire_bytes=self.strategy.wire_bytes(wire_payload, self.dp),
            link_bw=self.link_bw,
            measured_comm_s=comm, predicted_comm_s=predicted,
            measured_compute_s=compute, measured_update_s=upd,
            masked_measured=comm <= compute,
            masked_predicted=predicted <= compute,
            r_o_measured=r_o,
            tiers=self.strategy.tiers,
            wire_bytes_by_tier=(
                self.strategy.wire_bytes_by_tier(wire_payload, self.dp)
                if self.strategy.hierarchical else None),
            sync_overlap=self.sync_overlap,
            bucket_mb=self.bucket_mb if self.sync_overlap else 0.0,
            n_buckets=bplan.n_buckets if bplan else 1,
            bucket_sizes_bytes=bplan.sizes_bytes if bplan else None,
            per_bucket_comm_s=(tuple(self._calib["per_bucket"])
                               if self._calib.get("per_bucket") else None),
            exposed_comm_time=exposed,
            overlap_fraction=frac,
            overlapped_step_s=fused_wall,
        )

"""PipelineTrainer — executable 1F1B pipeline parallelism over a
``(pipe, data)`` mesh.

The registry model's cycle stack is cut into ``pipe`` contiguous stage
groups (:func:`repro.core.pipeline.balanced_stage_cut`); each stage holds
only its slice of the stacked slot parameters (stage 0 additionally the
embedding + prelude, the last stage the final norm and LM head).  A step
runs the non-interleaved 1F1B schedule (:func:`schedule_1f1b`) host-
orchestrated: every ``(stage, fwd|bwd, microbatch)`` op is one jitted
``shard_map`` call over that stage's flat ``data`` mesh, timed as a tracer
span (``pipe_fwd`` / ``pipe_bwd`` with ``stage``/``micro`` args).  The
measured span durations replay through :func:`simulate_1f1b` so the
per-step bubble fraction is reconciled against the analytic
``(p-1)/(m+p-1)`` model — that is :meth:`pipeline_report`.

Numerics are *bit-identical* to the single-stage
:class:`~repro.distributed.trainer.DataParallelTrainer` run on
``world // pipe`` devices with ``run.microbatch`` set to this trainer's
per-device microbatch rows, on the same token stream (asserted per
strategy by ``tests/test_pipeline.py``):

* the stage forward reuses the exact single-stage op sequence
  (``cast_params`` → embed → prelude scan → ``M._scan_cycles`` over the
  stage's cycle slice → final norm → logits → masked CE), so a
  microbatch's loss is the same op sequence split at cycle boundaries;
* the backward recomputes the stage forward under ``jax.vjp`` — the same
  deterministic ops on the same inputs the baseline's backward consumes;
* gradients accumulate into fp32 zeros with ``jnp.add`` in microbatch
  index order then divide by ``m`` — exactly
  :func:`repro.launch.steps.build_grad_fn`'s accumulation scan (1F1B
  completes backwards in index order on every stage, so the order
  matches);
* each stage syncs its gradient shard over its own flat ``data`` mesh
  with the same strategy: every member of the collectives zoo is
  element-wise over the data axis, so the per-stage sync of a slice
  equals the slice of the full sync;
* the synced shards reassemble into the full gradient tree (slot slices
  concatenate along the cycle axis; the tied embedding's two cotangents
  — lookup and head — add once, like autodiff's own accumulation) and
  ONE replicated :func:`~repro.optim.adamw.apply_updates` applies them,
  so the global gradient-norm clip sees the identical leaf set.

The tied-embedding cotangent add is fp32-exact only when ``cfg.dtype`` is
float32 (under bf16 compute the baseline sums the two cotangents in bf16
at the cast boundary); the bit-match tests therefore pin
``dtype="float32"`` while bf16 runs agree within mixed-precision
tolerance.

Bit-identity additionally requires every stage to hold **at least two
cycles**: a single-cycle stage lowers its ``lax.scan`` with trip count 1,
which XLA's while-loop simplifier inlines and re-fuses with the
surrounding stage ops — ulp-level reassociation relative to the
baseline's intact loop body (observed empirically: 1-cycle stages drift
at ~1e-7 relative, 2-cycle stages match exactly).  ``balanced_stage_cut``
yields ≥2-cycle stages whenever ``main_cycles(cfg) >= 2 * pipe``.

Restrictions: multi-codebook embeddings, VLM image prefixes, stateful
(error-feedback) compressors and ``unroll_layers`` are rejected — each
breaks the contiguous-stage or element-wise-sync argument above.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, SlotSpec
from repro.core.pipeline import (StepTimes, balanced_stage_cut,
                                 pipeline_bubble, schedule_1f1b,
                                 simulate_1f1b, simulate_serial)
from repro.distributed.collectives import SyncStrategy, get_strategy
from repro.distributed.compression import Compressor, get_compressor
from repro.distributed.trainer import (DEFAULT_LINK_BW, SyncReport, _stack,
                                       _unstack)
from repro.models import model as M
from repro.models.blocks import RunConfig, slot_forward
from repro.models.common import cross_entropy, materialize, rms_norm
from repro.obs import MetricsRegistry, Tracer
from repro.optim import adamw as opt_lib
from repro.train import loop as loop_lib


@dataclass
class PipelineReport:
    """Measured-vs-model 1F1B schedule numbers for one training run."""

    pipe: int
    n_microbatch: int
    stage_cut: Tuple[int, ...]
    bubble_measured: float      # span durations replayed via simulate_1f1b
    bubble_model: float         # (p-1)/(m+p-1)
    bubble_serial: float        # the no-overlap reference schedule
    makespan_s: float
    stage_busy_s: Tuple[float, ...]
    fwd_times_s: Tuple[Tuple[float, ...], ...]   # [stage][micro]
    bwd_times_s: Tuple[Tuple[float, ...], ...]

    def as_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__dataclass_fields__}


def _stage_params(params, cfg: ModelConfig, cut: Tuple[int, ...], s: int):
    """Stage ``s``'s parameter slice: slot stacks cut ``cut[s]:cut[s+1]``
    along the cycle axis, plus embedding/prelude on stage 0 and final norm
    (+ LM head, or the tied embedding under the ``embed_out`` key so its
    head cotangent stays separable) on the last stage."""
    p = len(cut) - 1
    sp: Dict[str, Any] = {
        "slots": jax.tree_util.tree_map(
            lambda a: a[cut[s]:cut[s + 1]], params["slots"])
    }
    if s == 0:
        sp["embed"] = params["embed"]
        if cfg.first_k_dense:
            sp["prelude"] = params["prelude"]
    if s == p - 1:
        sp["final_norm"] = params["final_norm"]
        if cfg.tie_embeddings:
            if p > 1:
                sp["embed_out"] = params["embed"]
            # p == 1: the stage's own "embed" serves lookup AND head, so
            # autodiff itself sums the two cotangents — like the baseline
        elif "lm_head" in params:
            sp["lm_head"] = params["lm_head"]
    return sp


def _positions(h):
    B, S = h.shape[:2]
    return jnp.broadcast_to(jnp.arange(S)[None], (B, S))


class PipelineTrainer:
    """Host-orchestrated 1F1B over ``pipe`` stages x ``world // pipe`` data
    shards, loop-compatible (``step_fn`` / ``train`` / ``report``) with the
    DataParallelTrainer so the Session can swap it in."""

    def __init__(self, cfg: ModelConfig, run: RunConfig,
                 opt: opt_lib.OptConfig, *,
                 pipe: int, n_microbatch: int = 0,
                 strategy: Union[str, SyncStrategy] = "all_reduce",
                 compression: Union[str, Compressor] = "none",
                 devices: Optional[List] = None,
                 link_bw: float = DEFAULT_LINK_BW,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None):
        if cfg.num_codebooks:
            raise NotImplementedError(
                "pipeline stages need a single token embedding "
                "(multi-codebook unsupported)")
        if cfg.num_image_tokens:
            raise NotImplementedError(
                "pipeline trainer does not take VLM image prefixes")
        if run.unroll_layers:
            raise NotImplementedError(
                "pipeline stages scan their cycle slice; unroll_layers "
                "is incompatible")
        if run.microbatch:
            raise ValueError(
                "set n_microbatch on the trainer, not run.microbatch — "
                "1F1B owns the microbatch loop")
        self.cfg, self.run, self.opt = cfg, run, opt
        self.tracer = (tracer if tracer is not None and tracer.enabled
                       else Tracer(enabled=True))
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.strategy = (get_strategy(strategy)
                         if isinstance(strategy, str) else strategy)
        self.compressor = (get_compressor(compression)
                           if isinstance(compression, str) else compression)
        if self.compressor.stateful:
            raise NotImplementedError(
                "stateful (error-feedback) compressors are not supported "
                "under the pipeline trainer")
        devs = list(devices if devices is not None else jax.devices())
        if pipe < 1 or len(devs) % pipe:
            raise ValueError(f"pipe={pipe} must divide the {len(devs)} "
                             "visible devices")
        self.pipe = int(pipe)
        self.dp = len(devs) // self.pipe          # data shards per stage
        self.n_microbatch = int(n_microbatch) or self.pipe
        if self.n_microbatch < self.pipe:
            raise ValueError(f"n_microbatch={self.n_microbatch} must be >= "
                             f"pipe={self.pipe} (1F1B needs a full fill)")
        if self.strategy.hierarchical:
            # per-stage meshes are flat: degenerate single-tier sizing,
            # exactly what the baseline resolves without a topology
            self.strategy = dataclasses.replace(self.strategy,
                                                tiers=(self.dp,))
        self.cycles = M.main_cycles(cfg)
        self.stage_cut = balanced_stage_cut(self.cycles, self.pipe)
        # one global mesh declares the (pipe, data) axes (analysis/mesh_axes
        # reads this literal); per-stage flat meshes execute the stage
        # programs — a stage's flat mesh syncs exactly like the baseline's
        grid = np.array(devs).reshape(self.pipe, self.dp)
        self.mesh = Mesh(grid, ("pipe", "data"))
        self.stage_meshes = [Mesh(grid[s], ("data",))
                             for s in range(self.pipe)]
        self.link_bw = link_bw
        self._grad_bytes = 0.0
        self._times: List[StepTimes] = []
        # per-step measured op durations: [step][stage][micro]
        self._fwd_obs: List[List[List[float]]] = []
        self._bwd_obs: List[List[List[float]]] = []
        self._build_phases()

    # ------------------------------------------------------------------
    @classmethod
    def from_plan(cls, plan, cfg: ModelConfig, run: RunConfig,
                  opt: opt_lib.OptConfig, *,
                  compression: Union[str, Compressor] = "none",
                  devices: Optional[List] = None,
                  link_bw: float = DEFAULT_LINK_BW,
                  tracer: Optional[Tracer] = None,
                  metrics: Optional[MetricsRegistry] = None
                  ) -> "PipelineTrainer":
        """Trainer whose stage count / microbatching / sync strategy come
        from a planner ``Plan`` (``resolve_sync()`` supplies the
        Lemma-3.2-sized strategy instance)."""
        return cls(cfg, run, opt, pipe=int(getattr(plan, "pipe", 1) or 1),
                   n_microbatch=int(getattr(plan, "n_microbatch", 0) or 0),
                   strategy=plan.resolve_sync(), compression=compression,
                   devices=devices, link_bw=link_bw, tracer=tracer,
                   metrics=metrics)

    # ------------------------------------------------------------------
    # Stage programs
    # ------------------------------------------------------------------
    def _inner_fns(self):
        """Unsharded per-stage computations over stage-sliced params.

        The carry between stages is ``(h, aux)`` — activations plus the
        running MoE aux-loss sum; every stage's aux cotangent is the
        constant ``cfg.aux_loss_alpha`` (its weight in
        :func:`repro.models.model.loss_fn`), so backward never threads it.
        """
        cfg, run, p = self.cfg, self.run, self.pipe

        def embed_prelude(cp, batch):
            h = M.embed_tokens(cp, batch, cfg)
            pos = _positions(h)
            if cfg.first_k_dense:
                pre_slot = SlotSpec(cfg.pattern[0].mixer, "dense")

                def pre_cycle(h, layer_params):
                    h, _, _ = slot_forward(layer_params, h, pos, cfg,
                                           pre_slot, run)
                    return h, None

                h, _ = jax.lax.scan(pre_cycle, h, cp["prelude"])
            return h, pos

        def first(sp, batch):
            """Stage 0 of p > 1: tokens -> (h, aux)."""
            cp = M.cast_params(sp, cfg)
            h, pos = embed_prelude(cp, batch)
            h, _, stats = M._scan_cycles(cp, h, pos, cfg, run, False)
            return h, stats["aux"]

        def mid(sp, h, aux_in):
            """Interior stage: (h, aux) -> (h, aux)."""
            cp = M.cast_params(sp, cfg)
            h, _, stats = M._scan_cycles(cp, h, _positions(h), cfg, run,
                                         False)
            return h, aux_in + stats["aux"]

        def head_loss(cp, h, batch, aux):
            h = rms_norm(h, cp["final_norm"], cfg.norm_eps)
            head = ({"embed": cp.get("embed_out", cp.get("embed"))}
                    if cfg.tie_embeddings else {"lm_head": cp["lm_head"]})
            logits = M.lm_logits(head, h, cfg)
            labels = batch["labels"]
            mask = (labels >= 0).astype(jnp.float32)
            ce = cross_entropy(logits, jnp.maximum(labels, 0), mask)
            return ce + cfg.aux_loss_alpha * aux

        def last(sp, batch, h, aux_in):
            """Final stage of p > 1: (h, aux) + labels -> loss."""
            cp = M.cast_params(sp, cfg)
            h, _, stats = M._scan_cycles(cp, h, _positions(h), cfg, run,
                                         False)
            return head_loss(cp, h, batch, aux_in + stats["aux"])

        def solo(sp, batch):
            """p == 1: the whole model, loss_fn's exact op sequence."""
            cp = M.cast_params(sp, cfg)
            h, pos = embed_prelude(cp, batch)
            h, _, stats = M._scan_cycles(cp, h, pos, cfg, run, False)
            return head_loss(cp, h, batch, stats["aux"])

        return first, mid, last, solo

    def _build_phases(self):
        p, dp = self.pipe, self.dp
        strat, comp, m = self.strategy, self.compressor, self.n_microbatch
        first, mid, last, solo = self._inner_fns()
        # d loss / d aux_s
        cot_aux = jnp.asarray(self.cfg.aux_loss_alpha, jnp.float32)

        # fwd: op call per (stage, microbatch); bwd: jax.vjp recompute.
        # Stacked (leading per-device axis) outputs mirror the baseline's
        # _stack convention so out_specs P("data") concatenates shards.
        self._fwd_fns: List[Any] = []
        self._bwd_fns: List[Any] = []
        for s in range(p):
            mesh, d = self.stage_meshes[s], P("data")
            if p == 1:
                def fwd_solo(sp, b):
                    return _stack(solo(sp, b))

                def bwd_solo(sp, b):
                    gp = jax.grad(solo)(sp, b)
                    return _stack(gp)

                self._fwd_fns.append(jax.jit(jax.shard_map(
                    fwd_solo, mesh=mesh, in_specs=(P(), d), out_specs=d,
                    check_vma=False)))
                self._bwd_fns.append(jax.jit(jax.shard_map(
                    bwd_solo, mesh=mesh, in_specs=(P(), d), out_specs=d,
                    check_vma=False)))
            elif s == 0:
                def fwd_first(sp, b):
                    h, aux = first(sp, b)
                    return h, _stack(aux)

                if self.cfg.tie_embeddings:
                    # fold the head cotangent (shipped from the last
                    # stage) into the lookup cotangent per microbatch —
                    # the add autodiff performs for the shared tied leaf,
                    # BEFORE accumulation, so the association matches
                    def bwd_first(sp, b, gy, gemb):
                        _, vjp = jax.vjp(lambda sp_: first(sp_, b), sp)
                        (gp,) = vjp((gy, cot_aux))
                        gp = dict(gp)
                        gp["embed"] = gp["embed"] + _unstack(gemb)
                        return _stack(gp)

                    self._bwd_fns.append(jax.jit(jax.shard_map(
                        bwd_first, mesh=mesh, in_specs=(P(), d, d, d),
                        out_specs=d, check_vma=False)))
                else:
                    def bwd_first(sp, b, gy):
                        _, vjp = jax.vjp(lambda sp_: first(sp_, b), sp)
                        (gp,) = vjp((gy, cot_aux))
                        return _stack(gp)

                    self._bwd_fns.append(jax.jit(jax.shard_map(
                        bwd_first, mesh=mesh, in_specs=(P(), d, d),
                        out_specs=d, check_vma=False)))
                self._fwd_fns.append(jax.jit(jax.shard_map(
                    fwd_first, mesh=mesh, in_specs=(P(), d),
                    out_specs=(d, d), check_vma=False)))
            elif s < p - 1:
                def fwd_mid(sp, h, aux):
                    h, aux = mid(sp, h, _unstack(aux))
                    return h, _stack(aux)

                def bwd_mid(sp, h, gy):
                    _, vjp = jax.vjp(
                        lambda sp_, h_: mid(sp_, h_, jnp.float32(0.0)),
                        sp, h)
                    gp, gh = vjp((gy, cot_aux))
                    return _stack(gp), gh

                self._fwd_fns.append(jax.jit(jax.shard_map(
                    fwd_mid, mesh=mesh, in_specs=(P(), d, d),
                    out_specs=(d, d), check_vma=False)))
                self._bwd_fns.append(jax.jit(jax.shard_map(
                    bwd_mid, mesh=mesh, in_specs=(P(), d, d),
                    out_specs=(d, d), check_vma=False)))
            else:
                def fwd_last(sp, b, h, aux):
                    return _stack(last(sp, b, h, _unstack(aux)))

                if self.cfg.tie_embeddings:
                    def bwd_last(sp, b, h):
                        # aux_in enters the loss additively (x alpha): it
                        # never touches this stage's cotangents, so
                        # backward runs with aux_in = 0, bitwise identical
                        gp, gh = jax.grad(
                            lambda sp_, h_: last(sp_, b, h_,
                                                 jnp.float32(0.0)),
                            argnums=(0, 1))(sp, h)
                        gp = dict(gp)
                        gemb = gp.pop("embed_out")
                        return _stack(gp), _stack(gemb), gh

                    self._bwd_fns.append(jax.jit(jax.shard_map(
                        bwd_last, mesh=mesh, in_specs=(P(), d, d),
                        out_specs=(d, d, d), check_vma=False)))
                else:
                    def bwd_last(sp, b, h):
                        gp, gh = jax.grad(
                            lambda sp_, h_: last(sp_, b, h_,
                                                 jnp.float32(0.0)),
                            argnums=(0, 1))(sp, h)
                        return _stack(gp), gh

                    self._bwd_fns.append(jax.jit(jax.shard_map(
                        bwd_last, mesh=mesh, in_specs=(P(), d, d),
                        out_specs=(d, d), check_vma=False)))
                self._fwd_fns.append(jax.jit(jax.shard_map(
                    fwd_last, mesh=mesh, in_specs=(P(), d, d, d),
                    out_specs=d, check_vma=False)))

        # per-stage gradient sync: divide the microbatch sum by m (exactly
        # build_grad_fn's gsum / n), compress, then the strategy's data-
        # axis mean — the baseline's sync_phase over this stage's mesh
        self._sync_fns = []
        for s in range(p):
            def sync_one(gstack):
                g = _unstack(gstack)
                g = jax.tree_util.tree_map(lambda x: x / m, g)
                g, _ = comp.apply(g, None)
                return strat.sync(g, "data", dp)

            self._sync_fns.append(jax.jit(jax.shard_map(
                sync_one, mesh=self.stage_meshes[s],
                in_specs=(P("data"),), out_specs=P(), check_vma=False)))

        # fp32 accumulators: zeros + g first (build_grad_fn starts from
        # zeros, and 0 + g is the baseline's first scan add), then g + g'
        self._acc_first = jax.jit(
            lambda g: jax.tree_util.tree_map(
                lambda x: jnp.zeros_like(x) + x, g))
        self._acc_add = jax.jit(
            lambda a, g: jax.tree_util.tree_map(jnp.add, a, g))
        self._loss_add = jax.jit(jnp.add)
        self._update_fn = jax.jit(
            lambda prm, st, g: opt_lib.apply_updates(self.opt, prm, g, st),
            donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    def init(self, seed: int = 0):
        """Replicated fp32 master params + opt state on the global mesh."""
        params = materialize(M.model_specs(self.cfg),
                             jax.random.PRNGKey(seed))
        state = opt_lib.init_state(self.opt, params)
        rep = NamedSharding(self.mesh, P())
        params = jax.device_put(params, rep)
        state = jax.device_put(state, rep)
        self._grad_bytes = 4.0 * sum(
            int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(params))
        return params, state

    def _stage_views(self, params):
        """Per-stage replicated views of the master params — the Fig.-1
        'parameter refresh' onto each stage's devices."""
        host = jax.tree_util.tree_map(np.asarray, params)
        return [
            jax.device_put(_stage_params(host, self.cfg, self.stage_cut, s),
                           NamedSharding(self.stage_meshes[s], P()))
            for s in range(self.pipe)
        ]

    def _shard_batch(self, batch, j: int):
        """Microbatch ``j``'s rows, dp-major: data shard ``d`` gets exactly
        the rows the baseline's device ``d`` consumes in accumulation-scan
        step ``j``."""
        m, dp = self.n_microbatch, self.dp
        out = {}
        for k, v in batch.items():
            arr = np.asarray(v)
            rows = arr.shape[0] // (dp * m)
            mb = arr.reshape((dp, m, rows) + arr.shape[1:])[:, j]
            out[k] = mb.reshape((dp * rows,) + arr.shape[1:])
        return out

    def _to_stage(self, x, s: int):
        """Move an array onto stage ``s``'s mesh, sharded over its data
        axis (host round-trip: bit-exact, device-set agnostic)."""
        return jax.device_put(np.asarray(x),
                              NamedSharding(self.stage_meshes[s], P("data")))

    def _reassemble(self, stage_grads):
        """Full gradient tree from the per-stage synced shards (leaf set
        and order identical to the baseline's grads, so the global-norm
        clip sees the same reduction)."""
        cfg, p = self.cfg, self.pipe
        rep = NamedSharding(self.mesh, P())
        gs = [jax.device_put(jax.tree_util.tree_map(np.asarray, g), rep)
              for g in stage_grads]
        full: Dict[str, Any] = {
            "slots": jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=0),
                *[g["slots"] for g in gs])
        }
        g0, gl = gs[0], gs[-1]
        # tied head cotangents were already folded into stage 0's embed
        # grad per microbatch (see bwd_first), so "embed" is complete here
        full["embed"] = g0["embed"]
        if not cfg.tie_embeddings and "lm_head" in gl:
            full["lm_head"] = gl["lm_head"]
        if cfg.first_k_dense:
            full["prelude"] = g0["prelude"]
        full["final_norm"] = gl["final_norm"]
        return full

    # ------------------------------------------------------------------
    def step_fn(self):
        """Loop-compatible step: one 1F1B round over ``m`` microbatches,
        per-stage sync, one replicated optimizer update."""
        p, m = self.pipe, self.n_microbatch
        order = schedule_1f1b(p, m)
        tr = self.tracer

        def step(params, opt_state, batch):
            with tr.span("param_refresh"):
                views = self._stage_views(params)
            micro = [self._shard_batch(batch, j) for j in range(m)]
            fwd_t = [[0.0] * m for _ in range(p)]
            bwd_t = [[0.0] * m for _ in range(p)]
            h_save: Dict[Tuple[int, int], Any] = {}   # stage input acts
            g_save: Dict[Tuple[int, int], Any] = {}   # pending h cotangents
            acc: List[Any] = [None] * p
            lsum = None
            with tr.span("compute"):
                for (s, kind, j) in order:
                    if kind == "fwd":
                        with tr.span("pipe_fwd", stage=s, micro=j) as sp:
                            out = self._run_fwd(s, j, views, micro, h_save)
                            jax.block_until_ready(out)
                        fwd_t[s][j] = sp.elapsed_s
                        if s == p - 1:
                            lsum = (out if lsum is None
                                    else self._loss_add(lsum, out))
                    else:
                        with tr.span("pipe_bwd", stage=s, micro=j) as sp:
                            gp = self._run_bwd(s, j, views, micro, h_save,
                                               g_save)
                            acc[s] = (self._acc_first(gp) if acc[s] is None
                                      else self._acc_add(acc[s], gp))
                            jax.block_until_ready(
                                jax.tree_util.tree_leaves(acc[s])[0])
                        bwd_t[s][j] = sp.elapsed_s
            with tr.span("dist_update") as sp_s:
                synced = []
                for s in range(p):
                    with tr.span("pipe_sync", stage=s):
                        g = self._sync_fns[s](acc[s])
                        jax.block_until_ready(
                            jax.tree_util.tree_leaves(g)[0])
                    synced.append(g)
            with tr.span("param_update") as sp_u:
                grads = self._reassemble(synced)
                params, opt_state, gnorm = self._update_fn(
                    params, opt_state, grads)
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(params)[0])
            self._fwd_obs.append(fwd_t)
            self._bwd_obs.append(bwd_t)
            self._publish(fwd_t, bwd_t, sp_s.elapsed_s, sp_u.elapsed_s)
            losses = jnp.asarray(lsum).reshape(-1) / m
            metrics = {"loss": jnp.mean(losses), "grad_norm": gnorm,
                       "t_comm": sp_s.elapsed_s, "t_update": sp_u.elapsed_s}
            return params, opt_state, metrics

        return step

    def _run_fwd(self, s, j, views, micro, h_save):
        p = self.pipe
        if p == 1:
            b = {k: self._to_stage(v, 0) for k, v in micro[j].items()}
            h_save[(0, j)] = b
            return self._fwd_fns[0](views[0], b)
        if s == 0:
            b = {"tokens": self._to_stage(micro[j]["tokens"], 0)}
            h_save[(0, j)] = b
            h, aux = self._fwd_fns[0](views[0], b)
            h_save[("out", 0, j)] = (h, aux)
            return h
        h_prev, aux_prev = h_save.pop(("out", s - 1, j))
        h_in = self._to_stage(h_prev, s)
        aux_in = self._to_stage(aux_prev, s)
        if s == self.pipe - 1:
            b = {"labels": self._to_stage(micro[j]["labels"], s)}
            h_save[(s, j)] = (b, h_in)
            return self._fwd_fns[s](views[s], b, h_in, aux_in)
        h_save[(s, j)] = h_in
        h, aux = self._fwd_fns[s](views[s], h_in, aux_in)
        h_save[("out", s, j)] = (h, aux)
        return h

    def _run_bwd(self, s, j, views, micro, h_save, g_save):
        p = self.pipe
        if p == 1:
            b = h_save.pop((0, j))
            return self._bwd_fns[0](views[0], b)
        if s == p - 1:
            b, h_in = h_save.pop((s, j))
            if self.cfg.tie_embeddings:
                gp, gemb, gh = self._bwd_fns[s](views[s], b, h_in)
                g_save[("emb", j)] = gemb
            else:
                gp, gh = self._bwd_fns[s](views[s], b, h_in)
            g_save[(s - 1, j)] = gh
            return gp
        gy = self._to_stage(g_save.pop((s, j)), s)
        if s == 0:
            b = h_save.pop((0, j))
            if self.cfg.tie_embeddings:
                gemb = self._to_stage(g_save.pop(("emb", j)), 0)
                return self._bwd_fns[0](views[0], b, gy, gemb)
            return self._bwd_fns[0](views[0], b, gy)
        h_in = h_save.pop((s, j))
        gp, gh = self._bwd_fns[s](views[s], h_in, gy)
        g_save[(s - 1, j)] = gh
        return gp

    def _publish(self, fwd_t, bwd_t, comm_s, upd_s):
        m = self.metrics
        busy = sum(sum(row) for row in fwd_t) + sum(sum(r) for r in bwd_t)
        m.inc("train/steps")
        m.observe("train/compute_s", busy)
        m.observe("train/dist_update_s", comm_s)
        m.observe("train/param_update_s", upd_s)
        m.observe("train/step_s", busy + comm_s + upd_s)

    # ------------------------------------------------------------------
    def train(self, *, batch: int, seq: int, steps: int, seed: int = 0,
              log_every: int = 10, params=None, opt_state=None,
              ckpt_dir: Optional[str] = None,
              ckpt_every: int = 0) -> loop_lib.TrainResult:
        rows = self.dp * self.n_microbatch
        if batch % rows:
            raise ValueError(
                f"batch {batch} not divisible by dp*n_microbatch={rows} "
                "(equal microbatch shards are required for exact means)")
        self._fwd_obs, self._bwd_obs = [], []
        if params is None or opt_state is None:
            params, opt_state = self.init(seed)
        elif self._grad_bytes == 0:
            self._grad_bytes = 4.0 * sum(
                int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(params))
        # batch_sharding=None: the loader hands the step host batches and
        # the 1F1B orchestration owns every h2d placement
        res = loop_lib.train(
            self.cfg, self.run, self.opt, batch=batch, seq=seq, steps=steps,
            seed=seed, log_every=log_every, params=params,
            opt_state=opt_state, step_fn=self.step_fn(),
            batch_sharding=None, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
            tracer=self.tracer)
        self._times = res.step_times
        return res

    # ------------------------------------------------------------------
    def report(self) -> SyncReport:
        """Session-compatible sync view: each stage worker syncs a 1/p
        parameter shard over its own dp-wide data axis."""
        steady = self._times[2:] or self._times
        comm = (float(np.mean([t.dist_update for t in steady]))
                if steady else 0.0)
        compute = (float(np.mean([t.compute for t in steady]))
                   if steady else 0.0)
        upd = (float(np.mean([t.param_update for t in steady]))
               if steady else 0.0)
        s_p = self._grad_bytes / self.pipe
        wire_payload = self.compressor.wire_bytes(s_p)
        predicted = self.strategy.predicted_comm_time(
            wire_payload, self.dp, self.link_bw)
        r_o = (float(np.mean([t.r_o() for t in steady])) if steady else 0.0)
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
                if self.strategy.hierarchical else None))

    def pipeline_report(self) -> PipelineReport:
        """Replay the steady-state measured op durations through the 1F1B
        DAG and set the resulting bubble against the analytic model and
        the serial reference schedule."""
        p, m = self.pipe, self.n_microbatch
        steady_f = self._fwd_obs[2:] or self._fwd_obs
        steady_b = self._bwd_obs[2:] or self._bwd_obs
        if not steady_f:
            raise RuntimeError("pipeline_report needs at least one "
                               "measured step; run train() first")
        # best-of over steady steps, per op: host noise only inflates
        fwd = tuple(tuple(min(step[s][j] for step in steady_f)
                          for j in range(m)) for s in range(p))
        bwd = tuple(tuple(min(step[s][j] for step in steady_b)
                          for j in range(m)) for s in range(p))
        sim = simulate_1f1b(fwd, bwd)
        serial = simulate_serial(fwd, bwd)
        model = pipeline_bubble(p, m)
        self.metrics.set_gauge("train/pipe", p)
        self.metrics.set_gauge("train/n_microbatch", m)
        self.metrics.set_gauge("train/bubble_measured", sim.bubble_fraction)
        self.metrics.set_gauge("train/bubble_model", model)
        return PipelineReport(
            pipe=p, n_microbatch=m, stage_cut=self.stage_cut,
            bubble_measured=sim.bubble_fraction, bubble_model=model,
            bubble_serial=serial.bubble_fraction,
            makespan_s=sim.makespan, stage_busy_s=sim.stage_busy,
            fwd_times_s=fwd, bwd_times_s=bwd)

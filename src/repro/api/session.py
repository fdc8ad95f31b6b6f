"""Session — resolve a :class:`JobSpec` through the planner and execute it.

One object owns the whole Fig.-1 procedure: the planner sizes the job
(microbatch, algorithms, sync schedule — Lemmas 3.1/3.2), then ``train`` /
``serve`` / ``bench`` run it and ``dryrun`` / ``plan`` stop at the
prediction.  Every method returns the same :class:`repro.api.Report`, so a
planner prediction and a measured run are directly comparable artifacts.

The planner always runs on the FULL architecture and the spec's production
shape/mesh — the paper's procedure sizes the real job; with
``spec.reduced`` the *execution* uses the smoke-scale family member.

``tune`` closes the loop on measurements (``repro.core.autotune``): it
times kernel variants, calibrates the hardware constants, runs the paper's
minibatch procedure, and re-plans — a session built with ``calibration=``
(or a ``Session.sweep(calibration=...)`` campaign) prices every prediction
on those measured constants.  See ``docs/tuning_guide.md``.
"""
from __future__ import annotations

import itertools
import math
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple, TYPE_CHECKING

if TYPE_CHECKING:  # import-light: autotune pulls kernels/jax lazily anyway
    from repro.core.autotune import Calibration, TuneResult

import numpy as np

from repro.api.campaign import Campaign
from repro.api.report import Report
from repro.api.spec import JobSpec
from repro.obs import MetricsRegistry, Tracer
from repro.obs.trace import monotonic
from repro.configs.base import ModelConfig, get_config, get_shape
from repro.core import amdahl, memory_model as mm, ps as ps_lib
from repro.core.hardware import (ClusterSpec, MeshSpec, MULTI_POD, SINGLE_POD,
                                 get_cluster)
from repro.core.planner import (Plan, estimate_step_time, plan as plan_fn,
                                r_o_from_terms)

# Lemma 3.1 efficiency/speedup are reported for these device counts (the
# paper's Fig. 4 sweep)
LEMMA31_G = (2, 4, 8, 16)


class Session:
    """Execute one JobSpec; every method returns a validated Report."""

    def __init__(self, spec: JobSpec, *, config: Optional[ModelConfig] = None,
                 calibration: Optional["Calibration"] = None):
        self.spec = spec
        self.cfg_full = get_config(spec.arch)
        self.cfg = config if config is not None else (
            self.cfg_full.reduced() if spec.reduced else self.cfg_full)
        if (spec.pipe > 1 and config is None and spec.reduced):
            # reduced() keeps one layer cycle — nothing to cut into stages.
            # Deepen to two cycles per stage: the minimum that both cuts
            # and keeps every stage's scan a real loop (trip-count-1 scans
            # get inlined/re-fused by XLA, breaking bit-identity with the
            # single-stage trainer — see repro.distributed.pipeline).
            from repro.models.model import main_cycles

            need = 2 * spec.pipe
            if main_cycles(self.cfg) < need:
                self.cfg = self.cfg.replace(
                    num_layers=self.cfg.first_k_dense
                    + need * len(self.cfg.pattern))
        self.shape = get_shape(spec.shape)
        if spec.topology:
            # a named cluster pins the mesh geometry to its chip count
            # (dp = chips, tp = 1: sweeps compare gradient-sync topologies)
            self.cluster: Optional[ClusterSpec] = get_cluster(spec.topology)
            self.mesh_spec = MeshSpec.from_cluster(self.cluster)
        else:
            self.mesh_spec = SINGLE_POD if spec.mesh == "single" else MULTI_POD
            self.cluster = self.mesh_spec.topology
        # a Calibration (repro.core.autotune) re-prices the mesh on measured
        # constants: every plan/prediction this session emits uses them
        self.calibration = calibration
        if calibration is not None:
            self.mesh_spec = calibration.apply(self.mesh_spec)
            self.cluster = self.mesh_spec.topology
        self._config_override = config is not None
        self._plan: Optional[Plan] = None
        self._tuned: Optional["TuneResult"] = None
        # telemetry of the last measured run (repro.obs) — set by
        # train/bench/serve/tune, inspectable after the Report comes back
        self.last_tracer: Optional[Tracer] = None
        self.last_metrics: Optional[MetricsRegistry] = None
        # what the last run produced: final parameters (train/bench) and
        # every generated token stream by request id (serve)
        self.last_params: Any = None
        self.last_tokens: Dict[int, np.ndarray] = {}

    # ------------------------------------------------------------------
    def _make_obs(self) -> Tuple[Tracer, MetricsRegistry]:
        """Fresh telemetry for one measured run.  The tracer is always
        enabled inside a Session: span wall clocks ARE the measurements
        (StepTimes / GenResult), and the ``metrics/v1`` section every
        measured Report must carry is rendered from the registry."""
        tracer = Tracer(enabled=True)
        metrics = MetricsRegistry()
        self.last_tracer, self.last_metrics = tracer, metrics
        return tracer, metrics

    def _save_trace(self, kind: str, tracer: Tracer) -> Dict[str, Any]:
        """Chrome-trace export to ``spec.trace_dir`` (when set); returns the
        meta fragment recording where it landed."""
        if not self.spec.trace_dir:
            return {}
        path = Path(self.spec.trace_dir) / f"trace_{kind}.json"
        tracer.save(path)
        return {"trace_file": str(path), "trace_events": len(tracer)}

    # ------------------------------------------------------------------
    def _overlap_kwargs(self) -> Dict[str, Any]:
        """Overlap knobs every planner/pricing call shares: the spec's
        ``sync_overlap``/``bucket_mb``, with the hideable window derated to
        the *measured* overlap fraction when a calibration carries one."""
        eff = 1.0
        if self.calibration is not None \
                and getattr(self.calibration, "bucket_mb", 0.0) > 0:
            # bucket_mb > 0 marks a *ran* overlap sweep; its fraction is
            # the measurement even when it measured 0.0 (no hiding
            # achieved) — do not fall back to the ideal window then
            eff = self.calibration.overlap_fraction
        return dict(sync_overlap=self.spec.sync_overlap,
                    bucket_mb=self.spec.bucket_mb, overlap_efficiency=eff)

    @property
    def resolved_plan(self) -> Plan:
        if self._plan is None:
            self._plan = plan_fn(self.cfg_full, self.shape, self.mesh_spec,
                                 pipe=self.spec.pipe or None,
                                 n_microbatch=self.spec.n_microbatch,
                                 staleness=self.spec.staleness,
                                 backup_workers=self.spec.backup_workers,
                                 **self._overlap_kwargs())
        return self._plan

    @property
    def tuned(self) -> "TuneResult":
        """The autotuner's result for this spec (runs the microbenchmarks +
        calibration on first access; cached for the session)."""
        if self._tuned is None:
            from repro.core import autotune

            spec = self.spec
            tracer, metrics = self._make_obs()
            self._tuned = autotune.autotune(
                self.cfg, self.cfg_full, self.shape, self.mesh_spec,
                batch=spec.batch, seq=spec.seq, steps=spec.tune_steps,
                dp=spec.dp, seed=spec.seed, cache_path=spec.tune_cache,
                tracer=tracer, metrics=metrics)
        return self._tuned

    def build_run_opt(self):
        """RunConfig/OptConfig for this spec — planner-adopted knobs when
        ``use_planner`` (exactly what ``launch/train.py --plan`` did), then
        measured-knob overrides (attention algorithm, feasible microbatch)
        when ``spec.tune``."""
        import dataclasses as _dc

        from repro.models.blocks import RunConfig
        from repro.optim.adamw import OptConfig

        spec = self.spec
        warmup = max(spec.steps // 10, 1)
        if spec.use_planner:
            p = self.resolved_plan
            run = RunConfig(
                attn_impl="dense" if p.attn_impl == "dense" else "auto",
                remat=p.remat, microbatch=min(p.microbatch, spec.batch))
            opt = OptConfig(kind=p.opt_kind, lr=spec.lr, warmup_steps=warmup,
                            total_steps=spec.steps)
        else:
            run = RunConfig(attn_impl="auto", remat="block")
            opt = OptConfig(lr=spec.lr, warmup_steps=warmup,
                            total_steps=spec.steps)
        if spec.tune:
            t = self.tuned
            # chosen_microbatch == 0 means the production job fits at no
            # microbatch — fall back to the most frugal setting (1), never
            # to 0 (RunConfig's "no accumulation", the *maximal* footprint)
            run = _dc.replace(
                run, attn_impl=t.attn_impl(),
                microbatch=max(min(t.chosen_microbatch, spec.batch), 1))
        return run, opt

    # ------------------------------------------------------------------
    # Predictive kinds
    # ------------------------------------------------------------------
    def plan(self) -> Report:
        """Resolve the planner only: spec + plan + Lemma predictions."""
        return self._report("plan", {}, self._predicted())

    def dryrun(self) -> Report:
        """Analytic dry run — plan plus the step-time roofline terms and
        the memory-model breakdown, no compile and no training.  (The
        heavyweight lower+compile sweep stays in ``repro.launch.dryrun``.)"""
        p = self.resolved_plan
        pred = self._predicted()
        dp, tp = self.mesh_spec.dp, self.mesh_spec.tp
        if self.shape.kind in ("train", "prefill"):
            mem = mm.train_memory(
                self.cfg_full, self.shape, dp=dp, tp=tp, fsdp=p.fsdp,
                microbatch=p.microbatch, attn_impl=p.attn_impl, remat=p.remat,
                seq_parallel=p.seq_parallel, opt_kind=p.opt_kind)
        else:
            mem = mm.decode_memory(self.cfg_full, self.shape, dp=dp, tp=tp,
                                   fsdp=p.fsdp)
        pred["memory_bytes"] = {
            k: float(getattr(mem, k))
            for k in ("params", "grads", "opt_state", "activations",
                      "logits", "kv_cache")}
        pred["memory_bytes"]["total"] = float(mem.total)
        pred["fits"] = p.fits
        return self._report("dryrun", {}, pred)

    # ------------------------------------------------------------------
    # Measured kinds
    # ------------------------------------------------------------------
    def tune(self) -> Report:
        """Run the closed-loop autotuner (repro.core.autotune): time the
        kernel algorithm variants, measure short trainer steps, calibrate
        the cluster constants, run the paper's minibatch/algorithm
        procedure, and re-plan on the measured numbers.  Returns a Report
        of kind ``tune`` whose ``measured["tuning"]`` section carries the
        ``repro.api/tuning/v1`` schema."""
        res = self.tuned
        measured: Dict[str, Any] = dict(res.measured)
        measured["tuning"] = res.section()
        if self.last_metrics is not None:
            measured["metrics"] = self.last_metrics.section()
        meta_extra = (self._save_trace("tune", self.last_tracer)
                      if self.last_tracer is not None else {})
        return self._report("tune", measured, self._predicted(),
                            meta_extra=meta_extra)

    def train(self) -> Report:
        """Run the training loop (single-process GSPMD, or the explicit
        data-parallel trainer when ``spec.dp > 0``)."""
        return self._run_train("train")

    def bench(self) -> Report:
        """A measured run reported as a benchmark artifact: identical
        execution to :meth:`train`, kind ``bench`` (no logging by default
        conventions is up to the spec)."""
        return self._run_train("bench")

    def _run_train(self, kind: str) -> Report:
        spec = self.spec
        run, opt = self.build_run_opt()  # may touch self.tuned (own obs)
        tracer, metrics = self._make_obs()
        loop_kw = dict(batch=spec.batch, seq=spec.seq, steps=spec.steps,
                       seed=spec.seed, log_every=spec.log_every,
                       ckpt_dir=spec.ckpt_dir or None,
                       ckpt_every=spec.ckpt_every)
        sync_rep = pipe_rep = async_rep = None
        if spec.pipe > 1:
            import dataclasses as _dc

            import jax

            from repro.distributed import PipelineTrainer
            from repro.launch.device import take_devices

            world = spec.dp or jax.device_count()
            devs = take_devices(world, f"pipe={spec.pipe}")
            # the 1F1B schedule owns microbatching — the planner's
            # accumulation knob must not nest another scan inside a stage
            run = _dc.replace(run, microbatch=0)
            strategy = (self.resolved_plan.resolve_sync()
                        if spec.sync == "auto" else spec.sync)
            trainer = PipelineTrainer(
                self.cfg, run, opt, pipe=spec.pipe,
                n_microbatch=spec.n_microbatch, strategy=strategy,
                compression=spec.compress, devices=devs,
                tracer=tracer, metrics=metrics)
            res = trainer.train(**loop_kw)
            sync_rep = trainer.report()
            pipe_rep = trainer.pipeline_report()
        elif spec.dp and (spec.staleness or spec.backup_workers):
            from repro.distributed import AsyncPSTrainer
            from repro.launch.device import take_devices

            # bounded staleness is a parameter-server schedule by
            # construction; "auto" resolves to it rather than the planner's
            # all-reduce pick
            strategy = ("parameter_server" if spec.sync == "auto"
                        else spec.sync)
            trainer = AsyncPSTrainer(
                self.cfg, run, opt, staleness=spec.staleness,
                backup_workers=spec.backup_workers, strategy=strategy,
                compression=spec.compress,
                devices=take_devices(spec.dp, f"dp={spec.dp}"),
                tracer=tracer, metrics=metrics)
            res = trainer.train(**loop_kw)
            sync_rep = trainer.report()
            async_rep = trainer.async_report()
        elif spec.dp:
            from repro.core.ps import DEFAULT_BUCKET_MB
            from repro.distributed import DataParallelTrainer
            from repro.launch.device import take_devices

            kw = dict(compression=spec.compress,
                      devices=take_devices(spec.dp, f"dp={spec.dp}"),
                      topology=self.cluster,
                      sync_overlap=spec.sync_overlap,
                      bucket_mb=spec.bucket_mb or DEFAULT_BUCKET_MB,
                      tracer=tracer, metrics=metrics)
            if spec.sync == "auto":
                trainer = DataParallelTrainer.from_plan(
                    self.resolved_plan, self.cfg, run, opt, **kw)
            else:
                trainer = DataParallelTrainer(self.cfg, run, opt,
                                              strategy=spec.sync, **kw)
            res = trainer.train(**loop_kw)
            sync_rep = trainer.report()
        else:
            from repro.train.loop import train as train_loop

            res = train_loop(self.cfg, run, opt, tracer=tracer, **loop_kw)
            # the single-process loop has no phase-publishing step_fn, so
            # the session publishes its StepTimes into the registry
            for t in res.step_times:
                metrics.inc("train/steps")
                metrics.observe("train/compute_s", t.compute)
                metrics.observe("train/dist_update_s", t.dist_update)
                metrics.observe("train/param_update_s", t.param_update)
                metrics.observe("train/step_s",
                                t.compute + t.dist_update + t.param_update)
        self.last_params = res.params
        measured = res.summary()
        metrics.set_gauge("train/tokens_per_s", measured["tokens_per_s"])
        metrics.set_gauge("train/r_o", measured["r_o"])
        if sync_rep is not None:
            measured["sync"] = sync_rep.as_dict()
        if pipe_rep is not None:
            measured["pipeline"] = pipe_rep.as_dict()
        if async_rep is not None:
            measured["async_ps"] = async_rep.as_dict()
        if spec.tune:  # the run adopted tuned knobs: record what they were
            measured["tuning"] = self.tuned.section()
        measured["metrics"] = metrics.section()
        predicted = self._predicted(measured_r_o=measured["r_o"])
        return self._report(kind, measured, predicted,
                            meta_extra=self._save_trace(kind, tracer))

    def serve(self) -> Report:
        """Batched generation, measured end to end.  ``spec.serve_mode``
        picks the runtime: ``continuous`` (in-flight batching over the
        paged KV cache — ``repro.serve.continuous``) or ``static`` (the
        FIFO Engine/BatchScheduler).  Both emit the same measured keys
        plus the ``repro.api/serving/v1`` section, so the two runtimes
        are directly comparable artifacts."""
        if self.spec.serve_mode == "continuous":
            return self._serve_continuous()
        return self._serve_static()

    def serve_workload(self):
        """The seeded synthetic workload both serve modes share: ragged
        prompt lengths in [8, 48) and ragged ``n_new`` in
        [max(1, n_new/4), n_new] — raggedness is what separates the two
        schedulers, so it is the spec, not an option."""
        spec, cfg = self.spec, self.cfg
        rng = np.random.default_rng(spec.seed)
        k = cfg.num_codebooks
        reqs = []
        for _ in range(spec.requests):
            n = int(rng.integers(8, 48))
            n_new = int(rng.integers(max(1, spec.n_new // 4),
                                     spec.n_new + 1))
            shape = (n, k) if k else (n,)
            prompt = rng.integers(0, cfg.vocab_size, shape).astype(np.int32)
            reqs.append((prompt, n, n_new))
        return reqs

    def kv_pool_blocks(self) -> int:
        """KV pool size: ``spec.max_kv_blocks`` when pinned, else the
        Eq. 5 analogue (``memory_model.max_kv_blocks`` on this mesh's
        chip, calibration-overlaid) capped at this run's working set
        (``max_batch`` full-length rows — reduced smoke configs would
        otherwise derive pools of millions of blocks)."""
        spec = self.spec
        if spec.max_kv_blocks:
            return spec.max_kv_blocks
        cap = spec.max_batch * math.ceil(spec.s_max / spec.kv_block)
        derived = mm.max_kv_blocks(self.cfg, self.mesh_spec.chip.hbm_bytes,
                                   block_size=spec.kv_block,
                                   max_batch=spec.max_batch)
        return min(derived, cap) if derived > 0 else cap

    @staticmethod
    def _latency_stats(latencies) -> Dict[str, float]:
        xs = np.asarray(sorted(latencies), float)
        return {"p50": float(np.percentile(xs, 50)),
                "p95": float(np.percentile(xs, 95)),
                "p99": float(np.percentile(xs, 99)),
                "mean": float(xs.mean()), "max": float(xs.max())}

    def _serving_section(self, *, mode: str, kv_stats: Dict[str, Any],
                         latencies, stats: Dict[str, Any], wall: float,
                         n_tokens: int, n_news, lengths,
                         metrics) -> Dict[str, Any]:
        """The ``repro.api/serving/v1`` block: measured distribution +
        the inference replica lemma's prediction next to it."""
        from repro.api.report import SERVING_SCHEMA_ID

        spec = self.spec
        lat = self._latency_stats(latencies)
        tps = n_tokens / max(wall, 1e-9)
        # measured per-step decode time (the lemma's t_step, observed)
        dh = metrics.histogram("serve/decode_s")
        t_step_meas = dh.sum / dh.count if dh.count else 0.0
        ph = metrics.histogram("serve/prefill_s")
        t_pre_meas = ph.sum / ph.count if ph.count else 0.0
        # predicted t_step from the cost model: decode is HBM-bound —
        # stream bf16 weights + the resident KV once per step (priced on
        # this session's chip, calibration-overlaid when present)
        chip = self.mesh_spec.chip
        param_bytes = 2.0 * mm.n_params(self.cfg)
        kv_bytes = spec.max_batch * spec.s_max * mm.kv_token_bytes(self.cfg)
        t_step_pred = ps_lib.decode_step_time(param_bytes, kv_bytes,
                                              chip.hbm_bw)
        mean_prompt = float(np.mean(list(lengths)))
        mean_n_new = float(np.mean(list(n_news)))
        # prefill prediction: per-token memory-bound like decode (crude
        # but unit-consistent; the measured column sits right next to it)
        t_pre_pred = mean_prompt * t_step_pred / max(spec.max_batch, 1)
        slo_s = spec.slo_ms / 1e3 if spec.slo_ms else 2.0 * lat["mean"]
        t_svc_pred = ps_lib.service_time(t_pre_pred, int(round(mean_n_new)),
                                         t_step_pred)
        # offered load for the lemma: spec-pinned, else 2x one replica
        rate = spec.arrival_rate or 2.0 * spec.max_batch / max(t_svc_pred,
                                                               1e-9)
        predicted = ps_lib.serve_replica_plan(
            arrival_rate=rate, t_prefill_s=t_pre_pred,
            t_step_s=t_step_pred, n_new=int(round(mean_n_new)),
            batch=spec.max_batch, slo_s=slo_s)
        return {
            "schema": SERVING_SCHEMA_ID,
            "mode": mode,
            "scheduler": {
                "max_batch": spec.max_batch,
                "requests": spec.requests,
                "arrival": spec.arrival,
                "prefill_chunk": spec.prefill_chunk,
            },
            "kv_cache": kv_stats,
            "latency_s": lat,
            "throughput": {
                "tokens_per_s": tps,
                "decode_token_steps": int(stats.get("decode_token_steps", 0)),
                "wasted_decode_steps": int(stats.get("wasted_decode_steps", 0)),
                "engine_steps": int(stats.get("engine_steps", 0)),
                "delivered_tokens": int(stats.get("delivered_tokens",
                                                  n_tokens)),
            },
            "slo": {"slo_s": slo_s, "attained": bool(lat["p99"] <= slo_s)},
            "replica_lemma": {
                "predicted": predicted,
                "measured": {
                    "t_step_s": t_step_meas,
                    "t_prefill_s": t_pre_meas,
                    "t_service_s": lat["mean"],
                    "tokens_per_s": tps,
                },
            },
        }

    @staticmethod
    def _per_request(results, latencies) -> List[Dict[str, Any]]:
        out = []
        for rid in sorted(results):
            toks = np.asarray(results[rid])
            head = toks[:8].tolist() if toks.ndim == 1 else toks[:2].tolist()
            out.append({"rid": rid, "tokens": int(toks.shape[0]),
                        "head": head,
                        "latency_s": float(latencies.get(rid, 0.0))})
        return out

    _STATIC_KV_STATS = {"block_size": 0, "n_blocks": 0, "used_blocks": 0,
                        "peak_blocks": 0, "peak_occupancy": 0.0,
                        "shared_block_hits": 0, "block_bytes": 0.0}

    def _serve_static(self) -> Report:
        """The FIFO Engine/BatchScheduler runtime (linear cache)."""
        from repro.models.blocks import RunConfig
        from repro.serve.engine import BatchScheduler, Engine

        spec, cfg = self.spec, self.cfg
        run = RunConfig(attn_impl="dense", remat="none")
        tracer, metrics = self._make_obs()
        eng = Engine(cfg, run, s_max=spec.s_max, seed=spec.seed,
                     tracer=tracer, metrics=metrics)
        sched = BatchScheduler(eng, max_batch=spec.max_batch)
        lengths, n_news = [], []
        for prompt, n, n_new in self.serve_workload():
            sched.submit(prompt, n_new)
            lengths.append(n)
            n_news.append(n_new)
        t0 = monotonic()
        results = sched.run()
        wall = monotonic() - t0
        self.last_tokens = results
        per_request = self._per_request(results, sched.latencies)
        n_tokens = sum(r["tokens"] for r in per_request)
        metrics.set_gauge("serve/wall_s", wall)
        metrics.set_gauge("serve/delivered_tokens_per_s",
                          n_tokens / max(wall, 1e-9))
        serving = self._serving_section(
            mode="static", kv_stats=dict(self._STATIC_KV_STATS),
            latencies=list(sched.latencies.values()), stats=sched.stats,
            wall=wall, n_tokens=n_tokens, n_news=n_news, lengths=lengths,
            metrics=metrics)
        measured = {
            "requests": spec.requests,
            "n_new": spec.n_new,
            "prompt_lengths": lengths,
            "n_tokens": n_tokens,
            "wall_s": wall,
            "tokens_per_s": n_tokens / max(wall, 1e-9),
            "batches": [g.stats() for g in sched.history],
            "per_request": per_request,
            "serving": serving,
            "metrics": metrics.section(),
        }
        return self._report("serve", measured, self._predicted(),
                            meta_extra=self._save_trace("serve", tracer))

    def _serve_continuous(self) -> Report:
        """In-flight batching over the paged KV cache, admission gated by
        the Eq. 5 block bound (``repro.serve.continuous``)."""
        from repro.models.blocks import RunConfig
        from repro.serve.arrivals import make_trace
        from repro.serve.continuous import (ContinuousEngine,
                                            ContinuousScheduler)
        from repro.serve.kvcache import PagedKVCache

        spec, cfg = self.spec, self.cfg
        run = RunConfig(attn_impl="dense", remat="none")
        tracer, metrics = self._make_obs()
        eng = ContinuousEngine(cfg, run, s_max=spec.s_max,
                               max_batch=spec.max_batch,
                               prefill_chunk=spec.prefill_chunk,
                               seed=spec.seed, tracer=tracer,
                               metrics=metrics)
        n_blocks = self.kv_pool_blocks()
        kv = PagedKVCache(cfg, block_size=spec.kv_block, n_blocks=n_blocks,
                          s_max=spec.s_max)
        sched = ContinuousScheduler(eng, kv)
        arrivals = make_trace(spec.arrival, spec.requests, seed=spec.seed)
        lengths, n_news = [], []
        for (prompt, n, n_new), step in zip(self.serve_workload(),
                                            arrivals):
            sched.submit(prompt, n_new, arrival_step=step)
            lengths.append(n)
            n_news.append(n_new)
        t0 = monotonic()
        results = sched.run()
        wall = monotonic() - t0
        self.last_tokens = results
        per_request = self._per_request(results, sched.latencies)
        n_tokens = sum(r["tokens"] for r in per_request)
        metrics.set_gauge("serve/wall_s", wall)
        metrics.set_gauge("serve/delivered_tokens_per_s",
                          n_tokens / max(wall, 1e-9))
        serving = self._serving_section(
            mode="continuous", kv_stats=kv.stats(),
            latencies=list(sched.latencies.values()), stats=sched.stats,
            wall=wall, n_tokens=n_tokens, n_news=n_news, lengths=lengths,
            metrics=metrics)
        measured = {
            "requests": spec.requests,
            "n_new": spec.n_new,
            "prompt_lengths": lengths,
            "n_tokens": n_tokens,
            "wall_s": wall,
            "tokens_per_s": n_tokens / max(wall, 1e-9),
            "per_request": per_request,
            "serving": serving,
            "metrics": metrics.section(),
        }
        return self._report("serve", measured, self._predicted(),
                            meta_extra=self._save_trace("serve", tracer))

    # ------------------------------------------------------------------
    # Campaigns: the paper's guidelines as one queryable sweep
    # ------------------------------------------------------------------
    SWEEP_KINDS = ("plan", "dryrun", "train", "bench", "serve", "tune")

    @classmethod
    def sweep(cls, base: JobSpec, grid: Dict[str, Sequence[Any]], *,
              kind: str = "plan", progress: bool = False,
              calibration: Optional["Calibration"] = None) -> Campaign:
        """Fan the cartesian product of ``grid`` out over ``base`` and run
        one Session method per cell.

        ``grid`` maps JobSpec field names to the values to sweep (arch x
        dp x sync x compress x batch x topology x ...); each cell is
        ``base.replace(**overrides)``.  ``kind`` picks what runs per cell:
        ``plan``/``dryrun`` stay predictive (fast), ``train``/``bench``/
        ``serve`` execute.  Cells whose spec is invalid (e.g. batch not
        divisible by dp) or whose run fails land in ``Campaign.skipped``
        with the error, so one bad cell cannot sink the campaign.

        ``calibration`` (a measured ``repro.core.autotune.Calibration``,
        e.g. ``Session(spec).tuned.calibration``) re-prices every cell on
        measured constants instead of datasheet numbers, so the campaign's
        predictive cells are comparable to wall-clock measurements.

        Note: predictive kinds only differentiate plan-affecting fields
        (``arch``/``shape``/``mesh``/``topology``) — the planner prices the
        production job, so sweeping execution knobs (batch/compress/dp/
        sync) under ``kind="plan"`` yields cells with identical metrics;
        run those grids with ``kind="train"`` to measure them.
        """
        if kind not in cls.SWEEP_KINDS:
            raise ValueError(f"sweep kind must be one of {cls.SWEEP_KINDS}, "
                             f"got {kind!r}")
        if not grid:
            raise ValueError("sweep needs a non-empty grid")
        keys = sorted(grid)
        values = [list(grid[k]) for k in keys]
        reports: List[Report] = []
        cells: List[Dict[str, Any]] = []
        skipped: List[Dict[str, Any]] = []
        for combo in itertools.product(*values):
            overrides = dict(zip(keys, combo))
            try:
                spec = base.replace(**overrides)
                rep = getattr(cls(spec, calibration=calibration), kind)()
            except Exception as e:  # record, keep sweeping
                skipped.append({"cell": overrides, "error": f"{type(e).__name__}: {e}"})
                if progress:
                    print(f"sweep[{kind}] {overrides} SKIPPED: {e}")
                continue
            reports.append(rep)
            cells.append(overrides)
            if progress:
                print(f"sweep[{kind}] {overrides} ok")
        return Campaign(kind=kind, grid={k: list(grid[k]) for k in keys},
                        cells=cells, reports=reports,
                        skipped=skipped).validate()

    # ------------------------------------------------------------------
    # Shared prediction / report assembly
    # ------------------------------------------------------------------
    def _predicted(self, *, measured_r_o: Optional[float] = None) -> Dict:
        p = self.resolved_plan
        out: Dict[str, Any] = {
            "est_step_time_s": p.est_step_time,
            "est_memory_gb": p.est_memory_gb,
            "efficiency_planned": p.efficiency,
        }
        # roofline terms (train-kind shapes only; decode is memory-bound)
        r_o_model = 0.0
        if self.shape.kind in ("train", "prefill"):
            terms = estimate_step_time(self.cfg_full, self.shape,
                                       self.mesh_spec, p.remat,
                                       max(p.microbatch, 1),
                                       pipe=getattr(p, "pipe", 1),
                                       n_microbatch=getattr(
                                           p, "n_microbatch", 0),
                                       **self._overlap_kwargs())
            out["step_time_terms"] = terms
            # with overlap on, only the exposed collective share is overhead
            r_o_model = r_o_from_terms(terms)
        if getattr(p, "pipe", 1) > 1:
            from repro.core.pipeline import pipeline_bubble

            out["pipeline"] = {
                "pipe": p.pipe,
                "n_microbatch": p.n_microbatch,
                "stage_cut": list(p.stage_cut or ()),
                "bubble_model": pipeline_bubble(p.pipe, p.n_microbatch),
            }
        # Lemma 3.1: efficiency/speedup curve from the best available R_O
        r_o = measured_r_o if measured_r_o is not None else r_o_model
        out["lemma31"] = {
            "r_o": r_o,
            "source": "measured" if measured_r_o is not None else "model",
            "per_device": {
                str(g): {"efficiency": amdahl.efficiency(g, r_o),
                         "speedup": amdahl.speedup(g, r_o)}
                for g in LEMMA31_G},
        }
        # Lemma 3.2: comm-time prediction for the planned schedule, priced
        # on the plan's topology tiers
        if p.sync_schedule in ("-", "") or not p.grad_bytes or p.link_bw <= 0:
            out["lemma32"] = {"schedule": p.sync_schedule or "-"}
        else:
            dp = p.mesh[0]
            t_c = (p.est_step_time if math.isfinite(p.est_step_time) else 1.0)
            tiers = p.dp_tiers()
            n_ps = ps_lib.n_parameter_servers(p.grad_bytes, dp, p.link_bw,
                                              max(t_c, 1e-9))
            comm = ps_lib.predicted_comm_time(
                p.sync_schedule, p.grad_bytes, dp, p.link_bw, n_ps=n_ps,
                tiers=tiers)
            out["lemma32"] = {
                "schedule": p.sync_schedule,
                "dp": dp,
                "grad_bytes": p.grad_bytes,
                "link_bw": p.link_bw,
                "n_parameter_servers": n_ps,
                "predicted_comm_s": comm,
                "t_c_s": t_c,
                "masked": comm <= t_c,
                "bottleneck_tier": p.bottleneck_tier,
            }
            if p.sync_overlap:
                # the overlapped refinement of the same lemma: comm that
                # stays exposed after hiding under the backward pass
                n_buckets = ps_lib.bucket_count(p.grad_bytes, p.bucket_mb)
                eff = self._overlap_kwargs()["overlap_efficiency"]
                exposed = ps_lib.overlap_exposed_comm(
                    comm, (1.0 - ps_lib.FWD_FRACTION) * t_c, n_buckets,
                    overlap_efficiency=eff)
                out["lemma32"]["overlap"] = {
                    "n_buckets": n_buckets,
                    "bucket_mb": p.bucket_mb or ps_lib.DEFAULT_BUCKET_MB,
                    "overlap_efficiency": eff,
                    "exposed_comm_s": exposed,
                    "hidden_comm_s": comm - exposed,
                    "masked_after_overlap": exposed <= t_c,
                }
            cluster = p.cluster
            if cluster is not None and not cluster.uniform:
                # tier-aware PS placement: B_ps in-node vs cross-node
                out["lemma32"]["ps_placement"] = ps_lib.ps_placement_plan(
                    p.grad_bytes, dp, cluster, max(t_c, 1e-9))
            if self.spec.staleness or self.spec.backup_workers:
                # bounded-staleness refinement: pull traffic amortized over
                # s+1 steps, straggler wait bought back by backup workers
                out["lemma32"]["async_ps"] = ps_lib.async_step_time(
                    p.grad_bytes, dp, n_ps, p.link_bw, max(t_c, 1e-9),
                    staleness=self.spec.staleness,
                    backup_workers=self.spec.backup_workers)
        return out

    def report_meta(self) -> Dict[str, Any]:
        """Provenance block shared by every Report this session emits —
        benchmarks that hand-build a Report must attach it too, so the
        artifact records the config that actually executed (which, with a
        ``config=`` override or ``reduced=True``, differs from the arch the
        spec/plan name)."""
        meta: Dict[str, Any] = {
            "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "executed_config": {
                "name": self.cfg.name,
                "d_model": self.cfg.d_model,
                "num_layers": self.cfg.num_layers,
                "vocab_size": self.cfg.vocab_size,
                "n_params": int(mm.n_params(self.cfg)),
            },
            "config_override": self._config_override,
        }
        if self.calibration is not None:
            meta["calibration"] = {
                "key": self.calibration.key,
                "achieved_flops": self.calibration.achieved_flops,
                "link_bw": self.calibration.link_bw,
            }
        if (self.spec.topology and self.spec.dp
                and self.cluster is not None
                and self.spec.dp != self.cluster.n_chips):
            meta["topology_note"] = (
                f"spec.dp={self.spec.dp} != topology "
                f"{self.spec.topology!r} chips={self.cluster.n_chips}: "
                "predicted blocks are priced on the full topology; the "
                "measured run executes on spec.dp devices, where the sync "
                "strategy may degenerate (see measured.sync.tiers)")
        return meta

    def _report(self, kind: str, measured: Dict, predicted: Dict, *,
                meta_extra: Optional[Dict[str, Any]] = None) -> Report:
        meta = self.report_meta()
        if meta_extra:
            meta.update(meta_extra)
        return Report(kind=kind, spec=self.spec.to_dict(),
                      plan=self.resolved_plan.to_dict(),
                      measured=measured, predicted=predicted,
                      meta=meta).validate()

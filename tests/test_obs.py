"""repro.obs — tracer, metrics, and their reconciliation with the
measurements they replaced.

The telemetry layer's contract is in three parts, each tested here:

1. **Tracer semantics** — span nesting/ordering, the Chrome-trace export
   shape, and the disabled fast path being genuinely free (identity
   singleton + no lingering allocations).
2. **Metrics semantics** — histogram percentiles against numpy, reservoir
   bounds, counter monotonicity, the ``metrics/v1`` section/validator
   round trip.
3. **Reconciliation** — spans do not *add* a second clock next to the old
   ``time.perf_counter()`` pairs, they ARE the clock: the values feeding
   ``SyncReport`` and ``GenResult.stats()`` must equal the span durations
   exactly, and a traced overlapped ``Session.train`` must emit a
   Chrome-trace file plus a validated ``metrics/v1`` section whose phase
   spans reconcile with the SyncReport wall clocks within 5%.
"""
import json
import threading
import tracemalloc

import numpy as np
import pytest

from repro.obs import (METRICS_SCHEMA_ID, Histogram, MetricsRegistry,
                       NULL_TRACER, Tracer, percentile, validate_metrics)
from repro.obs.trace import NULL_SPAN


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_span_nesting_depth_and_order():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner", k=1):
            pass
        with tr.span("inner", k=2):
            pass
    # completion order: children before parents
    names = [e.name for e in tr.events()]
    assert names == ["inner", "inner", "outer"]
    inner1, inner2, outer = tr.events()
    assert outer.depth == 0 and inner1.depth == inner2.depth == 1
    assert inner1.args == {"k": 1} and inner2.args == {"k": 2}
    # containment: children inside the parent's interval, in order
    assert outer.t0_s <= inner1.t0_s <= inner1.t1_s <= inner2.t0_s
    assert inner2.t1_s <= outer.t1_s
    assert outer.dur_s >= inner1.dur_s + inner2.dur_s


def test_span_elapsed_is_the_measurement():
    """elapsed_s after exit equals the recorded duration — one clock."""
    tr = Tracer()
    with tr.span("phase") as sp:
        sum(range(1000))
    assert sp.elapsed_s == tr.events("phase")[0].dur_s
    assert tr.total_s("phase") == sp.elapsed_s


def test_tracer_per_thread_stacks():
    tr = Tracer()
    errs = []
    # barrier keeps all 4 threads alive at once (thread idents are recycled
    # after a join, which would collapse the tid assertion)
    barrier = threading.Barrier(4)

    def worker(i):
        try:
            barrier.wait(timeout=10)
            with tr.span("t", i=i):
                with tr.span("u", i=i):
                    pass
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    evs = tr.events()
    assert len(evs) == 8
    # each thread saw its own stack: depth 0 for "t", 1 for "u"
    for e in evs:
        assert e.depth == (0 if e.name == "t" else 1)
    assert len({e.tid for e in evs}) == 4


def test_disabled_tracer_zero_allocation_fast_path():
    tr = Tracer(enabled=False)
    # identity: every disabled span() is the one shared singleton
    assert tr.span("a") is NULL_SPAN is tr.span("b", x=1)
    assert NULL_TRACER.span("c") is NULL_SPAN
    with tr.span("a") as sp:
        pass
    assert sp.elapsed_s == 0.0 and len(tr) == 0
    # no allocations survive the call (the transient kwargs dict may exist
    # inside it; nothing may linger)
    tr.span("warmup", k=0)
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for i in range(1000):
        with tr.span("hot", step=i):
            pass
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    growth = sum(s.size_diff for s in after.compare_to(before, "lineno")
                 if s.size_diff > 0)
    # allow a little interpreter noise, but nothing O(iterations)
    assert growth < 16_384, f"disabled tracer leaked {growth} bytes"
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_max_events_caps_memory_not_timing():
    tr = Tracer(max_events=3)
    durs = []
    for i in range(5):
        with tr.span("s", i=i) as sp:
            pass
        durs.append(sp.elapsed_s)
    assert len(tr) == 3 and tr.dropped == 2
    assert all(d > 0.0 for d in durs)  # capped spans still time correctly


def test_chrome_trace_shape_and_save(tmp_path):
    tr = Tracer()
    with tr.span("step", step=0):
        with tr.span("compute"):
            pass
    d = tr.chrome_trace(process_name="test")
    assert d["displayTimeUnit"] == "ms"
    evs = d["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["args"] == {"name": "test"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"step", "compute"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == 1
    path = tr.save(tmp_path / "sub" / "trace.json")
    loaded = json.loads(path.read_text())
    # save() uses the default process name; content otherwise identical
    assert loaded == json.loads(json.dumps(tr.chrome_trace()))


def test_clear_resets_epoch_and_events():
    tr = Tracer()
    with tr.span("a"):
        pass
    assert len(tr) == 1
    tr.clear()
    assert len(tr) == 0
    with tr.span("b"):
        pass
    assert tr.events("b")[0].t0_s >= 0.0


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def test_scope_names_a_component_and_refuses_others():
    import jax
    import jax.numpy as jnp

    from repro.obs.scopes import COMPONENTS, scope

    assert len(set(COMPONENTS)) == len(COMPONENTS)
    with pytest.raises(ValueError, match="unknown scope"):
        scope("backward")

    def f(x):
        with scope("mlp"):
            return jnp.sin(x)

    hlo = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "mlp/sin" in hlo


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for values in (rng.normal(10, 3, 257), rng.exponential(1.0, 100),
                   np.array([4.2]), np.arange(10.0)):
        for p in (0, 10, 50, 90, 95, 99, 100):
            assert percentile(list(values), p) == pytest.approx(
                float(np.percentile(values, p)), rel=1e-12, abs=1e-12)


def test_percentile_rejects_bad_input():
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_histogram_exact_until_reservoir_cap():
    h = Histogram(max_samples=1000)
    rng = np.random.default_rng(1)
    xs = rng.normal(0, 1, 500)
    for x in xs:
        h.observe(x)
    assert h.count == 500
    assert h.sum == pytest.approx(float(np.sum(xs)))
    assert h.min == float(np.min(xs)) and h.max == float(np.max(xs))
    for p in (50, 95, 99):
        assert h.quantile(p) == pytest.approx(float(np.percentile(xs, p)))
    s = h.summary()
    assert s["count"] == 500 and s["p50"] <= s["p95"] <= s["p99"]


def test_histogram_reservoir_bounds_memory_and_stays_sane():
    h = Histogram(max_samples=64, seed=0)
    for x in np.random.default_rng(2).uniform(0, 100, 10_000):
        h.observe(float(x))
    assert h.count == 10_000 and len(h._samples) == 64
    # quantiles of a uniform[0,100) sample stay in-range and ordered
    s = h.summary()
    assert 0 <= s["p50"] <= s["p95"] <= s["p99"] <= 100
    assert s["min"] <= s["p50"] and s["p99"] <= s["max"]
    # deterministic: same seed + stream -> same summary (CI reproducibility)
    h2 = Histogram(max_samples=64, seed=0)
    for x in np.random.default_rng(2).uniform(0, 100, 10_000):
        h2.observe(float(x))
    assert h2.summary() == s


def test_counter_monotonic_and_gauge_last_write():
    reg = MetricsRegistry()
    reg.inc("n", 2)
    reg.inc("n")
    assert reg.counter("n").value == 3.0
    with pytest.raises(ValueError):
        reg.inc("n", -1)
    reg.set_gauge("g", 1.0)
    reg.set_gauge("g", 2.5)
    assert reg.gauge("g").value == 2.5


def test_registry_section_validates_and_skips_empty_histograms():
    reg = MetricsRegistry()
    reg.inc("train/steps", 3)
    reg.set_gauge("train/r_o", 0.25)
    for v in (0.1, 0.2, 0.3):
        reg.observe("train/step_s", v)
    reg.histogram("train/empty")  # created but never observed
    sect = reg.section()
    assert sect["schema"] == METRICS_SCHEMA_ID
    assert "train/empty" not in sect["histograms"]
    assert validate_metrics(sect) is sect
    assert json.loads(json.dumps(sect)) == sect  # JSON-safe


def test_validate_metrics_rejects_malformed():
    good = MetricsRegistry()
    good.observe("h", 1.0)
    base = good.section()
    for mutate in (
        lambda d: d.update(schema="nope"),
        lambda d: d.pop("counters"),
        lambda d: d["histograms"]["h"].pop("p95"),
        lambda d: d["histograms"]["h"].update(count=0),
        lambda d: d["histograms"]["h"].update(p50=d["histograms"]["h"]["max"]
                                              + 1),
        lambda d: d["counters"].update(bad=-1),
    ):
        d = json.loads(json.dumps(base))
        mutate(d)
        with pytest.raises(ValueError):
            validate_metrics(d)


# ---------------------------------------------------------------------------
# Reconciliation: spans ARE the measurements
# ---------------------------------------------------------------------------


def test_trainer_spans_reconcile_with_sync_report(multi_device):
    """Serial trainer: the compute/dist_update/param_update spans of each
    step are exactly the phase values the loop folds into StepTimes, and
    the dist_update span total matches the SyncReport's measured comm."""
    from repro.configs.base import get_config
    from repro.distributed.trainer import DataParallelTrainer
    from repro.models.blocks import RunConfig
    from repro.optim.adamw import OptConfig

    cfg = get_config("granite-3-2b").reduced()
    run = RunConfig(attn_impl="dense", remat="none")
    opt = OptConfig(lr=1e-3, warmup_steps=0, total_steps=3)
    tracer = Tracer()
    metrics = MetricsRegistry()
    tr = DataParallelTrainer(cfg, run, opt, strategy="all_reduce",
                             devices=multi_device[:2], tracer=tracer,
                             metrics=metrics)
    res = tr.train(batch=4, seq=32, steps=3, seed=0, log_every=0)
    rep = tr.report()
    # span totals vs the trainer's phase bookkeeping: same clock, so the
    # 5% tolerance guards plumbing (not noise) — they're identical floats
    comm_spans = [e.dur_s for e in tracer.events("dist_update")]
    assert len(comm_spans) == 3
    # report() averages the steady window (first 2 steps are warmup/compile)
    assert np.mean(comm_spans[2:]) == pytest.approx(rep.measured_comm_s,
                                                    rel=0.05)
    # the StepTimes the loop reports decompose exactly into the spans
    for st, sp_comm, sp_upd in zip(res.step_times,
                                   comm_spans,
                                   [e.dur_s for e in
                                    tracer.events("param_update")]):
        assert st.dist_update == pytest.approx(sp_comm, rel=1e-9)
        assert st.param_update == pytest.approx(sp_upd, rel=1e-9)
    # metrics published alongside
    sect = validate_metrics(metrics.section())
    assert sect["counters"]["train/steps"] == 3.0
    assert sect["histograms"]["train/dist_update_s"]["count"] == 3


def test_engine_stats_equal_span_durations(multi_device):
    """GenResult.stats() prefill/decode ARE the span durations (identity,
    not approximation — the satellite's 'values identical' requirement)."""
    from repro.configs.base import get_config
    from repro.models.blocks import RunConfig
    from repro.serve.engine import BatchScheduler, Engine

    cfg = get_config("granite-3-2b").reduced()
    tracer = Tracer()
    metrics = MetricsRegistry()
    eng = Engine(cfg, RunConfig(attn_impl="dense", remat="none"),
                 s_max=64, tracer=tracer, metrics=metrics)
    sched = BatchScheduler(eng, max_batch=2)
    rng = np.random.default_rng(0)
    for _ in range(3):
        sched.submit(rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32),
                     3)
    results = sched.run()
    assert len(results) == 3
    stats = [g.stats() for g in sched.history]
    prefills = [e.dur_s for e in tracer.events("prefill")]
    decodes = [e.dur_s for e in tracer.events("decode")]
    assert [s["prefill_s"] for s in stats] == prefills
    assert [s["decode_s"] for s in stats] == decodes
    sect = validate_metrics(metrics.section())
    assert sect["counters"]["serve/requests"] == 3.0
    assert sect["histograms"]["serve/prefill_s"]["count"] == len(prefills)
    assert sect["histograms"]["serve/queue_depth"]["max"] == 3.0


def test_overlapped_session_train_emits_trace_and_metrics(multi_device,
                                                          tmp_path):
    """The PR's acceptance path: an overlapped Session.train run emits a
    Chrome-trace file plus a validated metrics/v1 section whose per-phase
    span sums reconcile with the SyncReport wall clock within 5%."""
    from repro.api import JobSpec, Session

    spec = JobSpec(arch="granite-3-2b", reduced=True, steps=6, batch=8,
                   seq=32, dp=2, sync="all_reduce", sync_overlap=True,
                   bucket_mb=0.05, log_every=0, trace_dir=str(tmp_path))
    sess = Session(spec)
    rep = sess.train()
    d = rep.to_dict()
    sync = d["measured"]["sync"]
    sect = validate_metrics(d["measured"]["metrics"])
    assert sect["gauges"]["train/overlap_fraction"] == \
        sync["overlap_fraction"]
    # per-bucket reconciliation: the last calibration step's bucket_sync
    # spans are per_bucket_comm_s (same clock -> 5% is plumbing tolerance)
    per_bucket = sync["per_bucket_comm_s"]
    spans = [e.dur_s for e in sess.last_tracer.events("bucket_sync")]
    assert spans[-len(per_bucket):] == pytest.approx(per_bucket, rel=0.05)
    # the trace file landed and carries the phase tree
    trace_path = tmp_path / "trace_train.json"
    assert str(trace_path) == d["meta"]["trace_file"]
    trace = json.loads(trace_path.read_text())
    names = {e.get("name") for e in trace["traceEvents"]}
    for needed in ("step", "compute", "dist_update", "bucket_sync",
                   "param_update", "fused_step"):
        assert needed in names
    buckets = [e for e in trace["traceEvents"]
               if e.get("name") == "bucket_sync"]
    assert all("bytes" in b["args"] and "bucket" in b["args"]
               for b in buckets)


def test_measuring_components_substitute_disabled_tracers(multi_device):
    """Passing a disabled tracer to a measuring component must not zero its
    measurements: the trainer/engine substitute a private live clock."""
    from repro.configs.base import get_config
    from repro.distributed.trainer import DataParallelTrainer
    from repro.models.blocks import RunConfig
    from repro.optim.adamw import OptConfig
    from repro.serve.engine import Engine

    cfg = get_config("granite-3-2b").reduced()
    run = RunConfig(attn_impl="dense", remat="none")
    tr = DataParallelTrainer(cfg, run, OptConfig(lr=1e-3),
                             strategy="all_reduce", devices=multi_device[:2],
                             tracer=NULL_TRACER)
    assert tr.tracer is not NULL_TRACER and tr.tracer.enabled
    res = tr.train(batch=4, seq=32, steps=2, seed=0, log_every=0)
    assert all(t.compute > 0 for t in res.step_times)
    eng = Engine(cfg, run, s_max=32, tracer=NULL_TRACER)
    assert eng.tracer is not NULL_TRACER and eng.tracer.enabled
    out = eng.generate(np.zeros((1, 4), np.int32), 2)
    assert out.prefill_s > 0 and out.decode_s > 0

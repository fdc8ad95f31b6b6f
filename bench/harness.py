"""What every cell shares: finding its files by name, the device, the
compile cache, host spans, the profiled window, and the result line.

A cell is a ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<traffic>.json``, whose ``driver`` names
``bench/drivers/<driver>.py``), and the limits of the comparison that
decides ``correct`` (``bench/limits/<cell>.json``).  Per-layer metrics are
read by ``bench/metrics/<metric>.py``.  A later cell, configuration or
metric adds files; none of these is edited for it.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits(cell: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell}.json")


_MODULES: Dict[str, Any] = {}


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded once (names may hold dots)."""
    key = f"{kind}/{name}"
    if key not in _MODULES:
        path = BENCH / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def metrics_for(bench: dict, cell: str) -> Dict[str, List[dict]]:
    """The end-to-end and per-layer metrics ``cell`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": per}


@dataclass
class Cell:
    """One workload with its files read."""

    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def load(cls, name: str, bench: Optional[dict] = None) -> "Cell":
        bench = bench or benchmark()
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                           f"{[w['name'] for w in bench['workloads']]}")
        m = metrics_for(bench, name)
        return cls(name, config(entry["config"]), traffic(entry["traffic"]),
                   limits(name), int(entry["chips"]), m["end_to_end"],
                   m["per_layer"])


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------


def device_info(n: int) -> dict:
    import jax

    devs = jax.devices()[:n]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(n: int) -> int:
    """``peak_bytes_in_use`` of the fullest of the first ``n`` devices."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n])


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``.jax_cache/`` in the checkout, a
    fixed path.  Every program is cached, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileClock:
    """Counts JAX's compiles and sums their durations while installed."""

    def __init__(self):
        import jax

        self.total_s = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.total_s += duration


# ---------------------------------------------------------------------------
# Host spans (benchmark side, around the calls into each layer)
# ---------------------------------------------------------------------------


@dataclass
class Span:
    name: str
    t0: float
    t1: float

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


@dataclass
class Spans:
    """Host-clock spans; each is also a profiler ``TraceAnnotation`` named
    ``bench.<name>`` so that a trace can name the host's work."""

    clock: Callable[[], float] = time.perf_counter
    events: List[Span] = field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(f"bench.{name}"):
            t0 = self.clock()
            try:
                yield
            finally:
                self.events.append(Span(name, t0, self.clock()))

    def total(self, name: str, t0: float = -math.inf,
              t1: float = math.inf) -> float:
        return sum(min(e.t1, t1) - max(e.t0, t0) for e in self.events
                   if e.name == name and e.t1 > t0 and e.t0 < t1)


@contextlib.contextmanager
def profiled(reduce: Callable[[str], dict], out: dict):
    """Profile the block into a temporary directory under ``$TMPDIR``,
    reduce the trace with ``reduce(xplane_path)`` into ``out``, and delete
    the directory."""
    import glob

    import jax

    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        out.update(reduce(paths[0]))
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# What a driver hands back
# ---------------------------------------------------------------------------


@dataclass
class Outcome:
    """A driver's measurement, before the comparison."""

    window_s: float
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    memory_peak_bytes: int
    chips: int
    spans: Spans
    window: tuple                      # (t0, t1) on the spans' clock
    counters: Dict[str, float] = field(default_factory=dict)
    trace: Dict[str, Any] = field(default_factory=dict)
    compiles_in_window: int = 0
    readings: Dict[str, Any] = field(default_factory=dict)


def per_layer_values(cell: Cell, outcome: Outcome, peak: dict) -> dict:
    """Each per-layer metric the cell lists, read by its own reader; a
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        v = module("metrics", m["name"]).read(outcome, cell, peak)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def result_line(cell: Cell, outcome: Outcome, checks: Dict[str, dict],
                dev: dict, trace: bool, peak: dict) -> dict:
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and outcome.failed == 0 and outcome.compiles_in_window == 0
    if trace:
        metrics = per_layer_values(cell, outcome, peak)
    else:
        metrics = {}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": outcome.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=outcome.memory_peak_bytes)
    line = {"correct": bool(correct), "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = outcome.trace["busy_s"]
        device["window_s"] = outcome.trace["window_s"]
        line["breakdown"] = {"device_ops": outcome.trace["device_ops"],
                             "idle_gaps": outcome.trace["idle_gaps"]}
    checks = dict(checks, compiles_in_window={
        "value": outcome.compiles_in_window, "limit": 0})
    # a number that is not finite failed its check; JSON has no spelling
    # for it
    line["checks"] = {k: dict(c, value=c["value"] if math.isfinite(
        c["value"]) else None) for k, c in checks.items()}
    return line


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, dev: dict, fault: Optional[str] = None) -> dict:
    """Measure ``cell``, then compare what its timed path produced with the
    plain reference; returns the result line."""
    peak = peaks(dev["kind"]) if dev["platform"] == "tpu" else {}
    driver = module("drivers", cell.traffic["driver"])
    outcome = driver.measure(cell, seed=seed, seconds=seconds, trace=trace,
                             t_start=t_start, fault=fault)
    checks = driver.check(cell, seed=seed, outcome=outcome)
    return result_line(cell, outcome, checks, dev, trace, peak)

"""Reduce one profiler trace (``*.xplane.pb``) to what the benchmark reports.

The window is the host annotation ``bench.window``.  On each device plane
(``/device:TPU:<n>``) the line ``XLA Ops`` holds the operations the core
ran; loops and conditionals appear as operations that enclose their body's.
Asynchronous copies (line ``Async XLA Ops``) overlap those and are not
counted as busy.

* busy: the union of the operations' intervals inside the window;
* idle share: 1 - busy / window;
* collective exposed: the union of collective operations' intervals minus
  the part of it in which a non-collective operation also runs;
* device ops: time by operation, each counted without the operations it
  encloses, averaged over devices;
* idle gaps: each stretch of the window with no operation running, cut
  where a ``bench.*`` host annotation opens or closes, each piece named by
  the innermost annotation open over it, summed by name and averaged over
  devices.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
TOP = 10

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_label(hlo: str) -> str:
    """``%fusion.5 = bf16[4,8]{1,0:T(8,128)} fusion(...), kind=kLoop, ...``
    -> ``fusion.5 fusion bf16[4,8] kLoop``."""
    m = re.match(r"%?([\w.\-]+) = (.*)", hlo, re.S)
    if not m:
        return hlo[:120]
    name, rest = m.groups()
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    m2 = re.match(r"(\(.*?\)|\S+) ([\w\-]+)\(", rest)
    if not m2:
        return name[:120]
    shape, opcode = m2.groups()
    kind = re.search(r"kind=(\w+)", rest)
    label = f"{name} {opcode} {shape}" + (f" {kind.group(1)}" if kind else "")
    return label[:160]


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Duration of each event less the events it encloses, by label."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []  # [end, label, self_time]
    for s, e, label in sorted(events, key=lambda x: (x[0], -(x[1] - x[0]))):
        while stack and stack[-1][0] <= s:
            end, lab, st = stack.pop()
            out[lab] += st
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, label, e - s])
    for end, lab, st in stack:
        out[lab] += st
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host_spans: List[Tuple[float, float, str]] = []
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW:
                        window = (s, e)
                    elif name.startswith("bench."):
                        host_spans.append((s, e, name))
        elif re.match(r"/device:[A-Z]+:\d+$", plane.name):
            devices.append(plane)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} host annotation")
    if not devices:
        raise ValueError(f"{path}: no device plane")
    lo, hi = window
    n = len(devices)
    cut_points = sorted({t for s, e, _ in host_spans for t in (s, e)})
    busy_s = exposed_s = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    for plane in devices:
        ops, coll, other = [], [], []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for name, s, e in _events(line):
                s, e = max(s, lo), min(e, hi)
                if s >= e:
                    continue
                label = op_label(name)
                ops.append((s, e, label))
                (coll if COLLECTIVE.search(label.split(" ")[1]
                                           if " " in label else label)
                 else other).append((s, e))
        busy = union([(s, e) for s, e, _ in ops])
        busy_s += length(busy)
        c = union(coll)
        exposed_s += length(c) - length(intersect(c, union(other)))
        for label, t in self_times(ops).items():
            op_time[label] += t / n
        for s, e in gaps(busy, lo, hi):
            cuts = [s] + [t for t in cut_points if s < t < e] + [e]
            for a, b in zip(cuts, cuts[1:]):
                mid = 0.5 * (a + b)
                open_ = [h for h in host_spans if h[0] <= mid < h[1]]
                name = max(open_)[2] if open_ else "no bench span"
                gap_time[name] += (b - a) / n
    window_s = hi - lo
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:TOP]]
    return {
        "window_s": window_s,
        "busy_s": busy_s / n,
        "idle_share": 1.0 - busy_s / n / window_s,
        "collective_exposed_s": exposed_s / n,
        "devices": n,
        "device_ops": top(op_time),
        "idle_gaps": top(gap_time),
    }

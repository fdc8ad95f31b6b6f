"""Device time of a profiled window by the program's named scopes.

The program names the device work of a training step with
``jax.named_scope`` (``repro.obs.scopes.COMPONENTS``).  JAX writes the scope
stack into each HLO instruction's ``op_name``; the profiler copies it into
the metadata of each device op as the stat ``tf_op``.
``jax.profiler.ProfileData`` exposes only per-event stats, so this module
decodes the ``.xplane.pb`` itself with ``google.protobuf``, from the schema
below, and reads each ``XLA Ops`` event's ``tf_op`` through its metadata id.

Each op's self time inside ``bench.window`` (``trace_reduce.self_times``)
goes to a (component, pass) pair:

* component: the innermost path element, with ``jvp(…)``, ``transpose(…)``
  and other wrappers stripped, that is a scope name; ``unscoped`` if none;
* pass: ``optimizer`` under the ``optimizer`` scope, else ``recompute`` for
  a block rematerialised in the backward pass (``rematted_computation``),
  else ``backward`` if a path element is a ``transpose(…)``, else
  ``forward``; ``unscoped`` for an unscoped op.

``scopes`` is ``{component: {pass: seconds}}``, averaged over devices; its
values sum to ``trace_reduce.reduce``'s ``busy_s``.  :func:`readings` turns
it into per-step times.

    python3 bench/scope_reduce.py --workload <cell> --seed <n> [--seconds <s>]

runs one cell as ``bench/run.py --trace 1`` does, prints its result line,
then one JSON line: ``scopes``, :func:`readings`, busy and window seconds,
and ``scopes_s``, the time decoding the trace by scope took.
"""
from __future__ import annotations

import functools
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import trace_reduce

TF_OP = "tf_op"
RECOMPUTE = "rematted_computation"
WRAPPER = re.compile(r"[\w.\-]+\((.*)\)")

# The fields this module reads, of openxla's
# xla/tsl/profiler/protobuf/xplane.proto (package tensorflow.profiler), with
# their published numbers; the parser skips the others.  A map field is
# written as its repeated key/value entry message, the same on the wire.
# (name, number, type, message type, repeated)
_XPLANE = {
    "XSpace": [("planes", 1, "message", "XPlane", True)],
    "XPlane": [
        ("name", 2, "string", None, False),
        ("lines", 3, "message", "XLine", True),
        ("event_metadata", 4, "message", "EventMetadataEntry", True),
        ("stat_metadata", 5, "message", "StatMetadataEntry", True)],
    "EventMetadataEntry": [
        ("key", 1, "int64", None, False),
        ("value", 2, "message", "XEventMetadata", False)],
    "StatMetadataEntry": [
        ("key", 1, "int64", None, False),
        ("value", 2, "message", "XStatMetadata", False)],
    "XLine": [
        ("name", 2, "string", None, False),
        ("timestamp_ns", 3, "int64", None, False),
        ("events", 4, "message", "XEvent", True)],
    "XEvent": [
        ("metadata_id", 1, "int64", None, False),
        ("offset_ps", 2, "int64", None, False),
        ("duration_ps", 3, "int64", None, False)],
    # str_value and ref_value are members of the oneof ``value``
    "XStat": [
        ("metadata_id", 1, "int64", None, False),
        ("str_value", 5, "string", None, False),
        ("ref_value", 7, "uint64", None, False)],
    "XEventMetadata": [
        ("name", 2, "string", None, False),
        ("stats", 5, "message", "XStat", True)],
    "XStatMetadata": [("name", 2, "string", None, False)],
}
_PACKAGE = "tensorflow.profiler"
_XSPACE = None


def xspace_class():
    """The ``XSpace`` message class, built once in a pool of its own."""
    global _XSPACE
    if _XSPACE is None:
        from google.protobuf import descriptor_pb2, descriptor_pool
        from google.protobuf import message_factory

        F = descriptor_pb2.FieldDescriptorProto
        f = descriptor_pb2.FileDescriptorProto(
            name="xplane.proto", package=_PACKAGE, syntax="proto3")
        for msg, fields in _XPLANE.items():
            m = f.message_type.add(name=msg)
            for name, number, typ, ref, repeated in fields:
                fd = m.field.add(
                    name=name, number=number,
                    type=getattr(F, "TYPE_" + typ.upper()),
                    label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
                if ref:
                    fd.type_name = f".{_PACKAGE}.{ref}"
                if name.endswith("_value"):
                    fd.oneof_index = 0
            if msg == "XStat":
                m.oneof_decl.add(name="value")
        pool = descriptor_pool.DescriptorPool()
        pool.Add(f)
        _XSPACE = message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))
    return _XSPACE


def read_xspace(path: str):
    space = xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    return space


def _stat_str(stat, stat_names: Dict[int, str]) -> Optional[str]:
    kind = stat.WhichOneof("value")
    if kind == "str_value":
        return stat.str_value
    if kind == "ref_value":
        return stat_names.get(stat.ref_value)
    return None


def event_names(plane) -> Dict[int, Tuple[str, str]]:
    """``{metadata id: (name, tf_op)}`` of the plane's events."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for e in plane.event_metadata:
        op = next((_stat_str(s, stat_names) for s in e.value.stats
                   if stat_names.get(s.metadata_id) == TF_OP), None)
        out[e.key] = (e.value.name, op or "")
    return out


def line_events(line, names) -> List[Tuple[float, float, str, str]]:
    """``(start_s, end_s, name, tf_op)`` of each event of ``line``, timed as
    ``ProfileData`` times them (whole nanoseconds); ``names`` is the
    plane's :func:`event_names`."""
    out = []
    for ev in line.events:
        name, op = names.get(ev.metadata_id, ("", ""))
        start = line.timestamp_ns + ev.offset_ps // 1000
        out.append((start * 1e-9, (start + ev.duration_ps // 1000) * 1e-9,
                    name, op))
    return out


def scope_path(op_name: str) -> Tuple[Tuple[str, ...], bool, bool]:
    """The scope names on ``op_name``'s path, outermost first, and whether
    the op is in the backward pass (a ``transpose(…)`` element) and in a
    rematerialised block.  An instruction that XLA merged from several
    carries their names joined by ``;``: the first is taken."""
    from repro.obs.scopes import COMPONENTS

    path = op_name.split(";")[0]
    names: List[str] = []
    backward = recompute = False
    for el in path.split("/"):
        while True:
            m = WRAPPER.fullmatch(el)
            if m is None:
                break
            backward |= el.startswith("transpose(")
            el = m.group(1)
        recompute |= el == RECOMPUTE
        if el in COMPONENTS:
            names.append(el)
    return tuple(names), backward, recompute


@functools.lru_cache(maxsize=None)
def component_pass(op_name: str) -> Tuple[str, str]:
    names, backward, recompute = scope_path(op_name)
    if not names:
        return "unscoped", "unscoped"
    if "optimizer" in names:
        return names[-1], "optimizer"
    return names[-1], ("recompute" if recompute else
                       "backward" if backward else "forward")


def scopes(path: str) -> Optional[Dict[str, Dict[str, float]]]:
    """``{component: {pass: seconds}}`` of the window's device self time,
    averaged over devices; None where the program names no scopes (a
    program older than ``repro.obs.scopes``)."""
    try:
        from repro.obs.scopes import COMPONENTS  # noqa: F401
    except ImportError:
        return None
    space = read_xspace(path)
    window = None
    devices = []
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            names = event_names(plane)
            for line in plane.lines:
                for s, e, name, _ in line_events(line, names):
                    if name == trace_reduce.WINDOW:
                        window = (s, e)
        elif re.match(r"/device:[A-Z]+:\d+$", plane.name):
            devices.append(plane)
    if window is None or not devices:
        raise ValueError(f"{path}: no {trace_reduce.WINDOW!r} host "
                         f"annotation or no device plane")
    lo, hi = window
    n = len(devices)
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for plane in devices:
        names = event_names(plane)
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for s, e, _, op in line_events(line, names):
                s, e = max(s, lo), min(e, hi)
                if s < e:
                    ops.append((s, e, component_pass(op)))
        for (comp, pas), t in trace_reduce.self_times(ops).items():
            out[comp][pas] += t / n
    return {c: dict(p) for c, p in out.items()}


def reduce(path: str) -> dict:
    """``trace_reduce.reduce`` with the key ``scopes`` added, where the
    program names its scopes."""
    out = trace_reduce.reduce(path)
    s = scopes(path)
    if s is not None:
        out["scopes"] = s
    return out


def readings(trace: dict, steps: int) -> Dict[str, float]:
    """Per profiled step: forward, backward (recompute included) and
    optimizer time, attention_core and ssd_scan time (both passes), in ms;
    unscoped time over busy time, in percent.  Empty without ``scopes``."""
    sc = trace.get("scopes")
    if sc is None:
        return {}

    def ms(t):
        return 1e3 * t / steps

    def by_pass(*passes):
        return sum(v for p in sc.values() for k, v in p.items()
                   if k in passes)

    return {
        "train.forward_ms": ms(by_pass("forward")),
        "train.backward_ms": ms(by_pass("backward", "recompute")),
        "train.optimizer_ms": ms(by_pass("optimizer")),
        "train.attention_core_ms": ms(sum(sc.get("attention_core",
                                                 {}).values())),
        "train.ssd_scan_ms": ms(sum(sc.get("ssd_scan", {}).values())),
        "train.unscoped_share": 100.0 * by_pass("unscoped") / trace["busy_s"],
    }


def main(argv=None) -> int:
    """One traced run of a cell with the scope table (see the module's
    docstring)."""
    import argparse
    import json
    import time

    import harness
    import run

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    base, out = trace_reduce.reduce, {}

    def reduce_timed(path):
        out.update(base(path))
        t0 = time.perf_counter()
        out["scopes"] = scopes(path)
        out["scopes_s"] = time.perf_counter() - t0
        return out

    # the train driver reduces its profile with trace_reduce.reduce
    trace_reduce.reduce = reduce_timed
    try:
        rc = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1"])
    finally:
        trace_reduce.reduce = base
    if rc == 0:
        steps = harness.Cell.load(args.workload).traffic["profile_steps"]
        print(json.dumps(dict(
            {k: out[k] for k in ("scopes", "busy_s", "window_s", "scopes_s")},
            steps=steps, readings=readings(out, steps))), flush=True)
    return rc


if __name__ == "__main__":
    import sys

    sys.exit(main())

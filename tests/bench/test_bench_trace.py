"""The reduction from a profiler trace to busy time, idle share, exposed
collectives, top operations and named idle gaps."""
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401
import trace_reduce as tr

SMALL = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"


def test_union_intersect_gaps():
    u = tr.union([(3, 4), (0, 1), (0.5, 2), (5, 6)])
    assert u == [(0, 2), (3, 4), (5, 6)]
    assert tr.length(u) == 4
    assert tr.intersect(u, [(1, 3.5), (5.5, 9)]) == [(1, 2), (3, 3.5),
                                                     (5.5, 6)]
    assert tr.gaps(u, -1, 7) == [(-1, 0), (2, 3), (4, 5), (6, 7)]


def test_self_times_subtract_enclosed_ops():
    events = [(0.0, 10.0, "while"), (1.0, 3.0, "a"), (4.0, 8.0, "b"),
              (5.0, 6.0, "c"), (12.0, 13.0, "a")]
    st = tr.self_times(events)
    assert st == {"while": 4.0, "a": 3.0, "b": 3.0, "c": 1.0}


def test_op_label():
    hlo = ("%fusion.556 = (f32[4,8]{1,0:T(4,128)S(1)}, bf16[4,8]{1,0}) "
           "fusion(bf16[4,8]{1,0} %p), kind=kOutput, calls=%fc.18")
    assert tr.op_label(hlo) == "fusion.556 fusion (f32[4,8], bf16[4,8]) " \
                               "kOutput"
    assert tr.op_label("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)"
                       ).split(" ")[1] == "all-reduce"


def test_small_trace_recorded_on_the_chip():
    r = tr.reduce(str(SMALL))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert r["collective_exposed_s"] == 0.0
    gaps = dict(r["idle_gaps"])
    # three 10 ms host sleeps with nothing queued: the longest named gap
    assert r["idle_gaps"][0][0] == "bench.host_wait"
    assert 0.03 <= gaps["bench.host_wait"] < 0.035
    assert 0 < gaps["bench.step"] < gaps["bench.host_wait"]
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    ops = [name for name, _ in r["device_ops"]]
    assert any(" fusion " in o for o in ops)
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] + 1e-9

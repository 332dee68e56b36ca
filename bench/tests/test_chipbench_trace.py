"""The reduction from a profiler trace to numbers: the busy-interval
union, the idle share, op self time and the sort share, and the naming
of idle gaps by host spans; on hand-made events and on a small trace
recorded on a v5e (``data/v5e_fixture.xplane.pb``)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tracing  # noqa: E402

FIXTURE = Path(__file__).parent / "data" / "v5e_fixture.xplane.pb"


def test_union_merges_and_clips():
    got = tracing.union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 1, 25)
    assert got == [[1, 3], [5, 12], [20, 25]]


def test_self_time_takes_nested_ops_off_their_parent():
    ops = [("while.1", 0, 100), ("sort.2 f32[4]", 10, 30),
           ("fusion.3", 40, 90), ("copy.4", 100, 120)]
    st = tracing.self_times(ops, 0, 110)
    assert st == {"while.1": 100 - 20 - 50, "sort.2 f32[4]": 20,
                  "fusion.3": 50, "copy.4": 10}


def _events():
    ops = {0: [("sort.1 s32[8]", 10, 30), ("fusion.2 f32[8]", 30, 45),
               ("while.3", 60, 100), ("sort.4 s32[8]", 70, 80)]}
    spans = [("bench.window", 0, 120), ("bench.fit", 0, 53),
             ("bench.submit", 47, 60), ("bench.engine_wait", 100, 120)]
    return tracing.Events(ops, spans)


def test_busy_idle_and_gaps():
    s = tracing.summarize(_events())
    assert s.window_ns == (0, 120)
    assert s.busy_ns == 35 + 40            # [10, 45] and [60, 100]
    assert s.idle_share == pytest.approx(1 - 75 / 120)
    assert s.gaps == [("bench.engine_wait", 20e-9), ("bench.submit", 15e-9),
                      ("bench.fit", 10e-9)]


def test_sort_share_counts_sort_self_time():
    s = tracing.summarize(_events())
    share = s.share(lambda k: tracing.opcode(k) == "sort")
    assert share == pytest.approx((20 + 10) / 75)


def test_share_of_an_idle_device_is_none():
    ev = tracing.Events({0: []}, [("bench.window", 0, 10)])
    s = tracing.summarize(ev)
    assert s.idle_share == 1.0 and s.share(lambda k: True) is None


def test_op_names_and_opcodes():
    n = tracing.op_name("%fusion.12 = (f32[4096]{0:T(1024)S(1)}, s32[4096]"
                        "{0:T(1024)}) fusion(f32[4096,128]{1,0} %x)")
    assert n == "fusion.12 f32[4096]"
    assert tracing.opcode("sort.3 f32[1000000,40]") == "sort"
    assert tracing.opcode("copy-done.1 f32[8]") == "copy-done"
    assert tracing.opcode("multiply_reduce_fusion f32[8]") == \
        "multiply_reduce_fusion"


def test_breakdown_is_ordered_and_short():
    b = tracing.breakdown(tracing.summarize(_events()), top=2)
    assert [k for k, _ in b["device_ops"]] == ["while.3", "sort.1 s32[8]"]
    assert len(b["idle_gaps"]) == 2


def test_recorded_v5e_trace():
    ev = tracing.load_events(str(FIXTURE))
    assert list(ev.device_ops) == [0]
    names = {n for n, _, _ in ev.host_spans}
    assert {"bench.window", "bench.fit", "bench.submit"} <= names
    s = tracing.summarize(ev)
    assert 0 < s.busy_ns < s.window_ns[1] - s.window_ns[0]
    assert 0.0 < s.idle_share < 1.0
    sort = s.share(lambda k: tracing.opcode(k) == "sort")
    assert 0.0 < sort < 1.0
    # the longest idle gap is the 5 ms sleep inside bench.submit
    label, secs = s.gaps[0]
    assert label == "bench.submit" and 0.004 < secs < 0.05

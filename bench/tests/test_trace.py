"""The trace reduction on a small trace recorded on the CPU
(``data/cpu_trace.xplane.pb``; ``record_cpu_trace.py`` makes it)."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(str(DATA))


def raw_device_intervals():
    """Every event with an ``hlo_op`` stat, read straight from the file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(DATA)).planes:
        for line in plane.lines:
            for e in line.events:
                if "hlo_op" in dict(e.stats):
                    a = e.start_ns * 1e-9
                    out.append((a, a + e.duration_ns * 1e-9))
    return out


def test_window_is_the_window_span(red):
    (w,) = red.spans_named("bench.window")
    assert red.window == (w.start, w.end)
    assert len(red.spans_named("bench.step_once")) == 3
    assert len(red.spans_named("bench.wait")) == 3


def test_busy_time_is_the_union_of_op_intervals(red):
    lo, hi = red.window
    events = sorted((max(a, lo), min(b, hi)) for a, b in
                    raw_device_intervals() if min(b, hi) > max(a, lo))
    total, end = 0.0, -1.0
    for a, b in events:                 # sweep: count each instant once
        if b > end:
            total += b - max(a, end)
            end = b
    assert red.devices == ["cpu"]
    assert red.busy_s == pytest.approx(total, rel=1e-9)
    assert 0 < red.busy_s < red.window_s


def test_events_by_program_and_name(red):
    by_module = red.time_by("module")
    assert by_module["jit_sparse_fn"] > 0 and by_module["jit_flash_like"] > 0
    for s in red.spans_named("bench.step_once"):
        assert red.ops_within(s.start, s.end, "sparse_fn", key="module")
        assert red.ops_within(s.start, s.end, "flash_like", key="module")
    named = red.time_by("name", pattern="dot")
    assert named and all("dot" in k for k in named)


def test_idle_gaps_carry_the_enclosing_span(red):
    gaps = red.idle_gaps()
    longest = gaps[:3]
    assert all(lab.startswith("bench.wait") for lab, _, _ in longest)
    assert all(0.025 < sec < 0.05 for _, _, sec in longest)
    idle = sum(sec for _, _, sec in gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-9)


def test_breakdown_lists_at_most_ten(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0].startswith("bench.wait")
    assert all(isinstance(v, float) for _, v in b["device_ops"])


@pytest.mark.parametrize("intervals,window,want", [
    ([(0, 2), (1, 3), (5, 6)], None, [(0, 3), (5, 6)]),
    ([(0, 2), (2, 3)], None, [(0, 3)]),
    ([(0, 10)], (2, 4), [(2, 4)]),
    ([(0, 1), (5, 6)], (2, 4), []),
])
def test_union(intervals, window, want):
    assert trace.union(intervals, window) == want


def test_leaves_leave_out_an_op_that_holds_others():
    ops = [trace.Op(0.0, 10.0, "while.1", "jit_fn", "d0"),
           trace.Op(1.0, 2.0, "fusion.1", "jit_fn", "d0"),
           trace.Op(3.0, 5.0, "flash_attn.2", "jit_fn", "d0"),
           trace.Op(11.0, 12.0, "copy.3", "jit_fn", "d0")]
    assert [o.name for o in trace.leaves(ops)] == ["fusion.1",
                                                   "flash_attn.2", "copy.3"]
    red = trace.Reduced((0.0, 12.0), ops, [], {}, ["d0"])
    assert red.device_s(ops) == 11.0
    assert red.busy_s == 11.0


@pytest.mark.parametrize("event,want", [
    ("%fusion.119 = s32[4]{0:T(128)S(1)} fusion(s32[8,4] %gte.7)",
     "fusion.119"),
    ("%flash_attn.6 = bf16[1,32,1,128,128]{4,3,2,1,0} custom-call(s32[1])",
     "flash_attn.6"),
    ("dot.3", "dot.3"),
])
def test_op_names(event, want):
    assert trace._op_name(event) == want

"""Trace reduction, checked on a trace recorded on a TPU v5e and on hand-made events.

``bench/testdata/v5e_segment_sum.xplane.pb`` is a profile of three launches
of a jitted ``fori_loop`` of gathers and ``segment_sum`` scatters on one
v5e chip, each launch wrapped in a ``bench.launch`` and its result fetch in a
``bench.fetch`` host annotation; only the chip's plane and the host's plane
are kept.  Its ``while`` operation spans the operations of its body, so
device events nest.
"""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace as tr

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / "v5e_segment_sum.xplane.pb"


def sweep_union(intervals):
    """Covered length by an event-point sweep (independent of ``tr.merge``)."""
    points = sorted([(lo, 1) for lo, hi in intervals if hi > lo] + [(hi, -1) for lo, hi in intervals if hi > lo])
    total, depth, last = 0.0, 0, None
    for t, step in points:
        if depth > 0:
            total += t - last
        depth += step
        last = t
    return total


@pytest.fixture(scope="module")
def recorded():
    return tr.load_xplane(str(RECORDED))


def test_recorded_trace_has_device_ops_and_annotations(recorded):
    assert list(recorded.ops) == [0]
    names = {tr.op_kind(e.name) for e in recorded.ops[0]}
    assert {"while", "fusion", "sort", "dynamic_slice"} <= names
    assert {a.name for a in recorded.annotations} == {"bench.launch", "bench.fetch"}
    assert sum(a.name == "bench.launch" for a in recorded.annotations) == 3


def test_busy_is_the_union_not_the_sum(recorded):
    lo, hi = tr.window(recorded, "bench.launch")
    ops = recorded.ops[0]
    clipped = tr.clip(ops, lo, hi)
    busy = tr.busy_ns(ops, lo, hi)
    assert busy == pytest.approx(sweep_union(clipped))
    # the while loops span their bodies: summing durations counts them twice
    assert sum(h - l for l, h in clipped) > 1.8 * busy
    reading = tr.read(recorded, [0], "bench.launch")
    assert reading.window_s == pytest.approx((hi - lo) * 1e-9)
    assert reading.busy_s[0] == pytest.approx(busy * 1e-9)
    assert 0 < reading.busy_s[0] < reading.window_s


def test_union_of_overlapping_intervals():
    events = [tr.Event("a", 0, 10), tr.Event("b", 5, 15), tr.Event("c", 20, 25), tr.Event("d", 21, 22)]
    assert tr.busy_ns(events, 0, 100) == 20
    assert tr.busy_ns(events, 8, 21.5) == 8.5
    assert tr.covered([(0, 10), (10, 12)]) == 12


def test_breakdown_names_host_annotations_and_self_time(recorded):
    reading = tr.read(recorded, [0], "bench.launch")
    gap_names = {name for name, _ in reading.gaps}
    assert gap_names <= {"bench.launch", "bench.fetch", "outside the benchmark's annotations"}
    assert "bench.fetch" in gap_names or "outside the benchmark's annotations" in gap_names
    assert all(s > 0 for _, s in reading.gaps)
    assert reading.gaps == sorted(reading.gaps, key=lambda g: -g[1])
    idle = reading.window_s - reading.busy_s[0]
    assert sum(s for _, s in reading.gaps) == pytest.approx(idle, rel=1e-9)
    top = dict(reading.top_ops)
    # self time: the loop's own time excludes its body, the scatter fusion leads
    assert reading.top_ops[0][0] == "fusion.20 f32[65536,64]"
    assert top.get("while.2", 0.0) < top["fusion.20 f32[65536,64]"]
    assert sum(top.values()) <= reading.busy_s[0] * (1 + 1e-9)


def test_self_time_subtracts_nested_events():
    events = [tr.Event("%while.1 = loop", 0, 100), tr.Event("%fusion.3 = x", 10, 40), tr.Event("%fusion.4 = y", 50, 60)]
    assert tr.self_times(events) == {"while.1": 60, "fusion.3": 30, "fusion.4": 10}
    assert tr.op_label("%fusion.9 = (f32[8,128]{1,0}, s32[8]{0}) fusion(%a)") == "fusion.9 f32[8,128]"


def test_collective_exposure_subtracts_overlapped_compute():
    events = sorted(
        [
            tr.Event("%while.7 = (...) while(...)", 0, 100),  # container: not compute
            tr.Event("%all-gather-start.2 = f32[8] all-gather-start(...)", 10, 40),
            tr.Event("%fusion.1 = f32[8] fusion(...)", 20, 30),
            tr.Event("%collective-permute.3 = f32[8] collective-permute(...)", 50, 60),
            tr.Event("%fusion.2 = f32[8] fusion(...)", 55, 70),
        ],
        key=lambda e: (e.start, -e.end),
    )
    # 10..40 minus 20..30, plus 50..55
    assert tr.collective_exposed_ns(events, 0, 100) == 25
    assert tr.collective_exposed_ns(events, 0, 35) == 15
    assert tr.is_collective(events[1].name) and not tr.is_collective(events[0].name)


def test_recorded_single_chip_trace_has_no_collectives(recorded):
    reading = tr.read(recorded, [0], "bench.launch")
    assert reading.collective_exposed_s == {0: 0.0}

"""The reading by program scope (``bench/scopes.py``) and the ``compile_s`` reader.

The recorded trace's ``Reading`` is pinned: a reading without a scope map
must stay what it was.  The scoped reading is checked on hand-made events,
on the recorded trace (which holds no program spans), and on a CPU profile
of real launches.
"""

from __future__ import annotations

import glob
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from bench import run as bench_run
from bench import scopes
from bench import spec
from bench import trace as tr
from bench.tests.test_bench_harness import ROOT, last_json, make_checkout, off_chip  # noqa: F401

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / "v5e_segment_sum.xplane.pb"
OUTSIDE = "outside the benchmark's annotations"


def compile_reader():
    cell = spec.load_cell("g500-s20-u7", ROOT)
    return spec.metric_reader(cell, "compile_s")


@pytest.fixture(scope="module")
def recorded():
    return tr.load_xplane(str(RECORDED))


def test_reading_without_a_map_is_pinned(recorded):
    reading = tr.read(recorded, [0], "bench.launch")
    assert reading.window_s == pytest.approx(0.130805585, rel=1e-12)
    assert reading.busy_s == {0: pytest.approx(0.026476584, rel=1e-12)}
    assert reading.collective_exposed_s == {0: 0.0}
    assert [name for name, _ in reading.top_ops] == [
        "fusion.20 f32[65536,64]",
        "fusion.19 f32[65536,64]",
        "sort.2 s32[65536]",
        "copy f32[65536,64]",
        "multiply_reduce_fusion f32[65536]",
        "dynamic_slice.16 s32[65536]",
        "fusion.18 s32[65536]",
        "broadcast_in_dim.7 f32[65536,64]",
        "broadcast_clamp_fusion.2 s32[65536]",
        "iota.2 s32[65536]",
    ]
    assert [s for _, s in reading.top_ops] == pytest.approx(
        [0.021809676, 0.003169527, 0.001332198, 4.9696e-05, 3.5214e-05, 3.0818e-05, 3.0157e-05, 1.5322e-05,
         1.518e-06, 1.453e-06], rel=1e-9)
    assert [name for name, _ in reading.gaps] == [OUTSIDE, OUTSIDE, OUTSIDE, "bench.fetch", OUTSIDE, "bench.fetch"]
    assert [s for _, s in reading.gaps] == pytest.approx([0.052293892, 0.052035104, 2e-09, 1e-09, 1e-09, 1e-09],
                                                         rel=1e-6)


def test_scoped_loader_and_read_keep_the_reading(recorded):
    """The recorded trace holds no ``s2v`` spans: the scoped loader gives the
    same events, and the scoped reading carries the very same ``Reading``."""
    own = scopes.load_xplane(str(RECORDED))
    assert own.ops == recorded.ops and own.annotations == recorded.annotations
    scoped = scopes.read(own, [0], "bench.launch", {"fusion.20": "stage0_1_m2/spmm"})
    assert scoped.reading == tr.read(recorded, [0], "bench.launch")
    assert scoped.top_ops[0] == ("fusion.20 f32[65536,64] @stage0_1_m2/spmm", pytest.approx(0.021809676))
    assert scoped.top_ops[1][0] == "fusion.19 f32[65536,64]"
    assert scoped.component_s("spmm") == pytest.approx(0.021809676)
    lo, hi = tr.window(recorded, "bench.launch")
    in_window = [tr.Event(e.name, max(e.start, lo), min(e.end, hi)) for e in recorded.ops[0]
                 if e.end > lo and e.start < hi]
    assert sum(scoped.scope_s.values()) == pytest.approx(sum(tr.self_times(in_window).values()) * 1e-9)
    assert scoped.scoped_share() == pytest.approx(0.021809676 / sum(scoped.scope_s.values()))


def test_scope_seconds_are_self_time_summed_by_scope():
    ops = [
        tr.Event("%while.1 = (f32[4]) while(%a)", 0, 100),
        tr.Event("%fusion.2 = f32[8,3]{1,0} fusion(%a)", 10, 40),
        tr.Event("%fusion.3 = f32[8,3]{1,0} fusion(%a)", 40, 60),
        tr.Event("%fusion.4 = f32[8] fusion(%b)", 60, 70),
        tr.Event("%copy.5 = f32[8] copy(%b)", 110, 115),
        tr.Event("%fusion.6 = f32[8] fusion(%c)", 120, 125),
    ]
    spans = [
        tr.Event("bench.window", 0, 130),
        tr.Event("s2v.count_keys_chunk", 0, 126),
        tr.Event("s2v.dispatch", 0, 1),
        tr.Event("s2v.fetch", 101, 112),
        tr.Event("s2v.scale", 116, 118),
    ]
    trace = tr.Trace(ops={0: ops}, annotations=spans)
    scope_map = {
        "while.1": "stage0_2_m3",
        "fusion.2": "stage0_2_m3/spmm",
        "fusion.3": "stage0_2_m3/ema",
        "fusion.4": "stage0_4_m4/spmm",
        "fusion.6": "root",
    }
    scoped = scopes.read(trace, [0], "bench.window", scope_map)
    assert scoped.scope_s == pytest.approx({
        "stage0_2_m3": 40e-9,
        "stage0_2_m3/spmm": 30e-9,
        "stage0_2_m3/ema": 20e-9,
        "stage0_4_m4/spmm": 10e-9,
        "": 5e-9,
        "root": 5e-9,
    })
    assert scoped.component_s("spmm") == pytest.approx(40e-9)
    assert scoped.component_s("ema") == pytest.approx(20e-9)
    assert scoped.scoped_share() == pytest.approx(1 - 5 / 110)
    labels = [label for label, _ in scoped.top_ops]
    assert labels[:2] == ["while.1 f32[4] @stage0_2_m3", "fusion.2 f32[8,3] @stage0_2_m3/spmm"]
    assert "copy.5 f32[8]" in labels
    assert scoped.unscoped_ops == [("copy.5 f32[8]", pytest.approx(5e-9))]
    # idle gaps are named by the innermost program span
    gaps = dict(scoped.reading.gaps)
    assert set(gaps) == {"s2v.fetch", "s2v.scale", "bench.window"}
    assert gaps["s2v.fetch"] == pytest.approx(10e-9)
    out = scopes.breakdown(scoped, colorings=2)
    assert out["spmm_s_per_coloring"] == pytest.approx(20e-9)
    assert out["ema_s_per_coloring"] == pytest.approx(10e-9)
    assert out["scopes"][0] == ("stage0_2_m3", pytest.approx(40e-9))


def test_cpu_profile_of_launches_keeps_program_spans(tmp_path):
    from repro.core import CountingEngine, get_template, rmat_graph

    eng = CountingEngine(rmat_graph(120, 500, seed=3), [get_template("u5-2")], backend="edges", chunk_size=2)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(1), 2))
    eng.count_keys_chunk(keys)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.count_keys_chunk(keys)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    names = {a.name for a in scopes.load_xplane(path).annotations}
    assert names == {"bench.window", "s2v.count_keys_chunk", "s2v.dispatch", "s2v.fetch", "s2v.scale"}
    assert {a.name for a in tr.load_xplane(path).annotations} == {"bench.window"}


class _Run:
    chips = 1


def test_compile_reader_sums_trace_lower_and_backend_compile(monkeypatch, capsys):
    from repro import tracing

    counters = {
        "trace": {"count": 1, "seconds": 0.5},
        "lower": {"count": 1, "seconds": 0.25},
        "backend_compile": {"count": 1, "seconds": 2.0},
        "cache_retrieval": {"count": 1, "seconds": 1.5},
    }
    asked = []

    def fake(span_name=None):
        asked.append(span_name)
        return counters

    monkeypatch.setattr(tracing, "compile_counters", fake)
    assert compile_reader()(_Run()) == pytest.approx(2.75)
    assert asked == ["count_keys_chunk"]
    assert "cache_retrieval_s=1.500" in capsys.readouterr().err
    counters = {k: {"count": 0, "seconds": 0.0} for k in counters}
    assert compile_reader()(_Run()) is None


def test_compile_reader_gives_nothing_without_the_counter(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert compile_reader()(_Run()) is None


def test_traced_run_reports_compile_s(tmp_path, off_chip, capsys):  # noqa: F811
    root = make_checkout(tmp_path, extra_metric=False)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        m["workloads"].append("tiny-u5")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    from repro import tracing

    # the counter is process-wide: launches of earlier tests are in it too
    earlier = sum(tracing.compile_counters("count_keys_chunk")[p]["seconds"]
                  for p in ("trace", "lower", "backend_compile"))
    assert bench_run.main(["--workload", "tiny-u5", "--seed", str(2**31 + 11), "--seconds", "0.2", "--trace", "1"],
                          root=root) == 0
    captured = capsys.readouterr()
    out = last_json(captured.out)
    assert out["correct"] is True
    compiled = out["metrics"]["compile_s"]["value"] - earlier
    assert 0 < compiled <= out["metrics"]["first_launch_s"]["value"]
    assert "[bench] compile_s=" in captured.err


def test_breakdown_tool_runs_a_cell(tmp_path, off_chip, capsys):  # noqa: F811
    root = make_checkout(tmp_path, extra_metric=False)
    assert scopes.main(["--workload", "tiny-u5", "--seed", str(2**32 + 5), "--launches", "2"], root=root) == 0
    out = last_json(capsys.readouterr().out)
    assert out["workload"] == "tiny-u5" and out["launches"] == 2
    assert out["warm_launch_compiles"]["backend_compile"]["count"] >= 1
    assert out["colorings_per_s_untraced"] > 0 and out["colorings_per_s_traced"] > 0
    assert out["scoped_ops"] > 0 and out["launch_scopes_s"] >= 0
    # the CPU profile has no TPU plane: busy time and operations are empty
    assert out["breakdown"]["busy_s"] == 0 and out["breakdown"]["device_ops"] == []

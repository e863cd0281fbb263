"""The ``edges`` backend's chunk: priced as the TPU program lays it out, and
picked by lanes filled.

On a TPU (lanes 128) the ``edges`` backend prices a launch of ``B``
colorings as one figure (:meth:`CostModel.edges_chunk_elements`) and takes,
among the chunks that fit, the one with the fewest padded aggregation lanes
a coloring.  With one lane (the CPU) every chunk ties and the pick is the
largest that fits, as before.  Everything here is computed from shapes: no
graph of the deployment's size is built.
"""

import types

import pytest

from repro import tracing
from repro.core import CountingEngine, get_template, rmat_graph
from repro.exec.base import EngineBackend
from repro.exec.mesh import MeshBackend
from repro.plan.cost import (
    DEVICE_BUDGET_FRACTION,
    CostModel,
    pick_chunk_by_lanes,
)
from repro.plan.ir import build_template_plan

GIB = 1 << 30
#: ``memory_stats()["bytes_limit"]`` of one TPU v5e chip (15.75 GiB).
V5E_BYTES_LIMIT = 16_909_336_064
#: The Graph500 scale-20 cell's graph: vertices and directed edges.
SCALE20 = types.SimpleNamespace(n=1 << 20, num_directed=31_404_412)
#: u7's column batch on a TPU: its widest passive state.
U7_COLUMN_BATCH = 35


def _u7_cost():
    plan = build_template_plan([get_template("u7")])
    cost = CostModel(plan, SCALE20)
    cost.lanes = 128
    return cost


def _edges_pick(cost, budget):
    return pick_chunk_by_lanes(
        lambda b: cost.edges_chunk_bytes(b, U7_COLUMN_BATCH),
        lambda b: cost.edges_lane_slots(b, U7_COLUMN_BATCH),
        budget,
    )


def _lanes128_pick(budget, want):
    """u7's sweeps are 7, 35 and 35 columns; rows a coloring by chunk
    ``B``, ``(ceil(7B/128) + 2 ceil(35B/128)) / B``, are 3.0 (1), 1.5 (2),
    1.0 (3), 0.71 (7), 0.70 (10), 0.64 (14), 0.61 (18): the pick is the
    fewest among the chunks that fit."""
    cost = _u7_cost()
    assert cost.edges_sweep_widths(U7_COLUMN_BATCH) == [7, 35, 35]
    got = _edges_pick(cost, budget)
    assert got == want
    assert cost.edges_chunk_bytes(got, U7_COLUMN_BATCH) <= budget
    rows = cost.edges_lane_slots(got, U7_COLUMN_BATCH) / got
    for b in range(1, 65):
        if cost.edges_chunk_bytes(b, U7_COLUMN_BATCH) <= budget:
            assert rows <= cost.edges_lane_slots(b, U7_COLUMN_BATCH) / b


def _lanes1_parent_pick(backend, budget, want):
    """With one lane, ``edges`` and another local backend pick the
    parent's chunk (the largest whose per-coloring figure fits) and keep
    its figure."""
    g = rmat_graph(300, 1500, seed=2)
    eng = CountingEngine(g, [get_template("u7")], backend=backend, memory_budget_bytes=budget)
    assert eng.cost.lanes == 1
    assert eng.chunk_size == want
    assert eng.predicted_peak_bytes() == want * eng.bytes_per_coloring()


def _mesh_figure_unchanged():
    """The mesh keeps its own per-shard figures and the base chunk rule."""
    plan = build_template_plan([get_template("u12")])
    cost = CostModel(plan, SCALE20, fusion_slack=1.0)
    cost.lanes = 128
    assert cost.mesh_resident_elements(262_144, 128) == 771_751_936
    assert cost.mesh_transient_elements(1 << 20, 7_936_680, 128) == 402_653_184
    assert cost.mesh_transient_elements(524_288, 1_984_170, 128) == 321_082_624
    assert MeshBackend.chunk_bytes is EngineBackend.chunk_bytes
    assert MeshBackend.pick_chunk_size is EngineBackend.pick_chunk_size


def _chunk18_refused_on_v5e():
    """u7's chunk 18 does not compile on a v5e at scale 20 (15.82 of 15.75
    GiB); its figure exceeds the budget.  Chunk 10, the pick there, fits
    and compiles."""
    budget = int(V5E_BYTES_LIMIT * DEVICE_BUDGET_FRACTION)
    cost = _u7_cost()
    assert cost.edges_chunk_bytes(18, U7_COLUMN_BATCH) == 3128 << 22
    assert cost.edges_chunk_bytes(18, U7_COLUMN_BATCH) > budget
    assert _edges_pick(cost, budget) == 10
    assert cost.edges_chunk_bytes(10, U7_COLUMN_BATCH) <= budget


def _gauges_count_u7_lanes(lanes, slots):
    """``edges_lane_slots`` and ``edges_lane_used`` for a launch of two u7
    colorings: 14, 70 and 70 useful lanes, in three 128-lane rows on a TPU."""
    g = rmat_graph(300, 1500, seed=2)
    eng = CountingEngine(g, [get_template("u7")], backend="edges", column_batch=U7_COLUMN_BATCH)
    eng.cost.lanes = lanes
    eng.backend_impl.publish_gauges(2)
    got = tracing.gauges()
    assert (got["edges_lane_slots"], got["edges_lane_used"]) == (slots, 154)


CASES = [
    pytest.param(_lanes128_pick, (2 * GIB, 1), id="lanes128-2GiB"),
    pytest.param(_lanes128_pick, (int(3.5 * GIB), 3), id="lanes128-3.5GiB"),
    pytest.param(_lanes128_pick, (6 * GIB, 7), id="lanes128-6GiB"),
    pytest.param(_lanes128_pick, (int(7.875 * GIB), 10), id="lanes128-7.875GiB"),
    pytest.param(_lanes128_pick, (9 * GIB, 14), id="lanes128-9GiB"),
    pytest.param(_lanes128_pick, (16 * GIB, 18), id="lanes128-16GiB"),
    pytest.param(
        _lanes128_pick, (int(V5E_BYTES_LIMIT * DEVICE_BUDGET_FRACTION), 10), id="lanes128-v5e"
    ),
    pytest.param(_lanes1_parent_pick, ("edges", 1 << 20, 3), id="lanes1-edges-1MiB"),
    pytest.param(_lanes1_parent_pick, ("edges", 4 << 20, 15), id="lanes1-edges-4MiB"),
    pytest.param(_lanes1_parent_pick, ("edges", 32 << 20, 64), id="lanes1-edges-32MiB"),
    pytest.param(_lanes1_parent_pick, ("ell", 4 << 20, 1), id="lanes1-ell-4MiB"),
    pytest.param(_lanes1_parent_pick, ("ell", 32 << 20, 14), id="lanes1-ell-32MiB"),
    pytest.param(_mesh_figure_unchanged, (), id="mesh-figure"),
    pytest.param(_chunk18_refused_on_v5e, (), id="u7-chunk18-v5e"),
    pytest.param(_gauges_count_u7_lanes, (128, 384), id="gauges-lanes128"),
    pytest.param(_gauges_count_u7_lanes, (1, 154), id="gauges-lanes1"),
]


@pytest.mark.parametrize("check,args", CASES)
def test_edges_chunk(check, args):
    check(*args)

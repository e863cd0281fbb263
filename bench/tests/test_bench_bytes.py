"""The compulsory-byte count of ``dp_roofline_share``, by hand and across backends."""

from __future__ import annotations

import pytest

import jax

from bench.metrics.dp_roofline_share import engine_launch_bytes, launch_bytes, stage_terms
from bench.treelets import plan_tree

U3 = ((0, 1), (1, 2))
U7 = ((0, 1), (1, 2), (1, 3), (0, 4), (4, 5), (4, 6))


def test_u3_by_hand():
    n, e, b = 10, 30, 3
    # root 1; stage {1,2}: leaf + leaf -> C(3,2)=3 sets; stage {0,1,2}: that + leaf -> 1 set
    # a leaf state counts as the colouring, one value per vertex
    stage1 = 8 * e + 4 * b * n * (1 + 1 + 3)
    stage2 = 8 * e + 4 * b * n * (3 + 1 + 1)
    assert stage_terms([(U3, 3)], n, e, b) == [
        {"edges": 8 * e, "active": 4 * b * n, "passive": 4 * b * n, "output": 4 * b * n * 3},
        {"edges": 8 * e, "active": 4 * b * n * 3, "passive": 4 * b * n, "output": 4 * b * n},
    ]
    assert launch_bytes([(U3, 3)], n, e, b) == stage1 + stage2 == 16 * e + 40 * b * n


def test_u7_by_hand():
    n, e, b = 1 << 20, 31_402_926, 2
    # root 1 (first vertex of degree 3); stages, active + passive -> output colour sets:
    #   {1,3}       leaf + leaf -> C(7,2) = 21
    #   {1,2,3}     21   + leaf -> C(7,3) = 35
    #   {0,4,5,6}   leaf + 35   -> C(7,4) = 35   ({4,5,6} shares the form of {1,2,3})
    #   all         35   + 35   -> C(7,7) = 1
    columns = (1 + 1 + 21) + (21 + 1 + 35) + (1 + 35 + 35) + (35 + 35 + 1)
    assert columns == 222
    plan = plan_tree(U7, 7)
    assert [(s.active_size, s.passive_size, s.size) for s in plan.stages] == [(1, 1, 2), (2, 1, 3), (1, 3, 4), (3, 4, 7)]
    assert plan.automorphisms == 8
    assert launch_bytes([(U7, 7)], n, e, b) == 4 * 8 * e + 4 * b * n * columns
    # about 1.43 GB of compulsory traffic per colouring at scale 20
    assert launch_bytes([(U7, 7)], n, e, b) / b == pytest.approx(1.43e9, rel=0.01)


def test_shared_stage_counts_once():
    n, e, b = 100, 400, 1
    assert launch_bytes([(U7, 7), (U7, 7)], n, e, b) == launch_bytes([(U7, 7)], n, e, b)


def test_same_count_for_every_backend():
    from repro.core import CountingEngine, get_template, rmat_graph

    g = rmat_graph(512, 3000, seed=3)
    t = get_template("u7")
    engines = [
        CountingEngine(g, [t], backend="edges", chunk_size=4),
        CountingEngine(g, [t], backend="blocked", chunk_size=4, interpret=True),
        CountingEngine(g, [t], mesh=jax.make_mesh((1,), ("dev",)), chunk_size=4),
    ]
    assert [eng.backend for eng in engines] == ["edges", "blocked", "mesh"]
    counts = {engine_launch_bytes(eng) for eng in engines}
    assert counts == {launch_bytes([(U7, 7)], g.n, g.num_directed, 4)}

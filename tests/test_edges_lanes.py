"""The ``edges`` backend's lane-dense aggregation operand.

``EdgesBackend.spmm`` flattens each ``(n, B, c)`` slice to one minor dim,
zero-pads it to a multiple of the lane width (``engine.cost.lanes``: 128 on
a TPU, 1 on the CPU), and runs the edge gather and the sorted
``segment_sum`` scatter-add on that operand.  These tests steer the lane
width by hand, so the TPU's padding runs here on XLA:CPU.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.exec.local as local
from repro.core import CountingEngine, get_template, rmat_graph

#: (B, c) slices: u12's 12/24/28-column tails, a 66-column and a full
#: 128-column batch, u7's (2, 7) leaf and (2, 35) states, a 150-column flat.
SHAPES = [(1, 12), (1, 28), (1, 66), (1, 128), (2, 7), (2, 35), (3, 50)]
#: edges per chunk: the test graph's 2294 directed edges make four, the last ragged.
CHUNK = 700


@pytest.fixture(scope="module")
def edges_engine():
    """An ``edges`` engine whose edge list spans several ragged chunks."""
    g = rmat_graph(300, 1500, seed=2)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(local, "EDGE_CHUNK", CHUNK)
        eng = CountingEngine(g, [get_template("u3")], backend="edges")
    assert eng.backend_impl._src.shape[0] // eng.backend_impl._edge_chunk > 1
    return eng


def _jit_spmm(impl):
    """``impl.spmm`` under a new jit: the trace cache is keyed on the
    function, not on the lane width the test sets."""
    return jax.jit(lambda m: impl.spmm(m))


@jax.jit
def _chunked_sum_3d(src, dst, m):
    """The edge loop on the ``(n, B, c)`` slice as it comes, unflattened."""

    def body(i, acc):
        s = jax.lax.dynamic_slice_in_dim(src, i * CHUNK, CHUNK)
        d = jax.lax.dynamic_slice_in_dim(dst, i * CHUNK, CHUNK)
        return acc + jax.ops.segment_sum(
            m[s], d, num_segments=m.shape[0], indices_are_sorted=True
        )

    return jax.lax.fori_loop(0, src.shape[0] // CHUNK, body, jnp.zeros_like(m))


def _operand_shapes(hlo: str, op: str):
    """Shapes of the first operand of every ``stablehlo.<op>`` in ``hlo``."""
    found = re.findall(rf'"stablehlo\.{op}".*?: \(tensor<([0-9x]+)xf32>', hlo, re.S)
    return [tuple(int(d) for d in s.split("x")) for s in found]


@pytest.mark.parametrize("check", ["values", "lowered"])
@pytest.mark.parametrize("b,c", SHAPES)
def test_edges_spmm_lane_dense(edges_engine, monkeypatch, b, c, check):
    eng = edges_engine
    impl = eng.backend_impl
    n = eng.graph.n
    m = jnp.asarray(np.random.default_rng(b * 1000 + c).random((n, b, c)), jnp.float32)
    width = -(-b * c // 128) * 128
    if check == "values":
        want = np.asarray(_chunked_sum_3d(impl._src, impl._dst, m))
        for lanes in (1, 128):
            monkeypatch.setattr(eng.cost, "lanes", lanes)
            got = np.asarray(_jit_spmm(impl)(m))
            assert got.shape == (n, b, c)
            # zero columns add nothing and every real column sums its edges
            # in the same order: XLA:CPU gives the same bits
            np.testing.assert_array_equal(got, want)
        return
    # gather and scatter-add on one minor dim, padded to the lane width
    for lanes, cols in ((1, b * c), (128, width)):
        monkeypatch.setattr(eng.cost, "lanes", lanes)
        hlo = _jit_spmm(impl).lower(m).as_text()
        assert ("stablehlo.pad" in hlo) == (cols != b * c)
        for op in ("gather", "scatter"):
            shapes = _operand_shapes(hlo, op)
            assert shapes and all(s == (n, cols) for s in shapes), (op, shapes)


@pytest.mark.parametrize("lanes", [1, 128])
def test_lane_pad_counters_count_one_u7_launch(monkeypatch, lanes):
    """One u7 launch at chip's column batch and chunk 2 traces four
    aggregations: the leaf's ``(2, 7)`` twice (two stages read it) and two
    ``(2, 35)`` states, padded 14 -> 128 and 70 -> 128 on a TPU."""
    g = rmat_graph(300, 1500, seed=2)
    eng = CountingEngine(
        g, [get_template("u7")], backend="edges", chunk_size=2, column_batch=35
    )
    monkeypatch.setattr(eng.cost, "lanes", lanes)
    keys = jax.random.key_data(jax.random.split(jax.random.key(0), 2))
    eng.count_keys_chunk(keys)
    padded, columns = (4, 2 * (128 - 14) + 2 * (128 - 70)) if lanes > 1 else (0, 0)
    want = {
        "passive_aggregations": 4,
        "lane_padded_aggregations": padded,
        "lane_pad_columns": columns,
    }
    assert eng.counters == want
    # counted when the launch is traced: a warm launch adds nothing
    eng.count_keys_chunk(keys)
    assert eng.counters == want

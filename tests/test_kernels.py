"""Per-kernel allclose tests vs pure-jnp oracles (interpret mode), with
shape/dtype sweeps and a full kernel-backed Algorithm 5 cross-check.
"""

from functools import partial

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import build_counting_plan, count_colorful_vectorized, get_template
from repro.core.colorsets import build_split_table
from repro.core.counting import _ema_apply
from repro.core.graph import erdos_renyi_graph, grid_graph, rmat_graph
from repro.kernels.spmm_blocked.ops import prepare_operand, spmm_blocked
from repro.kernels.spmm_blocked.ref import spmm_ref


def _rel_err(a, b):
    denom = float(jnp.max(jnp.abs(b))) + 1e-9
    return float(jnp.max(jnp.abs(a - b))) / denom


# ---------------------------------------------------------------------------
# SpMM kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["mxu", "loop"])
@pytest.mark.parametrize(
    "n,e,cols,block,chunk",
    [
        (200, 800, 16, 128, 128),
        (300, 1500, 40, 128, 256),
        (513, 2000, 130, 256, 256),  # ragged n and cols
        (64, 100, 1, 128, 128),      # single column (SpMV)
    ],
)
def test_spmm_blocked_shapes(mode, n, e, cols, block, chunk):
    g = rmat_graph(n, e, seed=n + e)
    op = prepare_operand(g, block_size=block, edge_chunk=chunk)
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.standard_normal((g.n, cols)).astype(np.float32))
    ref = spmm_ref(jnp.asarray(g.src), jnp.asarray(g.dst), g.n, m)
    out = spmm_blocked(op, m, mode=mode, interpret=True)
    assert out.shape == ref.shape
    assert _rel_err(out, ref) < 1e-5


def test_spmm_blocked_dtype_sweep():
    g = erdos_renyi_graph(150, 600, seed=1)
    op = prepare_operand(g, block_size=128, edge_chunk=128)
    rng = np.random.default_rng(0)
    base = rng.standard_normal((g.n, 24))
    for dtype, tol in [(np.float32, 1e-5), (np.float64, 1e-5)]:
        m = jnp.asarray(base.astype(dtype))
        ref = spmm_ref(jnp.asarray(g.src), jnp.asarray(g.dst), g.n, m)
        out = spmm_blocked(op, m, mode="mxu", interpret=True)
        assert _rel_err(out, ref) < tol


def test_spmm_blocked_empty_rows():
    """Isolated vertices must produce zero rows (dummy-pair zeroing path)."""
    import repro.core.graph as G

    # star graph: vertex 0 connected to 1..9; vertices 10..63 isolated
    src = np.array([0] * 9 + list(range(1, 10)), dtype=np.int32)
    dst = np.array(list(range(1, 10)) + [0] * 9, dtype=np.int32)
    order = np.lexsort((src, dst))
    g = G.Graph(n=64, src=src[order], dst=dst[order])
    op = prepare_operand(g, block_size=128, edge_chunk=128)
    m = jnp.ones((64, 8), dtype=jnp.float32)
    out = spmm_blocked(op, m, interpret=True)
    ref = spmm_ref(jnp.asarray(g.src), jnp.asarray(g.dst), g.n, m)
    assert _rel_err(out, ref) < 1e-6
    assert float(jnp.abs(out[10:]).max()) == 0.0


@given(
    n=st.integers(min_value=20, max_value=200),
    e=st.integers(min_value=20, max_value=600),
    cols=st.integers(min_value=1, max_value=48),
    seed=st.integers(min_value=0, max_value=99),
)
@settings(max_examples=10, deadline=None)
def test_spmm_blocked_property(n, e, cols, seed):
    g = erdos_renyi_graph(n, e, seed=seed)
    op = prepare_operand(g, block_size=128, edge_chunk=128)
    m = jnp.asarray(np.random.default_rng(seed).standard_normal((g.n, cols)).astype(np.float32))
    ref = spmm_ref(jnp.asarray(g.src), jnp.asarray(g.dst), g.n, m)
    out = spmm_blocked(op, m, interpret=True)
    assert _rel_err(out, ref) < 1e-5


def test_spmm_linearity_property():
    """SpMM(aX + bY) == a SpMM(X) + b SpMM(Y) — kernel is linear."""
    g = rmat_graph(100, 400, seed=2)
    op = prepare_operand(g, block_size=128, edge_chunk=128)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((g.n, 8)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((g.n, 8)).astype(np.float32))
    lhs = spmm_blocked(op, 2.0 * x + 3.0 * y, interpret=True)
    rhs = 2.0 * spmm_blocked(op, x, interpret=True) + 3.0 * spmm_blocked(op, y, interpret=True)
    assert _rel_err(lhs, rhs) < 1e-4


# ---------------------------------------------------------------------------
# eMA reference (the jnp fused gather-FMA; the eMA-only Pallas kernel was
# removed with kernels/ema — the fused kernels/spmm_ema path is covered by
# tests/test_fused.py)
# ---------------------------------------------------------------------------


def _ema_numpy_oracle(ma, b, idx_a, idx_p):
    n = ma.shape[0]
    n_out, n_splits = idx_a.shape
    out = np.zeros((n, n_out), np.float64)
    for o in range(n_out):
        for t in range(n_splits):
            out[:, o] += np.asarray(ma)[:, idx_a[o, t]].astype(np.float64) * np.asarray(b)[
                :, idx_p[o, t]
            ].astype(np.float64)
    return out


@pytest.mark.parametrize(
    "k,m,m_a,n",
    [
        (5, 3, 1, 100),
        (7, 5, 3, 777),
        (8, 4, 2, 256),
        (6, 6, 3, 333),  # full-size color set (top template)
        (9, 2, 1, 64),
    ],
)
def test_ema_apply_matches_oracle(k, m, m_a, n):
    t = build_split_table(k, m, m_a)
    rng = np.random.default_rng(k * m)
    from repro.core.colorsets import binom

    ma = jnp.asarray(rng.standard_normal((n, binom(k, m_a))).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((n, binom(k, m - m_a))).astype(np.float32))
    ia, ip = jnp.asarray(t.idx_a), jnp.asarray(t.idx_p)
    ref = _ema_numpy_oracle(ma, b, t.idx_a, t.idx_p)
    out = _ema_apply(ma, b, ia, ip)
    assert out.shape == ref.shape == (n, t.n_out)
    assert _rel_err(out, jnp.asarray(ref, jnp.float32)) < 1e-6


# ---------------------------------------------------------------------------
# Full Algorithm 5 running on the Pallas SpMM kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tname", ["u3", "u5-2", "u6"])
def test_full_dp_on_pallas_kernels(tname):
    g = rmat_graph(96, 380, seed=4)
    t = get_template(tname)
    plan = build_counting_plan(t)
    colors = np.random.default_rng(5).integers(0, t.k, size=g.n)

    from repro.core import spmm_edges

    jnp_spmm = partial(spmm_edges, jnp.asarray(g.src), jnp.asarray(g.dst), g.n)
    ref_total = float(count_colorful_vectorized(plan, jnp.asarray(colors), jnp_spmm))

    op = prepare_operand(g, block_size=128, edge_chunk=128)
    kern_spmm = lambda m: spmm_blocked(op, m, interpret=True)
    kern_total = float(
        count_colorful_vectorized(plan, jnp.asarray(colors), kern_spmm)
    )
    assert kern_total == pytest.approx(ref_total, rel=1e-5)


# ---------------------------------------------------------------------------
# Blocked-ELL build: heavy pairs split into fixed-capacity rows
# ---------------------------------------------------------------------------


def _hub_graph():
    """Skewed RMAT whose (block 0, block 0) pair holds far more edges than
    one operand row: the hub pair spans several rows."""
    return rmat_graph(300, 3000, seed=7, a=0.85, b=0.05, c=0.05)


@pytest.mark.parametrize("capacity", [1, 3, 8, 64])
def test_build_blocked_ell_small_capacity_keeps_every_edge(capacity):
    from repro.core.graph import blocked_ell_geometry, build_blocked_ell

    g = _hub_graph()
    bell = build_blocked_ell(g, block_size=64, pair_capacity=capacity)
    geo = blocked_ell_geometry(g, block_size=64, pair_capacity=capacity)
    assert bell.pair_capacity == capacity
    assert bell.n_pairs == geo.n_rows > geo.n_pairs  # some pair spilled
    valid = bell.edge_valid > 0
    rows = np.nonzero(valid)[0]
    dst = bell.pair_dst_block[rows] * 64 + bell.edge_dst_local[valid]
    src = bell.pair_src_block[rows] * 64 + bell.edge_src_local[valid]
    got = np.sort(dst.astype(np.int64) * g.n + src)
    want = np.sort(g.dst.astype(np.int64) * g.n + g.src)
    assert np.array_equal(got, want)  # every edge exactly once
    assert np.all(np.diff(bell.pair_dst_block) >= 0)  # rows sorted by dst block
    assert bell.row_block_ptr[-1] == bell.n_pairs


def test_split_hub_pair_matches_refs():
    """Split rows of a hub pair feed one accumulator: the blocked SpMM and
    the fused SpMM+eMA kernels (interpret mode) match their references."""
    from repro.core.colorsets import binom
    from repro.core.graph import blocked_ell_geometry
    from repro.kernels.spmm_ema.ops import prepare_fused_operand, spmm_ema
    from repro.kernels.spmm_ema.ref import spmm_ema_ref

    g = _hub_graph()
    geo = blocked_ell_geometry(g, block_size=128, pair_capacity=128)
    assert geo.n_rows > geo.n_pairs
    rng = np.random.default_rng(1)
    src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)

    op = prepare_operand(g, block_size=128, edge_chunk=128)
    m = jnp.asarray(rng.standard_normal((g.n, 24)).astype(np.float32))
    out = spmm_blocked(op, m, interpret=True)
    assert _rel_err(out, spmm_ref(src, dst, g.n, m)) < 1e-5

    table = build_split_table(6, 4, 2)
    fused = prepare_fused_operand(g, block_size=128, edge_chunk=128)
    m_p = jnp.asarray(rng.standard_normal((g.n, binom(6, 2))).astype(np.float32))
    m_a = jnp.asarray(rng.standard_normal((g.n, binom(6, 2))).astype(np.float32))
    got = spmm_ema(fused, m_p, m_a, table.idx_a, table.idx_p, interpret=True)
    ref = spmm_ema_ref(src, dst, g.n, m_p, m_a, jnp.asarray(table.idx_a), jnp.asarray(table.idx_p))
    assert _rel_err(got, ref) < 1e-5

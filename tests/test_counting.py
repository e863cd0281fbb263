"""The exactness chain (paper §VI-H / Fig 14):

brute-force colorful == traversal (Algorithm 2) == vectorized (Algorithm 5),
per coloring; and the multi-iteration estimator converges to the exact
embedding count.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    brute_force_colorful,
    brute_force_embeddings,
    build_counting_plan,
    count_colorful_traversal,
    count_colorful_vectorized,
    erdos_renyi_graph,
    estimate_embeddings,
    get_template,
    grid_graph,
    normalize_count,
    path_template,
    random_tree_template,
    rmat_graph,
    spmm_edges,
    spmm_ell,
    star_template,
)

TEMPLATES_SMALL = ["u3", "path4", "star4", "u5-1", "u5-2", "u6", "u7"]


def _spmm(graph):
    return partial(spmm_edges, jnp.asarray(graph.src), jnp.asarray(graph.dst), graph.n)


@pytest.mark.parametrize("tname", TEMPLATES_SMALL)
@pytest.mark.parametrize(
    "graph",
    [grid_graph(4, 4), erdos_renyi_graph(24, 60, seed=7), rmat_graph(32, 96, seed=5)],
    ids=["grid4x4", "er24", "rmat32"],
)
def test_exactness_chain_per_coloring(tname, graph):
    t = get_template(tname)
    plan = build_counting_plan(t)
    rng = np.random.default_rng(42)
    colors = rng.integers(0, t.k, size=graph.n)

    bf = brute_force_colorful(graph, t, colors)
    trav = count_colorful_traversal(plan, graph, colors) / plan.automorphisms
    vec = float(
        count_colorful_vectorized(plan, jnp.asarray(colors), _spmm(graph))
    ) / plan.automorphisms

    assert trav == pytest.approx(bf, rel=1e-9), "traversal != brute force"
    assert vec == pytest.approx(bf, rel=1e-5), "vectorized != brute force (Fig 14 bound)"


@pytest.mark.parametrize("tname", ["u3", "u5-2", "path5"])
def test_spmm_variants_agree(tname):
    graph = erdos_renyi_graph(40, 120, seed=3)
    t = get_template(tname)
    plan = build_counting_plan(t)
    colors = jnp.asarray(np.random.default_rng(0).integers(0, t.k, size=graph.n))
    v_edges = float(count_colorful_vectorized(plan, colors, _spmm(graph)))
    nbr, mask = graph.ell()
    v_ell = float(
        count_colorful_vectorized(
            plan, colors, partial(spmm_ell, jnp.asarray(nbr), jnp.asarray(mask))
        )
    )
    assert v_ell == pytest.approx(v_edges, rel=1e-5)


def test_estimator_converges_to_exact():
    graph = erdos_renyi_graph(30, 90, seed=11)
    t = get_template("u3")  # small template -> low variance
    exact = brute_force_embeddings(graph, t)
    res = estimate_embeddings(graph, t, iterations=300, seed=0)
    assert res.mean == pytest.approx(exact, rel=0.05)


def test_estimator_unbiased_across_templates():
    graph = grid_graph(5, 5)
    for tname in ["path4", "star4"]:
        t = get_template(tname)
        exact = brute_force_embeddings(graph, t)
        res = estimate_embeddings(graph, t, iterations=400, seed=2)
        # 3-sigma band of the iteration mean
        sem = res.std / np.sqrt(res.iterations)
        assert abs(res.mean - exact) < 4 * sem + 1e-6, (tname, res.mean, exact, sem)


@given(
    n=st.integers(min_value=8, max_value=28),
    e=st.integers(min_value=10, max_value=80),
    k=st.integers(min_value=3, max_value=5),
    seed=st.integers(min_value=0, max_value=1000),
)
@settings(max_examples=15, deadline=None)
def test_property_vectorized_equals_traversal(n, e, k, seed):
    """Property: for ANY random graph/template/coloring, Algorithm 5 == Algorithm 2."""
    graph = erdos_renyi_graph(n, e, seed=seed)
    t = random_tree_template(k, seed=seed + 1)
    plan = build_counting_plan(t)
    colors = np.random.default_rng(seed).integers(0, k, size=n)
    trav = count_colorful_traversal(plan, graph, colors)
    vec = float(count_colorful_vectorized(plan, jnp.asarray(colors), _spmm(graph)))
    assert vec == pytest.approx(trav, rel=1e-5, abs=1e-6)


@given(seed=st.integers(min_value=0, max_value=500))
@settings(max_examples=10, deadline=None)
def test_property_count_invariant_under_vertex_relabeling(seed):
    """Permuting graph vertex ids (and the coloring with them) preserves counts."""
    from repro.core.graph import Graph, _canonicalize

    rng = np.random.default_rng(seed)
    g = erdos_renyi_graph(20, 50, seed=seed)
    perm = rng.permutation(g.n).astype(np.int32)
    g2 = _canonicalize(g.n, perm[g.src], perm[g.dst])
    t = get_template("u5-2")
    plan = build_counting_plan(t)
    colors = rng.integers(0, t.k, size=g.n)
    colors2 = np.empty_like(colors)
    colors2[perm] = colors
    v1 = float(count_colorful_vectorized(plan, jnp.asarray(colors), _spmm(g)))
    v2 = float(count_colorful_vectorized(plan, jnp.asarray(colors2), _spmm(g2)))
    assert v1 == pytest.approx(v2, rel=1e-5)


def test_partition_root_choice_invariance():
    """Any partition root must give the same colorful count (plan property)."""
    graph = erdos_renyi_graph(25, 70, seed=9)
    t = get_template("u6")
    colors = np.random.default_rng(1).integers(0, t.k, size=graph.n)
    vals = []
    for root in range(t.k):
        plan = build_counting_plan(t, root=root)
        vals.append(
            float(count_colorful_vectorized(plan, jnp.asarray(colors), _spmm(graph)))
        )
    assert np.allclose(vals, vals[0], rtol=1e-5)


def test_counts_nonnegative_and_zero_on_empty():
    from repro.core.graph import Graph

    empty = Graph(n=10, src=np.zeros(0, np.int32), dst=np.zeros(0, np.int32))
    t = path_template(3)
    plan = build_counting_plan(t)
    colors = jnp.asarray(np.arange(10) % 3)
    v = float(count_colorful_vectorized(plan, colors, _spmm(empty)))
    assert v == 0.0


@given(seed=st.integers(min_value=0, max_value=300))
@settings(max_examples=10, deadline=None)
def test_property_disjoint_union_additivity(seed):
    """Counts over a disjoint union of two graphs = sum of the counts
    (connectivity property of tree embeddings)."""
    from repro.core.graph import Graph

    g1 = erdos_renyi_graph(14, 30, seed=seed)
    g2 = erdos_renyi_graph(12, 26, seed=seed + 1)
    union = Graph(
        n=g1.n + g2.n,
        src=np.concatenate([g1.src, g2.src + g1.n]),
        dst=np.concatenate([g1.dst, g2.dst + g1.n]),
    )
    t = get_template("u5-2")
    plan = build_counting_plan(t)
    rng = np.random.default_rng(seed)
    c1 = rng.integers(0, t.k, size=g1.n)
    c2 = rng.integers(0, t.k, size=g2.n)
    cu = np.concatenate([c1, c2])
    v1 = float(count_colorful_vectorized(plan, jnp.asarray(c1), _spmm(g1)))
    v2 = float(count_colorful_vectorized(plan, jnp.asarray(c2), _spmm(g2)))
    vu = float(count_colorful_vectorized(plan, jnp.asarray(cu), _spmm(union)))
    assert vu == pytest.approx(v1 + v2, rel=1e-5, abs=1e-6)


def test_path_counting_known_closed_form():
    """Complete graph K_n: # of k-paths = C(n,k) * k!/2 — exact check."""
    from itertools import combinations
    from math import comb, factorial
    from repro.core.graph import _canonicalize

    n, k = 9, 4
    pairs = np.array(list(combinations(range(n), 2)), dtype=np.int32)
    g = _canonicalize(n, pairs[:, 0], pairs[:, 1])
    exact = comb(n, k) * factorial(k) // 2
    t = path_template(k)
    assert brute_force_embeddings(g, t) == pytest.approx(exact)

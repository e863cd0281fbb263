"""CountingEngine tests: backend auto-selection, batched-vs-sequential
bit-exactness, multi-template sharing, and the memory-budget chunk picker."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CountingEngine,
    build_counting_plan,
    count_colorful_vectorized,
    get_template,
    grid_graph,
    pick_chunk_size,
    rmat_graph,
    select_backend,
    spmm_edges,
)
from repro.core.engine import DtypePolicy, MAX_CHUNK_SIZE, sub_template_canonical
from repro.core.graph import Graph


def _star_graph(n: int) -> Graph:
    """Hub 0 connected to all others — the ELL worst case (max_deg = n-1)."""
    src = np.concatenate([np.zeros(n - 1, np.int32), np.arange(1, n, dtype=np.int32)])
    dst = np.concatenate([np.arange(1, n, dtype=np.int32), np.zeros(n - 1, np.int32)])
    order = np.lexsort((src, dst))
    return Graph(n=n, src=src[order], dst=dst[order])


# ---------------------------------------------------------------------------
# Backend auto-selection
# ---------------------------------------------------------------------------


def test_backend_star_graph_picks_edges_not_ell():
    # high max-degree: ELL padding would cost n * (n-1) slots for 2(n-1) edges
    assert select_backend(_star_graph(600), platform="cpu") == "edges"


def test_backend_flat_degrees_pick_ell():
    # grid: max_deg == 4 == avg degree, padding waste is bounded
    assert select_backend(grid_graph(30, 30), platform="cpu") == "ell"


def test_backend_tiny_graph_picks_dense():
    assert select_backend(grid_graph(8, 8), platform="cpu") == "dense"


@pytest.mark.parametrize(
    "n,edges,picks_blocked",
    [
        # 256 block pairs, ~900 edges each: the operand is mostly edges
        (4096, 200_000, True),
        # scale-16 RMAT at edge factor 16: most block pairs hold a few
        # edges, each padded to a full row — the XLA backends serve it
        (1 << 16, 16 << 16, False),
    ],
)
def test_backend_large_tpu_graph_picks_blocked(n, edges, picks_blocked):
    g = rmat_graph(n, edges, seed=1)
    name, reason = select_backend(g, platform="tpu", explain=True)
    assert (name == "blocked") == picks_blocked, reason


def test_engine_resolves_auto_backend():
    eng = CountingEngine(_star_graph(600), get_template("u3"))
    assert eng.backend == "edges"


# ---------------------------------------------------------------------------
# Correctness vs the reference DP, across backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["edges", "ell", "dense"])
def test_engine_raw_counts_match_reference(backend):
    g = rmat_graph(300, 1500, seed=2)
    t = get_template("u6")
    plan = build_counting_plan(t)
    colors = np.random.default_rng(0).integers(0, t.k, size=g.n)
    ref = float(
        count_colorful_vectorized(
            plan, jnp.asarray(colors), partial(spmm_edges, jnp.asarray(g.src), jnp.asarray(g.dst), g.n)
        )
    )
    eng = CountingEngine(g, [t], backend=backend)
    got = float(eng.raw_counts(colors)[0])
    assert got == pytest.approx(ref, rel=1e-5)


@pytest.mark.parametrize("backend", ["edges", "mesh"])
def test_edge_chunked_reduction_matches_reference(backend, monkeypatch):
    """Edge lists longer than ``EDGE_CHUNK`` are reduced a chunk at a time,
    the last chunk padded; with a chunk that does not divide ``|E|`` the
    counts still match the reference DP."""
    import repro.exec.local
    import repro.plan.cost
    from repro.kernels.spmm_blocked.ref import spmm_ref

    g = rmat_graph(300, 1500, seed=2)
    chunk = 700
    assert g.num_directed > 2 * chunk and g.num_directed % chunk
    monkeypatch.setattr(repro.exec.local, "EDGE_CHUNK", chunk)
    monkeypatch.setattr(repro.plan.cost, "EDGE_CHUNK", chunk)
    t = get_template("u6")
    plan = build_counting_plan(t)
    colors = np.random.default_rng(0).integers(0, t.k, size=g.n)
    src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)
    ref = float(count_colorful_vectorized(plan, jnp.asarray(colors), partial(spmm_edges, src, dst, g.n)))
    if backend == "mesh":
        eng = CountingEngine(g, [t], mesh=jax.make_mesh((1,), ("dev",)))
    else:
        eng = CountingEngine(g, [t], backend=backend)
        m = jnp.asarray(np.random.default_rng(1).standard_normal((g.n, 2, 3)), jnp.float32)
        got_spmm = eng.backend_impl.spmm(m).reshape(g.n, 6)
        assert np.allclose(got_spmm, spmm_ref(src, dst, g.n, m.reshape(g.n, 6)), rtol=1e-5, atol=1e-5)
    assert float(eng.raw_counts(colors)[0]) == pytest.approx(ref, rel=1e-5)


def test_engine_blocked_pallas_backend_matches_edges():
    g = rmat_graph(200, 800, seed=3)
    t = get_template("u5-2")
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    ref = CountingEngine(g, [t], backend="edges", chunk_size=2).count_keys(keys)
    got = CountingEngine(g, [t], backend="blocked", interpret=True, chunk_size=2).count_keys(keys)
    assert np.allclose(got, ref, rtol=1e-5)


def test_engine_custom_spmm_fn():
    g = rmat_graph(300, 1200, seed=4)
    t = get_template("u5-1")
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    ref = CountingEngine(g, [t], backend="edges", chunk_size=3).count_keys(keys)
    custom = partial(spmm_edges, jnp.asarray(g.src), jnp.asarray(g.dst), g.n)
    got = CountingEngine(g, [t], spmm_fn=custom, chunk_size=3).count_keys(keys)
    assert got.shape == ref.shape
    assert np.allclose(got, ref, rtol=1e-6)


def test_estimates_beyond_fp32_range_stay_finite():
    """Raw totals come back in fp32 and are scaled to estimates in float64:
    an estimate past the fp32 maximum (u12 on a scale-20 RMAT) is finite."""
    g = rmat_graph(300, 1500, seed=4)
    t = get_template("u7")
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    base = CountingEngine(g, [t], backend="edges", chunk_size=2).count_keys(keys)
    # every aggregation scaled by s: estimates scale by s**(k-1) to 1e39,
    # the raw totals behind them (1e39 * 7!/7**7 * aut) stay inside fp32
    s = float((1e39 / base.max()) ** (1 / (t.k - 1)))
    edges = partial(spmm_edges, jnp.asarray(g.src), jnp.asarray(g.dst), g.n)
    big = CountingEngine(g, [t], spmm_fn=lambda m: edges(m) * s, chunk_size=2).count_keys(keys)
    assert np.all(np.isfinite(big)) and big.max() > float(np.finfo(np.float32).max)
    assert np.allclose(big, base * s ** (t.k - 1), rtol=1e-4)


# ---------------------------------------------------------------------------
# Batched vs sequential: same keys => bit-exact same estimates
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["edges", "ell"])
def test_batched_equals_sequential_bit_exact(backend):
    g = rmat_graph(400, 2400, seed=5)
    t = get_template("u6")
    keys = jax.random.split(jax.random.PRNGKey(0), 13)  # ragged: 13 = 2*5 + 3
    batched = CountingEngine(g, [t], backend=backend, chunk_size=5).count_keys(keys)
    sequential = CountingEngine(g, [t], backend=backend, chunk_size=1).count_keys(keys)
    assert np.array_equal(batched, sequential)


def test_estimate_deterministic_across_chunk_sizes():
    g = rmat_graph(300, 1500, seed=6)
    t = get_template("u5-2")
    r8 = CountingEngine(g, [t], chunk_size=8).estimate(iterations=16, seed=3)[0]
    r3 = CountingEngine(g, [t], chunk_size=3).estimate(iterations=16, seed=3)[0]
    assert np.array_equal(r8.per_iteration, r3.per_iteration)
    assert r8.mean == r3.mean


# ---------------------------------------------------------------------------
# Multi-template sharing
# ---------------------------------------------------------------------------


def test_multi_template_matches_independent_runs():
    g = rmat_graph(300, 1500, seed=2)
    treelets = [get_template(n) for n in ("path6", "star6", "bintree6", "u6")]
    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    multi = CountingEngine(g, treelets, chunk_size=4).count_keys(keys)
    assert multi.shape == (8, len(treelets))
    for ti, t in enumerate(treelets):
        single = CountingEngine(g, [t], chunk_size=4).count_keys(keys)[:, 0]
        assert np.allclose(multi[:, ti], single, rtol=1e-6), t.name


def test_multi_template_shares_subtemplate_state():
    """Isomorphic sub-templates across templates map to one canonical key,
    so the shared DP computes strictly fewer stages than the independent
    runs would (leaf + coinciding passive sub-templates)."""
    g = rmat_graph(200, 800, seed=1)
    treelets = [get_template(n) for n in ("path6", "star6", "u6")]
    eng = CountingEngine(g, treelets)
    unique_keys = {k for canons in eng._canons for k in canons}
    total_subs = sum(len(c) for c in eng._canons)
    assert len(unique_keys) < total_subs  # sharing actually happened
    # all leaves collapse onto a single canonical key
    leaf_key = sub_template_canonical(treelets[0], (0,), 0)
    assert leaf_key == "()"
    assert sum(1 for c in eng._canons for k in c if k == leaf_key) >= 3


def test_multi_template_requires_same_k():
    g = grid_graph(6, 6)
    with pytest.raises(ValueError, match="share one k"):
        CountingEngine(g, [get_template("u3"), get_template("u6")])


def test_shared_passive_grouping_fewer_aggregations():
    """Stages sharing a passive canon run over ONE column-batch sweep: the
    multi-template engine performs strictly fewer passive aggregations than
    the per-stage (unshared) execution would."""
    g = rmat_graph(200, 800, seed=1)
    treelets = [get_template(n) for n in ("path6", "star6", "bintree6", "u6")]
    eng = CountingEngine(g, treelets, backend="edges")
    # the schedule actually contains a shared group
    assert any(len(members) > 1 for members in eng._exec_groups.values())
    colors = np.random.default_rng(0).integers(0, 6, size=g.n)
    assert eng.counters["passive_aggregations"] == 0
    out = eng.raw_counts(colors)
    shared_calls = eng.counters["passive_aggregations"]
    # what the ungrouped execution would launch: one aggregation per
    # (stage, bucketed batch)
    unshared_calls = sum(
        len(eng._stage_tables[(q, j)].batches)
        for members in eng._exec_groups.values()
        for (q, j) in members
    )
    assert 0 < shared_calls < unshared_calls
    # ... and grouping does not change any count
    for ti, t in enumerate(treelets):
        single = CountingEngine(g, [t], backend="edges").raw_counts(colors)[0]
        assert float(out[ti]) == pytest.approx(float(single), rel=1e-6), t.name


def test_single_template_groups_are_singletons_and_exact():
    """Within one template the actives chain stage-to-stage, so grouping
    must not fire — and per-stage behavior is unchanged."""
    g = rmat_graph(150, 600, seed=3)
    t = get_template("star6")
    eng = CountingEngine(g, [t], backend="edges")
    assert all(len(m) == 1 for m in eng._exec_groups.values())
    colors = np.random.default_rng(1).integers(0, 6, size=g.n)
    got = float(eng.raw_counts(colors)[0])
    from repro.core import build_counting_plan, count_colorful_vectorized, spmm_edges

    plan = build_counting_plan(t)
    ref = float(
        count_colorful_vectorized(
            plan,
            jnp.asarray(colors),
            partial(spmm_edges, jnp.asarray(g.src), jnp.asarray(g.dst), g.n),
        )
    )
    assert got == pytest.approx(ref, rel=1e-5)


# ---------------------------------------------------------------------------
# Chunk-size picker / memory budget
# ---------------------------------------------------------------------------


def test_chunk_picker_respects_tiny_budget():
    g = rmat_graph(300, 1500, seed=2)
    t = get_template("u6")
    eng = CountingEngine(g, [t], memory_budget_bytes=1)
    assert eng.chunk_size == 1
    # ... and the engine still produces correct results at chunk 1
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    wide = CountingEngine(g, [t], memory_budget_bytes=1 << 30)
    assert wide.chunk_size > 1
    assert np.array_equal(eng.count_keys(keys), wide.count_keys(keys))


def test_chunk_picker_scales_with_budget_and_is_capped():
    assert pick_chunk_size(1000, 10_000) == 10
    assert pick_chunk_size(1000, 1) == 1
    assert pick_chunk_size(1, 1 << 40) == MAX_CHUNK_SIZE
    # bigger per-coloring footprint => smaller chunk at a fixed budget
    g = rmat_graph(2048, 20_000, seed=1)
    small_t = CountingEngine(g, [get_template("u5-1")])
    big_t = CountingEngine(g, [get_template("u7")])
    assert big_t.bytes_per_coloring() > small_t.bytes_per_coloring()
    assert big_t.chunk_size <= small_t.chunk_size


@pytest.mark.parametrize("bytes_limit", [None, 16 << 30])
def test_default_budget_follows_device_memory(monkeypatch, bytes_limit):
    """The budget is a share of the device's reported memory; the CPU
    constant applies only where the device reports none."""
    from repro.plan import cost

    class FakeDevice:
        def memory_stats(self):
            return None if bytes_limit is None else {"bytes_limit": bytes_limit}

    monkeypatch.setattr(cost.jax, "devices", lambda: [FakeDevice()])
    want = (
        cost.DEFAULT_MEMORY_BUDGET_BYTES
        if bytes_limit is None
        else int(bytes_limit * cost.DEVICE_BUDGET_FRACTION)
    )
    assert cost.default_memory_budget_bytes() == want


def test_compile_cache_dir_respects_env(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins untouched; without it the entry
    points' cache goes to a fixed directory inside the checkout."""
    import os

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.CACHE_DIR == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peak_columns_liveness_bounds():
    """The liveness-aware engine peak is sandwiched between the widest
    single stage (children + output must coexist) and the per-plan in-place
    bound (which counts each leaf separately; the engine shares one
    canonical leaf state, so it can only do better)."""
    t = get_template("u7")
    plan = build_counting_plan(t)
    eng = CountingEngine(rmat_graph(300, 1200, seed=0), [t], plans=[plan])
    assert eng.peak_columns() <= plan.peak_columns()
    assert eng.peak_columns() >= eng._max_stage_columns()


# ---------------------------------------------------------------------------
# Dtype policy
# ---------------------------------------------------------------------------


def test_dtype_policy_resolution():
    p32 = DtypePolicy.resolve("fp32")
    assert p32.store_dtype == jnp.float32 and p32.accum_dtype == jnp.float32
    p16 = DtypePolicy.resolve("bf16")
    assert p16.store_dtype == jnp.bfloat16 and p16.accum_dtype == jnp.float32
    with pytest.raises(ValueError):
        DtypePolicy.resolve("fp8")


def test_bf16_policy_close_to_fp32():
    g = rmat_graph(300, 1500, seed=2)
    t = get_template("u6")
    colors = np.random.default_rng(0).integers(0, t.k, size=g.n)
    f32 = float(CountingEngine(g, [t]).raw_counts(colors)[0])
    b16 = float(CountingEngine(g, [t], dtype_policy="bf16").raw_counts(colors)[0])
    # bf16 storage with fp32 accumulation: ~0.4% worst-case rounding (paper §VI)
    assert b16 == pytest.approx(f32, rel=2e-2)

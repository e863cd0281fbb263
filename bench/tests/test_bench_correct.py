"""How ``correct`` is decided: the reference, its control, and planted faults.

* The reference (``bench/references/tree_dp.py``) agrees with a brute-force
  count of colourful embeddings on tiny graphs.
* Its control, the program's own ``bf16`` storage path, fails the limit that
  the float32 program meets.
* A run whose timed path is broken underneath comes out not correct: an
  answer altered where it is produced, half of each launch's colourings left
  out (their answers copied from the rest), a non-finite answer.
"""

from __future__ import annotations

import itertools
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import run as bench_run
from bench.graphgen import rmat_edges
from bench.references.tree_dp import TreeReference
from bench.tests.test_bench_harness import last_json, make_checkout, off_chip  # noqa: F401  (fixture)
from bench.treelets import colourful_scale, plan_tree

ROOT = Path(__file__).resolve().parents[2]
U7_LIMIT = json.loads((ROOT / "bench" / "limits" / "g500-s20-u7.json").read_text())["max_rel_err"]["limit"]
TEMPLATES = {
    "u3": ((0, 1), (1, 2)),
    "u5-2": ((0, 1), (1, 2), (2, 3), (1, 4)),
    "u7": ((0, 1), (1, 2), (1, 3), (0, 4), (4, 5), (4, 6)),
}


def brute_force_estimate(edges, k, n, src, dst, colours):
    """Colourful embeddings by exhaustive search, scaled as the estimator does."""
    nbrs = [set() for _ in range(n)]
    for u, v in zip(src.tolist(), dst.tolist()):
        nbrs[u].add(v)
    order = [0]
    for _ in range(k - 1):
        order.append(next(w for u, w in itertools.chain(edges, [(b, a) for a, b in edges])
                          if u in order and w not in order))
    parent = {w: next(u for u in order[:i] if (u, w) in edges or (w, u) in edges) for i, w in enumerate(order) if i}
    count = 0

    def extend(i, image, used):
        nonlocal count
        if i == k:
            count += 1
            return
        w = order[i]
        for x in nbrs[image[parent[w]]]:
            if colours[x] not in used:
                image[w] = x
                extend(i + 1, image, used | {colours[x]})
        image.pop(w, None)

    for x in range(n):
        extend(1, {order[0]: x}, {colours[x]})
    return count * colourful_scale(k) / plan_tree(edges, k).automorphisms


@pytest.mark.parametrize("name", ["u3", "u5-2", "u7"])
def test_reference_matches_brute_force(name):
    edges = TEMPLATES[name]
    k = len(edges) + 1
    g = rmat_edges(11, scale=5, edgefactor=3, a=0.45, b=0.2, c=0.2)
    ref = TreeReference(plan_tree(edges, k), g.n, g.src, g.dst, capacity=2 * (3 << 5))
    rng = np.random.default_rng(0)
    for _ in range(3):
        colours = rng.integers(0, k, size=g.n)
        want = brute_force_estimate(edges, k, g.n, g.src, g.dst, colours)
        assert ref.estimate(colours) == pytest.approx(want, rel=1e-6)


def _rel_errs(policy):
    from repro.core import CountingEngine
    from repro.core.graph import Graph
    from repro.core.templates import Template

    g = rmat_edges(2**35 + 1, scale=10, edgefactor=16, a=0.57, b=0.19, c=0.19)
    engine = CountingEngine(Graph(n=g.n, src=g.src, dst=g.dst), [Template("u7", TEMPLATES["u7"])],
                            dtype_policy=policy, chunk_size=4)
    keys = bench_run.KeyStream(5, 4).next()
    got = engine.count_keys_chunk(keys)[:, 0]
    ref = TreeReference(plan_tree(TEMPLATES["u7"], 7), g.n, g.src, g.dst, capacity=2 * (16 << 10))
    want = [ref.estimate(jax.random.randint(jnp.asarray(key), (g.n,), 0, 7)) for key in keys]
    return np.abs(got - want) / np.abs(want)


def test_float32_program_meets_the_limit_and_bf16_control_fails_it():
    # the number compared is the largest error over a run's sampled colourings
    assert _rel_errs("fp32").max() <= U7_LIMIT
    assert _rel_errs("bf16").max() > 3 * U7_LIMIT


def _altered(orig):
    def count_keys_chunk(self, keys):
        out = orig(self, keys).copy()
        out[0] *= 1 + 1e-3
        return out
    return count_keys_chunk


def _half_left_out(orig):
    def count_keys_chunk(self, keys):
        keys = np.asarray(keys)
        half = orig(self, keys[: len(keys) // 2])
        return np.concatenate([half, half])[: len(keys)]
    return count_keys_chunk


def _non_finite(orig):
    def count_keys_chunk(self, keys):
        out = orig(self, keys).copy()
        out[-1] = np.nan
        return out
    return count_keys_chunk


@pytest.mark.parametrize("fault", [_altered, _half_left_out, _non_finite])
def test_broken_timed_path_is_not_correct(fault, tmp_path, off_chip, monkeypatch, capsys):  # noqa: F811
    from repro.core.engine import CountingEngine

    traffic = {
        "kind": "engine_stream",
        "templates": [{"name": "u7", "k": 7, "edges": [list(e) for e in TEMPLATES["u7"]]}],
        "chunk": 4,
        "check_colorings": 64,
    }
    root = make_checkout(tmp_path, traffic=traffic, extra_metric=False)
    monkeypatch.setattr(CountingEngine, "count_keys_chunk", fault(CountingEngine.count_keys_chunk))
    assert bench_run.main(["--workload", "tiny-u5", "--seed", "77", "--seconds", "0.2"], root=root) == 0
    out = last_json(capsys.readouterr().out)
    assert out["correct"] is False
    assert out["failed"] >= 1

"""CountingEngine mesh-backend tests (4 host devices via subprocess — the
test process itself must keep the default single-device view).

The acceptance bar for the mesh backend: counts comparable to the local
engine within fp32 tolerance for u3–u7 templates on a 4-virtual-device mesh,
identical PRNG-key -> coloring mapping, multi-template sharing, the dtype
policy, and the degree-balancing relabel all working under shard_map.
"""

import os
import subprocess
import sys

import pytest

# subprocess smokes over 4 virtual devices: the slow check.sh lane
pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_child(code: str, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=timeout
    )
    assert proc.returncode == 0, f"child failed:\nstdout={proc.stdout}\nstderr={proc.stderr}"
    return proc.stdout


def test_mesh_backend_matches_local_u3_to_u7():
    """Mesh counts == local engine counts (fp32 tolerance) for every paper
    template from u3 to u7, both for a fixed coloring (raw_counts) and for
    the batched PRNG-key path (count_keys shares the coloring draw)."""
    out = _run_child(
        r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import CountingEngine, get_template, rmat_graph

g = rmat_graph(240, 1200, seed=5)
mesh = jax.make_mesh((4,), ("dev",))
for tname in ("u3", "u5-1", "u5-2", "u6", "u7"):
    t = get_template(tname)
    colors = np.random.default_rng(3).integers(0, t.k, size=g.n)
    local = CountingEngine(g, [t], backend="edges")
    dist = CountingEngine(g, [t], backend="mesh", mesh=mesh, column_batch=8)
    a = float(local.raw_counts(colors)[0])
    b = float(dist.raw_counts(colors)[0])
    assert abs(a - b) <= 1e-5 * max(abs(a), 1.0), (tname, a, b)
    print("RAW_MATCH", tname, a)

# batched key path for one mid-size template: one jit, lax.map over chunks
t = get_template("u6")
keys = jax.random.split(jax.random.PRNGKey(1), 7)  # ragged: 7 = 2*3 + 1
ref = CountingEngine(g, [t], backend="edges", chunk_size=3).count_keys(keys)
got = CountingEngine(g, [t], backend="mesh", mesh=mesh, column_batch=8,
                     chunk_size=3).count_keys(keys)
assert np.allclose(got, ref, rtol=1e-5), (got, ref)
print("KEYS_MATCH")
"""
    )
    assert out.count("RAW_MATCH") == 5
    assert "KEYS_MATCH" in out


def test_mesh_backend_modes_and_policy():
    """Degree balancing, compressed gathers, and the bf16 dtype policy all
    agree with the local fp32 reference."""
    out = _run_child(
        r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import CountingEngine, get_template, rmat_graph

g = rmat_graph(300, 2400, seed=3, a=0.7, b=0.12, c=0.12)  # skewed
t = get_template("u6")
mesh = jax.make_mesh((2, 2), ("data", "model"))
colors = np.random.default_rng(0).integers(0, t.k, size=g.n)
ref = float(CountingEngine(g, [t], backend="edges").raw_counts(colors)[0])

for tag, kw, tol in (
    ("balanced", dict(balance_degrees=True), 1e-5),
    ("bf16_gather", dict(gather_dtype=jnp.bfloat16), 2e-2),
    ("bf16_policy", dict(dtype_policy="bf16"), 2e-2),
):
    eng = CountingEngine(g, [t], backend="mesh", mesh=mesh, column_batch=8, **kw)
    got = float(eng.raw_counts(colors)[0])
    assert abs(got - ref) <= tol * max(abs(ref), 1.0), (tag, got, ref)
    print("MODE_OK", tag)
"""
    )
    assert out.count("MODE_OK") == 3


def test_mesh_backend_multi_template_sharing():
    """Multi-template mesh run == independent local runs, and the shared
    canonical schedule computes fewer stages than the plans would alone."""
    out = _run_child(
        r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import CountingEngine, get_template, rmat_graph

g = rmat_graph(240, 1200, seed=2)
mesh = jax.make_mesh((4,), ("dev",))
treelets = [get_template(n) for n in ("path6", "star6", "u6")]
keys = jax.random.split(jax.random.PRNGKey(7), 4)
eng = CountingEngine(g, treelets, backend="mesh", mesh=mesh, column_batch=8,
                     chunk_size=2)
multi = eng.count_keys(keys)
unique = {k for canons in eng._canons for k in canons}
assert len(unique) < sum(len(c) for c in eng._canons)  # sharing happened
for ti, t in enumerate(treelets):
    single = CountingEngine(g, [t], backend="edges", chunk_size=2).count_keys(keys)[:, 0]
    assert np.allclose(multi[:, ti], single, rtol=1e-5), t.name
    print("TEMPLATE_OK", t.name)
"""
    )
    assert out.count("TEMPLATE_OK") == 3


def test_mesh_chunk_picker_uses_shard_model():
    """The mesh memory model is per shard: budget-driven chunk picking works
    and chunked vs unchunked estimates agree."""
    out = _run_child(
        r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import CountingEngine, get_template, rmat_graph

g = rmat_graph(240, 1200, seed=2)
t = get_template("u5-2")
mesh = jax.make_mesh((4,), ("dev",))
tiny = CountingEngine(g, [t], backend="mesh", mesh=mesh, column_batch=8,
                      memory_budget_bytes=1)
wide = CountingEngine(g, [t], backend="mesh", mesh=mesh, column_batch=8,
                      memory_budget_bytes=1 << 30)
assert tiny.chunk_size == 1 and wide.chunk_size > 1
assert tiny.bytes_per_coloring() == wide.bytes_per_coloring() > 0
keys = jax.random.split(jax.random.PRNGKey(0), 3)
assert np.allclose(tiny.count_keys(keys), wide.count_keys(keys), rtol=1e-6)
print("CHUNK_OK", wide.chunk_size, wide.bytes_per_coloring())
"""
    )
    assert "CHUNK_OK" in out

"""Distributed runtime tests (8 host devices via subprocess — the test
process itself must keep the default single-device view)."""

import json
import os
import subprocess
import sys

import pytest

# subprocess smokes over 8 virtual devices: the slow check.sh lane
pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_child(code: str, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=timeout
    )
    assert proc.returncode == 0, f"child failed:\nstdout={proc.stdout}\nstderr={proc.stderr}"
    return proc.stdout


def test_distributed_count_matches_single_device():
    out = _run_child(
        r"""
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh, shard_map
from functools import partial
from repro.core import (build_counting_plan, count_colorful_vectorized, get_template,
                        rmat_graph, spmm_edges)
from repro.core.distributed import shard_graph, make_distributed_count_fn

mesh = jax.make_mesh((2, 4), ("data", "model"))
g = rmat_graph(600, 3000, seed=2)
t = get_template("u6")
plan = build_counting_plan(t)
sg = shard_graph(g, 8)
fn = make_distributed_count_fn(plan, mesh, sg.n_padded, sg.edges_per_shard, column_batch=8)
colors = np.random.default_rng(1).integers(0, t.k, size=sg.n_padded).astype(np.int32)
with set_mesh(mesh):
    dist = float(fn(jnp.asarray(colors), jnp.asarray(sg.src), jnp.asarray(sg.dst_local),
                    jnp.asarray(sg.edge_mask)))
ref = float(count_colorful_vectorized(plan, jnp.asarray(colors[:g.n]),
    partial(spmm_edges, jnp.asarray(g.src), jnp.asarray(g.dst), g.n)))
assert abs(dist - ref) / max(abs(ref), 1e-9) < 1e-5, (dist, ref)
print("MATCH", dist, ref)
"""
    )
    assert "MATCH" in out


def test_distributed_count_balance_degrees():
    out = _run_child(
        r"""
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh, shard_map
from functools import partial
from repro.core import (build_counting_plan, count_colorful_vectorized, get_template,
                        rmat_graph, spmm_edges)
from repro.core.distributed import shard_graph, make_distributed_count_fn

mesh = jax.make_mesh((8,), ("data",))
g = rmat_graph(400, 4000, seed=3, a=0.7, b=0.12, c=0.12)  # skewed
t = get_template("u5-2")
plan = build_counting_plan(t)
sg_plain = shard_graph(g, 8)
sg_bal = shard_graph(g, 8, balance_degrees=True)
# round-robin balancing reduces the max per-shard edge padding on skewed graphs
print("PLAIN", sg_plain.edges_per_shard, "BAL", sg_bal.edges_per_shard)
assert sg_bal.edges_per_shard < sg_plain.edges_per_shard, (
    sg_bal.edges_per_shard, sg_plain.edges_per_shard)
colors_g = np.random.default_rng(0).integers(0, t.k, size=g.n).astype(np.int32)
ref = float(count_colorful_vectorized(plan, jnp.asarray(colors_g),
    partial(spmm_edges, jnp.asarray(g.src), jnp.asarray(g.dst), g.n)))
# balanced partition must count the same (after scattering colors with the
# recorded vertex relabeling; new ids live in [0, n_padded))
colors_bal = np.zeros(sg_bal.n_padded, np.int32)
colors_bal[sg_bal.perm] = colors_g  # color follows the vertex relabeling
fn = make_distributed_count_fn(plan, mesh, sg_bal.n_padded, sg_bal.edges_per_shard, column_batch=8)
with set_mesh(mesh):
    dist = float(fn(jnp.asarray(colors_bal), jnp.asarray(sg_bal.src),
                    jnp.asarray(sg_bal.dst_local), jnp.asarray(sg_bal.edge_mask)))
assert abs(dist - ref) / max(abs(ref), 1e-9) < 1e-5, (dist, ref)
print("MATCH")
"""
    )
    assert "MATCH" in out


def test_streamed_ema_equals_baseline():
    """Beyond-paper fusion (streamed eMA) must be bit-compatible with the
    paper-faithful batched Algorithm 5 (EXPERIMENTS.md §Perf, paper core)."""
    out = _run_child(
        r"""
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh, shard_map
from repro.core import build_counting_plan, get_template, rmat_graph
from repro.core.distributed import make_distributed_count_fn, shard_graph

mesh = jax.make_mesh((2, 4), ("data", "model"))
g = rmat_graph(500, 2500, seed=1)
t = get_template("u7")
plan = build_counting_plan(t)
sg = shard_graph(g, 8)
colors = jnp.asarray(np.random.default_rng(0).integers(0, t.k, size=sg.n_padded))
args = (colors, jnp.asarray(sg.src), jnp.asarray(sg.dst_local), jnp.asarray(sg.edge_mask))
f_base = make_distributed_count_fn(plan, mesh, sg.n_padded, sg.edges_per_shard, column_batch=8)
f_str = make_distributed_count_fn(plan, mesh, sg.n_padded, sg.edges_per_shard,
                                  column_batch=8, ema_mode="streamed")
with set_mesh(mesh):
    base = float(f_base(*args))
    streamed = float(f_str(*args))
assert abs(base - streamed) / max(abs(base), 1e-9) < 1e-6, (base, streamed)
print("STREAMED_MATCH", base)
"""
    )
    assert "STREAMED_MATCH" in out


def test_moe_ep_shard_map_matches_dense_path():
    """EP shard_map MoE == the single-device scatter path when capacity is
    ample (per-shard routing is identical for identical tokens)."""
    out = _run_child(
        r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh, shard_map
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs import dbrx_132b
from repro.models import layers as L

cfg = dataclasses.replace(dbrx_132b.SMOKE_CONFIG, capacity_factor=float(dbrx_132b.SMOKE_CONFIG.n_experts))
mesh = jax.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
params = L.init_moe(key, cfg)
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, cfg.d_model), jnp.float32)
ref, aux_ref = L.moe_apply(params, cfg, x)  # single-device scatter path

act_spec = P("data", "model", None)

def param_sharding(a):
    spec = P("model", None, None) if a.ndim == 3 else P(*([None] * a.ndim))
    return NamedSharding(mesh, spec)

with set_mesh(mesh):
    params_d = jax.device_put(params, jax.tree.map(param_sharding, params))
    x_d = jax.device_put(x, NamedSharding(mesh, act_spec))
    @jax.jit
    def f(p, xx):
        return L.moe_apply(p, cfg, xx, act_spec=act_spec)
    out, aux = f(params_d, x_d)
err = float(jnp.max(jnp.abs(out - ref)))
print("EP_ERR", err)
assert err < 1e-4, err
"""
    )
    assert "EP_ERR" in out


def test_compressed_psum_preserves_mean():
    out = _run_child(
        r"""
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh, shard_map
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.train.compression import compressed_psum

mesh = jax.make_mesh((8,), ("data",))
def f(x, res):
    return compressed_psum(x, ("data",), res)
g = shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
res = jnp.zeros_like(x)
with set_mesh(mesh):
    mean, new_res = g(x, res)
true_mean = np.asarray(x).mean(0)
got = np.asarray(mean)[0]
err = np.abs(got - true_mean).max() / (np.abs(true_mean).max() + 1e-9)
assert err < 0.05, err  # int8 quantization error bound
print("OK", err)
"""
    )
    assert "OK" in out


def test_lm_pjit_train_step_on_mesh():
    """End-to-end sharded LM train step on a (2, 4) host mesh."""
    out = _run_child(
        r"""
import dataclasses
import jax, jax.numpy as jnp
from jax import set_mesh
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.configs import granite_8b
from repro.models import transformer as T
from repro.train.optimizer import adamw_init, adamw_update

cfg = dataclasses.replace(granite_8b.SMOKE_CONFIG, n_heads=8, n_kv_heads=4, scan_layers=True)
# GSPMD-propagated (Auto) axes: the model code carries no out_sharding hints
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
params = T.init_params(jax.random.PRNGKey(0), cfg)
pspecs = T.param_pspecs(cfg, model_size=4)
with set_mesh(mesh):
    params = jax.device_put(params, jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                                                 is_leaf=lambda x: isinstance(x, P)))
    opt = adamw_init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))

    @jax.jit
    def step(params, opt, tokens):
        loss, grads = jax.value_and_grad(T.loss_fn)(params, cfg, tokens, tokens, P("data", "model", None))
        params, opt = adamw_update(grads, opt, params, 1e-3)
        return params, opt, loss

    l0 = None
    for i in range(3):
        params, opt, loss = step(params, opt, tokens)
        l0 = l0 or float(loss)
    assert float(loss) < l0, (float(loss), l0)
print("TRAINED", float(loss))
"""
    )
    assert "TRAINED" in out

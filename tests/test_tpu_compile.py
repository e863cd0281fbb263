"""Compile the engine's Pallas kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached.  It refuses what interpret mode accepts: blocks not
aligned to the (8, 128) tiling, scalar prefetch that overflows SMEM, and
scratch that overflows VMEM.  These tests compile both kernels, and one
fused-kernel engine stage, at the u7 and u12 widths the engine launches,
and both kernels at the most operand rows the TPU pick admits.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CountingEngine, build_counting_plan, get_template, rmat_graph
from repro.core.colorsets import binom
from repro.kernels.spmm_blocked.kernel import spmm_blocked_call
from repro.exec.select import BLOCKED_MAX_ROWS
from repro.kernels.spmm_ema.kernel import VMEM_BUDGET_BYTES, pad8, spmm_ema_call, vmem_bytes

#: Colorings per launch: a chunk the engine's VMEM cap admits at u12.
CHUNK = 4
BLOCK = 256
ROWS = 2048  # operand rows (fixed-capacity slices of block pairs)
N_PADDED = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: entries
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _stage_tables(tname):
    seen = {}
    for t in build_counting_plan(get_template(tname)).tables:
        if t is not None:
            seen.setdefault((t.k, t.m, t.m_a), t)
    return list(seen.values())


def _edge_specs(sharding, rows=ROWS):
    return (
        _spec(sharding, (rows, 1, BLOCK), jnp.int32),
        _spec(sharding, (rows, 1, BLOCK), jnp.int32),
        _spec(sharding, (rows, 1, BLOCK), jnp.float32),
    )


@pytest.mark.parametrize("tname", ["u7", "u12"])
def test_fused_spmm_ema_kernel_compiles(one_chip, tname):
    """Every distinct stage of the template, ``CHUNK`` colorings per launch."""
    for t in _stage_tables(tname):
        c_a, c_p = binom(t.k, t.m_a), binom(t.k, t.m_p)
        args = (
            _spec(one_chip, (CHUNK * pad8(c_p), N_PADDED), jnp.float32),
            _spec(one_chip, (CHUNK * pad8(c_a), N_PADDED), jnp.float32),
            _spec(one_chip, (t.n_out * t.n_splits,), jnp.int32),
            _spec(one_chip, (t.n_out * t.n_splits,), jnp.int32),
            *[_spec(one_chip, (ROWS,), jnp.int32)] * 4,
            *_edge_specs(one_chip),
        )

        def stage(*a, t=t):
            return spmm_ema_call(
                *a, n_colorings=CHUNK, n_out=t.n_out, n_splits=t.n_splits,
                block_size=BLOCK, edge_chunk=BLOCK,
            )

        compiled = jax.jit(stage).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tname", ["u7", "u12"])
def test_blocked_spmm_kernel_compiles(one_chip, tname):
    """The SpMM kernel over the widest passive state of the template."""
    widest = max(binom(t.k, t.m_p) for t in _stage_tables(tname))
    cols = -(-CHUNK * widest // 128) * 128
    args = (
        _spec(one_chip, (cols, N_PADDED), jnp.float32),
        *[_spec(one_chip, (ROWS,), jnp.int32)] * 3,
        *_edge_specs(one_chip),
    )
    compiled = jax.jit(
        lambda *a: spmm_blocked_call(*a, block_size=BLOCK, edge_chunk=BLOCK)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tname", ["u7", "u12"])
def test_blocked_engine_stage_compiles(one_chip, tname):
    """Every fused stage, as the ``blocked`` backend binds it: its own
    operand (passed as arguments) at the VMEM-capped chunk, the largest a
    TPU's memory budget lets the engine pick."""
    g = rmat_graph(4096, 200_000, seed=1)
    eng = CountingEngine(g, [get_template(tname)], backend="blocked")
    impl = eng.backend_impl
    b = impl.max_chunk_size()
    assert 1 <= eng.chunk_size <= b

    operands = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), impl.device_operands()
    )
    for stage in eng.plan_ir.stages:
        if stage.table_key is None:
            continue
        tables = impl.stage_tables[(stage.plan_idx, stage.sub_idx)]

        def run(operands, m_p, m_a, tables=tables):
            with impl.bind_operands(operands):
                return impl.aggregate_ema(m_p, m_a, tables)

        compiled = jax.jit(run).lower(
            operands,
            _spec(one_chip, (g.n, b, stage.passive_columns), jnp.float32),
            _spec(one_chip, (g.n, b, stage.active_columns), jnp.float32),
        ).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["fused", "spmm"])
def test_kernels_compile_at_row_bound(one_chip, kernel):
    """``BLOCKED_MAX_ROWS`` operand rows (their per-row scalars in SMEM)
    beside u12's widest split tables, at the chunk the VMEM cap admits."""
    tables = _stage_tables("u12")
    chunk = VMEM_BUDGET_BYTES // max(
        vmem_bytes(1, binom(t.k, t.m_p), binom(t.k, t.m_a), t.n_out, BLOCK) for t in tables
    )
    rows = BLOCKED_MAX_ROWS
    if kernel == "fused":
        t = max(tables, key=lambda t: t.n_out * t.n_splits)
        c_a, c_p = binom(t.k, t.m_a), binom(t.k, t.m_p)
        args = (
            _spec(one_chip, (chunk * pad8(c_p), N_PADDED), jnp.float32),
            _spec(one_chip, (chunk * pad8(c_a), N_PADDED), jnp.float32),
            _spec(one_chip, (t.n_out * t.n_splits,), jnp.int32),
            _spec(one_chip, (t.n_out * t.n_splits,), jnp.int32),
            *[_spec(one_chip, (rows,), jnp.int32)] * 4,
            *_edge_specs(one_chip, rows),
        )

        def fn(*a):
            return spmm_ema_call(
                *a, n_colorings=chunk, n_out=t.n_out, n_splits=t.n_splits,
                block_size=BLOCK, edge_chunk=BLOCK,
            )
    else:
        widest = max(binom(t.k, t.m_p) for t in tables)
        args = (
            _spec(one_chip, (-(-chunk * widest // 128) * 128, N_PADDED), jnp.float32),
            *[_spec(one_chip, (rows,), jnp.int32)] * 3,
            *_edge_specs(one_chip, rows),
        )

        def fn(*a):
            return spmm_blocked_call(*a, block_size=BLOCK, edge_chunk=BLOCK)

    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()

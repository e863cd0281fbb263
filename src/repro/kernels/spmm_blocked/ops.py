"""jit'd wrapper around the blocked-ELL SpMM Pallas kernel.

Handles host-side preprocessing (blocked-ELL build, padding to kernel tile
alignment) and the row-major <-> transposed layout conversion so callers can
stay in the ``(n, C)`` orientation used by the high-level DP.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (BLOCKED_BLOCK_SIZE, BLOCKED_ROW_CAPACITY, Graph,
                              build_blocked_ell)

from .kernel import spmm_blocked_call

__all__ = ["BlockedSpmmOperand", "prepare_operand", "spmm_blocked"]


@dataclass(frozen=True)
class BlockedSpmmOperand:
    """Device-ready blocked-ELL arrays (+ static geometry)."""

    n: int
    n_padded: int
    block_size: int
    edge_chunk: int
    pair_src_block: jnp.ndarray
    pair_dst_block: jnp.ndarray
    pair_is_first: jnp.ndarray
    edge_dst_local: jnp.ndarray
    edge_src_local: jnp.ndarray
    edge_valid: jnp.ndarray


# a pytree, so jitted programs take the arrays as arguments
jax.tree_util.register_dataclass(
    BlockedSpmmOperand,
    data_fields=[
        "pair_src_block", "pair_dst_block", "pair_is_first",
        "edge_dst_local", "edge_src_local", "edge_valid",
    ],
    meta_fields=["n", "n_padded", "block_size", "edge_chunk"],
)


def prepare_operand(
    graph: Graph, block_size: int = BLOCKED_BLOCK_SIZE, edge_chunk: int = BLOCKED_ROW_CAPACITY
) -> BlockedSpmmOperand:
    """Blocked-ELL build in ``edge_chunk``-slot rows + dummy rows for empty
    destination blocks."""
    bell = build_blocked_ell(graph, block_size=block_size, pair_capacity=edge_chunk)
    n_blocks = bell.n_blocks
    pair_dst = bell.pair_dst_block
    pair_src = bell.pair_src_block
    cap = bell.pair_capacity
    dst_loc, src_loc, valid = bell.edge_dst_local, bell.edge_src_local, bell.edge_valid

    # Every destination block must appear in >= 1 pair so its output tile is
    # zeroed (kernel writes only visited tiles).  Add all-invalid dummy pairs.
    present = np.zeros(n_blocks, dtype=bool)
    present[pair_dst] = True
    missing = np.nonzero(~present)[0].astype(np.int32)
    if missing.size:
        pair_dst = np.concatenate([pair_dst, missing])
        pair_src = np.concatenate([pair_src, np.zeros_like(missing)])
        dst_loc = np.concatenate([dst_loc, np.zeros((missing.size, cap), np.int32)])
        src_loc = np.concatenate([src_loc, np.zeros((missing.size, cap), np.int32)])
        valid = np.concatenate([valid, np.zeros((missing.size, cap), np.float32)])
        order = np.argsort(pair_dst, kind="stable")
        pair_dst, pair_src = pair_dst[order], pair_src[order]
        dst_loc, src_loc, valid = dst_loc[order], src_loc[order], valid[order]

    is_first = np.ones(len(pair_dst), dtype=np.int32)
    is_first[1:] = (pair_dst[1:] != pair_dst[:-1]).astype(np.int32)

    return BlockedSpmmOperand(
        n=graph.n,
        n_padded=bell.n_padded,
        block_size=block_size,
        edge_chunk=edge_chunk,
        pair_src_block=jnp.asarray(pair_src),
        pair_dst_block=jnp.asarray(pair_dst),
        pair_is_first=jnp.asarray(is_first),
        # (n_rows, 1, capacity): the kernels' per-row block layout
        edge_dst_local=jnp.asarray(dst_loc[:, None, :]),
        edge_src_local=jnp.asarray(src_loc[:, None, :]),
        edge_valid=jnp.asarray(valid[:, None, :]),
    )


@functools.partial(
    jax.jit,
    static_argnames=("n", "n_padded", "block_size", "edge_chunk", "col_tile", "mode", "interpret"),
)
def _spmm_blocked_jit(
    m: jnp.ndarray,
    pair_src_block, pair_dst_block, pair_is_first,
    edge_dst_local, edge_src_local, edge_valid,
    *, n, n_padded, block_size, edge_chunk, col_tile, mode, interpret,
):
    c = m.shape[1]
    c_pad = ((c + col_tile - 1) // col_tile) * col_tile
    mt = jnp.zeros((c_pad, n_padded), dtype=m.dtype)
    mt = mt.at[:c, :n].set(m.T)
    bt = spmm_blocked_call(
        mt,
        pair_src_block, pair_dst_block, pair_is_first,
        edge_dst_local, edge_src_local, edge_valid,
        block_size=block_size,
        col_tile=col_tile,
        edge_chunk=edge_chunk,
        mode=mode,
        interpret=interpret,
    )
    return bt[:c, :n].T


def spmm_blocked(
    operand: BlockedSpmmOperand,
    m: jnp.ndarray,
    *,
    col_tile: int = 128,
    mode: str = "mxu",
    interpret: bool = False,
) -> jnp.ndarray:
    """``B = A_G @ M`` with ``M`` in row-major ``(n, C)`` orientation."""
    return _spmm_blocked_jit(
        m,
        operand.pair_src_block, operand.pair_dst_block, operand.pair_is_first,
        operand.edge_dst_local, operand.edge_src_local, operand.edge_valid,
        n=operand.n,
        n_padded=operand.n_padded,
        block_size=operand.block_size,
        edge_chunk=operand.edge_chunk,
        col_tile=col_tile,
        mode=mode,
        interpret=interpret,
    )

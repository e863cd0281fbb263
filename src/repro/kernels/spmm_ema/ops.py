"""Host-side wrappers for the fused SpMM+eMA Pallas kernel.

Handles blocked-ELL preprocessing (+ the per-pair ``is_last`` run-tail
flags), padding to kernel tile alignment, the row-major ``(n, C)`` <->
transposed ``(C, n)`` conversion, and the engine's fused ``(n, B, C)``
coloring-batch layout: a chunk of ``B`` colorings is folded into the
*row* axis of the transposed operands, so one kernel launch serves the
whole chunk.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import BLOCKED_BLOCK_SIZE, BLOCKED_ROW_CAPACITY, Graph
from repro.kernels.spmm_blocked.ops import BlockedSpmmOperand, prepare_operand

from .kernel import pad8, spmm_ema_call

__all__ = [
    "FusedSpmmEmaOperand",
    "prepare_fused_operand",
    "spmm_ema",
    "spmm_ema_batched",
]


@dataclass(frozen=True)
class FusedSpmmEmaOperand:
    """Blocked-ELL arrays plus destination-run tail flags."""

    blocked: BlockedSpmmOperand
    pair_is_last: jnp.ndarray  # (n_rows,) int32


jax.tree_util.register_dataclass(
    FusedSpmmEmaOperand, data_fields=["blocked", "pair_is_last"], meta_fields=[]
)


def prepare_fused_operand(
    graph: Graph, block_size: int = BLOCKED_BLOCK_SIZE, edge_chunk: int = BLOCKED_ROW_CAPACITY
) -> FusedSpmmEmaOperand:
    """Blocked-ELL build + the ``is_last`` flag ending each dst-block run."""
    blocked = prepare_operand(graph, block_size=block_size, edge_chunk=edge_chunk)
    pair_dst = np.asarray(blocked.pair_dst_block)
    is_last = np.ones(pair_dst.shape[0], dtype=np.int32)
    if pair_dst.shape[0] > 1:
        is_last[:-1] = (pair_dst[1:] != pair_dst[:-1]).astype(np.int32)
    return FusedSpmmEmaOperand(blocked=blocked, pair_is_last=jnp.asarray(is_last))


def spmm_ema(
    operand: FusedSpmmEmaOperand,
    m_p: jnp.ndarray,    # (n, C_p)
    m_a: jnp.ndarray,    # (n, C_a)
    idx_a: np.ndarray,   # (n_out, n_splits) host-side int32
    idx_p: np.ndarray,   # (n_out, n_splits) host-side int32
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused ``M_s = eMA(M_a, A_G @ M_p)`` with row-major ``(n, C)`` operands."""
    out = spmm_ema_batched(
        operand, m_p[:, None, :], m_a[:, None, :], idx_a, idx_p, interpret=interpret
    )
    return out[:, 0, :]


def spmm_ema_batched(
    operand: FusedSpmmEmaOperand,
    m_p: jnp.ndarray,    # (n, B, C_p)
    m_a: jnp.ndarray,    # (n, B, C_a)
    idx_a: np.ndarray,   # (n_out, n_splits) host-side int32
    idx_p: np.ndarray,   # (n_out, n_splits) host-side int32
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused stage over a chunk of ``B`` colorings -> ``(n, B, n_out)`` fp32.

    Each coloring's columns become an 8-row-aligned band of the transposed
    operands; one split table serves every band (the kernel derives each
    coloring's row offset), and the aggregate scratch stays one VMEM tile
    per destination block for the whole chunk.
    """
    blocked = operand.blocked
    n, bsz, c_p = m_p.shape
    c_a = m_a.shape[2]
    idx_a = np.asarray(idx_a, dtype=np.int32)
    idx_p = np.asarray(idx_p, dtype=np.int32)
    n_out, n_splits = idx_a.shape

    cp_pad = pad8(c_p)
    ca_pad = pad8(c_a)
    nout_pad = pad8(n_out)

    def to_bands(m, c, c_pad):
        # (n, B, c) -> (B * c_pad, n_padded), coloring b in rows [b*c_pad, ...)
        mt = jnp.moveaxis(m.astype(jnp.float32), 0, 2)  # (B, c, n)
        mt = jnp.pad(mt, ((0, 0), (0, c_pad - c), (0, blocked.n_padded - n)))
        return mt.reshape(bsz * c_pad, blocked.n_padded)

    mp_t = to_bands(m_p, c_p, cp_pad)
    ma_t = to_bands(m_a, c_a, ca_pad)

    # one flat coloring-local table: the kernel offsets coloring b's reads
    # into its own band from the output row index
    out_t = spmm_ema_call(
        mp_t,
        ma_t,
        jnp.asarray(idx_a.reshape(-1)),
        jnp.asarray(idx_p.reshape(-1)),
        blocked.pair_src_block,
        blocked.pair_dst_block,
        blocked.pair_is_first,
        operand.pair_is_last,
        blocked.edge_dst_local,
        blocked.edge_src_local,
        blocked.edge_valid,
        n_colorings=bsz,
        n_out=n_out,
        n_splits=n_splits,
        block_size=blocked.block_size,
        edge_chunk=blocked.edge_chunk,
        interpret=interpret,
    )  # (B * nout_pad, n_padded)
    out = out_t.reshape(bsz, nout_pad, blocked.n_padded)[:, :n_out, :n]
    return out.transpose(2, 0, 1)  # (n, B, n_out)

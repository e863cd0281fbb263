"""Blocked-ELL SpMM Pallas TPU kernel — "CSC-Split, TPU edition".

Computes ``B = A_G @ M`` for a 0/1 sparse adjacency ``A_G`` and a dense count
matrix ``M``, with both ``M`` and ``B`` stored **transposed** ``(C, n)`` —
the TPU mapping of the paper's column-major layout (§V-B): the vectorized
axis is the vertex axis (lanes), the combinatorial color-set axis is tiled.

Sparse structure (preprocessed host-side, ``repro.core.graph.build_blocked_ell``):
vertices are tiled into blocks of ``block_size``; edges are grouped by
(dst-block, src-block) pairs in rows of ``pair_capacity`` slots (a heavy
pair spans consecutive rows), and rows are sorted by destination block.  Per grid step the kernel holds one source tile
of ``M^T`` and one destination accumulator tile of ``B^T`` in VMEM.

Two inner-loop strategies:

* ``mode="mxu"`` (default) — gather/scatter as two MXU matmuls per edge
  chunk: ``acc += (M_tile @ onehot_srcᵀ) @ onehot_dst``.  One-hot matrices are
  built in-register from an iota comparison.  This converts the irregular
  per-edge access into dense systolic work — the TPU analogue of the paper's
  observation that SpMM beats pointer chasing even at higher nominal FLOPs.
* ``mode="loop"`` — per-edge dynamic-slice FMA on the VPU (closer to the
  CPU CSC-Split inner loop; used as a structural cross-check).

Grid: ``(num_col_tiles, n_pairs)`` — pair axis innermost so all pairs sharing
a destination block are visited consecutively and the output tile stays
resident in VMEM (accumulation-safe; zeroed at each pair-run head via the
``is_first`` scalar-prefetch flag).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["spmm_blocked_kernel", "spmm_blocked_call"]


def _mxu_chunk(m_blk, src_ids, dst_ids, valid, block_size, acc):
    """acc += onehot(dst)ᵀ-scatter( onehot(src)-gather(m_blk) ) for one chunk.

    Both products run at ``HIGHEST`` precision: the one-hot factors are
    exact in any format, but the counts are not, and the MXU's default
    single bf16 pass would round every gathered count to 8 mantissa bits.
    """
    e = src_ids.shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (e, block_size), 1)
    onehot_src = jnp.where(src_ids[:, None] == lanes, valid[:, None], 0.0)
    onehot_dst = jnp.where(dst_ids[:, None] == lanes, 1.0, 0.0)
    # gather: (C_tile, bs) @ (bs, e) -> (C_tile, e)
    gathered = jax.lax.dot_general(
        m_blk, onehot_src,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    # scatter: (C_tile, e) @ (e, bs) -> (C_tile, bs)
    return acc + jax.lax.dot_general(
        gathered, onehot_dst,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def spmm_blocked_kernel(
    # scalar prefetch
    src_blk_ref, dst_blk_ref, first_ref,
    # inputs
    m_ref, dst_loc_ref, src_loc_ref, valid_ref,
    # output
    out_ref,
    *,
    block_size: int,
    edge_chunk: int,
    mode: str,
):
    p = pl.program_id(1)

    @pl.when(first_ref[p] == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    n_chunks = src_loc_ref.shape[1] // edge_chunk
    m_blk = m_ref[...]  # (C_tile, block_size)

    if mode == "mxu":
        def body(i, acc):
            start = i * edge_chunk
            src_ids = src_loc_ref[0, pl.dslice(start, edge_chunk)]
            dst_ids = dst_loc_ref[0, pl.dslice(start, edge_chunk)]
            valid = valid_ref[0, pl.dslice(start, edge_chunk)]
            return _mxu_chunk(m_blk, src_ids, dst_ids, valid, block_size, acc)

        acc = jax.lax.fori_loop(
            0, n_chunks, body, jnp.zeros_like(out_ref[...]), unroll=False
        )
        out_ref[...] += acc
    elif mode == "loop":
        total = src_loc_ref.shape[1]

        def body(e, acc):
            s = src_loc_ref[0, e]
            d = dst_loc_ref[0, e]
            v = valid_ref[0, e]
            col = jax.lax.dynamic_slice(m_blk, (0, s), (m_blk.shape[0], 1))
            upd = jax.lax.dynamic_slice(acc, (0, d), (acc.shape[0], 1)) + v * col
            return jax.lax.dynamic_update_slice(acc, upd, (0, d))

        acc = jax.lax.fori_loop(0, total, body, jnp.zeros_like(out_ref[...]))
        out_ref[...] += acc
    else:  # pragma: no cover
        raise ValueError(f"unknown mode {mode!r}")


def spmm_blocked_call(
    mt: jnp.ndarray,           # (C, n_padded) transposed dense counts
    pair_src_block: jnp.ndarray,   # (n_pairs,) int32
    pair_dst_block: jnp.ndarray,   # (n_pairs,) int32
    pair_is_first: jnp.ndarray,    # (n_pairs,) int32 — 1 at head of a dst-run
    edge_dst_local: jnp.ndarray,   # (n_rows, 1, capacity) int32
    edge_src_local: jnp.ndarray,   # (n_rows, 1, capacity) int32
    edge_valid: jnp.ndarray,       # (n_rows, 1, capacity) f32
    *,
    block_size: int,
    col_tile: int = 128,
    edge_chunk: int = 256,
    mode: str = "mxu",
    interpret: bool = False,
) -> jnp.ndarray:
    """``B^T = (A_G @ M)^T`` via the blocked-ELL kernel.  Shapes must satisfy
    ``C % col_tile == 0``, ``n_padded % block_size == 0``,
    ``capacity % edge_chunk == 0`` (pad host-side)."""
    c, n_padded = mt.shape
    n_pairs, _, capacity = edge_dst_local.shape
    if c % col_tile:
        raise ValueError(f"C={c} not a multiple of col_tile={col_tile}")
    if capacity % edge_chunk:
        raise ValueError(f"capacity={capacity} not a multiple of edge_chunk={edge_chunk}")
    grid = (c // col_tile, n_pairs)
    # per-row edge slices: a squeezed leading axis keeps the block's last
    # two dims (1, capacity) equal to the array's, as Mosaic requires
    edge_spec = pl.BlockSpec((None, 1, capacity), lambda ci, p, *_: (p, 0, 0))

    kernel = functools.partial(
        spmm_blocked_kernel, block_size=block_size, edge_chunk=edge_chunk, mode=mode
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((col_tile, block_size), lambda ci, p, sb, db, fi: (ci, sb[p])),
            edge_spec,
            edge_spec,
            edge_spec,
        ],
        out_specs=pl.BlockSpec((col_tile, block_size), lambda ci, p, sb, db, fi: (ci, db[p])),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, n_padded), mt.dtype),
        interpret=interpret,
    )(
        pair_src_block, pair_dst_block, pair_is_first, mt,
        edge_dst_local, edge_src_local, edge_valid,
    )

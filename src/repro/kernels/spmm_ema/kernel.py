"""Fused SpMM+eMA Pallas TPU kernel — the whole DP stage in one pass.

Computes, for one stage of SUBGRAPH2VEC's Algorithm 5,

    M_s[o, :] = sum_t  M_a[idx_a[o, t], :] * (A_G @ M_p)[idx_p[o, t], :]

WITHOUT ever materializing the aggregate product ``B = A_G @ M_p``: per
destination vertex block, the aggregate columns live only in a VMEM scratch
tile that is consumed by the eMA FMA the moment the block's last edge pair
has been accumulated.  This subsumed (and replaced — the package is gone)
the standalone eMA kernel that once lived at ``repro.kernels.ema``, which
fused only the multiply-add half and still read a full HBM-resident ``B``.

Layout is the paper's column-major design (§V-B) transposed for TPU: all
matrices are ``(colorsets, vertices)`` with the vertex axis on lanes.  The
sparse structure is the blocked-ELL build of ``repro.kernels.spmm_blocked``
(edges grouped by (dst-block, src-block) pair, pairs sorted by destination
block) plus an ``is_last`` flag marking the final pair of each
destination-block run.

Grid: ``(n_rows,)`` — one fixed-capacity row of a (dst-block, src-block)
pair per step.  Per step the kernel

1. zeroes the scratch aggregate tile at a run head (``is_first``),
2. accumulates the row's edges into it with the MXU one-hot gather/scatter
   trick shared with the blocked SpMM kernel,
3. at the run tail (``is_last``, the block's last row) applies the eMA against the VMEM-resident
   ``M_a^T`` destination tile and writes the ``M_s^T`` output tile — the
   only thing that ever reaches HBM.

Everything accumulates in fp32; the split tables ride in SMEM via scalar
prefetch as one flat ``(n_out * n_splits,)`` table per stage, shared by all
``B`` colorings of the chunk (each coloring's band offset is derived from
the output row index), so SMEM use grows with neither ``B`` nor the lane
padding of a 2-D table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmm_blocked.kernel import _mxu_chunk

__all__ = [
    "spmm_ema_kernel", "spmm_ema_call", "pad8", "vmem_bytes",
    "VMEM_LIMIT_BYTES", "VMEM_BUDGET_BYTES",
]

#: Scoped VMEM the kernel may claim (the compiler's default is 16 MiB; a
#: TPU v5e core has 128 MiB).  Chunk sizes are capped so that
#: :func:`vmem_bytes` stays within :data:`VMEM_BUDGET_BYTES`.
VMEM_LIMIT_BYTES = 96 * 2**20

#: Share of the limit :func:`vmem_bytes` may fill, leaving the rest to the
#: compiler's internal scratch (one-hot tiles, split-table rows).
VMEM_BUDGET_BYTES = 72 * 2**20


def pad8(x: int) -> int:
    """``x`` rounded up to whole 8-row sublane tiles (one coloring's band)."""
    return ((x + 7) // 8) * 8


def vmem_bytes(n_colorings: int, c_p: int, c_a: int, n_out: int, block_size: int) -> int:
    """VMEM the kernel holds: double-buffered ``M_p``/``M_a`` input tiles and
    output tile, the fp32 aggregate scratch, and the SpMM step's values of
    the aggregate's height (loaded source tile, gathered edge chunk, loaded
    and updated aggregate), which the compiler spills to VMEM."""
    cp_pad, ca_pad, nout_pad = pad8(c_p), pad8(c_a), pad8(n_out)
    rows = 2 * cp_pad + 2 * ca_pad + 2 * nout_pad + cp_pad + 4 * cp_pad
    return 4 * block_size * rows * n_colorings


def spmm_ema_kernel(
    # scalar prefetch (SMEM)
    src_blk_ref, dst_blk_ref, first_ref, last_ref, idx_a_ref, idx_p_ref,
    # inputs (VMEM)
    mp_ref,       # (B * cp_pad, block_size) — source block of M_p^T
    ma_ref,       # (B * ca_pad, block_size) — destination block of M_a^T
    dst_loc_ref, src_loc_ref, valid_ref,  # (1, capacity) per row
    # output
    out_ref,      # (B * nout_pad, block_size) — destination block of M_s^T
    # scratch
    bcol_ref,     # VMEM (B * cp_pad, block_size) fp32 aggregate tile
    *,
    block_size: int,
    edge_chunk: int,
    n_colorings: int,
    n_out: int,
    n_splits: int,
    ca_pad: int,
    cp_pad: int,
    nout_pad: int,
):
    p = pl.program_id(0)

    @pl.when(first_ref[p] == 1)
    def _zero_aggregate():
        bcol_ref[...] = jnp.zeros_like(bcol_ref)

    # -- SpMM half: fold this row's edges into the aggregate scratch tile.
    m_blk = mp_ref[...]
    n_chunks = src_loc_ref.shape[1] // edge_chunk

    def chunk_body(i, carry):
        start = i * edge_chunk
        src_ids = src_loc_ref[0, pl.dslice(start, edge_chunk)]
        dst_ids = dst_loc_ref[0, pl.dslice(start, edge_chunk)]
        valid = valid_ref[0, pl.dslice(start, edge_chunk)]
        bcol_ref[...] = _mxu_chunk(m_blk, src_ids, dst_ids, valid, block_size, bcol_ref[...])
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)

    # -- eMA half: the block's aggregate is complete — consume it in place.
    # One flat (n_out * n_splits) table serves every coloring: coloring b
    # reads its own 8-row-aligned band, offset from the row index.
    @pl.when(last_ref[p] == 1)
    def _ema_consume():
        v_tile = out_ref.shape[1]

        def out_row(r, carry):
            b = r // n_out
            o = r - b * n_out
            base = o * n_splits

            def split_body(t, acc):
                ia = idx_a_ref[base + t] + b * ca_pad
                ip = idx_p_ref[base + t] + b * cp_pad
                ra = ma_ref[pl.dslice(ia, 1), :]
                rb = bcol_ref[pl.dslice(ip, 1), :]
                return acc + ra * rb

            row = jax.lax.fori_loop(
                0, n_splits, split_body, jnp.zeros((1, v_tile), out_ref.dtype)
            )
            out_ref[pl.dslice(b * nout_pad + o, 1), :] = row
            return carry

        jax.lax.fori_loop(0, n_colorings * n_out, out_row, 0)


def spmm_ema_call(
    mp_t: jnp.ndarray,             # (B * cp_pad, n_padded) transposed passive state
    ma_t: jnp.ndarray,             # (B * ca_pad, n_padded) transposed active state
    idx_a: jnp.ndarray,            # (n_out * n_splits,) int32, coloring-local
    idx_p: jnp.ndarray,            # (n_out * n_splits,) int32, coloring-local
    pair_src_block: jnp.ndarray,   # (n_rows,) int32
    pair_dst_block: jnp.ndarray,   # (n_rows,) int32
    pair_is_first: jnp.ndarray,    # (n_rows,) int32 — head of a dst-block run
    pair_is_last: jnp.ndarray,     # (n_rows,) int32 — tail of a dst-block run
    edge_dst_local: jnp.ndarray,   # (n_rows, 1, capacity) int32
    edge_src_local: jnp.ndarray,   # (n_rows, 1, capacity) int32
    edge_valid: jnp.ndarray,       # (n_rows, 1, capacity) f32
    *,
    n_colorings: int,
    n_out: int,
    n_splits: int,
    block_size: int,
    edge_chunk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """``M_s^T = eMA(M_a^T, A_G @ M_p^T)`` fused per destination block.

    ``capacity % edge_chunk == 0``, ``n_padded % block_size == 0`` and the
    band heights ``cp_pad``/``ca_pad`` multiples of 8 (pad host-side; see
    ``repro.kernels.spmm_ema.ops``).  Returns ``(B * nout_pad, n_padded)``
    in ``mp_t``'s dtype (use fp32: the aggregate scratch accumulates in
    fp32 regardless); band rows past ``n_out`` are not written.
    """
    cp_tot, n_padded = mp_t.shape
    ca_tot = ma_t.shape[0]
    n_rows, _, capacity = edge_dst_local.shape
    if capacity % edge_chunk:
        raise ValueError(f"capacity={capacity} not a multiple of edge_chunk={edge_chunk}")
    if n_padded % block_size:
        raise ValueError(f"n_padded={n_padded} not a multiple of block_size={block_size}")
    cp_pad, ca_pad = cp_tot // n_colorings, ca_tot // n_colorings
    nout_pad = pad8(n_out)

    kernel = functools.partial(
        spmm_ema_kernel,
        block_size=block_size,
        edge_chunk=edge_chunk,
        n_colorings=n_colorings,
        n_out=n_out,
        n_splits=n_splits,
        ca_pad=ca_pad,
        cp_pad=cp_pad,
        nout_pad=nout_pad,
    )
    # per-row edge slices: a squeezed leading axis keeps the block's last
    # two dims (1, capacity) equal to the array's, as Mosaic requires
    edge_spec = pl.BlockSpec((None, 1, capacity), lambda p, *_: (p, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_rows,),
        in_specs=[
            pl.BlockSpec((cp_tot, block_size), lambda p, sb, db, fi, la, ia, ip: (0, sb[p])),
            pl.BlockSpec((ca_tot, block_size), lambda p, sb, db, fi, la, ia, ip: (0, db[p])),
            edge_spec,
            edge_spec,
            edge_spec,
        ],
        out_specs=pl.BlockSpec(
            (n_colorings * nout_pad, block_size),
            lambda p, sb, db, fi, la, ia, ip: (0, db[p]),
        ),
        scratch_shapes=[pltpu.VMEM((cp_tot, block_size), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_colorings * nout_pad, n_padded), mp_t.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(
        pair_src_block, pair_dst_block, pair_is_first, pair_is_last, idx_a, idx_p,
        mp_t, ma_t,
        edge_dst_local, edge_src_local, edge_valid,
    )

"""Graph containers, sparse formats, and synthetic generators.

The counting DP only needs undirected, unweighted simple graphs.  Three device
layouts are supported, mirroring the paper's CSR / CSC-Split discussion but
re-thought for the TPU memory hierarchy (DESIGN.md §2):

* **edge list** — ``(src, dst)`` int32 pairs with both directions present; the
  high-level SpMM is ``segment_sum(M[src], dst)``.  This is the layout used by
  the distributed path (edges shard cleanly).
* **ELL** — ``(n, max_deg)`` padded neighbor table + validity mask; SpMM is a
  row gather + masked sum (best when the degree distribution is flat).
* **blocked-ELL ("CSC-Split, TPU edition")** — vertices tiled into blocks of
  ``block_size`` rows; edges grouped by (dst-block, src-block) tile pair in
  fixed-capacity rows (a heavy pair spans several rows); the Pallas kernel streams one source tile of ``M`` into VMEM per
  pair and accumulates into the destination tile.  The per-row-range grouping
  is exactly the locality trick of the paper's CSC-Split format.

Generators: RMAT (the paper's synthetic workhorse), Erdos-Renyi, and a tiny
deterministic PPIN-like graph for examples.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

__all__ = [
    "Graph",
    "BlockedELL",
    "BlockedGeometry",
    "SellGraph",
    "blocked_ell_geometry",
    "build_blocked_ell",
    "build_sell",
    "rmat_graph",
    "erdos_renyi_graph",
    "grid_graph",
]


@dataclass(frozen=True)
class Graph:
    """Undirected simple graph in canonical edge-list form.

    ``src``/``dst`` contain *both* directions of every undirected edge and are
    sorted by ``(dst, src)`` so that segment reductions over ``dst`` are
    contiguous.  ``n`` is the vertex count; ``num_undirected`` the number of
    undirected edges (``len(src) == 2 * num_undirected``).
    """

    n: int
    src: np.ndarray  # (2E,) int32
    dst: np.ndarray  # (2E,) int32

    @property
    def num_directed(self) -> int:
        return int(self.src.shape[0])

    @property
    def num_undirected(self) -> int:
        return self.num_directed // 2

    @property
    def avg_degree(self) -> float:
        return self.num_directed / max(self.n, 1)

    def degrees(self) -> np.ndarray:
        return np.bincount(self.dst, minlength=self.n).astype(np.int32)

    def max_degree(self) -> int:
        return int(self.degrees().max(initial=0))

    def csr(self) -> Tuple[np.ndarray, np.ndarray]:
        """(row_ptr, col_idx) over destination-major ordering."""
        deg = self.degrees()
        row_ptr = np.zeros(self.n + 1, dtype=np.int64)
        np.cumsum(deg, out=row_ptr[1:])
        return row_ptr, self.src.astype(np.int32)

    def ell(self, max_deg: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Padded neighbor table ``(n, max_deg)`` + bool mask.

        Padded slots point at vertex 0 and are masked out.
        """
        deg = self.degrees()
        md = int(max_deg if max_deg is not None else deg.max(initial=1))
        nbr = np.zeros((self.n, md), dtype=np.int32)
        mask = np.zeros((self.n, md), dtype=bool)
        row_ptr, col_idx = self.csr()
        for i in range(self.n):
            lo, hi = int(row_ptr[i]), int(row_ptr[i + 1])
            d = min(hi - lo, md)
            nbr[i, :d] = col_idx[lo : lo + d]
            mask[i, :d] = True
        return nbr, mask

    def dense_adjacency(self) -> np.ndarray:
        a = np.zeros((self.n, self.n), dtype=np.float32)
        a[self.dst, self.src] = 1.0
        return a

    def signature(self) -> str:
        """Content hash of ``(n, src, dst)`` — the graph half of the engine
        cache key.  Graphs in canonical form (sorted, symmetrized) with the
        same structure hash identically regardless of construction route.
        """
        h = hashlib.sha1()
        h.update(np.int64(self.n).tobytes())
        h.update(np.ascontiguousarray(self.src, dtype=np.int64).tobytes())
        h.update(np.ascontiguousarray(self.dst, dtype=np.int64).tobytes())
        return h.hexdigest()


def _canonicalize(n: int, u: np.ndarray, v: np.ndarray) -> Graph:
    """Dedup, drop self-loops, symmetrize, and sort by (dst, src)."""
    keep = u != v
    u, v = u[keep], v[keep]
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    und = np.unique(lo.astype(np.int64) * n + hi.astype(np.int64))
    lo = (und // n).astype(np.int32)
    hi = (und % n).astype(np.int32)
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    order = np.lexsort((src, dst))
    return Graph(n=n, src=src[order], dst=dst[order])


def rmat_graph(
    n: int,
    num_edges: int,
    seed: int = 0,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
) -> Graph:
    """R-MAT generator (Chakrabarti et al. 2004), the paper's synthetic data.

    ``a + b + c + d = 1`` with ``d = 1 - a - b - c``; larger ``a`` skews the
    degree distribution (the paper's ``K`` parameter sweeps this skew).
    """
    scale = int(np.ceil(np.log2(max(n, 2))))
    n_pow = 1 << scale
    rng = np.random.default_rng(seed)
    # Vectorized bit-by-bit quadrant descent for all edges at once.
    u = np.zeros(num_edges, dtype=np.int64)
    v = np.zeros(num_edges, dtype=np.int64)
    for _ in range(scale):
        r = rng.random(num_edges)
        right = (r >= a + b) & (r < a + b + c) | (r >= a + b + c)
        down = (r >= a) & (r < a + b) | (r >= a + b + c)
        u = (u << 1) | down.astype(np.int64)
        v = (v << 1) | right.astype(np.int64)
    u, v = (u % n).astype(np.int32), (v % n).astype(np.int32)
    return _canonicalize(n, u, v)


def erdos_renyi_graph(n: int, num_edges: int, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, size=num_edges).astype(np.int32)
    v = rng.integers(0, n, size=num_edges).astype(np.int32)
    return _canonicalize(n, u, v)


def grid_graph(rows: int, cols: int) -> Graph:
    """Deterministic 2-D grid — handy exact-count test fixture."""
    idx = np.arange(rows * cols).reshape(rows, cols)
    edges = []
    edges.append(np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1))
    edges.append(np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1))
    e = np.concatenate(edges, axis=0)
    return _canonicalize(rows * cols, e[:, 0].astype(np.int32), e[:, 1].astype(np.int32))


# ---------------------------------------------------------------------------
# SELL (sliced, degree-sorted ELL) — scatter-free CPU neighbor gather.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SellGraph:
    """Degree-sorted sliced-ELL layout: a *scatter-free* SpMM for skewed graphs.

    Vertices are sorted by descending degree and cut into groups of
    ``group_size`` rows; each group's neighbor lists are padded only to that
    group's own max degree (classic SELL-C-sigma with a full sort).  The
    neighbor reduction is then a padded row gather + masked sum per group —
    pure gathers and dense reductions, no scatter at all; results come back
    to original vertex order through one inverse-permutation gather.

    This exists because XLA:CPU's scatter (``segment_sum``) falls off a
    performance cliff on large edge lists (observed: ~2 ms at |E|≈30k/n=2k
    but ~400–600 ms at |E|≈130k/n=8k regardless of column count) and carries
    an |E|-proportional fixed cost per call that the fused column-batched
    pipeline would multiply.  Degree sorting bounds the padding waste that
    plain ELL suffers on power-law graphs (one hub row would pad every row
    to ``max_degree``).

    Attributes:
      group_rows: per group, (rows,) int32 — vertex ids in degree order
        (concatenating all groups gives the full degree-sorted order).
      group_nbr:  per group, (rows, d_group) int32 padded neighbor table.
      group_mask: per group, (rows, d_group) float32 validity mask.
      inv_order:  (n,) int32 — position of each degree-rank slot for the
        inverse gather: ``out = concat(group results)[inv_order]``.
      padded_slots: total padded neighbor slots across groups (the memory
        model's transient unit; ``>= num_directed``).
    """

    n: int
    group_size: int
    group_rows: Tuple[np.ndarray, ...]
    group_nbr: Tuple[np.ndarray, ...]
    group_mask: Tuple[np.ndarray, ...]
    inv_order: np.ndarray
    padded_slots: int


def build_sell(graph: Graph, group_size: int = 128) -> SellGraph:
    """Degree-sort vertices and build per-group padded neighbor tables."""
    deg = graph.degrees()
    row_ptr, col_idx = graph.csr()
    order = np.argsort(-deg, kind="stable")
    groups_rows = []
    groups_nbr = []
    groups_mask = []
    padded = 0
    for lo in range(0, graph.n, group_size):
        rows = order[lo : lo + group_size]
        d_max = max(int(deg[rows].max(initial=0)), 1)
        nbr = np.zeros((rows.size, d_max), dtype=np.int32)
        mask = np.zeros((rows.size, d_max), dtype=np.float32)
        for r, v in enumerate(rows):
            a, b = int(row_ptr[v]), int(row_ptr[v + 1])
            nbr[r, : b - a] = col_idx[a:b]
            mask[r, : b - a] = 1.0
        groups_rows.append(rows.astype(np.int32))
        groups_nbr.append(nbr)
        groups_mask.append(mask)
        padded += nbr.size
    inv_order = np.empty(graph.n, dtype=np.int32)
    inv_order[order] = np.arange(graph.n, dtype=np.int32)
    return SellGraph(
        n=graph.n,
        group_size=group_size,
        group_rows=tuple(groups_rows),
        group_nbr=tuple(groups_nbr),
        group_mask=tuple(groups_mask),
        inv_order=inv_order,
        padded_slots=padded,
    )


# ---------------------------------------------------------------------------
# Blocked-ELL (CSC-Split, TPU edition) — preprocessing for the Pallas SpMM.
# ---------------------------------------------------------------------------


#: Blocked-ELL geometry the ``blocked`` backend builds and the TPU pick
#: judges: vertex tile edge, and edge slots per operand row (one MXU edge
#: chunk of the kernels).
BLOCKED_BLOCK_SIZE = 256
BLOCKED_ROW_CAPACITY = 256


@dataclass(frozen=True)
class BlockedELL:
    """Edges grouped by (dst-block, src-block) tile pairs, in fixed-size rows.

    Each nonempty pair holds ``ceil(count / pair_capacity)`` consecutive
    rows of ``pair_capacity`` edge slots, so a hub pair spills into several
    rows instead of padding every pair to the largest one.  Rows are sorted
    by destination block; the kernels' ``is_first``/``is_last`` run flags
    let every row of a destination block feed one accumulator tile.

    Attributes:
      n_padded: vertex count padded to a multiple of ``block_size``.
      block_size: tile edge (rows of M resident in VMEM per step).
      pair_dst_block: (n_rows,) int32 — destination block id per row.
      pair_src_block: (n_rows,) int32 — source block id per row.
      edge_dst_local: (n_rows, pair_capacity) int32 — dst row within block.
      edge_src_local: (n_rows, pair_capacity) int32 — src row within block.
      edge_valid:     (n_rows, pair_capacity) float32 — 1.0 valid / 0.0 pad.
      row_block_ptr:  (n_blocks + 1,) int32 — rows for dst block b live in
        ``[row_block_ptr[b], row_block_ptr[b+1])``.
    """

    n_padded: int
    block_size: int
    pair_dst_block: np.ndarray
    pair_src_block: np.ndarray
    edge_dst_local: np.ndarray
    edge_src_local: np.ndarray
    edge_valid: np.ndarray
    row_block_ptr: np.ndarray

    @property
    def n_blocks(self) -> int:
        return self.n_padded // self.block_size

    @property
    def n_pairs(self) -> int:
        """Rows of the operand (a split hub pair counts once per row)."""
        return int(self.pair_dst_block.shape[0])

    @property
    def pair_capacity(self) -> int:
        return int(self.edge_dst_local.shape[1])


@dataclass(frozen=True)
class BlockedGeometry:
    """Size of a blocked-ELL operand, computed without building it.

    ``padded_slots = n_rows * pair_capacity``; the operand holds three
    4-byte arrays per slot (dst, src, valid) and four int32 per-row
    scalars (src/dst block, run-head and run-tail flags).
    """

    n_blocks: int
    n_pairs: int
    n_rows: int
    pair_capacity: int
    num_edges: int

    @property
    def padded_slots(self) -> int:
        return self.n_rows * self.pair_capacity

    @property
    def operand_bytes(self) -> int:
        return 12 * self.padded_slots + 16 * self.n_rows

    @property
    def edge_bytes(self) -> int:
        """The same three per-edge arrays without padding."""
        return 12 * max(self.num_edges, 1)

    @property
    def padding_factor(self) -> float:
        return self.operand_bytes / self.edge_bytes


def _pair_runs(graph: Graph, block_size: int):
    """Edge order grouping each (dst-block, src-block) pair, plus the
    per-pair key, start and count."""
    n_blocks = (graph.n + block_size - 1) // block_size
    key = (graph.dst // block_size).astype(np.int64) * n_blocks + graph.src // block_size
    order = np.argsort(key, kind="stable")
    uniq, starts, counts = np.unique(key[order], return_index=True, return_counts=True)
    return n_blocks, order, uniq, starts, counts


def blocked_ell_geometry(
    graph: Graph, block_size: int = BLOCKED_BLOCK_SIZE, pair_capacity: int = BLOCKED_ROW_CAPACITY
) -> BlockedGeometry:
    """Rows and padded bytes :func:`build_blocked_ell` would produce."""
    n_blocks, _, _, _, counts = _pair_runs(graph, block_size)
    n_rows = int((-(-counts // pair_capacity)).sum())
    return BlockedGeometry(
        n_blocks=n_blocks,
        n_pairs=int(counts.size),
        n_rows=n_rows,
        pair_capacity=pair_capacity,
        num_edges=graph.num_directed,
    )


def build_blocked_ell(
    graph: Graph, block_size: int = BLOCKED_BLOCK_SIZE, pair_capacity: int = BLOCKED_ROW_CAPACITY
) -> BlockedELL:
    """Group edges into (dst-block, src-block) pairs of ``pair_capacity``-slot rows.

    A pair with more edges than ``pair_capacity`` continues in the next
    rows; no edge is ever dropped.  Built with array operations only.
    """
    if pair_capacity < 1:
        raise ValueError(f"pair_capacity={pair_capacity} must be positive")
    bs, cap = block_size, pair_capacity
    n_blocks, order, uniq, starts, counts = _pair_runs(graph, bs)
    rows_per_pair = -(-counts // cap)
    n_rows = int(rows_per_pair.sum())
    first_row = np.cumsum(rows_per_pair) - rows_per_pair
    pair_of_edge = np.repeat(np.arange(uniq.size), counts)
    rank = np.arange(order.size) - starts[pair_of_edge]
    row = first_row[pair_of_edge] + rank // cap
    slot = rank % cap

    edge_dst_local = np.zeros((n_rows, cap), dtype=np.int32)
    edge_src_local = np.zeros((n_rows, cap), dtype=np.int32)
    edge_valid = np.zeros((n_rows, cap), dtype=np.float32)
    edge_dst_local[row, slot] = graph.dst[order] % bs
    edge_src_local[row, slot] = graph.src[order] % bs
    edge_valid[row, slot] = 1.0

    row_pair_key = np.repeat(uniq, rows_per_pair)
    pair_dst_block = (row_pair_key // n_blocks).astype(np.int32)
    pair_src_block = (row_pair_key % n_blocks).astype(np.int32)
    row_block_ptr = np.zeros(n_blocks + 1, dtype=np.int32)
    row_block_ptr[1:] = np.cumsum(np.bincount(pair_dst_block, minlength=n_blocks))
    return BlockedELL(
        n_padded=n_blocks * bs,
        block_size=bs,
        pair_dst_block=pair_dst_block,
        pair_src_block=pair_src_block,
        edge_dst_local=edge_dst_local,
        edge_src_local=edge_src_local,
        edge_valid=edge_valid,
        row_block_ptr=row_block_ptr,
    )

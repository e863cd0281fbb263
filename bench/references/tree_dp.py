"""Plain colour-coding count of a tree template: the reference behind ``correct``.

For one colouring it runs the dynamic programme of ``bench/treelets.py``
stage by stage in float32 on the device, with elementwise products and
scatter-adds only (no matrix unit, so no reduced-precision passes):

* the leaf state is the colouring's one-hot, ``(k, n)``;
* a stage's passive state is summed over each vertex's neighbours, 128
  colour sets and ``EDGE_CHUNK`` edges at a time;
* the stage state is ``sum_j active[idx_a[:, j]] * neighbour_sum[idx_p[:, j]]``
  over the ways to split each colour set between the two children;
* the root state's single column is summed over the vertices on the host in
  float64 and scaled by ``k**k / k! / |Aut(T)|``.

States are kept colour-set-major, ``(columns, n)``, freed after their
last read, and wait in host memory while the next stage does not read them,
so that one chip holds at most a stage's own inputs and output.  Edge
arrays are padded to a capacity fixed by the configuration, and the chunk
count is a run-time loop bound, so every graph of one configuration reuses
one compiled program per stage shape.

The module imports nothing of the program under test.
"""

from __future__ import annotations

from collections import Counter
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from bench.treelets import LEAF, TreePlan, colourful_scale, split_table

#: Edges per scatter-add step, colour sets per neighbour sweep, and output
#: colour sets per block of the split sum.
EDGE_CHUNK = 1 << 20
COLUMN_BLOCK = 128
ROW_BLOCK = 64


@partial(jax.jit, static_argnames=("chunk",))
def _neighbour_sums(state, src, dst, n_chunks, *, chunk):
    """``out[:, v] = sum over edges (u -> v) of state[:, u]`` for a ``(c, n)``
    state, ``COLUMN_BLOCK`` colour sets and ``chunk`` edges at a time."""
    c, n = state.shape

    def block_sum(block):
        def body(i, acc):
            s = jax.lax.dynamic_slice_in_dim(src, i * chunk, chunk)
            d = jax.lax.dynamic_slice_in_dim(dst, i * chunk, chunk)
            # padded edges carry dst == n and are dropped
            return acc.at[d].add(block[s], indices_are_sorted=True, mode="drop")

        return jax.lax.fori_loop(0, n_chunks, body, jnp.zeros_like(block))

    out = jnp.zeros_like(state)
    for lo in range(0, c, COLUMN_BLOCK):
        block = state[lo:lo + COLUMN_BLOCK].T  # (n, width): a vertex's row is contiguous
        out = out.at[lo:lo + COLUMN_BLOCK].set(block_sum(block).T)
    return out


@jax.jit
def _combine(active, agg, idx_a, idx_p):
    """``out[S] = sum_j active[idx_a[S, j]] * agg[idx_p[S, j]]`` over ``(columns, n)``
    states, ``ROW_BLOCK`` output colour sets at a time."""
    n_out, n_splits = idx_a.shape
    out = jnp.zeros((n_out, active.shape[1]), active.dtype)
    for lo in range(0, n_out, ROW_BLOCK):
        ia, ip = idx_a[lo:lo + ROW_BLOCK], idx_p[lo:lo + ROW_BLOCK]

        def body(j, acc, ia=ia, ip=ip):
            return acc + active[ia[:, j]] * agg[ip[:, j]]

        block = jnp.zeros((ia.shape[0], active.shape[1]), active.dtype)
        out = out.at[lo:lo + ROW_BLOCK].set(jax.lax.fori_loop(0, n_splits, body, block))
    return out


class TreeReference:
    """Estimates of one tree template on one graph, a colouring at a time.

    Args:
      plan: the template's stages (``bench.treelets.plan_tree``).
      n: vertex count.
      src, dst: the canonical directed edge list (host arrays, sorted by dst).
      capacity: edge slots to pad to (fixed per configuration).
    """

    def __init__(self, plan: TreePlan, n: int, src: np.ndarray, dst: np.ndarray, capacity: int):
        self.plan = plan
        self.n = int(n)
        num = int(src.shape[0])
        capacity = max(capacity, num)
        capacity = -(-capacity // EDGE_CHUNK) * EDGE_CHUNK
        self._src = jnp.asarray(np.pad(np.asarray(src, np.int32), (0, capacity - num)))
        self._dst = jnp.asarray(np.pad(np.asarray(dst, np.int32), (0, capacity - num), constant_values=self.n))
        self._n_chunks = jnp.int32(-(-num // EDGE_CHUNK))
        self._tables = {
            s.canon: tuple(jnp.asarray(t) for t in split_table(plan.k, s.size, s.active_size))
            for s in plan.stages
        }

    @staticmethod
    def _read(states, reads, canon) -> None:
        """Count one read of ``canon``; free its state after the last."""
        reads[canon] -= 1
        if reads[canon] == 0:
            del states[canon]

    def colourful_total(self, colours) -> float:
        """Colourful embeddings of the rooted template under one colouring ``(n,)``."""
        k = self.plan.k
        colours = jnp.asarray(colours, jnp.int32)
        states = {LEAF: (colours[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None]).astype(jnp.float32)}
        reads = Counter()
        for s in self.plan.stages:
            reads[s.active] += 1
            reads[s.passive] += 1
        stages = self.plan.stages
        for i, s in enumerate(stages):
            states[s.passive] = jnp.asarray(states[s.passive])
            agg = _neighbour_sums(states[s.passive], self._src, self._dst, self._n_chunks, chunk=EDGE_CHUNK)
            self._read(states, reads, s.passive)
            states[s.active] = jnp.asarray(states[s.active])
            idx_a, idx_p = self._tables[s.canon]
            out = _combine(states[s.active], agg, idx_a, idx_p)
            del agg
            self._read(states, reads, s.active)
            states[s.canon] = out
            del out
            # a state the next stage does not read waits in host memory
            upcoming = {stages[i + 1].active, stages[i + 1].passive} if i + 1 < len(stages) else set()
            for canon in list(states):
                if canon not in upcoming and isinstance(states[canon], jax.Array):
                    states[canon] = np.asarray(states[canon])
        root = np.asarray(states[self.plan.root], np.float64)
        return float(root.sum())

    def estimate(self, colours) -> float:
        """The colour-coding estimate of the template's copies for one colouring."""
        return self.colourful_total(colours) * colourful_scale(self.plan.k) / self.plan.automorphisms

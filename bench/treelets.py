"""Tree templates as the benchmark sees them: decomposition, colour sets, symmetry.

Color coding (Alon, Yuster and Zwick, 1995) counts the copies of a tree
template ``T`` with ``k`` vertices in a graph: colour every vertex with one of
``k`` colours at random, count the *colourful* embeddings (all colours
distinct) with a dynamic programme over rooted sub-templates, and scale by
``k**k / k!`` and by ``1 / |Aut(T)|``.

The decomposition is the single-edge cut of FASCIA and of the SubGraph2Vec
paper (arXiv:2009.11665): root the template at its first vertex of largest
degree, cut the edge from the root to its smallest neighbour, and recurse
into the part that keeps the root (the *active* child) and the cut-off
subtree (the *passive* child).  Sub-templates with the same rooted canonical
form have the same state for every colouring, so each form is one stage.

Everything here is the benchmark's own: the byte count of
``metrics/dp_roofline_share.py`` and the reference of
``references/tree_dp.py`` both read it, and neither reads the program.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from math import comb, factorial
from typing import Dict, List, Sequence, Tuple

import numpy as np

LEAF = "()"


@dataclass(frozen=True)
class Stage:
    """One combine step: the state of ``canon`` from ``active`` and ``passive``."""

    canon: str
    size: int
    active: str
    passive: str
    active_size: int
    passive_size: int


@dataclass(frozen=True)
class TreePlan:
    """A tree template's stages in execution order (one per canonical form)."""

    k: int
    stages: Tuple[Stage, ...]
    automorphisms: int

    @property
    def root(self) -> str:
        return self.stages[-1].canon

    def columns(self, size: int) -> int:
        """Colour-set columns of a state over ``size`` template vertices."""
        return comb(self.k, size)


def _adjacency(edges: Sequence[Sequence[int]], k: int) -> List[List[int]]:
    adj: List[List[int]] = [[] for _ in range(k)]
    for u, v in edges:
        adj[u].append(v)
        adj[v].append(u)
    return adj


def _canon(adj, vertices: frozenset, root: int, parent: int = -1) -> str:
    kids = sorted(_canon(adj, vertices, c, root) for c in adj[root] if c != parent and c in vertices)
    return "(" + "".join(kids) + ")"


def _component(adj, start: int, blocked: int, vertices: frozenset) -> frozenset:
    seen, stack = {start}, [start]
    while stack:
        u = stack.pop()
        for w in adj[u]:
            if w != blocked and w in vertices and w not in seen:
                seen.add(w)
                stack.append(w)
    return frozenset(seen)


def check_tree(edges: Sequence[Sequence[int]], k: int) -> None:
    """Raise unless ``edges`` is a tree on the vertices ``0 .. k-1``."""
    if k < 1 or len(edges) != k - 1:
        raise ValueError(f"a tree on {k} vertices has {k - 1} edges, got {len(edges)}")
    if any(not (0 <= u < k and 0 <= v < k) or u == v for u, v in edges):
        raise ValueError(f"edges {edges} do not join distinct vertices of 0..{k - 1}")
    adj = _adjacency(edges, k)
    if len(_component(adj, 0, -1, frozenset(range(k)))) != k:
        raise ValueError(f"edges {edges} do not connect all {k} vertices")


def automorphisms(edges: Sequence[Sequence[int]], k: int) -> int:
    """|Aut(T)| of a tree, from its centre(s) and rooted canonical forms."""
    if k == 1:
        return 1
    adj = _adjacency(edges, k)
    everything = frozenset(range(k))

    def rooted(root: int, parent: int) -> Tuple[str, int]:
        forms, aut = [], 1
        for c in adj[root]:
            if c != parent:
                f, a = rooted(c, root)
                forms.append(f)
                aut *= a
        for _, group in itertools.groupby(sorted(forms)):
            aut *= factorial(len(list(group)))
        return "(" + "".join(sorted(forms)) + ")", aut

    # the centre: peel leaves until one or two vertices remain
    alive = set(everything)
    while len(alive) > 2:
        leaves = [v for v in alive if sum(w in alive for w in adj[v]) <= 1]
        alive -= set(leaves)
    centres = sorted(alive)
    if len(centres) == 1:
        return rooted(centres[0], -1)[1]
    (f1, a1), (f2, a2) = rooted(centres[0], centres[1]), rooted(centres[1], centres[0])
    return a1 * a2 * (2 if f1 == f2 else 1)


def plan_tree(edges: Sequence[Sequence[int]], k: int) -> TreePlan:
    """Stages of the single-edge-cut decomposition, deduplicated by canonical form."""
    edges = [tuple(int(x) for x in e) for e in edges]
    check_tree(edges, k)
    adj = _adjacency(edges, k)
    degree = [len(a) for a in adj]
    root = degree.index(max(degree))
    stages: List[Stage] = []
    seen = {LEAF}

    def rec(vertices: frozenset, rho: int) -> Tuple[str, int]:
        canon = _canon(adj, vertices, rho)
        if len(vertices) == 1:
            return canon, 1
        tau = min(w for w in adj[rho] if w in vertices)
        passive = _component(adj, tau, rho, vertices)
        a_canon, a_size = rec(vertices - passive, rho)
        p_canon, p_size = rec(passive, tau)
        if canon not in seen:
            seen.add(canon)
            stages.append(Stage(canon, len(vertices), a_canon, p_canon, a_size, p_size))
        return canon, len(vertices)

    if k == 1:
        raise ValueError("a one-vertex template has no stage to count")
    rec(frozenset(range(k)), root)
    return TreePlan(k=k, stages=tuple(stages), automorphisms=automorphisms(edges, k))


def colour_sets(k: int, size: int) -> Dict[int, int]:
    """Bit mask of each ``size``-subset of ``k`` colours -> its column, in
    lexicographic order of the subsets."""
    return {
        sum(1 << c for c in subset): i
        for i, subset in enumerate(itertools.combinations(range(k), size))
    }


def split_table(k: int, size: int, active_size: int) -> Tuple[np.ndarray, np.ndarray]:
    """For every colour set ``S`` of ``size`` colours (rows) and every way to
    give ``active_size`` of them to the active child (columns): the active
    child's column and the passive child's column."""
    out = colour_sets(k, size)
    act = colour_sets(k, active_size)
    pas = colour_sets(k, size - active_size)
    n_splits = comb(size, active_size)
    idx_a = np.zeros((len(out), n_splits), np.int32)
    idx_p = np.zeros((len(out), n_splits), np.int32)
    for mask, row in out.items():
        colours = [c for c in range(k) if mask >> c & 1]
        for j, chosen in enumerate(itertools.combinations(colours, active_size)):
            a_mask = sum(1 << c for c in chosen)
            idx_a[row, j] = act[a_mask]
            idx_p[row, j] = pas[mask ^ a_mask]
    return idx_a, idx_p


def colourful_scale(k: int) -> float:
    """``k**k / k!``: one over the chance that a fixed copy comes out colourful."""
    return float(k) ** k / factorial(k)

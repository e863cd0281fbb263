"""Graph500-parameter R-MAT graphs, made on the device from a seed.

The generator follows the R-MAT recursion (Chakrabarti, Zhan and Faloutsos,
2004) as the Graph500 specification parameterises it: ``2**scale``
vertices, ``edgefactor * 2**scale`` sampled edges, and at each of ``scale``
levels the quadrant probabilities ``a``, ``b``, ``c`` and ``d = 1 - a - b - c``.
The samples are then made a simple undirected graph: self-loops dropped,
duplicates merged, both directions stored, sorted by ``(dst, src)``.  That is
the canonical edge list the counting program takes.

Everything up to the final slice runs in one jitted call whose shapes depend
on the configuration only, so every seed of one configuration reuses one
compiled program.  The edge count is data-dependent; the device returns
arrays at the fixed capacity ``2 * samples`` with the unused tail sorted
last, and the host slices them.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class EdgeList:
    """Canonical undirected edge list: both directions, sorted by (dst, src)."""

    n: int
    src: np.ndarray  # (num_directed,) int32
    dst: np.ndarray  # (num_directed,) int32

    @property
    def num_directed(self) -> int:
        return int(self.src.shape[0])


def key_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words of a raw PRNG key for ``stream`` of ``seed``.

    ``seed`` may be any non-negative integer (NumPy's ``SeedSequence`` takes
    arbitrarily large ones); distinct streams give independent keys.
    """
    seq = np.random.SeedSequence([int(seed), int(stream)])
    return seq.generate_state(2, dtype=np.uint32)


@partial(jax.jit, static_argnames=("scale", "samples", "a", "b", "c", "permute"))
def _rmat_device(key, *, scale: int, samples: int, a: float, b: float, c: float, permute: bool):
    n = 1 << scale
    u = jnp.zeros(samples, jnp.int32)
    v = jnp.zeros(samples, jnp.int32)
    level_keys = jax.random.split(key, scale + 1)
    for level_key in level_keys[:scale]:
        r = jax.random.uniform(level_key, (samples,), jnp.float32)
        right = ((r >= a + b) & (r < a + b + c)) | (r >= a + b + c)
        down = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        u = (u << 1) | down.astype(jnp.int32)
        v = (v << 1) | right.astype(jnp.int32)
    if permute:
        # Graph500 relabels the vertices by a random permutation
        perm = jax.random.permutation(level_keys[scale], n).astype(jnp.int32)
        u, v = perm[u], perm[v]
    lo, hi = jnp.minimum(u, v), jnp.maximum(u, v)
    # self-loops sort last under the sentinel vertex n
    loop = lo == hi
    lo = jnp.where(loop, n, lo)
    hi = jnp.where(loop, n, hi)
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    first = jnp.concatenate([jnp.ones(1, bool), (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    keep = first & (lo < n)
    lo = jnp.where(keep, lo, n)
    hi = jnp.where(keep, hi, n)
    src = jnp.concatenate([lo, hi])
    dst = jnp.concatenate([hi, lo])
    dst, src = jax.lax.sort((dst, src), num_keys=2)
    return src, dst, 2 * keep.sum()


def rmat_edges(seed: int, *, scale: int, edgefactor: int, a: float, b: float, c: float,
               permute: bool = False) -> EdgeList:
    """The R-MAT graph of ``seed``: ``2**scale`` vertices, ``edgefactor << scale``
    samples, vertex labels permuted at random where ``permute`` is set."""
    key = jnp.asarray(key_words(seed, 0))
    src, dst, count = _rmat_device(
        key, scale=int(scale), samples=int(edgefactor) << int(scale), a=float(a), b=float(b), c=float(c),
        permute=bool(permute),
    )
    count = int(count)
    src, dst = jax.device_get((src, dst))
    return EdgeList(n=1 << int(scale), src=np.ascontiguousarray(src[:count]), dst=np.ascontiguousarray(dst[:count]))

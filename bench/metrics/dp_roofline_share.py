"""Share of the HBM roofline reached by the counting DP's compulsory bytes, in %.

The bound is HBM bandwidth alone: the DP's products run in float32 on the
vector units, and the TPU v5e has no published float32 peak, so no compute
roofline is drawn.

Compulsory bytes of one launch of ``B`` colourings: for every stage of the
benchmark's own decomposition (``bench/treelets.py``; a stage shared by two
templates of the launch counts once)

* the edge indices once: ``|E| * 8`` B (source and destination, int32);
* the active state read once: ``B * n * 4 * C(k, m_a)`` B;
* the passive state read once: ``B * n * 4 * C(k, m_p)`` B;
* the output state written once: ``B * n * 4 * C(k, m)`` B;

where a one-vertex (leaf) state counts as the colouring itself, ``B * n * 4``
B, since an implementation may build its one-hot on the fly.  Values are at
float32.  Lane padding, gather amplification and copies are an
implementation's costs and are left out, so any stage-by-stage
implementation moves at least this much.

share = launches in the traced window * bytes per launch
        / (HBM bytes/s * busy seconds summed over the chips used)
"""

from __future__ import annotations

from math import comb
from typing import Dict, List, Sequence, Tuple

from bench.treelets import plan_tree

STATE_BYTES = 4
EDGE_BYTES = 8


def _columns(k: int, size: int) -> int:
    return 1 if size == 1 else comb(k, size)


def stage_terms(templates: Sequence[Tuple[Sequence[Sequence[int]], int]], n: int, num_directed: int,
                chunk: int) -> List[Dict[str, int]]:
    """Bytes of each distinct stage of one launch, term by term."""
    seen = set()
    terms = []
    for edges, k in templates:
        for s in plan_tree(edges, k).stages:
            if s.canon in seen:
                continue
            seen.add(s.canon)
            per_state = chunk * n * STATE_BYTES
            terms.append({
                "edges": num_directed * EDGE_BYTES,
                "active": per_state * _columns(k, s.active_size),
                "passive": per_state * _columns(k, s.passive_size),
                "output": per_state * _columns(k, s.size),
            })
    return terms


def launch_bytes(templates, n: int, num_directed: int, chunk: int) -> int:
    """Compulsory HBM bytes of one launch (see the module docstring)."""
    return sum(sum(t.values()) for t in stage_terms(templates, n, num_directed, chunk))


def engine_launch_bytes(engine) -> int:
    """:func:`launch_bytes` of a counting engine's launch, read from its
    templates, its graph's sizes and its chunk: nothing of its backend."""
    templates = [(t.edges, t.k) for t in engine.templates]
    return launch_bytes(templates, engine.graph.n, engine.graph.num_directed, engine.chunk_size)


def read(run):
    reading = run.trace
    busy = sum(reading.busy_s.values()) if reading is not None else 0.0
    if busy <= 0:
        return None
    return 100.0 * run.launches * run.launch_bytes / (run.peaks["hbm_bytes_per_s"] * busy)

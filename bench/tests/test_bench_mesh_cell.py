"""The four-chip cell ``g500-s20-u12-4chip`` runs through the harness.

Each run is a child process with four virtual CPU devices (the test process
keeps JAX's single-device view).  The cell is resolved by name from a copy
of ``BENCHMARK.json`` and ``bench/`` whose configuration is cut to a tiny
scale; ``bench.run.run_cell`` then builds the mesh engine on the four
devices, runs a window, checks a colouring against the plain reference, and
in a traced run reports the cell's per-layer metrics.  Nothing here loads
the TPU runtime.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "g500-s20-u12-4chip"
MESH_METRICS = {"collective_exposed_share", "collective_bytes_per_coloring", "mesh_edge_pad_share"}


def _child(code: str, *args: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code, *args], capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, f"child failed:\nstdout={proc.stdout}\nstderr={proc.stderr}"
    return proc.stdout


def tiny_checkout(tmp_path: Path, scale: int = 9) -> Path:
    """A copy of the benchmark whose four-chip configuration has ``2**scale`` vertices."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    path = root / entry["file"]
    config = json.loads(path.read_text())
    config["scale"] = scale
    path.write_text(json.dumps(config))
    return root


RUN_CELL = r"""
import json, sys
from pathlib import Path
import jax
from bench import run as bench_run, spec

bench_run.enable_cache = lambda: None  # keep JAX's own cache setting
root = Path(sys.argv[1])
cell = spec.load_cell(sys.argv[2], root)
peaks = json.loads((root / "bench" / "peaks.json").read_text())["devices"]["TPU v5 lite"]
devices = jax.devices()[:cell.chips]
assert len(devices) == cell.chips == 4
for traced in (False, True):
    out = bench_run.run_cell(cell, 2**33 + 11, 0.2, traced, devices, peaks)
    print(json.dumps({"traced": traced, **out}))
"""


def test_four_chip_cell_runs_correct_and_reports_its_metrics(tmp_path):
    root = tiny_checkout(tmp_path)
    untraced, traced = (json.loads(line) for line in _child(RUN_CELL, str(root), CELL).strip().splitlines()[-2:])
    for out in (untraced, traced):
        assert out["correct"] is True and out["failed"] == 0
        assert out["checks"]["invalid_estimates"]["value"] == 0
        assert out["checks"]["max_rel_err"]["value"] <= out["checks"]["max_rel_err"]["limit"]
        assert out["device"]["count"] == 4
    assert set(untraced["metrics"]) == {"colorings_per_s", "setup_s"}
    assert set(traced["metrics"]) == MESH_METRICS
    got = {name: m["value"] for name, m in traced["metrics"].items()}
    assert got["collective_bytes_per_coloring"] > 0
    assert 0.0 <= got["mesh_edge_pad_share"] < 100.0
    # the CPU trace has no TPU planes: no collective time is seen
    assert got["collective_exposed_share"] == 0.0


READ_WITHOUT_MESH = r"""
import sys
from pathlib import Path
from bench import spec
from bench.graphgen import rmat_edges
from repro.core import CountingEngine
from repro.core.graph import Graph
from repro.core.templates import Template

cell = spec.load_cell(sys.argv[1], Path(sys.argv[2]))
names = ("collective_bytes_per_coloring", "mesh_edge_pad_share")
print("before", [spec.metric_reader(cell, m)(None) for m in names])
g = rmat_edges(3, scale=6, edgefactor=4, a=0.57, b=0.19, c=0.19)
CountingEngine(Graph(n=g.n, src=g.src, dst=g.dst), [Template("u3", ((0, 1), (1, 2)))], backend="edges")
print("local", [spec.metric_reader(cell, m)(None) for m in names])
"""


def test_mesh_readers_give_nothing_without_a_mesh_engine():
    out = _child(READ_WITHOUT_MESH, CELL, str(ROOT), devices=1)
    assert "before [None, None]" in out
    assert "local [None, None]" in out

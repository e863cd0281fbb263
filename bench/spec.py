"""Finds a cell's pieces by name: ``BENCHMARK.json`` and the files under ``bench/``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a file of its own, found by name:

* ``BENCHMARK.json``'s ``configs`` entry gives the configuration's ``file``;
* ``bench/traffic/<traffic>.json`` is the traffic mix;
* ``bench/limits/<workload>.json`` holds the limits of the cell's checks;
* ``bench/metrics/<metric>.py`` reads one metric (``read(run)``);
* ``bench/references/<reference>.py`` is the plain reference a
  configuration names.

Adding a cell, a configuration, a traffic mix or a metric is adding files
and entries; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

#: The checkout holding ``BENCHMARK.json`` and ``bench/``.
ROOT = Path(__file__).resolve().parent.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: Path


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Optional[Path] = None) -> Cell:
    """Resolve workload ``name`` of ``<root>/BENCHMARK.json``; raise ``KeyError`` if absent."""
    root = Path(root or ROOT)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench_dir = root / "bench"
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench_dir / "limits" / f"{name}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        limits=limits,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir,
    )


def _load_module(path: Path, label: str):
    mod_spec = importlib.util.spec_from_file_location(label, path)
    if mod_spec is None or mod_spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def metric_reader(cell: Cell, metric: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = cell.bench_dir / "metrics" / f"{metric}.py"
    return _load_module(path, f"bench_metric_{metric.replace('.', '_').replace('-', '_')}").read


def reference_module(cell: Cell):
    """The module ``bench/references/<reference>.py`` the configuration names."""
    ref = cell.config["reference"]
    return _load_module(cell.bench_dir / "references" / f"{ref}.py", f"bench_reference_{ref}")


def device_peaks(bench_dir: Path, device_kind: str) -> Dict:
    """The published peaks of ``device_kind``; a kind not in the table is an error."""
    table = json.loads((bench_dir / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]

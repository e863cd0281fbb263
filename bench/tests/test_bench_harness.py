"""The harness finds cells by name, and refuses to run off the chip.

The runs here go through ``bench.run.main`` on the CPU at a tiny size, with
the harness's look for a TPU replaced by the test; nothing here loads the TPU
runtime.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

import jax

from bench import run as bench_run
from bench import spec

ROOT = Path(__file__).resolve().parents[2]
V5E = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"]["TPU v5 lite"]

TINY_CONFIG = {
    "name": "tiny-rmat",
    "source": "test",
    "generator": "rmat",
    "scale": 8,
    "edgefactor": 8,
    "a": 0.57,
    "b": 0.19,
    "c": 0.19,
    "permute": True,
    "precision": "fp32",
    "reference": "tree_dp",
    "reduced": ["scale", "edgefactor"],
}
U5_TRAFFIC = {
    "kind": "engine_stream",
    "templates": [{"name": "u5-2", "k": 5, "edges": [[0, 1], [1, 2], [2, 3], [1, 4]]}],
    "chunk": 4,
    "check_colorings": 4,
}
LAUNCH_METRIC = '''"""Launches in the window (a metric that a later change adds as a file)."""


def read(run):
    return run.launches
'''


def make_checkout(tmp_path: Path, cell="tiny-u5", traffic=U5_TRAFFIC, extra_metric=True) -> Path:
    """A copy of ``BENCHMARK.json`` and ``bench/``, plus one configuration,
    one traffic mix, one limit and one metric added as new files only."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny-rmat.json").write_text(json.dumps(TINY_CONFIG))
    (root / "bench" / "traffic" / "tiny-u5.json").write_text(json.dumps(traffic))
    (root / "bench" / "limits" / f"{cell}.json").write_text(json.dumps({"max_rel_err": {"limit": 1e-5}}))
    bench["configs"].append({"name": "tiny-rmat", "source": "test", "file": "bench/configs/tiny-rmat.json",
                             "reduced": ["scale", "edgefactor"], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tiny-rmat", "traffic": "tiny-u5", "chips": 1, "why": "test"})
    if extra_metric:
        (root / "bench" / "metrics" / "launches.py").write_text(LAUNCH_METRIC)
        bench["end_to_end"].append({"name": "launches", "unit": "launches", "better": "higher", "bound": 0.25,
                                    "source": "host_clock", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def off_chip(monkeypatch):
    """Let the harness run on the CPU: its look for a TPU returns CPU devices."""
    monkeypatch.setattr(bench_run, "require_chips", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(bench_run.spec, "device_peaks", lambda bench_dir, kind: V5E)
    monkeypatch.setattr(bench_run, "enable_cache", lambda: None)


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_registered_cells_resolve():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        assert cell.chips == w["chips"]
        assert cell.traffic["kind"] == "engine_stream"
        assert cell.limits["max_rel_err"]["limit"] > 0
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(cell, m["name"]))
        assert hasattr(spec.reference_module(cell), "TreeReference")


def test_added_files_make_a_runnable_cell(tmp_path, off_chip, capsys):
    root = make_checkout(tmp_path)
    cell = spec.load_cell("tiny-u5", root)
    assert cell.config["scale"] == 8 and cell.traffic["templates"][0]["name"] == "u5-2"
    assert [m["name"] for m in cell.end_to_end] == ["colorings_per_s", "setup_s", "launches"]
    assert bench_run.main(["--workload", "tiny-u5", "--seed", str(2**33 + 7), "--seconds", "0.2"], root=root) == 0
    captured = capsys.readouterr()
    out = last_json(captured.out)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"colorings_per_s", "setup_s", "launches"}
    assert out["metrics"]["launches"]["value"] >= 1
    assert out["attempted"] == 4 * out["metrics"]["launches"]["value"]
    assert list(out)[-1] == "checks"
    assert captured.err.strip().splitlines()[-1].startswith("check invalid_estimates")


def test_seed_fixes_the_inputs(tmp_path, off_chip):
    cfg = dict(TINY_CONFIG)
    a, b = bench_run.make_graph(cfg, 5), bench_run.make_graph(cfg, 5)
    c = bench_run.make_graph(cfg, 6)
    assert (a.src == b.src).all() and (a.dst == b.dst).all()
    assert a.num_directed != c.num_directed or (a.src != c.src).any()
    k1, k2 = bench_run.KeyStream(2**40, 3), bench_run.KeyStream(2**40, 3)
    assert (k1.next() == k2.next()).all()


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    assert jax.devices()[0].platform != "tpu"
    rc = bench_run.main(["--workload", "g500-s20-u7", "--seed", "1", "--seconds", "1"])
    captured = capsys.readouterr()
    assert rc != 0
    assert captured.out.strip() == ""
    assert "not a TPU" in captured.err


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        spec.device_peaks(ROOT / "bench", "cpu")
    assert spec.device_peaks(ROOT / "bench", "TPU v5 lite")["hbm_bytes_per_s"] == 819e9

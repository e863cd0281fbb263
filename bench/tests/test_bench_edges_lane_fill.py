"""``edges_lane_fill_share`` reads the ``edges`` backend's lane gauges."""

import importlib.util
from pathlib import Path

import pytest

from repro import tracing

READER = Path(__file__).resolve().parents[1] / "metrics" / "edges_lane_fill_share.py"


def _reader():
    spec = importlib.util.spec_from_file_location("edges_lane_fill_share", READER)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize(
    "set_gauges,want",
    [
        ({}, None),
        ({"edges_lane_slots": 0, "edges_lane_used": 0}, None),
        ({"edges_lane_slots": 384, "edges_lane_used": 154}, 100.0 * 154 / 384),
        ({"edges_lane_slots": 2432, "edges_lane_used": 2014}, 100.0 * 2014 / 2432),
    ],
    ids=["no-gauges", "no-slots", "u7-chunk2", "u12-chunk1"],
)
def test_lane_fill_share_reads_the_gauges(monkeypatch, set_gauges, want):
    monkeypatch.setattr(tracing, "gauges", lambda: dict(set_gauges))
    assert _reader()(None) == want

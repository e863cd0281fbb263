"""Autotuner tests: search determinism, cache round-trip/robustness, the
backend resolution ladder (explicit > env > tuned > heuristic), mixed-
backend execution equality, and the quarantine -> tuned-entry interop."""

import json

import jax
import numpy as np
import pytest

from repro.core import CountingEngine, engine_cache_key, get_template, rmat_graph
from repro.core.graph import erdos_renyi_graph, grid_graph
from repro.exec.select import resolve_backend_config, tune_mode
from repro.plan.cost import CostModel
from repro.plan.ir import build_template_plan
from repro.tune import (
    TUNING_SCHEMA_VERSION,
    TuningCache,
    TuningConfig,
    consult,
    tune,
)
from repro.tune.cache import entry_key, load_calibration


def _graph():
    return rmat_graph(120, 600, seed=3)


def _leaders(graph, tname):
    plan = build_template_plan([get_template(tname)])
    cost = CostModel(plan, graph, np.float32)
    return plan, cost.tree_group_leaders()


def _mixed_config(leaders, backends=("edges", "sell")):
    return TuningConfig(
        default_backend=backends[0],
        group_backends=tuple(
            (addr, backends[k % len(backends)]) for k, addr in enumerate(leaders)
        ),
    )


# ---------------------------------------------------------------------------
# TuningConfig: JSON round trip, normalization, key fragments
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "cfg",
    [
        TuningConfig(default_backend="edges"),
        TuningConfig(default_backend="sell", column_batch=8, chunk_size=24),
        TuningConfig(
            default_backend="edges",
            group_backends=(((0, 5), "sell"), ((0, 4), "edges")),
            column_batch=4,
        ),
    ],
)
def test_config_json_roundtrip_bit_exact(cfg):
    # through an actual serialize/parse cycle, not just dict identity
    back = TuningConfig.from_json(json.loads(json.dumps(cfg.to_json())))
    assert back == cfg
    assert back.key_fragment() == cfg.key_fragment()
    assert back.describe() == cfg.describe()


def test_config_bindings_normalized_sorted():
    a = TuningConfig(
        "edges", group_backends=(((0, 5), "sell"), ((0, 4), "edges"))
    )
    b = TuningConfig(
        "edges", group_backends=(((0, 4), "edges"), ((0, 5), "sell"))
    )
    assert a == b and a.key_fragment() == b.key_fragment()
    assert a.mixed and a.backend_name == "mixed"
    assert not TuningConfig("edges", group_backends=(((0, 4), "edges"),)).mixed


def test_config_version_mismatch_raises():
    data = TuningConfig("edges").to_json()
    data["version"] = TUNING_SCHEMA_VERSION + 1
    with pytest.raises(ValueError):
        TuningConfig.from_json(data)
    with pytest.raises(ValueError):
        TuningConfig.from_json({"default_backend": "edges"})  # no version
    with pytest.raises(ValueError):
        TuningConfig.from_json("edges")  # not an object


# ---------------------------------------------------------------------------
# TuningCache: persistence round trip + corrupt-file robustness
# ---------------------------------------------------------------------------


def test_cache_roundtrip_bit_exact(tmp_path):
    path = str(tmp_path / "tuned.json")
    cfg = TuningConfig(
        "edges", group_backends=(((0, 4), "sell"),), column_batch=6, chunk_size=20
    )
    cache = TuningCache(path)
    cache.put("sig-a", [[0, 1, 2]], cfg, device="cpu", meta={"measured_us": 1.5})
    cache.merge_calibration({"edges": 1.25, "sell": 0.8})
    assert cache.save() == path

    loaded = TuningCache.load(path)
    assert loaded.get("sig-a", [[0, 1, 2]], "cpu") == cfg
    assert loaded.get("sig-a", [[0, 1, 2]], "cpu").key_fragment() == cfg.key_fragment()
    assert loaded.meta("sig-a", [[0, 1, 2]], "cpu")["measured_us"] == 1.5
    assert loaded.calibration == {"edges": 1.25, "sell": 0.8}
    # the memoized read path sees the same entry
    assert consult("sig-a", [[0, 1, 2]], device="cpu", path=path) == cfg
    assert load_calibration(path) == {"edges": 1.25, "sell": 0.8}
    # a different graph / canons / device is a miss, not a crash
    assert loaded.get("sig-b", [[0, 1, 2]], "cpu") is None
    assert loaded.get("sig-a", [[9, 9]], "cpu") is None
    assert loaded.get("sig-a", [[0, 1, 2]], "tpu") is None


@pytest.mark.parametrize(
    "content",
    [
        "this is not json{{{",
        json.dumps([1, 2, 3]),  # not an object
        json.dumps({"version": TUNING_SCHEMA_VERSION + 7, "entries": {}}),
        json.dumps({}),  # missing version
    ],
)
def test_cache_corrupt_or_stale_files_ignored(tmp_path, content, caplog):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as fh:
        fh.write(content)
    with caplog.at_level("WARNING", logger="repro.tune"):
        cache = TuningCache.load(path)
    assert cache.entries == {} and cache.calibration == {}
    # never raises on the resolution hot path either
    assert consult("sig", [[0]], device="cpu", path=path) is None
    assert load_calibration(path) == {}


def test_cache_entries_keyed_by_device_kind():
    """Entries carry the chip model, not the platform: a winner measured on
    one TPU generation must not bind on another."""
    from repro.tune.cache import device_kind

    assert device_kind() == jax.devices()[0].device_kind
    assert entry_key("sig-a", [[0, 1]]).endswith("|" + device_kind())
    assert entry_key("sig-a", [[0, 1]], "TPU v5 lite") != entry_key(
        "sig-a", [[0, 1]], "TPU v4"
    )


def test_cache_malformed_entry_ignored(tmp_path):
    path = str(tmp_path / "tuned.json")
    key = entry_key("sig-a", [[0, 1]], "cpu")
    with open(path, "w") as fh:
        json.dump(
            {
                "version": TUNING_SCHEMA_VERSION,
                "entries": {key: {"config": {"version": 99, "default_backend": 3}}},
                "calibration": {"edges": "NaNsense", "sell": -2, "dense": 1.5},
            },
            fh,
        )
    cache = TuningCache.load(path)
    assert cache.get("sig-a", [[0, 1]], "cpu") is None  # warned, not raised
    assert cache.calibration == {"dense": 1.5}  # bad ratios dropped


# ---------------------------------------------------------------------------
# The search: deterministic given the measurements
# ---------------------------------------------------------------------------


def _fake_measure(engine, probes):
    # a pure function of the probed configuration: favors sell strongly so
    # the winner differs from the lattice's predicted order
    base = {"edges": 50.0, "ell": 40.0, "sell": 10.0, "dense": 70.0}.get(
        engine.backend, 30.0
    )
    return base + 0.01 * engine.chunk_size + 0.1 * (engine.column_batch or 0)


def test_tuner_determinism_same_measurements_same_config(tmp_path):
    g = _graph()
    templates = [get_template("u5-1")]
    results = [
        tune(g, templates, top_n=4, probes=1, save=False, measure_fn=_fake_measure)
        for _ in range(2)
    ]
    assert results[0].config == results[1].config
    assert results[0].measured == results[1].measured
    assert results[0].calibration == results[1].calibration
    assert results[0].cache_path is None  # save=False never writes
    # the winner is the injected-measurement argmin, not the predicted one
    best = min(results[0].measured, key=lambda m: m.measured_us)
    assert results[0].config == best.config


def test_tune_persists_and_engine_picks_it_up(tmp_path, monkeypatch):
    path = str(tmp_path / "tuned.json")
    g = _graph()
    templates = [get_template("u5-1")]
    result = tune(
        g, templates, top_n=2, probes=1, cache_path=path, measure_fn=_fake_measure
    )
    assert result.cache_path == path
    plan = build_template_plan(templates)
    assert consult(g.signature(), plan.canons, path=path) == result.config

    # a fresh engine under REPRO_TUNE=cached (the default) resolves to it
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    monkeypatch.delenv("REPRO_ENGINE_BACKEND", raising=False)
    eng = CountingEngine(g, templates)
    d = eng.describe()["backend"]
    assert d["source"] == "tuned"
    assert d["name"] == result.config.backend_name
    if result.config.chunk_size is not None:
        assert eng.chunk_size == result.config.chunk_size
    if result.config.column_batch is not None:
        assert eng.column_batch == result.config.column_batch
    # pre-construction key == built key (the service's contract)
    assert engine_cache_key(g, templates) == eng.cache_key()
    assert eng.cache_key()[-1] == result.config.key_fragment()


# ---------------------------------------------------------------------------
# Resolution ladder: explicit > env > tuned > heuristic
# ---------------------------------------------------------------------------


def _seed_cache(path, g, templates, backend="sell"):
    plan = build_template_plan(templates)
    cache = TuningCache(path)
    cache.put(
        g.signature(), plan.canons, TuningConfig(default_backend=backend)
    )
    cache.save()
    return plan


def test_env_override_beats_tuned_and_heuristic(tmp_path, monkeypatch):
    path = str(tmp_path / "tuned.json")
    g = _graph()
    templates = [get_template("u5-1")]
    _seed_cache(path, g, templates, backend="sell")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)

    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "dense")
    eng = CountingEngine(g, templates)
    d = eng.describe()["backend"]
    assert (d["name"], d["source"]) == ("dense", "env")
    assert eng.cache_key()[-1] is None  # env result is not a tuned engine

    # explicit backend= beats even the env override
    eng2 = CountingEngine(g, templates, backend="edges")
    d2 = eng2.describe()["backend"]
    assert (d2["name"], d2["source"]) == ("edges", "explicit")


def test_tune_mode_off_falls_back_to_heuristic(tmp_path, monkeypatch):
    path = str(tmp_path / "tuned.json")
    g = _graph()
    templates = [get_template("u5-1")]
    _seed_cache(path, g, templates, backend="sell")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    monkeypatch.delenv("REPRO_ENGINE_BACKEND", raising=False)

    monkeypatch.setenv("REPRO_TUNE", "off")
    d = CountingEngine(g, templates).describe()["backend"]
    assert d["source"] == "heuristic"

    monkeypatch.setenv("REPRO_TUNE", "cached")
    d = CountingEngine(g, templates).describe()["backend"]
    assert (d["name"], d["source"]) == ("sell", "tuned")


def test_tune_mode_bad_value_warns_and_defaults(monkeypatch, caplog):
    monkeypatch.setenv("REPRO_TUNE", "frobnicate")
    with caplog.at_level("WARNING", logger="repro.engine"):
        assert tune_mode() == "cached"  # never raises


def test_resolve_backend_config_sources(tmp_path, monkeypatch):
    g = _graph()
    monkeypatch.delenv("REPRO_ENGINE_BACKEND", raising=False)
    name, source, reason, cfg = resolve_backend_config(g, backend="edges")
    assert (name, source, cfg) == ("edges", "explicit", None)
    name, source, reason, cfg = resolve_backend_config(g, backend="auto")
    assert source == "heuristic" and reason
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "sell")
    name, source, _, _ = resolve_backend_config(g, backend="auto")
    assert (name, source) == ("sell", "env")


# ---------------------------------------------------------------------------
# Mixed-backend execution == single-backend oracle (bit-exact)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tname", ["u3", "u5-1", "u5-2", "u6", "u7"])
def test_mixed_backend_bit_exact_vs_uniform(tname):
    graphs = [
        rmat_graph(120, 600, seed=3),
        erdos_renyi_graph(100, 500, seed=1),
        grid_graph(8, 12),
    ]
    for g in graphs:
        plan, leaders = _leaders(g, tname)
        cfg = _mixed_config(leaders)
        oracle = CountingEngine(g, [get_template(tname)], backend="edges")
        mixed = CountingEngine(
            g, [get_template(tname)], backend="mixed", tuning=cfg
        )
        rng = np.random.default_rng(7)
        for _ in range(2):
            colors = rng.integers(0, get_template(tname).k, size=g.n)
            a = np.asarray(oracle.raw_counts(colors))
            b = np.asarray(mixed.raw_counts(colors))
            assert np.array_equal(a, b), (tname, g.signature(), a, b)


def test_mixed_engine_requires_tuning_config():
    g = _graph()
    with pytest.raises(ValueError):
        CountingEngine(g, [get_template("u5-1")], backend="mixed")


# ---------------------------------------------------------------------------
# REPRO_TUNE=full: the service self-queues, the frontend drains
# ---------------------------------------------------------------------------


def test_full_mode_service_queues_and_frontend_drains_tune(tmp_path, monkeypatch):
    from repro.serve import CountingService
    from repro.serve.frontend import make_frontend

    path = str(tmp_path / "tuned.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    monkeypatch.setenv("REPRO_TUNE", "full")
    monkeypatch.delenv("REPRO_ENGINE_BACKEND", raising=False)
    # canned measurements: probe engines are built but never launched
    monkeypatch.setattr("repro.tune.search.measure_engine_us", _fake_measure)

    g = _graph()
    svc = CountingService(chunk_size=4)
    svc.register_graph("g", g)
    fe = make_frontend(svc, manual=True)
    fut = fe.submit("t0", "g", "u5-1", iterations=4, seed=1)
    fe.drain()
    assert fut.done() and not fut.failed()
    # the untuned workload self-queued a background tune at submit; it
    # drains through the frontend's warm/tune round slot
    tuned_round = None
    for _ in range(4):
        info = fe.step()
        if info["tuned"] is not None:
            tuned_round = info["tuned"]
            break
    assert tuned_round == ("g", ("u5-1",))
    assert fe.tunes_run == 1 and svc.tunes_completed == 1
    assert svc.stats()["tuning"]["tunes_completed"] == 1
    plan = build_template_plan([get_template("u5-1")])
    assert consult(g.signature(), plan.canons, path=path) is not None
    # the tuned workload is not re-queued, and new queries resolve tuned
    q = svc.submit("g", "u5-1", iterations=2, seed=2)
    svc.run()
    assert q.done
    assert svc.engine(q.engine_key).describe()["backend"]["source"] == "tuned"
    assert svc.stats()["tuning"]["pending"] == 0


# ---------------------------------------------------------------------------
# Quarantine interop: a quarantined key loses its tuned entry
# ---------------------------------------------------------------------------


def test_quarantine_drops_tuned_cache_entry(tmp_path, monkeypatch):
    from repro.serve import CountingService

    path = str(tmp_path / "tuned.json")
    g = _graph()
    templates = [get_template("u5-1")]
    plan = _seed_cache(path, g, templates, backend="edges")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    assert consult(g.signature(), plan.canons, path=path) is not None

    svc = CountingService()
    svc.register_graph("g", g)
    key = svc.engine_key_for("g", svc._resolve_templates("u5-1"))
    assert key[-1] is not None  # the tuned fragment is in the key
    svc._drop_tuned_entry(key)
    assert consult(g.signature(), plan.canons, path=path) is None

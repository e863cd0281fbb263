"""Names on the chip's time (``repro.tracing``): scopes in the launch's HLO,
host spans around ``count_keys_chunk``, and the compile counter."""

import contextlib
import glob
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core import CountingEngine, get_template, rmat_graph

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*\S+\s+([a-z][a-z\-]*)\(")
_COMPUTATION = re.compile(r"^%?(\S+)\s.*\{\s*$")


def _engine(templates=("u7",), **kw):
    g = rmat_graph(300, 1500, seed=2)
    return CountingEngine(g, [get_template(t) for t in templates], backend="edges", chunk_size=2, **kw)


def _keys(n=2, seed=0):
    return np.asarray(jax.random.split(jax.random.PRNGKey(seed), n))


def _launch_hlo(engine) -> str:
    return engine._compiled(engine._get_chunk_fn(), (engine.chunk_size, 2)).as_text()


def _group_scopes(engine):
    """Expected stage name of each exec group, in plan order."""
    ir = engine.plan_ir
    return {
        leader: tracing.stage(*leader, ir.counting_plans[leader[0]].partition.subs[leader[1]].size)
        for leader in ir.exec_groups
    }


def test_op_scopes_reads_the_scope_path_of_each_instruction():
    text = "\n".join([
        '  %fusion.3 = f32[8,2]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(f)/s2v/stage0_2_m3/spmm/'
        'while/body/scatter-add" source_file="x.py" source_line=3}',
        '  ROOT %tuple.1 = (f32[8]) tuple(%a), metadata={op_name="jit(f)/s2v/root/reduce_sum"}',
        '  gather.2 = f32[4] gather(%a, %b), metadata={op_name="jit(f)/s2v/colors/vmap(jit(_randint))/gather"}',
        '  %copy.9 = f32[8]{0} copy(%a)',
        '  %add.1 = f32[8]{0} add(%a, %b), metadata={op_name="jit(f)/notours/add"}',
        '  %mul.2 = f32[8]{0} multiply(%a, %b), metadata={op_name="jit(f)/xs2v/mul"}',
        '  %ag.1 = f32[8]{0} all-gather(%a), metadata={op_name="jit(f)/s2v/stage1_4_m5/spmm/gather/all_gather"}',
        "}",
        "",
        "%fused_computation.7 (param_0.1: f32[4,8]) -> f32[16,8] {",
        "  %param_0.1 = f32[4,8]{1,0} parameter(0)",
        '  %bitcast.2 = f32[4,8]{1,0} bitcast(%param_0.1), metadata={op_name="jit(f)/s2v/stage0_5_m4/ema/while/'
        'body/gather"}',
        "  ROOT %dynamic-update-slice.3 = f32[16,8]{1,0} dynamic-update-slice(%c, %bitcast.2, %i, %i)",
        "}",
        "",
        "ENTRY %main.9 (p: f32[4,8]) -> f32[16,8] {",
        "  %dus_fusion.4 = f32[16,8]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.7",
        "  %other_fusion.5 = f32[16,8]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.8",
    ])
    assert tracing.op_scopes(text) == {
        "fusion.3": "stage0_2_m3/spmm",
        "tuple.1": "root",
        "gather.2": "colors",
        "ag.1": "stage1_4_m5/spmm/gather",
        "bitcast.2": "stage0_5_m4/ema",
        # a fusion without metadata of its own: the scope of its instructions
        "dus_fusion.4": "stage0_5_m4/ema",
    }


def test_nested_scopes_sit_under_one_root():
    def f(x):
        with tracing.scope(tracing.stage(0, 2, 3)):
            with tracing.scope("spmm"):
                y = jnp.sin(x)
            with tracing.scope("ema"):
                return y * 2.0

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "s2v/stage0_2_m3/spmm/sin" in text
    assert "s2v/stage0_2_m3/ema/mul" in text
    assert "s2v/spmm" not in text and "s2v/ema" not in text


def test_launch_scopes_cover_every_scatter_gather_and_fusion():
    """Every scatter and gather of a u7 launch on ``edges`` carries a scope
    path, and so does every fusion XLA built from instructions that carry
    any metadata (XLA:CPU wraps metadata-free constant broadcasts and
    tree-reduction rewrites in fusions of their own)."""
    eng = _engine()
    scopes = eng.launch_scopes()
    text = _launch_hlo(eng)
    computations, current = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and not line.startswith(" "):
            current = computations.setdefault(m.group(1).lstrip("%"), [])
        elif current is not None:
            current.append(line)
    checked = 0
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None or m.group(2) not in ("scatter", "gather", "fusion"):
            continue
        name, opcode = m.groups()
        if opcode == "fusion" and name not in scopes:
            called = re.search(r"calls=%?([^\s,]+)", line).group(1)
            assert not any("op_name=" in inner for inner in computations[called]), line
            continue
        assert name in scopes, line
        checked += 1
    assert checked >= 10
    paths = set(scopes.values())
    assert all(p.split("/")[0] in ("colors", "root") or p.startswith("stage") for p in paths)


@pytest.mark.parametrize("templates", [("u7",), ("path6", "star6", "bintree6", "u6")])
def test_one_spmm_and_one_ema_per_exec_group(templates):
    """Each exec group is a stage scope holding ``ema``, and ``spmm`` unless
    an earlier group aggregated the same passive state: XLA computes that
    repeat once, under the first group's name."""
    eng = _engine(templates)
    paths = set(eng.launch_scopes().values())
    names = _group_scopes(eng)
    ir = eng.plan_ir
    passive_first = {}
    for leader in ir.exec_groups:  # plan order
        p, i = leader
        passive = ir.canons[p][ir.counting_plans[p].partition.subs[i].passive]
        passive_first.setdefault(passive, leader)
    if templates == ("path6", "star6", "bintree6", "u6"):
        assert any(len(m) > 1 for m in ir.exec_groups.values())
    expected_ema = {f"{names[l]}/ema" for l in names}
    required_spmm = {f"{names[l]}/spmm" for l in passive_first.values()}
    allowed_spmm = {f"{names[l]}/spmm" for l in names}
    assert {"colors", "root"} <= paths
    assert expected_ema <= paths
    assert required_spmm <= paths
    assert paths <= {"colors", "root"} | expected_ema | allowed_spmm


def test_scopes_change_only_the_metadata_of_the_launch(monkeypatch):
    strip = re.compile(r",? metadata=\{[^}]*\}")

    def instructions(text):
        return [line for line in strip.sub("", text).splitlines() if " = " in line]

    with_scopes = instructions(_launch_hlo(_engine()))
    monkeypatch.setattr(tracing, "scope", lambda name: contextlib.nullcontext())
    without = instructions(_launch_hlo(_engine()))
    assert with_scopes == without


def test_warm_launch_compiles_nothing():
    eng = _engine()
    keys = _keys()
    before = tracing.compile_counters("count_keys_chunk")
    eng.count_keys_chunk(keys)
    first = tracing.compile_counters("count_keys_chunk")
    eng.count_keys_chunk(keys)
    warm = tracing.compile_counters("count_keys_chunk")
    assert first["backend_compile"]["count"] == before["backend_compile"]["count"] + 1
    assert first["trace"]["count"] >= before["trace"]["count"] + 1
    assert first["trace"]["seconds"] > before["trace"]["seconds"]
    assert warm == first
    # launch_scopes finds the launch's program in JAX's caches
    eng.launch_scopes()
    assert eng.trace_count == 1


def test_compile_counter_files_events_under_open_spans():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones(7)
    outside = tracing.compile_counters("elsewhere")
    total = tracing.compile_counters()
    with tracing.span("probe"):
        before = tracing.compile_counters("probe")
        f(x).block_until_ready()
    after = tracing.compile_counters("probe")
    assert after["backend_compile"]["count"] == before["backend_compile"]["count"] + 1
    assert after["lower"]["count"] == before["lower"]["count"] + 1
    # one trace of the jitted function: traces nested in it are not counted
    assert after["trace"]["count"] == before["trace"]["count"] + 1
    assert tracing.compile_counters("elsewhere") == outside
    grown = tracing.compile_counters()
    assert grown["backend_compile"]["count"] == total["backend_compile"]["count"] + 1


def test_profiler_session_holds_the_launch_spans(tmp_path):
    eng = _engine()
    keys = _keys()
    eng.count_keys_chunk(keys)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.count_keys_chunk(keys)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    from jax.profiler import ProfileData

    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("s2v."):
                        spans[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    assert set(spans) == {"s2v.count_keys_chunk", "s2v.dispatch", "s2v.fetch", "s2v.scale"}
    lo, hi = spans["s2v.count_keys_chunk"]
    d, f, s = spans["s2v.dispatch"], spans["s2v.fetch"], spans["s2v.scale"]
    assert lo <= d[0] <= d[1] <= f[0] <= f[1] <= s[0] <= s[1] <= hi


def test_spans_are_inert_without_a_profiler():
    eng = _engine()
    keys = _keys(3, seed=4)
    with tracing.span("outer"):
        got = eng.count_keys_chunk(keys[:1])
    assert got.shape == (1, 1) and np.isfinite(got).all()
    assert eng.count_keys_chunk(keys[:0]).shape == (0, 1)


@pytest.mark.slow
def test_mesh_engine_gives_the_same_stage_names():
    """The mesh path (4 virtual devices) names its stages as the local engine
    does, and puts its all-gather and ring steps inside ``spmm``."""
    code = r"""
import jax, numpy as np
from repro.core import CountingEngine, get_template, rmat_graph
g = rmat_graph(240, 1200, seed=5)
mesh = jax.make_mesh((4,), ("dev",))
t = [get_template("u7")]
def stages(scopes):
    return sorted({p.split("/")[0] for p in scopes.values() if p.startswith("stage")})
local = CountingEngine(g, t, backend="edges", chunk_size=2).launch_scopes()
print("LOCAL", stages(local))
for comm in ("blocking", "pipelined"):
    eng = CountingEngine(g, t, backend="mesh", mesh=mesh, column_batch=8, chunk_size=2, mesh_comm=comm)
    scopes = eng.launch_scopes()
    paths = set(scopes.values())
    tail = "gather" if comm == "blocking" else "ring"
    assert eng.backend_impl.comm == comm
    assert {"colors", "root"} <= paths, paths
    assert any(p.endswith("/spmm/" + tail) for p in paths), paths
    assert any(p.endswith("/ema") for p in paths), paths
    print("MESH", comm, stages(scopes))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr
    lines = dict(
        (line.split(" [", 1)[0], line.split(" [", 1)[1]) for line in proc.stdout.splitlines() if " [" in line
    )
    assert lines["MESH blocking"] == lines["LOCAL"]
    assert lines["MESH pipelined"] == lines["LOCAL"]
    assert lines["LOCAL"].count("stage") == 4

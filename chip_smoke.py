"""Smoke test of the counting engine and service on TPU chips.

    python chip_smoke.py              # one chip: the four phases below
    python chip_smoke.py --chips 4    # only the 4-chip mesh path and its comparison

One chip:

* ``exactness``  — u7 on ``rmat_graph(2048, 20_000)`` through the auto-picked
  backend: ``raw_counts`` of fixed colorings against the Algorithm 2
  reference (``count_colorful_traversal``, float64 NumPy).
* ``fused``      — ``backend="blocked"`` (the fused Pallas SpMM+eMA kernel)
  on a graph whose blocked-ELL operand is mostly edges, u7 and u12, against
  ``backend="edges"`` on the same coloring keys.
* ``deployment`` — the paper's RMAT family at Graph500 parameters (a/b/c =
  0.57/0.19/0.19, edge factor 16): u7 on scale 20 and u12 on scale 19, through
  ``CountingEngine`` with the auto-picked backend; two chunks of estimates,
  and the first key's coloring against the mesh backend on a one-chip mesh
  (the separately written DP of ``repro.core.distributed``).  u12 does not
  fit one 16 GB chip at scale 20 even one coloring at a time, so its graph
  is cut to scale 19.
* ``service``    — ``CountingService`` with the scale-20 graph registered
  answers three (epsilon, delta) queries, one of them with a deadline; a
  retry, a degradation-ladder rung or a quarantine fails the phase.

``--chips 4``: u12 on the scale-19 graph over a 4-chip mesh, then with the
local engine on the first chip, on the same coloring keys; the compiled
program's per-chip bytes against the same program for one chip (the DP state
is spread, not replicated); then u12 on the scale-20 graph over the mesh with
the pipelined ring and with blocking collectives, on the same keys (no one
chip holds u12 at scale 20, so the two comm schedules check each other).

Progress goes to standard output one record per line.  The last line is a
JSON object ``{"ok": true, "device": {...}}``, printed only when every phase
agreed with its reference.  The script exits non-zero, and prints no result,
when JAX's default device is not a TPU or any phase failed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEED = 0
#: RMAT scale of the deployment graph, and the cut one for u12.
DEPLOY_SCALE = 20
U12_SCALE = 19
#: Graph500 edge factor: edges sampled per vertex.
EDGE_FACTOR = 16
#: fp32 agreement: small graphs, and deployment sizes (longer fp32 sums).
RTOL_SMALL = 1e-5
RTOL_DEPLOY = 1e-4


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(phase, **fields):
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def rel_err(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def peak_bytes(devices=None):
    import jax

    devices = devices or jax.devices()[:1]
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1)) for d in devices]


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


_GRAPHS = {}


def deploy_graph(scale: int):
    """The RMAT deployment graph at ``scale`` (made once per process)."""
    from repro.core import rmat_graph

    if scale not in _GRAPHS:
        g, secs = timed(rmat_graph, 1 << scale, EDGE_FACTOR << scale, SEED)
        log("setup", graph=f"rmat_scale{scale}", n=g.n, directed_edges=g.num_directed,
            generate_s=f"{secs:.1f}")
        _GRAPHS[scale] = g
    return _GRAPHS[scale]


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_exactness():
    import numpy as np

    from repro.core import (CountingEngine, build_counting_plan,
                            count_colorful_traversal, get_template, rmat_graph)

    g = rmat_graph(2048, 20_000, seed=SEED)
    t = get_template("u7")
    plan = build_counting_plan(t)
    eng = CountingEngine(g, [t])
    d = eng.describe()
    log("exactness", backend=eng.backend, source=d["backend"]["source"],
        reason=repr(d["backend"]["reason"]), interpret=eng.interpret)
    check(not eng.interpret, "engine runs its kernels in interpret mode")
    rng = np.random.default_rng(SEED)
    for i in range(2):
        colors = rng.integers(0, t.k, size=g.n)
        got, secs = timed(lambda c: float(eng.raw_counts(c)[0]), colors)
        ref = count_colorful_traversal(plan, g, colors)
        err = rel_err(got, ref)
        log("exactness", coloring=i, raw=got, algorithm2=ref, rel_err=f"{err:.3e}",
            seconds=f"{secs:.2f}")
        check(err <= RTOL_SMALL, f"u7 raw count {got} != Algorithm 2 {ref}")
    log("exactness", peak_bytes_in_use=peak_bytes()[0])


def phase_fused():
    import jax
    import numpy as np

    from repro.core import CountingEngine, get_template, rmat_graph
    from repro.core.graph import blocked_ell_geometry

    g = rmat_graph(4096, 200_000, seed=1)
    geo = blocked_ell_geometry(g)
    log("fused", n=g.n, directed_edges=g.num_directed, operand_rows=geo.n_rows,
        padding_factor=f"{geo.padding_factor:.3f}")
    for tname in ("u7", "u12"):
        t = get_template(tname)
        blocked = CountingEngine(g, [t], backend="blocked")
        chunk = blocked.chunk_size
        keys = jax.random.split(jax.random.PRNGKey(SEED), chunk)
        lowered = blocked._get_chunk_fn().lower(keys)
        check("tpu_custom_call" in lowered.as_text(), "no Pallas kernel in the blocked program")
        got, first_s = timed(blocked.count_keys_chunk, keys)
        _, warm_s = timed(blocked.count_keys_chunk, keys)
        del blocked
        edges = CountingEngine(g, [t], backend="edges", chunk_size=chunk)
        ref = edges.count_keys_chunk(keys)
        del edges
        err = rel_err(got, ref)
        log("fused", template=tname, chunk=chunk, first_launch_s=f"{first_s:.2f}",
            warm_launch_s=f"{warm_s:.3f}", blocked_mean=float(np.mean(got)),
            edges_mean=float(np.mean(ref)), rel_err=f"{err:.3e}")
        check(np.all(np.isfinite(got)) and np.all(got > 0), f"{tname}: bad blocked estimates")
        check(err <= RTOL_SMALL, f"{tname}: blocked kernel != edges backend")
        gc.collect()
    log("fused", peak_bytes_in_use=peak_bytes()[0])


def phase_deployment():
    import jax
    import numpy as np

    from repro.core import CountingEngine, get_template, select_backend

    for tname, scale in (("u7", DEPLOY_SCALE), ("u12", U12_SCALE)):
        g = deploy_graph(scale)
        t = get_template(tname)
        picked, reason = select_backend(g, explain=True)
        eng, build_s = timed(CountingEngine, g, [t])
        check(eng.backend == picked, f"engine bound {eng.backend}, select_backend says {picked}")
        chunk = eng.chunk_size
        keys = jax.random.split(jax.random.PRNGKey(SEED), 2 * chunk)
        first, first_s = timed(eng.count_keys_chunk, keys[:chunk])
        second, warm_s = timed(eng.count_keys_chunk, keys[chunk:])
        est = np.concatenate([first, second])
        log("deployment", template=tname, scale=scale, backend=eng.backend,
            reason=repr(reason), chunk=chunk, column_batch=eng.column_batch,
            setup_s=f"{build_s:.1f}", first_launch_s=f"{first_s:.1f}",
            warm_launch_s=f"{warm_s:.2f}", predicted_chunk_bytes=eng.predicted_peak_bytes(),
            estimates=[float(x) for x in est[:, 0]])
        check(np.all(np.isfinite(est)) and np.all(est > 0), f"{tname}: bad estimates {est}")
        del eng
        gc.collect()

        # the first key's coloring, through the independently written mesh DP
        mesh = jax.make_mesh((1,), ("dev",), devices=jax.devices()[:1])
        ref_eng = CountingEngine(g, [t], mesh=mesh, chunk_size=1)
        ref, ref_s = timed(ref_eng.count_keys_chunk, keys[:1])
        del ref_eng
        gc.collect()
        err = rel_err(est[:1], ref)
        log("deployment", template=tname, key0_estimate=float(est[0, 0]),
            mesh1_estimate=float(ref[0, 0]), rel_err=f"{err:.3e}",
            mesh1_s=f"{ref_s:.1f}", peak_bytes_in_use=peak_bytes()[0])
        check(err <= RTOL_DEPLOY, f"{tname}: {picked} backend != mesh backend on a fixed coloring")


def phase_service():
    import numpy as np

    from repro.core import select_backend
    from repro.serve import CountingService

    g = deploy_graph(DEPLOY_SCALE)
    svc = CountingService()
    svc.register_graph("rmat20", g)
    picked = select_backend(g)
    specs = [
        dict(epsilon=0.05, delta=0.1, seed=1),
        dict(epsilon=0.1, delta=0.05, seed=2),
        dict(epsilon=0.05, delta=0.1, seed=3, deadline=900.0),
    ]
    queries = [svc.submit("rmat20", "u7", iterations=16, **kw) for kw in specs]
    _, secs = timed(svc.run)
    stats = svc.stats()
    faults = stats["faults"]
    for q, kw in zip(queries, specs):
        check(q.done, f"query {q.qid} ended {q.status}: {q.error}")
        (est,) = q.result()
        eng = svc.engine(q.engine_key)
        log("service", query=q.qid, **{k: v for k, v in kw.items() if k != "seed"},
            iterations=q.iterations, mean=est.mean, halfwidth=est.halfwidth,
            converged=est.converged, degraded=est.degraded,
            backend=eng.describe()["backend"]["name"] if eng else None)
        check(np.isfinite(est.mean) and est.mean > 0, f"query {q.qid}: bad mean {est.mean}")
        check(not est.degraded, f"query {q.qid} resolved degraded at its deadline")
        check(eng is not None and eng.backend == picked,
              f"query {q.qid} served by {eng and eng.backend}, picked {picked}")
    log("service", seconds=f"{secs:.1f}", launches=stats["launches"],
        queries_completed=stats["queries_completed"], faults=json.dumps(
            {k: v for k, v in faults.items() if k not in ("keys", "ladder", "quarantined_keys")}),
        peak_bytes_in_use=peak_bytes()[0])
    check(stats["queries_failed"] == 0 and stats["queries_degraded"] == 0, "failed/degraded queries")
    check(faults["retries"] == 0, f"{faults['retries']} retries")
    check(not faults["ladder"], f"degradation ladder walked: {faults['ladder']}")
    check(not faults["quarantined_keys"], "an engine key was quarantined")
    check(all(faults[k] == 0 for k in ("transient", "memory", "deterministic", "invalid", "non_finite")),
          f"classified failures: {faults}")


def phase_mesh4():
    import jax
    import numpy as np

    from repro.core import CountingEngine, get_template

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    t = get_template("u12")
    mesh = jax.make_mesh((4,), ("dev",), devices=devices[:4])

    # u12, scale 19: the mesh against the local engine on the first chip
    g = deploy_graph(U12_SCALE)
    keys = jax.random.split(jax.random.PRNGKey(SEED), 2)
    dist, build_s = timed(CountingEngine, g, [t], mesh=mesh, chunk_size=1)
    check(dist.backend == "mesh", f"mesh= engine bound {dist.backend}")
    first, first_s = timed(dist.count_keys_chunk, keys[:1])
    second, warm_s = timed(dist.count_keys_chunk, keys[1:])
    got = np.concatenate([first, second])
    log("mesh4", template="u12", scale=U12_SCALE, chunk=1, comm=(dist.describe().get("comm") or {}).get("mode"),
        setup_s=f"{build_s:.1f}", first_launch_s=f"{first_s:.1f}", warm_launch_s=f"{warm_s:.2f}",
        per_chip_peak_bytes_in_use=peak_bytes(devices[:4]))
    check(np.all(np.isfinite(got)) and np.all(got > 0), "bad mesh estimates")
    # spread: per-chip program bytes (XLA's compile-time temp allocation,
    # which holds the DP state) against the same program on one chip
    temp4 = dist.compiled_memory_analysis()["actual_temp_bytes"]
    del dist
    gc.collect()
    mesh1 = jax.make_mesh((1,), ("dev",), devices=devices[:1])
    temp1 = CountingEngine(g, [t], mesh=mesh1, chunk_size=1).compiled_memory_analysis()["actual_temp_bytes"]
    gc.collect()
    check(temp4 and temp1, "memory_analysis unavailable")
    log("mesh4", per_chip_temp_bytes_4chips=int(temp4), temp_bytes_1chip=int(temp1),
        ratio=f"{temp4 / temp1:.3f}")
    check(temp4 <= 0.5 * temp1, f"state not spread: {temp4} B per chip of 4 vs {temp1} B on one")
    local = CountingEngine(g, [t], chunk_size=1)
    ref, local_s = timed(
        lambda: np.concatenate([local.count_keys_chunk(keys[i:i + 1]) for i in range(2)])
    )
    del local
    gc.collect()
    err = rel_err(got, ref)
    log("mesh4", local_backend="edges", local_s=f"{local_s:.1f}",
        mesh_estimates=[float(x) for x in got[:, 0]],
        local_estimates=[float(x) for x in np.asarray(ref)[:, 0]], rel_err=f"{err:.3e}")
    check(err <= RTOL_DEPLOY, "4-chip mesh != local engine on the same keys")

    # u12, scale 20: pipelined ring against blocking collectives
    g = deploy_graph(DEPLOY_SCALE)
    out, chunk = {}, None
    for comm in ("pipelined", "blocking"):
        eng, build_s = timed(CountingEngine, g, [t], mesh=mesh, mesh_comm=comm, chunk_size=chunk)
        chunk = eng.chunk_size
        keys = jax.random.split(jax.random.PRNGKey(SEED), chunk)
        out[comm], first_s = timed(eng.count_keys_chunk, keys)
        _, warm_s = timed(eng.count_keys_chunk, keys)
        log("mesh4", template="u12", scale=DEPLOY_SCALE, comm=comm, chunk=chunk,
            setup_s=f"{build_s:.1f}", first_launch_s=f"{first_s:.1f}", warm_launch_s=f"{warm_s:.2f}",
            estimates=[float(x) for x in out[comm][:, 0]])
        del eng
        gc.collect()
        check(np.all(np.isfinite(out[comm])) and np.all(out[comm] > 0),
              f"scale {DEPLOY_SCALE}, {comm}: bad estimates {out[comm]}")
    err = rel_err(out["pipelined"], out["blocking"])
    log("mesh4", scale=DEPLOY_SCALE, pipelined_vs_blocking=f"{err:.3e}",
        bit_exact=bool(np.array_equal(out["pipelined"], out["blocking"])))
    check(err <= RTOL_DEPLOY, "pipelined ring != blocking collectives at scale 20")


ONE_CHIP_PHASES = (
    ("exactness", phase_exactness),
    ("fused", phase_fused),
    ("deployment", phase_deployment),
    ("service", phase_service),
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the 4-chip mesh path")
    args = ap.parse_args(argv)

    try:
        import repro  # noqa: F401  (the package this script drives)
    except ImportError as exc:
        print(f"chip_smoke: cannot import the repro package ({exc}); run from a checkout",
              file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: default device is {dev.platform!r}, not a TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    log("setup", platform=dev.platform, kind=repr(dev.device_kind), count=len(jax.devices()),
        jax=jax.__version__, compile_cache=enable_compile_cache())

    phases = (("mesh4", phase_mesh4),) if args.chips == 4 else ONE_CHIP_PHASES
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as exc:  # every phase runs; any failure fails the smoke
            traceback.print_exc()
            failed.append(name)
            log(name, status="FAILED", error=repr(exc)[:500])
        else:
            log(name, status="ok", seconds=f"{time.perf_counter() - t0:.1f}")
        gc.collect()
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Perf driver — mesh collectives A/B (blocking vs pipelined ring).

A real runnable benchmark over virtual host devices::

    python scripts/perf_subgraph_u20.py --devices 4
    python scripts/perf_subgraph_u20.py --devices 8 --comm pipelined
    python scripts/perf_subgraph_u20.py --devices 4 --template u20 --static

Per comm mode it records, on a ``--devices``-shard 1-D mesh:

* measured wall-clock per coloring (interleaved A/B when ``--comm both``,
  so machine drift hits both arms equally);
* **measured overlap efficiency** — the fraction of the comm model's
  predicted wire time the ring actually hid,
  ``clip((t_blocking - t_pipelined) / predicted_comm_us, 0, 1)``;
* **per-shard byte fraction** — the pipelined transient footprint over the
  blocking one (two ring slots vs the full all-gathered batch);
* the resolved per-stage ``CommSchedule`` (``describe()["comm"]``).

``--static`` skips execution and reports the compile-time memory /
HLO-collective analysis instead (the original single-pod static mode,
kept for the u20-at-512-devices paper cell where running is not the
point).  Output JSON -> ``results/perf/subgraph_u20.json``.
"""

import argparse
import json
import os
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual host devices / mesh shards (default 4)")
    ap.add_argument("--comm", choices=("blocking", "pipelined", "both"),
                    default="both", help="which collective scheme(s) to run")
    ap.add_argument("--template", default="u12",
                    help="template to count (default u12; u20 for the "
                    "paper cell — slow when executing)")
    ap.add_argument("--n", type=int, default=4096, help="graph vertices")
    ap.add_argument("--edges", type=int, default=32768, help="graph edges")
    ap.add_argument("--column-batch", type=int, default=64)
    ap.add_argument("--chunk-size", type=int, default=4)
    ap.add_argument("--iters", type=int, default=12,
                    help="colorings measured (chunks = iters / chunk-size)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="interleaved A/B rounds; per-arm time is the min")
    ap.add_argument("--static", action="store_true",
                    help="compile-only memory/HLO analysis at 512 devices "
                    "(the original paper-cell mode; no execution)")
    ap.add_argument("--out", default="results/perf/subgraph_u20.json")
    return ap.parse_args(argv)


def _engine(args, g, t, mesh, comm):
    from repro.core import CountingEngine

    return CountingEngine(
        g, [t], backend="mesh", mesh=mesh, column_batch=args.column_batch,
        chunk_size=args.chunk_size, mesh_comm=comm,
    )


def _measure_us_per_coloring(engine, keys, repeats):
    """Min wall-clock us/coloring over ``repeats`` timed runs (warm)."""
    engine.count_keys(keys)  # warmup: compile + operand transfer
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        engine.count_keys(keys)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6 / keys.shape[0]


def run_ab(args):
    import jax
    import numpy as np

    from repro.core import get_template, rmat_graph

    g = rmat_graph(args.n, args.edges, seed=7)
    t = get_template(args.template)
    mesh = jax.make_mesh((args.devices,), ("dev",), devices=jax.devices()[: args.devices])
    keys = jax.random.split(jax.random.PRNGKey(0), args.iters)

    modes = ("blocking", "pipelined") if args.comm == "both" else (args.comm,)
    engines = {m: _engine(args, g, t, mesh, m) for m in modes}
    for m, eng in engines.items():
        eng.count_keys(keys)  # both arms warm before any timing

    # interleaved A/B: alternate arms each round so drift cancels
    times = {m: float("inf") for m in modes}
    for _ in range(max(1, args.repeats)):
        for m in modes:
            t0 = time.perf_counter()
            engines[m].count_keys(keys)
            times[m] = min(times[m], time.perf_counter() - t0)
    us = {m: times[m] * 1e6 / args.iters for m in modes}

    out = {
        "cell": f"subgraph2vec/{args.template}/{args.devices}dev",
        "devices": args.devices,
        "template": args.template,
        "graph": {"n": g.n, "edges": g.num_undirected},
        "column_batch": args.column_batch,
        "chunk_size": args.chunk_size,
        "iters": args.iters,
    }
    for m in modes:
        eng = engines[m]
        comm = eng.backend_impl.describe_comm()
        out[m] = {
            "us_per_coloring": us[m],
            "comm": comm,
            "transient_elements_per_shard": eng.backend_impl.transient_elements(),
        }
    if len(modes) == 2:
        b, p = engines["blocking"], engines["pipelined"]
        # counts must be BIT-exact across the arms — the A/B is meaningless
        # if the arms compute different things
        cb = np.asarray(b.count_keys(keys[:2]))
        cp = np.asarray(p.count_keys(keys[:2]))
        assert np.array_equal(cb, cp), "pipelined != blocking counts"
        predicted_comm_us = sum(
            s["comm_us"] for s in out["pipelined"]["comm"]["schedule"]
        )
        hidden_us = max(0.0, us["blocking"] - us["pipelined"])
        out["ratio_pipelined_vs_blocking"] = (
            us["pipelined"] / us["blocking"] if us["blocking"] else None
        )
        out["measured_overlap_efficiency"] = (
            min(1.0, hidden_us / predicted_comm_us) if predicted_comm_us else 0.0
        )
        out["per_shard_byte_fraction"] = (
            out["pipelined"]["transient_elements_per_shard"]
            / max(1, out["blocking"]["transient_elements_per_shard"])
        )
        out["bit_exact"] = True
    return out


def run_static(args):
    """The original compile-only paper cell: resident bytes + HLO
    collective bytes for loop vs streamed eMA at 512 devices."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.registry import SUBGRAPH_SHAPES
    from repro.core import build_counting_plan
    from repro.core.colorsets import binom
    from repro.core.distributed import (
        distributed_input_specs,
        make_distributed_count_fn,
    )
    from repro.core.templates import PAPER_TEMPLATES
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import collective_wire_bytes

    mesh = make_production_mesh()
    shape = [s for s in SUBGRAPH_SHAPES if s.name == "rmat1m_u20"][0]
    k = shape.params["k"]
    plan = build_counting_plan(PAPER_TEMPLATES["u20"])
    n_shards = mesh.devices.size
    n = shape.params["n_vertices"]
    n_padded = ((n + n_shards - 1) // n_shards) * n_shards
    e_directed = 2 * shape.params["n_edges"]
    edges_per_shard = ((int(e_directed / n_shards * 1.2) + 7) // 8) * 8
    rows = n_padded // n_shards
    b_traffic = sum(
        2.0 * rows * binom(k, t.m_p) * 4 for t in plan.tables if t is not None
    )
    out = {
        "cell": "subgraph2vec/rmat1m_u20/single",
        "analytic_B_roundtrip_bytes_per_device": b_traffic,
    }
    for mode in ("loop", "streamed"):
        print(f"compiling {mode}...")
        fn = make_distributed_count_fn(
            plan, mesh, n_padded, edges_per_shard,
            column_batch=128, ema_mode=mode,
        )
        specs = distributed_input_specs(n_padded, mesh.devices.size,
                                        edges_per_shard)
        every = tuple(mesh.axis_names)
        in_sh = tuple(NamedSharding(mesh, P(every)) for _ in specs)
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn, in_shardings=in_sh).lower(*specs).compile()
        ms = compiled.memory_analysis()
        resident = ms.argument_size_in_bytes + ms.temp_size_in_bytes + max(
            ms.output_size_in_bytes - ms.alias_size_in_bytes, 0
        )
        coll, counts = collective_wire_bytes(compiled.as_text())
        out[mode] = {
            "mode": mode,
            "resident_bytes_per_device": float(resident),
            "temp_bytes": float(ms.temp_size_in_bytes),
            "collective_bytes": float(coll),
            "collective_counts": counts,
            "fits_16GB": bool(resident < 16e9),
        }
        print(json.dumps(out[mode], indent=1))
    return out


def main(argv=None):
    args = parse_args(argv)
    if "jax" not in sys.modules:
        # run as a script: give the CPU backend its virtual devices.  XLA_FLAGS
        # must be set before jax imports — which is why every import of
        # jax/repro in this script is function-local.  A caller that already
        # holds a backend (a chip host) brings its own devices.
        devices = 512 if args.static else args.devices
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    out = run_static(args) if args.static else run_ab(args)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    summary = {k: v for k, v in out.items() if not isinstance(v, dict)}
    for m in ("blocking", "pipelined"):
        if m in out and isinstance(out[m], dict) and "us_per_coloring" in out[m]:
            summary[f"{m}_us_per_coloring"] = out[m]["us_per_coloring"]
    print(json.dumps(summary, indent=1))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

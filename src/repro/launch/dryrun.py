import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (the two lines above must execute before any
other jax-touching import — jax locks the device count on first init).

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--include-subgraph]
  python -m repro.launch.dryrun --list

Per cell it runs ``jax.jit(fn, in_shardings=...).lower(*specs).compile()``,
prints ``memory_analysis()`` (fits-in-HBM proof) and ``cost_analysis()``
(FLOPs/bytes for §Roofline), and appends a JSON record to
``results/dryrun/<arch>__<shape>__<mesh>.json``.
"""

import argparse
import json
import sys
import time
import traceback


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str, probe: bool = False) -> dict:
    import jax

    from repro.configs.registry import shapes_for
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import analyze_compiled

    shape = next(s for s in shapes_for(arch) if s.name == shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_devices = mesh.devices.size

    t0 = time.monotonic()
    with jax.set_mesh(mesh):
        cell = build_cell(arch, shape, mesh)
        jitted = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            donate_argnums=cell.donate_argnums,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

    mem = compiled.memory_analysis()
    print(f"== {arch} x {shape_name} x {mesh_name} ({n_devices} devices) ==")
    print(f"memory_analysis: {mem}")
    ca = compiled.cost_analysis() or {}
    print(
        "cost_analysis: flops/device=%.3e bytes/device=%.3e"
        % (ca.get("flops", 0.0), ca.get("bytes accessed", 0.0))
    )

    report = analyze_compiled(
        compiled,
        arch=arch,
        shape=shape_name,
        mesh_name=mesh_name,
        n_devices=n_devices,
        model_flops=cell.model_flops,
        meta={**cell.meta, "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)},
    )
    rec = report.to_json()

    if probe:
        from repro.launch.probes import probe_costs
        from repro.launch.roofline import HBM_BW, ICI_BW, PEAK_FLOPS

        with jax.set_mesh(mesh):
            corr = probe_costs(arch, shape, mesh)
        if corr is not None:
            rec["probe"] = corr
            rec["hlo_flops"] = corr["flops"]
            rec["hlo_bytes"] = corr["bytes"]
            rec["collective_bytes"] = corr["collective_bytes"]
            rec["compute_s"] = corr["flops"] / PEAK_FLOPS
            rec["memory_s"] = corr["bytes"] / HBM_BW
            rec["collective_s"] = corr["collective_bytes"] / ICI_BW
            terms = {
                "compute": rec["compute_s"],
                "memory": rec["memory_s"],
                "collective": rec["collective_s"],
            }
            rec["bottleneck"] = max(terms, key=terms.get)
            denom = corr["flops"] * n_devices
            rec["useful_flops_ratio"] = cell.model_flops / denom if denom else 0.0
            print(
                f"probe-corrected: compute={rec['compute_s']:.3e}s memory={rec['memory_s']:.3e}s "
                f"collective={rec['collective_s']:.3e}s bottleneck={rec['bottleneck']} "
                f"useful={rec['useful_flops_ratio']:.3f} ({corr['method']})"
            )
    hbm = 16e9
    per_dev = report.per_device_memory_bytes or 0.0
    rec["fits_hbm"] = bool(per_dev < hbm)
    print(
        f"roofline: compute={report.compute_s:.3e}s memory={report.memory_s:.3e}s "
        f"collective={report.collective_s:.3e}s bottleneck={report.bottleneck} "
        f"useful_flops_ratio={report.useful_flops_ratio:.3f}"
    )
    print(f"per-device bytes (arg+out+temp): {per_dev:.3e} fits_16GB={rec['fits_hbm']}")

    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"wrote {path}")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--include-subgraph", action="store_true")
    ap.add_argument("--probe", action="store_true", help="scan-corrected roofline costs")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    from repro.configs.registry import all_cells

    if args.list:
        for arch, shape in all_cells(include_subgraph=True):
            print(f"{arch} {shape.name}")
        return 0

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = (
        all_cells(include_subgraph=args.include_subgraph)
        if args.all
        else [(args.arch, s) for s in __import__("repro.configs.registry", fromlist=["shapes_for"]).shapes_for(args.arch) if args.shape in (None, s.name)]
    )

    failures = []
    for arch, shape in cells:
        for mesh_name in meshes:
            try:
                run_cell(arch, shape.name, mesh_name, args.out, probe=args.probe)
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape.name, mesh_name, repr(e)))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        return 1
    print("\nALL CELLS COMPILED")
    return 0


if __name__ == "__main__":
    sys.exit(main())

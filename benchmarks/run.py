"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only tableIII,fig14,...]

Emits ``name,us_per_call,derived`` CSV rows, and writes every recorded row
(plus the derived engine speedups) to ``BENCH_counting.json`` so the perf
trajectory is tracked across PRs.  Before overwriting, this run's rows are
diffed against the previous file's and a regression/trend table is printed
(see README §Benchmarks for the workflow).
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import traceback

from . import (
    bench_counting,
    bench_error,
    bench_kernels,
    bench_scaling,
    bench_service,
    bench_template_scaling,
    bench_tuning,
)
from repro.compile_cache import enable_compile_cache

from .common import ROWS, emit_header

BENCHES = {
    "tableIII": bench_counting.run,        # S vs F execution time + speedup
    "fig8": bench_counting.run,            # same data isolates the vectorization win
    "fig12": bench_template_scaling.run,   # template-size scaling / memory
    "fig13": bench_scaling.run,            # distributed strong scaling
    "fig14": bench_error.run,              # relative error
    "kernels": bench_kernels.run,          # Table IV analogue (SpMM/eMA)
    "service": bench_service.run,          # CountingService qps/latency/adaptive
    "tuning": bench_tuning.run,            # autotuner winner vs heuristic
}

#: Rows slower than the previous run by more than this fraction are flagged.
REGRESSION_THRESHOLD = 0.10

#: ``tuned_vs_heuristic`` rows whose measured heuristic/tuned ratio falls
#: below this are flagged: the tuner picked a config >5% SLOWER than the
#: analytic heuristic it was supposed to beat (or at least match).
TUNING_RATIO_FLOOR = 0.95


def print_trend(prev_rows: dict, threshold: float = REGRESSION_THRESHOLD) -> int:
    """Diff this run's rows against the previous ``BENCH_counting.json``.

    Prints a per-row trend table (previous vs current us_per_call, delta %)
    to stderr, flags rows slower by more than ``threshold``, and returns the
    number of flagged regressions.  Micro-benchmarks on shared CI hosts are
    noisy — the flag is a prompt to re-run, not a hard failure.
    """
    regressions = flag_tuning_ratios()
    if not prev_rows:
        print("trend: no previous BENCH_counting.json — baseline run", file=sys.stderr)
        return regressions
    width = max((len(name) for name, _, _ in ROWS), default=20)
    fresh = 0
    print(f"\n== trend vs previous run ({len(ROWS)} rows) ==", file=sys.stderr)
    print(f"{'name':<{width}}  {'prev_us':>12}  {'now_us':>12}  {'delta':>8}", file=sys.stderr)
    for name, us, _ in ROWS:
        prev = prev_rows.get(name)
        prev_us = prev.get("us_per_call") if prev else None
        if prev_us is not None:
            # tolerate unparsable previous values (hand-edited files, rows
            # written by newer schema) — treat them as newly-introduced keys
            try:
                prev_us = float(prev_us)
            except (TypeError, ValueError):
                prev_us = None
        if prev_us is None:
            fresh += 1
            print(f"{name:<{width}}  {'-':>12}  {us:>12.1f}  {'new':>8}", file=sys.stderr)
            continue
        if prev_us == 0.0:
            # legit zero baseline (e.g. derived-only rows): nothing to diff
            print(f"{name:<{width}}  {prev_us:>12.1f}  {us:>12.1f}  {'n/a':>8}", file=sys.stderr)
            continue
        delta = (us - prev_us) / prev_us
        flag = ""
        if delta > threshold:
            flag = "  <-- REGRESSION"
            regressions += 1
        print(
            f"{name:<{width}}  {prev_us:>12.1f}  {us:>12.1f}  {delta:>+7.1%}{flag}",
            file=sys.stderr,
        )
    if fresh:
        print(f"trend: {fresh} new row(s) with no previous record", file=sys.stderr)
    if regressions:
        print(
            f"trend: {regressions} row(s) regressed beyond {threshold:.0%} — "
            "re-run to rule out machine noise",
            file=sys.stderr,
        )
    return regressions


def flag_tuning_ratios(floor: float = TUNING_RATIO_FLOOR) -> int:
    """Flag ``tuned_vs_heuristic`` rows whose ratio fell below ``floor``.

    The ratio is measured *within* this run (interleaved launches), so
    unlike the cross-run trend diff it needs no previous file — a tuner
    that loses to the heuristic by >5% is flagged on every run.
    """
    flagged = 0
    for name, _, derived in ROWS:
        if not name.endswith("/tuned_vs_heuristic"):
            continue
        m = re.search(r"ratio=([0-9.]+)", derived)
        if m and float(m.group(1)) < floor:
            flagged += 1
            print(
                f"trend: {name} ratio {float(m.group(1)):.3f} < {floor} — "
                f"the tuned config is slower than the heuristic "
                f"<-- REGRESSION",
                file=sys.stderr,
            )
    return flagged


def emit_json(path: str = "BENCH_counting.json") -> None:
    """Persist all recorded rows + headline engine speedups for trend tracking.

    Merges into an existing file (rows keyed by name, new results win) so a
    partial ``--only`` run refreshes its own rows without clobbering the
    speedup record of the last full run.  The previous file's rows are
    diffed against this run's first (:func:`print_trend`).
    """
    existing_rows: dict = {}
    speedups: dict = {}
    try:
        with open(path) as fh:
            prev = json.load(fh)
        existing_rows = {r["name"]: r for r in prev.get("rows", [])}
        speedups = dict(prev.get("engine_speedup_vs_loop", {}))
    except (FileNotFoundError, json.JSONDecodeError, KeyError, TypeError):
        pass
    print_trend(existing_rows)
    for name, us, derived in ROWS:
        existing_rows[name] = {"name": name, "us_per_call": us, "derived": derived}
        m = re.match(r"engine/(.+)/batched(\d+)$", name)
        sp = re.search(r"speedup=([0-9.]+)x", derived)
        if m and sp:
            speedups[f"{m.group(1)}/{m.group(2)}iter"] = float(sp.group(1))
    payload = {
        "rows": sorted(existing_rows.values(), key=lambda r: r["name"]),
        "engine_speedup_vs_loop": speedups,
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    print(f"wrote {path} ({len(ROWS)} new rows)", file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench keys")
    ap.add_argument("--json", default="BENCH_counting.json", help="output JSON path")
    ap.add_argument(
        "--quick",
        action="store_true",
        help="~1min smoke subset (rmat2k engine rows + the rmat8k cliff "
        "rows), merged into the JSON so the trend diff still flags them",
    )
    args = ap.parse_args()
    enable_compile_cache()
    emit_header()
    failed = []
    if args.quick:
        try:
            bench_counting.run(quick=True)
            bench_service.run(quick=True)
            bench_tuning.run(quick=True)
        except Exception:
            traceback.print_exc()
            failed.append("quick")
    else:
        keys = list(dict.fromkeys(args.only.split(","))) if args.only else [
            "tableIII", "fig12", "fig13", "fig14", "kernels", "service",
            "tuning",
        ]
        for key in keys:
            try:
                BENCHES[key]()
            except Exception:
                traceback.print_exc()
                failed.append(key)
    emit_json(args.json)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

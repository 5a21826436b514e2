"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Usage:
    PYTHONPATH=src python -m benchmarks.run [--only fig7,fig9,...] [--tiny]

``--tiny`` shrinks the workload sizes (CI bench-smoke mode: exercises every
code path, measures nothing meaningful). An ``--only`` filter matching no
suite is an error (exit 2) — a silent empty run would upload a header-only
CSV and pass CI.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings to filter suites")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny workload sizes (CI smoke; sets REPRO_BENCH_TINY)")
    args = ap.parse_args()
    from repro import config
    config.enable_compile_cache()
    if args.tiny:
        # Before the suite imports: sizes are chosen at module/run scope.
        config.set_bench_tiny(True)

    from . import (bench_position_sampling, bench_uniform_e2e, bench_poisson,
                   bench_build_probe, bench_probe_fused, bench_draw_fused,
                   bench_full_join, bench_qc, bench_caching,
                   bench_engine_cache, bench_sharded_engine, bench_serve,
                   bench_throughput, bench_updates, bench_pipeline,
                   bench_kernels, roofline)
    suites = [
        ("fig7_position_sampling", bench_position_sampling.run),
        ("fig8_uniform_e2e", bench_uniform_e2e.run),
        ("fig9_poisson", bench_poisson.run),
        ("table3_build_probe", bench_build_probe.run),
        # Both feed the "probe" suite / BENCH_probe.json: fused GET rows,
        # then the one-launch fused-draw rows (DESIGN.md §14).
        ("probe", bench_probe_fused.run),
        ("probe", bench_draw_fused.run),
        ("table4_full_join", bench_full_join.run),
        ("fig10_qc", bench_qc.run),
        ("table6_caching", bench_caching.run),
        ("engine_cache", bench_engine_cache.run),
        ("sharded_engine", bench_sharded_engine.run),
        ("serve", bench_serve.run),
        ("throughput", bench_throughput.run),
        ("updates", bench_updates.run),
        ("pipeline", bench_pipeline.run),
        ("kernels", bench_kernels.run),
        ("roofline", roofline.run),
    ]
    if args.only:
        keys = [k for k in args.only.split(",") if k]
        selected = [(n, f) for n, f in suites if any(k in n for k in keys)]
        if not selected:
            names = ", ".join(n for n, _ in suites)
            print(f"benchmarks.run: --only {args.only!r} matched no suites "
                  f"(available: {names})", file=sys.stderr)
            sys.exit(2)
        suites = selected

    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites:
        print(f"# --- {name} ---")
        try:
            fn(print)
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

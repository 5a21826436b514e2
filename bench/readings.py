#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: for each seed, the
program as it is ("sound"), the control (the reference, one precision
below the configuration's, in the program's place) and each planted fault
the cell can have, each a whole run of the cell through ``harness.run``
(its warm-up, window and comparison) on one set-up per seed. Not part of
a benchmark run.

    python3 bench/readings.py --workload job-star-p32.draw --seeds 11 12 13 \
        --seconds 10 [--faults sound control altered half stale]

Prints one JSON line per (seed, fault) with every number compared.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", nargs="+", default=None)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro import config

    config.enable_compile_cache()
    from bench import harness

    cell = harness.resolve(args.workload)
    harness.require_devices(cell.chips)
    kinds = args.faults or (
        ["sound", "control", "altered", "half"]
        if cell.traffic["call"] == "full_join"
        else ["sound", "control", "altered", "half", "stale"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        sess = harness.build_session(cell, seed)
        for kind in kinds:
            line = harness.run(args.workload, seed, args.seconds, False,
                               t_start=time.perf_counter(), fault=kind,
                               sess=sess, log=lambda *a, **k: None)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "fault": kind, "attempted": line["attempted"],
                              "failed": line["failed"],
                              "correct": line["correct"],
                              "numbers": {k: v["value"] for k, v
                                          in line["checks"].items()},
                              "e2e": {k: v["value"] for k, v
                                      in line["metrics"].items()},
                              "elapsed_s": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

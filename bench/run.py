#!/usr/bin/env python3
"""Run one cell of the benchmark in BENCHMARK.json on this machine's chips.

    python3 bench/run.py --workload job-star.draw --seed 7 --seconds 20 --trace 0

Prints one JSON line last on standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, ``setup`` (the executables set-up compiled and loaded from
the compile cache; ``cold`` where it compiled any), and last ``checks``,
every number compared with its limit.
The same numbers end standard error. Exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the trace's reduced events to this file")
    args = ap.parse_args(argv)

    # The compile cache sits at a fixed path inside the checkout, and the
    # program is told to use it, whatever the environment held.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro import config

    import jax

    config.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import harness

    try:
        line = harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START,
                           keep_trace=args.keep_trace)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find the highest rate
the system sustains without a growing backlog. Not part of a benchmark
run: the cell's rate is fixed in its traffic file from such a sweep.

    python3 bench/sweep.py --workload stats-ceb.serve --seed 5 \
        --seconds 20 --rates 10 20 30 40

For each rate, on one set-up: the nearest-rank p50 and p95 latency from
scheduled arrival, the p95 of the first and the last third of the
requests (a backlog that grows shows as the last above the first), the
mean lanes per flush, and how late the generator ran.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro import config

    config.enable_compile_cache()
    import numpy as np

    from bench import harness, loops

    cell = harness.resolve(args.workload)
    harness.require_devices(cell.chips)
    sess = harness.build_session(cell, args.seed)
    warm = True
    for rate in args.rates:
        traffic = dict(cell.traffic, rate_per_s=rate)
        drv = harness.driver_for(sess, traffic, args.seed, sess.engine)
        if warm:
            drv.warm()
            warm = False
        w = drv.window(args.seconds)
        lat = (np.where(np.isnan(w.done), w.drained, w.done)
               - w.scheduled).tolist()
        third = max(len(lat) // 3, 1)
        mb = drv.batcher
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "p50_s": loops.nearest_rank(lat, 0.5),
            "p95_s": loops.nearest_rank(lat, 0.95),
            "p95_first_third_s": loops.nearest_rank(lat[:third], 0.95),
            "p95_last_third_s": loops.nearest_rank(lat[-third:], 0.95),
            "lanes_per_flush": mb.served / max(mb.flushes, 1),
            "generator_late_p95_s": loops.nearest_rank(
                (w.submitted - w.scheduled).tolist(), 0.95),
            "drained_after_s": w.drained - w.start}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

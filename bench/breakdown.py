#!/usr/bin/env python3
"""Where a cell's time goes, by the program's own names. Not part of a
benchmark run: it reads the spans, counters and device scopes that the
program opens (``repro.obs``), for the breakdown in PERF.md.

    python3 bench/breakdown.py --workload job-star-p32.draw --seed 7 \\
        --seconds 15 --untraced-seconds 30 --excerpt out.json

One set-up with the program's record open, then an untraced window of
``--untraced-seconds`` (the cost of tracing is read against it), then a
traced window of ``--seconds`` with the record open. Prints one JSON line
last on standard output:

* ``setup``: ``index_build_s`` and its passes from the record
  (``index_group_s`` = ``shred_keys`` + ``shred_sort`` + ``shred_link``,
  ``index_place_s``, ``index_pack_s``, ``plan_bind_s``) and the share of
  ``index_build_s`` they cover;
* ``draw``: device seconds per draw under the benchmark's ``draw`` span,
  in total and by program scope (each op looked up in the module that
  ran it), the part in no scope, and the arrival lanes used
  (``mean_count`` over ``draw_arrival_lanes`` per draw); ``readers``:
  what the per-layer readers give on the same trace (ops looked up by
  name and shape), their difference from the module-based values, and
  ``blanked``, the op names the readers' merged map gives no scope
  because two live modules place them in different scopes;
* ``cost``: seconds per draw untraced and traced;
* ``breakdown``: device seconds by innermost scope and the longest idle
  gaps, each named by the innermost span open on the host, the program's
  included.

``--excerpt FILE`` writes a few milliseconds of the scoped events of the
first draw (where its USR locate starts, where its column gather starts,
and where it ends), with the numbers a reduction of them must give, for
the tests.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("exprace", "usr_locate", "usr_gather")


def setup_split(rec, index_build_s: float) -> dict:
    """The index build's passes from the program's record."""
    out = {"index_build_s": index_build_s,
           "index_group_s": sum(rec.seconds(n) for n in (
               "shred_keys", "shred_sort", "shred_link")),
           "index_place_s": rec.seconds("shred_place"),
           "index_pack_s": rec.seconds("shred_pack"),
           "plan_bind_s": rec.seconds("plan_bind")}
    out["covered_share"] = 100.0 * sum(
        out[k] for k in ("index_group_s", "index_place_s", "index_pack_s",
                         "plan_bind_s")) / index_build_s
    return out


def blanked(modules: dict) -> int:
    """Op names that ``scopes._merge`` leaves without a scope because two
    of ``modules`` place them in different scopes."""
    from bench import scopes

    merged = scopes._merge(modules.values())
    return sum(1 for k, v in merged.items() if not v and any(
        scopes.scope_path(m.get(k, "")) for m in modules.values()))


def _cut(ev: dict, lo: float, hi: float) -> dict:
    """The events of ``[lo, hi]``, cut to it; the interval is the slice's
    ``window``."""
    def cut(events):
        out = []
        for e in events:
            s, end = max(e[1], lo), min(e[1] + e[2], hi)
            if end > s or (e[2] == 0 and lo <= e[1] <= hi):
                out.append([e[0], s, max(end - s, 0.0)] + list(e[3:]))
        return out

    return {"device": {p: cut(evs) for p, evs in ev["device"].items()},
            "host": [["window", lo, hi - lo]] + [
                h for h in cut(ev["host"]) if h[0] != "window"]}


def excerpt(ev: dict, ms: float) -> list:
    """Slices of ``ms`` milliseconds of the first draw: centred on the
    first op of its USR locate, on the first op of its column gather, and
    on its end (the boundary with the next draw)."""
    from bench import scopes

    d0, d1 = sorted((s, s + d) for n, s, d in ev["host"] if n == "draw")[0]
    ops = sorted((e[1], scopes.scope_path(e[3]))
                 for evs in ev["device"].values() for e in evs
                 if d0 <= e[1] <= d1)
    points = [next(t for t, path in ops if phase in path)
              for phase in ("usr_locate", "usr_gather")] + [d1]
    half = ms * 5e5
    return [_cut(ev, t - half, t + half) for t in points]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--untraced-seconds", type=float, default=0.0)
    ap.add_argument("--excerpt", default=None,
                    help="write a few ms of the scoped trace to this file")
    ap.add_argument("--excerpt-ms", type=float, default=2.0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro import config, obs

    import jax

    config.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import harness, loops, scopes, tracing

    cell = harness.resolve(args.workload)
    try:
        devices = harness.require_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"breakdown: {e}", file=sys.stderr)
        return 2
    with obs.record() as setup_rec:
        sess = harness.build_session(cell, args.seed)
    spans = tracing.Spans()
    drv = harness.driver_for(sess, cell.traffic, args.seed, spans=spans)
    drv.warm()
    line = {"workload": args.workload, "seed": args.seed,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind},
            "setup": setup_split(setup_rec, sess.index_build_s),
            "setup_s": time.perf_counter() - T_START}
    cost = {}
    if args.untraced_seconds:
        w = drv.window(args.untraced_seconds)
        cost["untraced_draw_s"] = loops.per_call_seconds(w)
        drv.settle(w)
    tmp = tempfile.mkdtemp(prefix="bench-breakdown-")
    try:
        with obs.record() as rec:
            jax.profiler.start_trace(tmp)
            with spans.span("window"):
                w = drv.window(args.seconds)
            jax.profiler.stop_trace()
        ev = scopes.events_from_xplane(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    got = drv.settle(w)
    counters = drv.counters(w, got)
    st = scopes.ScopedTrace.from_events(ev)
    cost["traced_draw_s"] = loops.per_call_seconds(w)
    cost["traced_window_s_per_draw"] = st.window_s() / w.calls
    total = st.span_device_s("draw")
    draw = {"draws": w.calls, "draw_device_s": total,
            "scopes": {s: st.scope_device_s(s) for s in obs.SCOPES},
            "unscoped_s": st.unscoped_device_s()}
    if total:
        draw["phase_share"] = 100.0 * sum(
            draw["scopes"][p] or 0.0 for p in PHASES) / total
        draw["unscoped_share"] = 100.0 * draw["unscoped_s"] / total
    lanes = rec.counters.get("draw_arrival_lanes", 0)
    if lanes and counters.get("mean_count"):
        draw["draw_lane_use_share"] = (100.0 * counters["mean_count"]
                                       / (lanes / rec.counters["draws"]))
    # what the per-layer readers see: a plain trace, ops by name and shape
    plain = tracing.Trace.from_events(
        {"device": {p: [e[:3] for e in evs] for p, evs in ev["device"].items()},
         "host": [h for h in ev["host"] if h[0] in tracing.SPAN_NAMES]})
    readers = {s: scopes.draw_scope_s(plain, s) for s in PHASES
               + ("search",)}
    draw["readers"] = {
        "values": readers,
        "minus_modules": {s: (v - draw["scopes"][s]
                              if None not in (v, draw["scopes"][s])
                              else None) for s, v in readers.items()},
        "blanked": blanked(scopes.live_op_names())}
    line.update(draw=draw, cost=cost, counters=dict(counters, **rec.counters),
                breakdown={"device_scopes": st.device_scopes(),
                           "idle_gaps": st.idle_gaps(),
                           "device_ops": st.top_ops()})
    if args.excerpt:
        slices = excerpt(ev, args.excerpt_ms)
        for sl in slices:
            sx = scopes.ScopedTrace.from_events(sl)
            sl["expected"] = {
                "busy_s": sx.busy_s(),
                "scopes": {s: sx.scope_device_s(s) for s in obs.SCOPES},
                "unscoped_s": sx.unscoped_device_s(),
                "device_scopes": sx.device_scopes()}
        with open(args.excerpt, "w") as f:
            json.dump({"source": f"bench/breakdown.py --workload "
                                 f"{args.workload} --seed {args.seed} "
                                 f"--excerpt; {devices[0].device_kind}",
                       "slices": slices}, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

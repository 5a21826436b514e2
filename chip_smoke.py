#!/usr/bin/env python3
"""End-to-end smoke run of the join engine on a TPU, checked against a
plain numpy reference.

    python chip_smoke.py              # one chip: the engine's main path
    python chip_smoke.py --chips 4    # four chips: the sharded engine and
                                      # the single-device run it must match

Data: the JOB star of ``benchmarks/workloads.job_like`` (Title |><| Cast |><|
Comp, probability on Title) at ``--scale`` titles — by default 2,528,312,
the IMDB ``title`` cardinality of JOB (Leis et al., VLDB 2015): ~10.1M Cast
rows, ~5.1M Comp rows, a join of ~2e7 tuples, all held on the device.

One chip: index build, ``full_join``, ``poisson_sample``, ``sample_batch``
(B=8), one ``apply_delta`` and a draw on the new snapshot, and a few dozen
requests through ``launch.fleet.batcher.MicroBatcher``. Checks: the join
size equals sum_t |Cast_t|*|Comp_t|; the full-join bag equals the
reference's (row count and an order-independent checksum); every sampled
tuple belongs to the join and equals the full join's tuple at its flat
position; every sample count lies within 5 sigma of sum(p*w).

Four chips (``--chips 4``): ``full_join(mesh=)`` equals the single-device
result, order included; ``sample(mesh=)`` and ``sample_batch(mesh=)`` equal
the host fold emulation (per shard, the single-device executor under
``fold_in(key, shard)``); the stacked index lands on four devices.

Exits non-zero, printing no result line, when JAX finds no TPU, when the
repository's code is missing, when interpret mode is in force on the TPU,
and on any failed phase or check. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
JOB_TITLE_ROWS = 2_528_312
SIGMAS = 5.0


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok  {what}", flush=True)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# numpy reference (independent of the engine)
# ---------------------------------------------------------------------------

def _mix(x):
    """splitmix64 finalizer over uint64 arrays (wrapping arithmetic)."""
    import numpy as np

    x = x ^ (x >> np.uint64(30))
    x = x * np.uint64(0xBF58476D1CE4E5B9)
    x = x ^ (x >> np.uint64(27))
    x = x * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def bag_checksum(cols) -> int:
    """Order-independent checksum of a bag of tuples: the wrapping sum of a
    per-tuple hash over the columns (t, kind, p, person, comp)."""
    import numpy as np

    h = np.zeros(len(cols[0]), np.uint64)
    with np.errstate(over="ignore"):
        for c in cols:
            c = np.ascontiguousarray(c)
            bits = c.view(np.uint64) if c.dtype == np.float64 \
                else c.astype(np.int64).view(np.uint64)
            h = _mix(h ^ bits)
        return int(h.sum(dtype=np.uint64))


COLS = ("t", "kind", "p", "person", "comp")


def _members(keys, x) -> bool:
    """Whether every value of ``x`` occurs in the sorted unique ``keys``."""
    import numpy as np

    if x.shape[0] == 0:
        return True
    i = np.minimum(np.searchsorted(keys, x), keys.shape[0] - 1)
    return bool((keys[i] == x).all())


class Reference:
    """The JOB star's join, computed directly from the generated columns."""

    def __init__(self, title, cast, comp):
        import numpy as np

        self.title, self.cast, self.comp = title, cast, comp
        n_t = title["t"].shape[0]
        if not np.array_equal(title["t"], np.arange(n_t)):
            raise SmokeFailure("Title.t must be the row index")
        cnt_c = np.bincount(cast["t"], minlength=n_t)
        cnt_m = np.bincount(comp["t"], minlength=n_t)
        self.w = cnt_c.astype(np.int64) * cnt_m
        self.size = int(self.w.sum())
        p = title["p"]
        self.mean = float((self.w * p).sum())
        self.std = float(np.sqrt((self.w * p * (1.0 - p)).sum()))
        self.cnt_m = cnt_m
        self.pm = int(max(cast["person"].max(), 0)) + 1
        self.cm = int(max(comp["comp"].max(), 0)) + 1
        self.cast_keys = np.unique(cast["t"] * self.pm + cast["person"])
        self.comp_keys = np.unique(comp["t"] * self.cm + comp["comp"])

    def bag(self):
        """The full join as columns, one row per (cast row, comp row) pair
        sharing a title."""
        import numpy as np

        cast_t = self.cast["t"]
        order_m = np.argsort(self.comp["t"], kind="stable")
        start_m = np.concatenate([[0], np.cumsum(self.cnt_m)])[:-1]
        reps = self.cnt_m[cast_t]
        ci = np.repeat(np.arange(cast_t.shape[0]), reps)
        off = np.arange(self.size) - np.repeat(np.cumsum(reps) - reps, reps)
        mi = order_m[start_m[cast_t[ci]] + off]
        t = cast_t[ci]
        return (t, self.title["kind"][t], self.title["p"][t],
                self.cast["person"][ci], self.comp["comp"][mi])

    def check_members(self, cols, what: str) -> None:
        """Every tuple of ``cols`` (dict of numpy columns) is in the join."""
        import numpy as np

        t = cols["t"]
        k = t.shape[0]
        check(k == 0 or ((t >= 0).all() and (t < self.title["t"].shape[0])
                         .all()), f"{what}: titles in range ({k} tuples)")
        check(np.array_equal(cols["kind"], self.title["kind"][t])
              and np.array_equal(cols["p"], self.title["p"][t]),
              f"{what}: Title attributes match")
        check(_members(self.cast_keys, t * self.pm + cols["person"]),
              f"{what}: every (t, person) is a Cast row")
        check(_members(self.comp_keys, t * self.cm + cols["comp"]),
              f"{what}: every (t, comp) is a Comp row")

    def check_count(self, count: int, what: str) -> None:
        z = (count - self.mean) / max(self.std, 1e-12)
        check(abs(z) <= SIGMAS, f"{what}: count {count} vs mean "
              f"{self.mean:.1f} (z = {z:+.2f}, |z| <= {SIGMAS})")


def make_data(seed: int, scale: int):
    """Generate the JOB star and return (db, query, numpy columns)."""
    import numpy as np

    from benchmarks.workloads import job_like

    db, q = job_like(seed=seed, scale=scale)
    cols = {name: {c: np.asarray(v) for c, v in rel.columns.items()}
            for name, rel in db.relations.items()}
    return db, q, cols


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def host(sample, k=None):
    """A sample's columns as numpy, cut to the first ``k`` lanes."""
    import numpy as np

    return {c: np.asarray(v)[:k] for c, v in sample.columns.items()}


def one_chip(args, dev_label: str) -> None:
    import jax
    import numpy as np

    from repro.core.delta import DeltaBatch
    from repro.engine import QueryEngine
    from repro.launch.fleet.batcher import JoinSampleRequest, MicroBatcher

    t0 = time.perf_counter()
    db, q, cols = make_data(args.seed, args.scale)
    ref = Reference(cols["Title"], cols["Cast"], cols["Comp"])
    log(f"data: Title={cols['Title']['t'].shape[0]} "
        f"Cast={cols['Cast']['t'].shape[0]} Comp={cols['Comp']['t'].shape[0]}"
        f" join={ref.size} E[k]={ref.mean:.1f} "
        f"({time.perf_counter() - t0:.2f} s host)")

    engine = QueryEngine(db)
    plan, t_build = timed(lambda: engine.compile(q))
    log(f"phase build  {t_build:.3f} s  [{dev_label}]")
    log(engine.explain(q))
    check(plan.join_size == ref.size,
          f"join size {plan.join_size} == sum_t |Cast_t|*|Comp_t|")

    # Full join (Yannakakis through the index).
    full, cold = timed(lambda: engine.full_join(q))
    _, warm = timed(lambda: engine.full_join(q))
    log(f"phase full_join  cold {cold:.3f} s  warm {warm:.3f} s  [{dev_label}]")
    fh = {c: np.asarray(full[c]) for c in COLS}
    check(fh["t"].shape[0] == ref.size, f"full join rows {fh['t'].shape[0]}")
    got = bag_checksum([fh[c] for c in COLS])
    want = bag_checksum(ref.bag())
    check(got == want, f"full-join bag checksum {got:#018x} == reference")

    def check_sample(count, overflow, positions, sc, what):
        k = int(count)
        check(not bool(overflow), f"{what}: no overflow")
        ref.check_count(k, what)
        sc = {c: v[:k] for c, v in sc.items()}
        ref.check_members(sc, what)
        pos = np.asarray(positions)[:k]
        check(k == 0 or (np.all(np.diff(pos) > 0) and pos[-1] < ref.size),
              f"{what}: positions ascending within the join")
        check(all(np.array_equal(sc[c], fh[c][pos]) for c in COLS),
              f"{what}: tuples equal the full join at their positions")

    # One Poisson draw.
    smp, cold = timed(lambda: engine.poisson_sample(q, jax.random.key(1)))
    smp2, warm = timed(lambda: engine.poisson_sample(q, jax.random.key(2)))
    log(f"phase poisson_sample  cold {cold:.3f} s  warm {warm:.3f} s  "
        f"k={int(smp.count)}  [{dev_label}]")
    for s_, what in ((smp, "poisson_sample"), (smp2, "poisson_sample warm")):
        check_sample(s_.count, s_.overflow, s_.positions, host(s_), what)

    # B = 8 draws in one dispatch.
    keys = jax.random.split(jax.random.key(3), 8)
    bat, cold = timed(lambda: engine.sample_batch(q, keys))
    _, warm = timed(lambda: engine.sample_batch(
        q, jax.random.split(jax.random.key(4), 8)))
    log(f"phase sample_batch(B=8)  cold {cold:.3f} s  warm {warm:.3f} s  "
        f"[{dev_label}]")
    bcols, bpos = host(bat), np.asarray(bat.positions)
    bcount, bovf = np.asarray(bat.count), np.asarray(bat.overflow)
    for b in range(8):
        check_sample(bcount[b], bovf[b], bpos[b],
                     {c: v[b] for c, v in bcols.items()},
                     f"sample_batch lane {b}")

    # MicroBatcher: 32 requests, flushed in batches of 8 (the warm bucket).
    mb = MicroBatcher(engine, max_batch=8, max_wait_ms=1e9,
                      collect_rows=True)
    t1 = time.perf_counter()
    done = []
    for i in range(32):
        done += mb.submit(JoinSampleRequest(q, seed=1000 + i))
    done += mb.flush()
    dt = time.perf_counter() - t1
    lat = sorted(r.latency_s for r in done)
    log(f"phase micro_batcher  32 requests in {dt:.3f} s  flushes="
        f"{mb.flushes} dispatches={mb.dispatches}  p50 latency "
        f"{lat[len(lat) // 2]:.3f} s  [{dev_label}]")
    check(len(done) == 32 and mb.flushes == 4, "32 requests served, 4 flushes")
    check(not any(r.overflow for r in done), "requests: no overflow")
    zs = [(r.count - ref.mean) / max(ref.std, 1e-12) for r in done]
    check(max(abs(z) for z in zs) <= SIGMAS,
          f"requests: every count within {SIGMAS} sigma of {ref.mean:.1f} "
          f"(z in [{min(zs):+.2f}, {max(zs):+.2f}])")
    for r in done[:4]:
        ref.check_members(r.rows, f"request {r.seed}")

    # One delta: replace 1000 Cast rows (row count unchanged, so the warm
    # executors keep their traces), then draw from the new snapshot.
    rng = np.random.default_rng(args.seed + 1)
    n_c = cols["Cast"]["t"].shape[0]
    n_t = cols["Title"]["t"].shape[0]
    gone = rng.choice(n_c, 1000, replace=False)
    ins = {"t": rng.integers(0, n_t, 1000).astype(cols["Cast"]["t"].dtype),
           "person": rng.integers(0, 2 * n_t, 1000).astype(
               cols["Cast"]["person"].dtype)}
    _, t_delta = timed(lambda: engine.apply_delta(
        DeltaBatch.of(Cast={"delete": gone, "insert": ins})))
    keep = np.ones(n_c, bool)
    keep[gone] = False
    cast2 = {c: np.concatenate([v[keep], ins[c]])
             for c, v in cols["Cast"].items()}
    ref = Reference(cols["Title"], cast2, cols["Comp"])
    smp3, t_draw = timed(lambda: engine.poisson_sample(q, jax.random.key(5)))
    log(f"phase apply_delta  {t_delta:.3f} s  draw after {t_draw:.3f} s  "
        f"[{dev_label}]")
    check(engine.join_size(q) == ref.size,
          f"post-delta join size {engine.join_size(q)} == reference")
    k = int(smp3.count)
    check(not bool(smp3.overflow), "post-delta draw: no overflow")
    ref.check_count(k, "post-delta draw")
    ref.check_members(host(smp3, k), "post-delta draw")
    log(f"stats: {engine.stats}")

    kernels(plan, dev_label)

    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device peak bytes in use: {stats['peak_bytes_in_use']}  "
            f"[{dev_label}]")


def kernels(plan, dev_label: str) -> None:
    """The Pallas kernels kept on the TPU route, compiled, at index widths,
    against exact numpy results: the prefix sum of the root weights and
    the compare-and-count search into a full-size (MAX_TABLE) prefix."""
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.bsearch_probe import MAX_TABLE

    w = np.asarray(plan.w).astype(np.int32)
    got, t_ps = timed(lambda: ops.prefix_sum(w))
    check(np.array_equal(np.asarray(got), np.cumsum(w, dtype=np.int32)),
          f"prefix_sum kernel == numpy over {w.shape[0]} root weights")
    pref = np.concatenate([[0], np.cumsum(w[:MAX_TABLE - 1])]).astype(
        np.int32)
    q = np.random.default_rng(0).integers(
        0, int(pref[-1]) + 1, 1 << 20).astype(np.int32)
    got, t_bs = timed(lambda: ops.searchsorted_prefix(pref, q))
    check(np.array_equal(np.asarray(got),
                         np.searchsorted(pref, q, side="right") - 1),
          f"bsearch_probe kernel == numpy ({q.shape[0]} queries into "
          f"{MAX_TABLE} entries)")
    log(f"phase kernels  prefix_sum {t_ps:.3f} s  bsearch_probe {t_bs:.3f} s"
        f" (cold)  [{dev_label}]")


def four_chips(args, dev_label: str) -> None:
    import jax
    import numpy as np

    from repro.engine import QueryEngine, ShardedPlan
    from repro.engine.executors import _sample_jit
    from repro.launch.mesh import make_data_mesh

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices >= 4")
    mesh = make_data_mesh(4)
    db, q, cols = make_data(args.seed, args.scale)
    ref = Reference(cols["Title"], cols["Cast"], cols["Comp"])
    log(f"data: join={ref.size} E[k]={ref.mean:.1f}")
    engine = QueryEngine(db)

    single, t_single = timed(lambda: engine.full_join(q))
    sharded, t_sharded = timed(lambda: engine.full_join(q, mesh=mesh))
    log(f"phase full_join  single {t_single:.3f} s  mesh {t_sharded:.3f} s"
        f"  [{dev_label}]")
    check(all(np.array_equal(np.asarray(single[c]), np.asarray(sharded[c]))
              for c in COLS),
          f"full_join(mesh=) == single device, order included "
          f"({ref.size} rows)")

    plan = engine.compile_sharded(q, mesh)
    check(isinstance(plan, ShardedPlan) and plan.num_shards == 4,
          f"sharded plan over {plan.num_shards} shards")
    log(engine.explain(q))
    key = jax.random.key(7)
    st = plan.stacked
    leaves = jax.tree.leaves((st.shred, st.w, st.p, st.prefE, plan.massE))

    def split4(x):  # one (1, ...) block of the shard axis per device
        return (len(x.sharding.device_set) == 4
                and x.sharding.shard_shape(x.shape)[0] * 4 == x.shape[0] == 4)

    check(all(split4(x) for x in leaves),
          f"every stacked index leaf ({len(leaves)}) split over 4 devices, "
          f"one shard each")
    step, _ = timed(lambda: plan.sample_step(key))
    devs = {s.device for s in step[0].positions.addressable_shards}
    check(len(devs) == 4, f"draw output on {len(devs)} distinct devices")

    def emulate(base_key):
        """Host fold emulation: shard s draws on one device under
        fold_in(base_key, s); results concatenate in shard order."""
        got = {c: [] for c in COLS}
        pos, base = [], 0
        for s in range(plan.num_shards):
            sh = jax.tree.map(lambda x: x[s], st.shred)
            r = _sample_jit(sh, st.w[s], st.p[s], st.prefE[s],
                            jax.random.fold_in(base_key, s), cap=plan.cap,
                            rep=plan.rep, method="exprace", acap=plan.acap,
                            massE=plan.massE[s])
            c = int(r.count)
            for col in COLS:
                got[col].append(np.asarray(r.columns[col])[:c])
            pos.append(np.asarray(r.positions)[:c] + base)
            base += int(st.prefE[s, -1])
        return ({c: np.concatenate(v) for c, v in got.items()},
                np.concatenate(pos))

    smp, t_smp = timed(lambda: engine.sample(q, key, mesh=mesh))
    log(f"phase sample(mesh=)  {t_smp:.3f} s  k={int(smp.count)}  "
        f"[{dev_label}]")
    k = int(smp.count)
    want, want_pos = emulate(key)
    check(not bool(smp.overflow), "sample(mesh=): no overflow")
    check(all(np.array_equal(np.asarray(smp.columns[c])[:k], want[c])
              for c in COLS)
          and np.array_equal(np.asarray(smp.positions)[:k], want_pos),
          f"sample(mesh=) == host fold emulation ({k} tuples)")
    ref.check_count(k, "sample(mesh=)")

    keys = jax.random.split(jax.random.key(8), 2)
    bat, t_bat = timed(lambda: engine.sample_batch(q, keys, mesh=mesh))
    log(f"phase sample_batch(mesh=, B=2)  {t_bat:.3f} s  [{dev_label}]")
    for b in range(2):
        kb = int(bat.count[b])
        want, want_pos = emulate(keys[b])
        check(all(np.array_equal(np.asarray(bat.columns[c][b])[:kb], want[c])
                  for c in COLS)
              and np.array_equal(np.asarray(bat.positions[b])[:kb],
                                 want_pos),
              f"sample_batch(mesh=) lane {b} == host fold emulation "
              f"({kb} tuples)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=JOB_TITLE_ROWS,
                    help="Title rows (default: JOB's IMDB title count)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {platform!r})",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]
    try:
        from repro import config
    except ImportError as e:
        print(f"chip_smoke: repository code not found: {e}", file=sys.stderr)
        return 2
    cache = config.enable_compile_cache()
    pol = config.current_policy()
    kind = devices[0].device_kind
    label = f"{platform} {kind} x{len(devices)}"
    log(f"device: {label}  compile cache: {cache}  policy: {pol}")
    try:
        check(not pol.interpret, "compiled Pallas kernels (interpret off)")
        t0 = time.perf_counter()
        (four_chips if args.chips == 4 else one_chip)(args, label)
        log(f"total {time.perf_counter() - t0:.3f} s  [{label}]")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Runs one cell of ``BENCHMARK.json``: set-up, a measured window, the
comparison with the plain reference, and the result line.

Everything is found by name, so that a cell, a configuration, a traffic
mix or a per-layer metric is added with new files and entries alone:

* ``bench/configs/<config>.json``: sizes, join tree, guarantees, source;
* ``bench/configs/<config>.py``: ``generate(cfg, seed)`` -> numpy tables;
* ``bench/traffic/<traffic>.json``: the mix's parameters, read by the one
  generator below (a closed loop or an open loop; which entry point, how
  many keys a call, which query shapes in what proportion, the arrival
  process and its rate, the batcher's settings);
* ``bench/limits/<cell>.json``: the limit of every number compared;
* ``bench/metrics/<metric>.py``: ``read(ctx)`` -> the per-layer metric or
  ``None`` where its cell gives it nothing to read; a metric named
  ``<base>.<suffix>`` without a file of its own is read by ``<base>.py``;
* ``bench/peaks.json``: peaks by ``device_kind``.

A cell named ``<config>.<traffic>`` that ``BENCHMARK.json`` does not list
resolves from these files alone (the tools and the tests run such cells).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check, faults, loops, tracing
from bench.reference import Reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

__all__ = ["Cell", "load_benchmark", "resolve", "metric_file", "NoDevice",
           "require_devices", "Session", "build_session", "driver_for", "run",
           "key_seed"]


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path (its name may hold dots and dashes)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    generator: object     # its module
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def resolve(workload: str, bench: dict = None) -> Cell:
    """The cell named ``workload`` with its files loaded. A cell that the
    benchmark does not list is ``<config>.<traffic>``, with the traffic's
    own metric and ``setup_s`` as its end-to-end metrics."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload in cells:
        w = cells[workload]
        conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
        cfg_file, traffic_name = os.path.join(ROOT, conf["file"]), w["traffic"]
        chips = int(w["chips"])
    else:
        config, _, traffic_name = workload.partition(".")
        cfg_file = os.path.join(BENCH, "configs", config + ".json")
        if not (traffic_name and os.path.isfile(cfg_file)):
            raise KeyError(f"no workload {workload!r}; listed: {sorted(cells)}")
        chips = 1
    traffic = load_json(os.path.join(BENCH, "traffic", traffic_name + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if workload not in cells:
        e2e = [m for m in e2e if m["name"] == "setup_s"] + [
            {"name": traffic["metric"], "unit": "s"}]
    names = {m["name"] for m in e2e}
    return Cell(
        name=workload, chips=chips, config=load_json(cfg_file),
        generator=load_module(cfg_file[:-len(".json")] + ".py"),
        traffic=traffic,
        limits=load_json(os.path.join(BENCH, "limits", workload + ".json")),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"]
                   if workload in cells and _applies(m, workload, names)])


def metric_file(name: str) -> str:
    """The reader of per-layer metric ``name``: ``bench/metrics/<name>.py``,
    or for ``<base>.<suffix>`` without a file of its own, ``<base>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.isfile(path) and "." in name:
        path = os.path.join(BENCH, "metrics", name.rsplit(".", 1)[0] + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no reader for metric {name!r}")
    return path


class NoDevice(RuntimeError):
    pass


def require_devices(chips: int):
    """The TPU devices of this process; fails without ``chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX has "
                       f"{len(devices)}")
    return devices


def key_seed(seed: int, i: int, stream: int = 0) -> int:
    """The PRNG seed of the ``i``-th draw or request of a run."""
    ss = np.random.SeedSequence([seed % (1 << 64), stream, i])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(2))


@dataclasses.dataclass
class Session:
    """One configuration at one seed: its tables, the program's engine
    over them, one query per shape the traffic asks for, and the index
    build's time.

    ``tables`` holds every node of the join tree under its own name, with
    the node's variables as columns: a node with a ``table`` key (a self
    join's alias) binds its variables, in order, to that generated table's
    columns."""

    config: dict
    tables: Dict[str, Dict[str, np.ndarray]]
    trees: List[List[dict]]        # the join tree of each query shape
    queries: List[object]          # one JoinQuery per shape
    engine: object
    index_build_s: float
    refs: Dict[int, Reference] = dataclasses.field(default_factory=dict)

    @property
    def query(self):
        return self.queries[0]

    def shape_of(self, query) -> int:
        return self.queries.index(query)

    def reference(self, shape: int = 0) -> Reference:
        if shape not in self.refs:
            self.refs[shape] = Reference(self.trees[shape], self.tables,
                                         self.config["prob_var"])
        return self.refs[shape]


def shape_trees(tree: List[dict], traffic: dict) -> List[List[dict]]:
    """The join tree of each query shape of the traffic: ``queries`` lists
    shapes as the relations they join, a subtree holding the root; without
    it, the one shape is the configuration's whole tree."""
    shapes = [q["relations"] for q in traffic.get("queries", ())] or \
        [[n["relation"] for n in tree]]
    out = []
    for rels in shapes:
        sub = [n for n in tree if n["relation"] in rels]
        names = {n["relation"] for n in sub}
        if (len(sub) != len(rels) or sub[0] is not tree[0]
                or any(n["parent"] not in names for n in sub[1:])):
            raise ValueError(f"query shape {rels} is no subtree holding "
                             f"the root {tree[0]['relation']!r}")
        out.append(sub)
    return out


def build_session(cell: Cell, seed: int, rows: dict = None) -> Session:
    """Generate the data from ``seed`` and build the program's index for
    every query shape of the cell's traffic (``rows`` replaces the
    configuration's row counts, for tests)."""
    import jax

    from repro.core import Atom, Database, JoinQuery
    from repro.engine import QueryEngine

    cfg = dict(cell.config, rows=rows or cell.config["rows"])
    raw = cell.generator.generate(cfg, seed)
    views, atoms = {}, {}
    for n in cfg["tree"]:
        name, table = n["relation"], n.get("table", n["relation"])
        cols = list(raw[table])
        if len(cols) != len(n["vars"]):
            raise ValueError(f"{name}: {len(n['vars'])} variables for the "
                             f"{len(cols)} columns of {table}")
        views[name] = {v: raw[table][c] for v, c in zip(n["vars"], cols)}
        atoms[name] = Atom.of(table, *n["vars"],
                              alias=None if table == name else name)
    db = Database.from_columns(raw)
    trees = shape_trees(cfg["tree"], cell.traffic)
    queries = [JoinQuery(tuple(atoms[n["relation"]] for n in t),
                         prob_var=cfg["prob_var"]) for t in trees]
    engine = QueryEngine(db)
    t0 = time.perf_counter()
    for q in queries:
        jax.block_until_ready(engine.compile(q).shred)
    return Session(cfg, views, trees, queries, engine,
                   time.perf_counter() - t0)


def _mix(traffic: dict, seed: int) -> Callable[[int], int]:
    weights = [int(q.get("weight", 1)) for q in traffic.get("queries", ())]
    return loops.mix_sequence(weights or [1], seed)


def _numbers(sess: Session, got: dict, numbers) -> Dict[str, float]:
    """``numbers(reference, sizes, checked)`` of every query shape that
    answers were seen of, merged into one set."""
    shapes = sorted(set(got["sizes"]) | set(got["checked"]))
    return check.merged([numbers(sess.reference(s), got["sizes"].get(s, []),
                                 got["checked"].get(s, [])) for s in shapes])


# ---------------------------------------------------------------------------
# Traffic drivers: warm-up, window, and what the window is judged by.
# ---------------------------------------------------------------------------

class Closed:
    """One client calling ``poisson_sample``, ``sample_batch`` (``batch``
    keys a call) or ``full_join`` back to back, each call over the query
    shape that the traffic's mix gives it."""

    CALLS = ("poisson_sample", "sample_batch", "full_join")

    def __init__(self, sess: Session, traffic: dict, seed: int, engine=None,
                 spans=None):
        self.sess, self.traffic, self.seed = sess, traffic, seed
        self.engine = engine or sess.engine
        self.spans = spans or loops.NoSpans()
        self.call_name = traffic["call"]
        if self.call_name not in self.CALLS:
            raise ValueError(f"closed loop cannot call {self.call_name!r}; "
                             f"it calls one of {self.CALLS}")
        self.shape = _mix(traffic, seed)
        self.reservoir = None

    def _call(self, i: int, shape: int, stream: int = 0):
        import jax

        query = self.sess.queries[shape]
        if self.call_name == "full_join":
            return self.engine.full_join(query)
        if self.call_name == "sample_batch":
            import jax.numpy as jnp

            b = int(self.traffic["batch"])
            with self.spans.span("make_keys"):
                keys = jnp.stack([jax.random.key(key_seed(self.seed, i * b + j,
                                                          stream))
                                  for j in range(b)])
            return self.engine.sample_batch(query, keys)
        with self.spans.span("make_keys"):
            key = jax.random.key(key_seed(self.seed, i, stream))
        return self.engine.poisson_sample(query, key)

    def warm(self) -> None:
        import jax

        for s in range(len(self.sess.queries)):
            jax.block_until_ready(self._call(s, s, stream=1))

    def _keep(self, i: int, out):
        s = self.shape(i)
        self.reservoir.offer(i, (s, out))
        if self.call_name == "full_join":
            return s, int(next(iter(out.values())).shape[0])
        return s, out.count, out.overflow

    def window(self, seconds: float):
        import jax

        self.reservoir = loops.Reservoir(
            int(self.traffic["check_calls"]),
            np.random.default_rng([self.seed % (1 << 64), 7]))
        return loops.closed_loop(
            lambda i: self._call(i, self.shape(i)), jax.block_until_ready,
            seconds, spans=self.spans, span=self.traffic["span"],
            keep=self._keep)

    def settle(self, w) -> dict:
        """Host copies of what the window produced, by query shape; frees
        the device."""
        checked: Dict[int, list] = {}
        for _, (s, out) in sorted(self.reservoir.items, key=lambda t: t[0]):
            mine = checked.setdefault(s, [])
            if self.call_name == "full_join":
                mine.append({v: np.asarray(c) for v, c in out.items()})
                continue
            count = np.atleast_1d(np.asarray(out.count))
            over = np.atleast_1d(np.asarray(out.overflow))
            cols = {v: np.asarray(c).reshape(count.shape[0], -1)
                    for v, c in out.columns.items()}
            for lane, (k, o) in enumerate(zip(count, over)):
                if not o:
                    mine.append({v: c[lane, :k] for v, c in cols.items()})
        self.reservoir = None
        sizes: Dict[int, list] = {}
        failed = 0
        for s, *res in w.results:
            if self.call_name == "full_join":
                sizes.setdefault(s, []).append(res[0])
                continue
            for c, o in zip(np.atleast_1d(np.asarray(res[0])),
                            np.atleast_1d(np.asarray(res[1]))):
                failed += bool(o)
                if not o:
                    sizes.setdefault(s, []).append(int(c))
        return {"sizes": sizes, "checked": checked, "failed": failed}

    def numbers(self, got: dict) -> Dict[str, float]:
        return _numbers(self.sess, got, check.join_numbers
                        if self.call_name == "full_join"
                        else check.sample_numbers)

    def end_to_end(self, w) -> Dict[str, float]:
        return {self.traffic["metric"]: loops.per_call_seconds(w)}

    def attempted(self, w) -> int:
        if self.call_name == "sample_batch":
            return w.calls * int(self.traffic["batch"])
        return w.calls

    def counters(self, w, got: dict) -> Dict[str, float]:
        sizes = [n for ns in got["sizes"].values() for n in ns]
        key = "join_size" if self.call_name == "full_join" else "mean_count"
        return {key: float(np.mean(sizes)) if sizes else None}


def timed_batcher(engine, spans, **kw):
    """A ``MicroBatcher`` that opens the ``flush`` span around each flush
    and notes, on every request it flushes, when that flush began."""
    from repro.launch.fleet.batcher import MicroBatcher

    class TimedBatcher(MicroBatcher):
        def flush(self):
            t = self.clock()
            for r in self.pending:
                r.flush_start_s = t
            with spans.span("flush"):
                return super().flush()

    return TimedBatcher(engine, **kw)


class Open:
    """Independent draw requests arriving by the traffic's arrival process
    (``loops.arrivals``), each over the query shape the mix gives it,
    served by ``MicroBatcher`` over ``QueryEngine.sample_batch``."""

    def __init__(self, sess: Session, traffic: dict, seed: int, engine=None,
                 spans=None):
        self.sess, self.traffic, self.seed = sess, traffic, seed
        self.spans = spans or loops.NoSpans()
        self._last = _LastEngine(engine or sess.engine)
        self.engine = faults.SpanEngine(self._last, self.spans)
        if traffic["call"] != "sample_batch":
            raise ValueError("an open loop serves sample_batch draws")
        loops.arrivals(traffic, 1.0, 0)  # an unknown process fails here
        self.shape = _mix(traffic, seed)
        self.batcher = None

    def _batcher(self):
        return timed_batcher(
            self.engine, self.spans, max_batch=int(self.traffic["max_batch"]),
            max_wait_ms=float(self.traffic["max_wait_ms"]),
            collect_rows=bool(self.traffic["collect_rows"]))

    def _request(self, i: int, stream: int = 0, shape: int = None):
        from repro.launch.fleet.batcher import JoinSampleRequest

        s = self.shape(i) if shape is None else shape
        return JoinSampleRequest(self.sess.queries[s],
                                 seed=key_seed(self.seed, i, stream))

    def warm(self) -> None:
        """For every query shape, every power-of-two batch bucket up to
        ``max_batch`` through a real flush, then the host-side steps of a
        flush of every other size (stacking its keys, padding them to the
        bucket, cutting the bucket's result back) without drawing again."""
        import jax
        import jax.numpy as jnp

        from repro.engine.executors import bucket_size, pad_batch_keys

        top = int(self.traffic["max_batch"])
        mb = self._batcher()
        i = 0
        for s in range(len(self.sess.queries)):
            buckets, b = {}, 1
            while b <= top:
                for _ in range(b):
                    mb.submit(self._request(i, stream=1, shape=s))
                    i += 1
                mb.flush()
                buckets[b] = self._last.last
                b *= 2
            for n in range(1, top + 1):
                if n in buckets:
                    continue
                keys = jnp.stack([jax.random.key(key_seed(self.seed, j, 2))
                                  for j in range(n)])
                padded, _ = pad_batch_keys(keys)
                out = buckets[bucket_size(n)]
                jax.block_until_ready((padded,
                                       jax.tree.map(lambda x: x[:n], out)))
        self._last.last = None

    def window(self, seconds: float):
        sched = loops.arrivals(self.traffic, seconds, self.seed)
        self.batcher = self._batcher()
        return loops.open_loop(sched, self._request, self.batcher,
                               spans=self.spans)

    def settle(self, w) -> dict:
        answered = [i for i, d in enumerate(w.done) if not math.isnan(d)]
        ok = [i for i in answered if not w.requests[i].overflow]
        rng = np.random.default_rng([self.seed % (1 << 64), 7])
        k = min(int(self.traffic["check_requests"]), len(ok))
        pick = sorted(rng.choice(len(ok), k, replace=False)) if k else []
        sizes: Dict[int, list] = {}
        checked: Dict[int, list] = {}
        for i in ok:
            sizes.setdefault(self.shape(i), []).append(w.requests[i].count)
        for j in pick:
            i = ok[j]
            checked.setdefault(self.shape(i), []).append(w.requests[i].rows)
        return {"sizes": sizes, "checked": checked,
                "unanswered": w.unanswered(),
                "failed": w.unanswered() + len(answered) - len(ok)}

    def numbers(self, got: dict) -> Dict[str, float]:
        out = _numbers(self.sess, got, check.sample_numbers)
        out["unanswered"] = float(got["unanswered"])
        return out

    def end_to_end(self, w) -> Dict[str, float]:
        lat = np.where(np.isnan(w.done), w.drained, w.done) - w.scheduled
        return {self.traffic["metric"]: loops.nearest_rank(lat.tolist(), 0.95)}

    def attempted(self, w) -> int:
        return len(w.requests)

    def counters(self, w, got: dict) -> Dict[str, float]:
        mb = self.batcher
        waits = w.queue_waits()
        waits = waits[~np.isnan(waits)]
        sizes = [n for ns in got["sizes"].values() for n in ns]
        return {"flushes": mb.flushes, "served": mb.served,
                "dispatches": mb.dispatches,
                "queue_wait_p95_s": (loops.nearest_rank(waits.tolist(), 0.95)
                                     if waits.size else None),
                "mean_count": float(np.mean(sizes)) if sizes else None,
                "generator_late_p95_s": loops.nearest_rank(
                    (w.submitted - w.scheduled).tolist(), 0.95)}


class _LastEngine(faults.Proxy):
    """Keeps the last batched draw, so warm-up can cut it to every size."""

    last = None

    def sample_batch(self, query, keys, **kw):
        self.last = self._engine.sample_batch(query, keys, **kw)
        return self.last


def driver_for(sess: Session, traffic: dict, seed: int, engine=None,
               spans=None):
    if traffic["loop"] == "closed":
        return Closed(sess, traffic, seed, engine, spans)
    if traffic["loop"] == "open":
        return Open(sess, traffic, seed, engine, spans)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

class Builds:
    """Counts executables built since it was made: ``built`` compiled or
    loaded from the persistent cache, ``loaded`` the latter."""

    def __init__(self):
        import jax.monitoring

        self.built = self.loaded = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.built += 1

    def _event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.loaded += 1

    def close(self):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    cell: Cell
    trace: Optional[tracing.Trace]
    counters: Dict[str, float]
    peaks: Optional[dict]
    tables: Dict[str, Dict[str, np.ndarray]]


class _Phases:
    """Logs the host-clock seconds of each phase of a run."""

    def __init__(self, log):
        self.log, self.t = log, time.perf_counter()

    def __call__(self, name: str) -> None:
        t = time.perf_counter()
        self.log(f"phase {name} {t - self.t:.3f} s", file=sys.stderr)
        self.t = t


def _peaks(kind: str) -> dict:
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, rows: dict = None, traffic: dict = None,
        fault: str = "sound", require: Callable = require_devices,
        keep_trace: str = None, log=print, bench: dict = None,
        sess: Session = None) -> dict:
    """Run a cell and return its result line (as a dict). ``fault`` puts
    the control or a planted fault (``bench/faults.py``) in the program's
    place; ``sess`` reuses a session built for the same cell and seed."""
    import jax

    builds = Builds()
    try:
        cell = resolve(workload, bench)
        if traffic:
            cell.traffic = dict(cell.traffic, **traffic)
        devices = require(cell.chips)
        phase = _Phases(log)
        sess = sess or build_session(cell, seed, rows)
        phase("data and index")
        prob_var = sess.config["prob_var"]
        engine = faults.planted(
            fault, sess.engine, prob_var=prob_var, seed=seed,
            precision=sess.config["precision"][prob_var],
            ref_of=lambda q: sess.reference(sess.shape_of(q)))
        spans = tracing.Spans() if trace else loops.NoSpans()
        drv = driver_for(sess, cell.traffic, seed, engine, spans)
        drv.warm()
        phase("warm-up")
        setup = {"compiled": builds.built - builds.loaded,
                 "loaded": builds.loaded}
        setup["cold"] = setup["compiled"] > 0
        tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        try:
            if trace:
                jax.profiler.start_trace(tmp)
            setup_s = time.perf_counter() - t_start
            before = builds.built
            with spans.span("window"):
                w = drv.window(seconds)
            window_builds = builds.built - before
            if trace:
                jax.profiler.stop_trace()
                events = tracing.events_from_xplane(tmp)
                if keep_trace:
                    with open(keep_trace, "w") as f:
                        json.dump(events, f)
        finally:
            if tmp:
                shutil.rmtree(tmp, ignore_errors=True)
    finally:
        builds.close()
    phase("window")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    got = drv.settle(w)
    e2e = dict(drv.end_to_end(w), setup_s=setup_s)
    counters = dict(drv.counters(w, got), index_build_s=sess.index_build_s,
                    window_compiles=window_builds)
    phase("copy-out")
    correct, table = check.judge(drv.numbers(got), cell.limits)
    phase("reference and checks")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": int(drv.attempted(w)),
            "failed": int(got["failed"])}
    if trace:
        tr = tracing.Trace.from_events(events)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        ctx = Context(cell, tr, counters, _peaks(devices[0].device_kind),
                      sess.tables)
        metrics = {}
        for m in cell.per_layer:
            v = load_module(metric_file(m["name"])).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = {"device_ops": tr.top_ops(),
                             "idle_gaps": tr.idle_gaps()}
    else:
        line["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
        line["device"] = device
    line["setup"] = setup
    log(json.dumps({"setup": setup, "counters": counters}), file=sys.stderr)
    for k, v in table.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    line["checks"] = table
    return line

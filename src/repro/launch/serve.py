"""Serving demos: (1) LM batched prefill + decode with a request queue
(continuous-batching-lite) on the reduced configs, and (2) a join-sampling
service — single-engine micro-batching (DESIGN.md §10) or, with
``--replicas N``, a replicated fleet (DESIGN.md §12) behind a router with
log-shipped deltas and an injected replica crash.

The serving *library* lives in ``repro.launch.fleet`` (router, replica,
transport, log, micro-batcher); this module is a thin demo over it and
re-exports the single-engine names (``MicroBatcher`` & co.) so existing
imports keep working.

The decode step function is the same one the dry-run lowers for the
decode_32k / long_500k cells (launch/dryrun.py `make_serve_step`); here it
runs eagerly on CPU to demonstrate correctness and the batching behavior.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import config, configs
from repro.launch.metrics import percentile
from repro.launch.fleet import (  # noqa: F401  (re-exported public API)
    JoinSampleRequest, MicroBatcher, Rejected, UpdateRequest,
    serve_fleet, serve_join_samples,
)
from repro.models import decode_step, encode, forward, init_cache, init_model, prefill


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new: int = 16
    out: Optional[List[int]] = None


def serve_batch(arch: str, requests: List[Request], seed: int = 0,
                greedy: bool = True) -> List[Request]:
    """Pad requests to one batch, prefill, then decode in lockstep."""
    cfg = configs.reduced(configs.get_config(arch))
    params = init_model(cfg, jax.random.key(seed))
    B = len(requests)
    plens = [len(r.prompt) for r in requests]
    S = max(plens)
    max_new = max(r.max_new for r in requests)
    total = S + max_new + 1
    toks = np.zeros((B, S), np.int32)
    for i, r in enumerate(requests):
        toks[i, : len(r.prompt)] = r.prompt

    mem = None
    if cfg.n_memory_tokens and not cfg.has_encoder:
        mem = jnp.zeros((B, cfg.n_memory_tokens, cfg.d_model), jnp.float32)
    if cfg.has_encoder:
        frames = jnp.zeros((B, cfg.n_memory_tokens, cfg.enc_d_model), jnp.float32)
        mem = encode(params, cfg, frames)

    _, cache = prefill(params, cfg, jnp.asarray(toks), total, mem)
    step = jax.jit(lambda c, t, cur: decode_step(params, cfg, c, t, cur))

    outs = [[] for _ in requests]
    cur_tok = jnp.asarray(toks[:, -1:])
    for t in range(max_new):
        logits, cache = step(cache, cur_tok, jnp.asarray(S + t, jnp.int32))
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        cur_tok = nxt[:, None]
        for i in range(B):
            if t < requests[i].max_new:
                outs[i].append(int(nxt[i]))
    for r, o in zip(requests, outs):
        r.out = o
    return requests


# ---------------------------------------------------------------------------
# Join-sampling demos (engine-backed): single-engine loop and fleet
# ---------------------------------------------------------------------------

def _demo_stream(db, n_requests: int, updates: int):
    """The shared demo workload: two tenant query shapes + optional
    shape-preserving doc churn spread through the stream."""
    from repro.core import Atom, JoinQuery
    from repro.core.delta import DeltaBatch

    q_qual = JoinQuery((Atom.of("ClusterQuality", "clust", "p"),
                        Atom.of("Doc", "doc", "clust")), prob_var="p")
    q_flat = JoinQuery((Atom.of("ClusterQuality", "clust", "p"),),
                       prob_var="p")
    rng = np.random.default_rng(0)
    reqs: List = [JoinSampleRequest(query=q_qual if i % 3 else q_flat, seed=i)
                  for i in range(n_requests)]
    if updates:
        n_docs = int(db.relations["Doc"].num_rows)
        every = max(1, n_requests // updates)
        for u in range(updates):
            delta = DeltaBatch.of(Doc={
                "insert": {"doc": rng.integers(0, n_docs, 4),
                           "clust": rng.integers(0, 64, 4)},
                "delete": rng.choice(n_docs, size=4, replace=False)})
            reqs.insert(min((u + 1) * every + u, len(reqs)),
                        UpdateRequest(delta))
    return reqs, (q_qual, q_flat)


def _join_demo(n_requests: int, devices: int = 1, max_batch: int = 64,
               max_wait_ms: float = 2.0, updates: int = 0) -> None:
    from repro.data.pipeline import make_corpus_db
    from repro.engine import QueryEngine
    from repro.launch.mesh import force_host_devices, make_data_mesh

    mesh = None
    if devices > 1:
        mesh = make_data_mesh(force_host_devices(devices))

    db = make_corpus_db(n_docs=20_000, n_clusters=64, seq_len=8, vocab=256)
    reqs, (q_qual, _) = _demo_stream(db, n_requests, updates)
    engine = QueryEngine(db)
    mb = MicroBatcher(engine, max_batch=max_batch, max_wait_ms=max_wait_ms,
                      mesh=mesh)
    t0 = time.perf_counter()
    done: List = []
    for r in reqs:
        done += mb.submit(r)
        done += mb.poll()
    done += mb.flush()
    wall = time.perf_counter() - t0
    assert len(done) == n_requests + (updates or 0)
    draws = [r for r in done if isinstance(r, JoinSampleRequest)]
    lats = [r.latency_s * 1e3 for r in draws]
    st = engine.stats
    shards = ""
    if mesh is not None:  # the planner may degrade to the unsharded plan
        from repro.engine import ShardedPlan
        plan = engine.compile_sharded(q_qual, mesh)
        shards = (f"  shards={plan.num_shards}"
                  if isinstance(plan, ShardedPlan) else "  shards=1")
    print(f"[serve-join] {n_requests} requests in {mb.flushes} flushes "
          f"({mb.dispatches} dispatches){shards}  "
          f"max_batch={max_batch} max_wait={max_wait_ms}ms")
    print(f"  draws/sec={n_requests/wall:,.0f}  "
          f"latency p50={percentile(lats, .5):.1f}ms "
          f"p99={percentile(lats, .99):.1f}ms  "
          f"(incl. cold compile in early flushes)")
    print(f"  cache: shred_builds={st.shred_builds} shred_hits={st.shred_hits} "
          f"plan_hits={st.plan_hits} plan_misses={st.plan_misses}")
    if updates:
        print(f"  updates: applied={mb.updates_applied} "
              f"db_version={engine.db.version} "
              f"upgrades: shred={st.shred_upgrades} plan={st.plan_upgrades}")


def _fleet_demo(n_requests: int, replicas: int, max_batch: int = 64,
                max_wait_ms: float = 2.0, updates: int = 0,
                crash: bool = True) -> None:
    """The replicated fleet demo (DESIGN.md §12): serve the same stream
    through ``--replicas N`` engine replicas, fail-stop one replica
    mid-stream, and verify the results bit-identical to the single-engine
    micro-batcher baseline per (seed, version)."""
    from repro.data.pipeline import make_corpus_db
    from repro.engine import QueryEngine

    db = make_corpus_db(n_docs=20_000, n_clusters=64, seq_len=8, vocab=256)
    reqs, _ = _demo_stream(db, n_requests, updates)
    crash_at = n_requests // 2 if crash and replicas > 1 else None

    t0 = time.perf_counter()
    done, fleet = serve_fleet(
        db, reqs, replicas=replicas, max_batch=max_batch,
        max_wait_ms=max_wait_ms, clock="real", retry_timeout_s=30.0,
        crash_at=crash_at, crash_replica=replicas - 1)
    wall = time.perf_counter() - t0

    draws = [r for r in done if isinstance(r, JoinSampleRequest)]
    rejected = [r for r in done if isinstance(r, Rejected)]
    assert len(draws) + len(rejected) == n_requests, \
        f"lost requests: {len(draws)}+{len(rejected)} != {n_requests}"
    assert len({id(r) for r in draws}) == len(draws), "request served twice"

    # Bit-identical to the single-engine baseline, per (seed, version).
    baseline = {}
    for r in serve_join_samples(QueryEngine(db),
                                _demo_stream(db, n_requests, updates)[0],
                                max_batch=max_batch):
        if isinstance(r, JoinSampleRequest):
            baseline[(r.seed, r.db_version)] = (r.count, r.overflow)
    mismatches = [r.seed for r in draws
                  if baseline.get((r.seed, r.db_version))
                  != (r.count, r.overflow)]
    assert not mismatches, f"fleet != single-engine for seeds {mismatches}"

    lats = [r.latency_s * 1e3 for r in draws]
    st = fleet.stats()
    rt = fleet.router
    crashed = [r.name for r in fleet.replicas
               if r.state == "down" and r.name not in rt.drained]
    print(f"[serve-fleet] {n_requests} requests over {replicas} replicas  "
          f"max_batch={max_batch} max_wait={max_wait_ms}ms  "
          f"crash_injected={crash_at is not None}")
    print(f"  draws/sec={len(draws)/wall:,.0f}  "
          f"latency p50={percentile(lats, .5):.1f}ms "
          f"p99={percentile(lats, .99):.1f}ms  "
          f"rejected={len(rejected)} retries={rt.retries} "
          f"crashed_replicas={len(crashed)}")
    print(f"  fleet cache (aggregated): shred_builds={st.shred_builds} "
          f"plan_misses={st.plan_misses} plan_hits={st.plan_hits} "
          f"upgrades: shred={st.shred_upgrades} plan={st.plan_upgrades}")
    print(f"  log: head_lsn={fleet.log.head} "
          f"committed_version={fleet.db_version}  "
          f"results bit-identical to single-engine baseline: OK")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "join"), default="lm")
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--devices", type=int, default=1,
                    help="join mode: serve through the engine's sharded plan "
                         "on this many (virtual) host devices")
    ap.add_argument("--replicas", type=int, default=1,
                    help="join mode: serve through a replicated fleet of "
                         "this many engine replicas (DESIGN.md §12)")
    ap.add_argument("--requests", type=int, default=256,
                    help="join mode: number of requests in the demo stream")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="join mode: flush when this many requests are queued")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="join mode: flush when the oldest pending request "
                         "has waited this long")
    ap.add_argument("--updates", type=int, default=0,
                    help="join mode: interleave this many shape-preserving "
                         "update requests into the demo stream")
    ap.add_argument("--no-crash", action="store_true",
                    help="fleet mode: skip the injected mid-stream replica "
                         "crash")
    args = ap.parse_args()
    config.enable_compile_cache()
    if args.mode == "join":
        if args.replicas > 1:
            _fleet_demo(args.requests, args.replicas,
                        max_batch=args.max_batch,
                        max_wait_ms=args.max_wait_ms, updates=args.updates,
                        crash=not args.no_crash)
        else:
            _join_demo(args.requests, devices=args.devices,
                       max_batch=args.max_batch,
                       max_wait_ms=args.max_wait_ms, updates=args.updates)
        return
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=list(rng.integers(1, 200, rng.integers(4, 12))),
                    max_new=args.max_new) for _ in range(args.batch)]
    t0 = time.time()
    done = serve_batch(args.arch, reqs)
    dt = time.time() - t0
    ntok = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)} requests, {ntok} tokens in {dt:.2f}s "
          f"({ntok/dt:.1f} tok/s batched)")
    for i, r in enumerate(done):
        print(f"  req{i}: prompt[:4]={r.prompt[:4]} -> out[:8]={r.out[:8]}")


if __name__ == "__main__":
    main()

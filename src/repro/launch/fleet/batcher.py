"""The micro-batching request loop over ``QueryEngine.sample_batch``
(DESIGN.md §10) — the per-engine serving core, shared by the single-engine
serve loop and every fleet replica (DESIGN.md §12).

Moved here from ``launch/serve.py`` when the fleet library landed;
``launch.serve`` re-exports these names, so existing imports keep working.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

__all__ = ["JoinSampleRequest", "UpdateRequest", "MicroBatcher",
           "serve_join_samples"]


@dataclasses.dataclass
class JoinSampleRequest:
    """One tenant request: draw an independent Poisson sample of ``query``."""

    query: "JoinQuery"
    seed: int = 0
    count: Optional[int] = None       # filled by the service
    overflow: Optional[bool] = None   # filled by the service
    latency_s: Optional[float] = None  # enqueue -> results routed back
    enqueued_s: Optional[float] = None  # set by MicroBatcher.submit
    db_version: Optional[int] = None  # snapshot version the draw was served from
    rows: Optional[Dict[str, np.ndarray]] = None  # collect_rows=True only


@dataclasses.dataclass
class UpdateRequest:
    """One tenant update: advance the engine's snapshot by ``delta`` (a
    ``core.delta.DeltaBatch``). Serialized against draws by the micro-batch
    loop (DESIGN.md §11): draws enqueued before the update are flushed
    against the pre-delta snapshot first, so no in-flight batch ever mixes
    versions."""

    delta: object
    applied_version: Optional[int] = None  # post-apply db version
    latency_s: Optional[float] = None
    enqueued_s: Optional[float] = None


class MicroBatcher:
    """Micro-batching front end over ``QueryEngine.sample_batch``
    (DESIGN.md §10).

    Requests accumulate in an arrival-ordered queue and are flushed as
    batched dispatches when either trigger fires:

      * **size** — the queue reaches ``max_batch`` requests;
      * **deadline** — the oldest pending request has waited
        ``max_wait_ms`` (checked by ``poll()``, which the serving loop
        calls between arrivals).

    A flush groups pending requests by query fingerprint and issues ONE
    ``sample_batch`` dispatch per distinct shape — mixed-tenant queues
    share the engine's plan cache (one plan per shape, reused across
    flushes), and per-request results are routed back by lane index.
    ``clock`` is injectable so deadline behavior is unit-testable
    (``tests/test_serve_batcher.py``).

    ``UpdateRequest``s interleave with draws (DESIGN.md §11): an update
    acts as a barrier — pending draws flush first (reading the pre-delta
    snapshot), then the delta is applied via ``engine.apply_delta`` (warm
    cache entries upgrade in place, so the next flush pays no rebuild),
    and draws submitted afterwards read the new version. Every completed
    draw records the ``db_version`` it was served from.

    ``collect_rows=True`` additionally copies each draw's valid sample
    rows (the first ``count`` lanes, host-side numpy) onto
    ``JoinSampleRequest.rows`` — the fleet's determinism harness compares
    these bit-for-bit against the single-engine baseline (DESIGN.md §12).
    Off by default: it forces a device->host transfer per flush.
    """

    def __init__(self, engine, *, max_batch: int = 64,
                 max_wait_ms: float = 2.0, mesh=None, axes=None,
                 clock=time.perf_counter, collect_rows: bool = False):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.engine = engine
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self.mesh = mesh
        self.axes = axes
        self.clock = clock
        self.collect_rows = collect_rows
        self.pending: List[JoinSampleRequest] = []
        self.flushes = 0
        self.dispatches = 0
        self.served = 0
        self.updates_applied = 0

    def submit(self, req) -> List:
        """Enqueue one request; returns completed requests (non-empty only
        when this arrival triggered work: a full batch for draws, or the
        flush-then-apply barrier for updates)."""
        req.enqueued_s = self.clock()
        if isinstance(req, UpdateRequest):
            return self._apply_update(req)
        self.pending.append(req)
        if len(self.pending) >= self.max_batch:
            return self.flush()
        return []

    def _apply_update(self, req: UpdateRequest) -> List:
        """The update barrier: drain pending draws on the current snapshot,
        then advance it. In-flight batches therefore always read ONE
        consistent version; later draws read the next."""
        done = self.flush()
        self.engine.apply_delta(req.delta)
        req.applied_version = self.engine.db.version
        req.latency_s = self.clock() - req.enqueued_s
        self.updates_applied += 1
        return done + [req]

    def poll(self) -> List[JoinSampleRequest]:
        """Deadline check: flush iff the oldest pending request has waited
        at least ``max_wait_ms``. Call between arrivals / when idle."""
        if self.pending and \
                (self.clock() - self.pending[0].enqueued_s) * 1e3 >= self.max_wait_ms:
            return self.flush()
        return []

    def flush(self) -> List[JoinSampleRequest]:
        """Dispatch everything pending now (one batched draw per distinct
        query fingerprint) and route results back to their requests."""
        from repro.engine import query_fingerprint

        batch, self.pending = self.pending, []
        if not batch:
            return []
        groups: Dict[str, List[JoinSampleRequest]] = {}
        for r in batch:
            groups.setdefault(query_fingerprint(r.query), []).append(r)
        version = getattr(self.engine.db, "version", 0)
        with obs.span("batcher_flush", flush=self.flushes + 1,
                      lanes=len(batch), shapes=len(groups)):
            for reqs in groups.values():
                self._dispatch(reqs, version)
        self.flushes += 1
        self.served += len(batch)
        return batch

    def _dispatch(self, reqs: List[JoinSampleRequest], version: int):
        """One batched draw for requests of one query shape, its results
        routed back onto the requests."""
        with obs.span("batcher_keys"):
            keys = jnp.stack([jax.random.key(r.seed) for r in reqs])
        with obs.span("batcher_dispatch"):
            smp = self.engine.sample_batch(reqs[0].query, keys,
                                           mesh=self.mesh, axes=self.axes)
        with obs.span("batcher_wait"):
            jax.block_until_ready(smp.count)
        done_t = self.clock()
        with obs.span("batcher_copy_out"):
            counts = np.asarray(smp.count)
            overflow = np.asarray(smp.overflow)
            cols = ({c: np.asarray(v) for c, v in smp.columns.items()}
                    if self.collect_rows else None)
            for lane, r in enumerate(reqs):
                r.count = int(counts[lane])
                r.overflow = bool(overflow[lane])
                r.latency_s = done_t - r.enqueued_s
                r.db_version = version
                if cols is not None:
                    r.rows = {c: v[lane, : r.count].copy()
                              for c, v in cols.items()}
        self.dispatches += 1


def serve_join_samples(engine, requests: List, mesh=None,
                       max_batch: int = 64, max_wait_ms: float = 2.0,
                       collect_rows: bool = False) -> List:
    """Serve a request list through the micro-batcher (closed loop: submit
    everything, then drain). The list may interleave ``JoinSampleRequest``
    draws with ``UpdateRequest`` deltas; updates barrier the stream in
    arrival order (DESIGN.md §11). Kept as the library entry point the demo
    and tests share; results are routed back onto the request objects.

    This is also the fleet's single-engine *baseline*: ``Fleet`` serving
    the same stream must reproduce these results bit-for-bit per
    (seed, version) (DESIGN.md §12)."""
    mb = MicroBatcher(engine, max_batch=max_batch, max_wait_ms=max_wait_ms,
                      mesh=mesh, collect_rows=collect_rows)
    done: List[JoinSampleRequest] = []
    for r in requests:
        done += mb.submit(r)
        done += mb.poll()
    done += mb.flush()  # drain the tail regardless of deadline
    return done

"""Spans, counters and the names of the program's device scopes
(DESIGN.md §16).

* ``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation``, so a
  profiler trace holds the span on the host's timeline, on the clock of
  the device's operations. While a ``record()`` is open in the current
  context, the span is also kept there with its parent, on
  ``time.perf_counter_ns``.
* ``count(name, n)`` adds to the open record's counters: each draw
  executor call counts ``draws``, ``draws_int32`` where EXPRACE runs in
  int32, ``draw_arrival_lanes``, and ``draw_bit_columns`` (the float64
  columns it gathers as bit patterns, times its draws).
* Device phases inside jitted code are named with ``jax.named_scope``
  (the names in ``SCOPES``). A scope changes only the op-name metadata of
  the compiled program, never what it computes; a ``TraceAnnotation``
  inside a jitted function would time its tracing, not its run.

Nothing turns this on or off. With no trace taken and no record open, a
span costs one TraceMe construction and one context-variable read.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import time
from typing import Dict, Iterator, List, Optional

import jax

__all__ = ["SPANS", "SCOPES", "Span", "Record", "span", "count", "record"]

# Host spans, parents before their children.
SPANS = (
    "shred_build",          # core/shred.build_shred
    "shred_keys",           #   pass 1: dense edge keys
    "shred_sort",           #   pass 2: stable sort by key, weight prefix
    "shred_link",           #   pass 3: link columns
    "shred_place",          #   host-to-device copies of index columns
    "shred_pack",           #   pack_index: the int32 arena
    "plan_bind",            # engine/plan.CompiledPlan._bind_shred
    "engine_sample",        # QueryEngine.poisson_sample
    "engine_sample_batch",  # QueryEngine.sample_batch
    "engine_full_join",     # QueryEngine.full_join
    "plan_draw",            # one draw executor call: route, cap, acap,
                            #   batch, narrow
    "batcher_flush",        # MicroBatcher.flush
    "batcher_keys",         #   stacking the requests' PRNG keys
    "batcher_dispatch",     #   the engine call
    "batcher_wait",         #   waiting for the device
    "batcher_copy_out",     #   results copied to the host and routed back
)

# Device scopes (``jax.named_scope``), outer phases before the stages
# inside them; ``search`` nests under whichever phase searched.
SCOPES = (
    "exprace",              # core/sampling.exprace_positions
    "exprace_arrivals",     #   arrival placement over the mass prefix
    "exprace_dedupe",       #   sort, unique cells, per-root hits
    "exprace_compact",      #   compaction of the unique cells
    "exprace_emit",         #   output slots
    "usr_locate",           # core/probe.get_rows: root and child locates
    "usr_gather",           # core/probe.gather_columns
    "usr_gather_bits",      #   its float64 columns, gathered as bit patterns
    "search",               # kernels/ops searches
)

@dataclasses.dataclass
class Span:
    """One closed (or still open) span of a record: ``parent`` is the
    index of the enclosing span in ``Record.spans``, ``None`` at the top."""

    name: str
    parent: Optional[int]
    t0: int
    t1: Optional[int] = None

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9


@dataclasses.dataclass
class Record:
    """The spans and counters seen while a ``record()`` was open."""

    spans: List[Span] = dataclasses.field(default_factory=list)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)

    def seconds(self, name: str) -> float:
        """Total duration of the closed spans named ``name``."""
        return sum(s.seconds for s in self.spans
                   if s.name == name and s.t1 is not None)

    def self_seconds(self, name: str) -> float:
        """``seconds(name)`` less the part its direct children cover."""
        total = 0.0
        for i, s in enumerate(self.spans):
            if s.name != name or s.t1 is None:
                continue
            kids = sorted((c.t0, c.t1) for c in self.spans
                          if c.parent == i and c.t1 is not None)
            covered, end = 0, s.t0
            for a, b in kids:
                a = max(a, end)
                if b > a:
                    covered += b - a
                    end = b
            total += (s.t1 - s.t0 - covered) / 1e9
        return total


# (open record, index of the innermost open span or None), or None.
_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_active", default=None)


@contextlib.contextmanager
def span(name: str, **args) -> Iterator[None]:
    """A host span: a profiler annotation, and an entry of the open record."""
    active = _ACTIVE.get()
    with jax.profiler.TraceAnnotation(name, **args):
        if active is None:
            yield
            return
        rec, parent = active
        s = Span(name, parent, time.perf_counter_ns())
        rec.spans.append(s)
        token = _ACTIVE.set((rec, len(rec.spans) - 1))
        try:
            yield
        finally:
            _ACTIVE.reset(token)
            s.t1 = time.perf_counter_ns()


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the open record; no-op without one."""
    active = _ACTIVE.get()
    if active is not None:
        c = active[0].counters
        c[name] = c.get(name, 0) + int(n)


@contextlib.contextmanager
def record() -> Iterator[Record]:
    """Keep the spans and counters of this context until the block ends."""
    rec = Record()
    token = _ACTIVE.set((rec, None))
    try:
        yield rec
    finally:
        _ACTIVE.reset(token)

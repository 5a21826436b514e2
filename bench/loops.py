"""Load generators: a closed loop of one client and an open loop of
arrivals through a micro-batcher, the arrival processes and query mixes
that a traffic file names, and the window arithmetic of the end-to-end
metrics.

Neither loop imports the program. The closed loop is handed a ``call``
that starts one operation and a ``wait`` that blocks until its result is
on the device; the open loop is handed a batcher with ``submit``/``poll``/
``flush``/``pending``/``max_wait_ms``. Clocks and sleeps are injectable so
that the arithmetic is tested without a device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, List, Optional

import numpy as np

__all__ = ["Reservoir", "ClosedWindow", "closed_loop", "per_call_seconds",
           "arrival_schedule", "arrivals", "mix_sequence", "OpenWindow",
           "open_loop", "nearest_rank", "NoSpans"]


class NoSpans:
    """Span recorder that records nothing (runs with tracing off)."""

    @contextlib.contextmanager
    def span(self, name: str):
        yield


class Reservoir:
    """A uniform sample of at most ``k`` of the items offered, drawn from
    ``rng`` (algorithm R); holds no more than ``k`` items at a time."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k = k
        self.rng = rng
        self.seen = 0
        self.items: List[tuple] = []

    def offer(self, index: int, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append((index, item))
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            self.items[j] = (index, item)


def nearest_rank(xs, q: float) -> float:
    """Nearest-rank q-th percentile (q in [0, 1]): the ceil(q*N)-th
    smallest value."""
    ys = sorted(xs)
    if not ys:
        raise ValueError("percentile of an empty sequence")
    return ys[max(1, math.ceil(q * len(ys))) - 1]


@dataclasses.dataclass
class ClosedWindow:
    start: float                 # window start (clock seconds)
    end: float                   # window end (start + seconds)
    done: List[float]            # completion time of each call, in order
    results: List[object]        # small per-call results kept for checks

    @property
    def calls(self) -> int:
        return len(self.done)


def per_call_seconds(w: ClosedWindow) -> float:
    """Seconds per call: from the window's start to the completion of the
    last call started in it, over the number of calls. The last call may
    complete after the window closes; its whole time counts."""
    if not w.done:
        raise ValueError("no call completed")
    return (w.done[-1] - w.start) / len(w.done)


def closed_loop(call: Callable[[int], object], wait: Callable[[object], object],
                seconds: float, *, clock=time.perf_counter,
                spans=None, span: str = "call",
                keep: Optional[Callable[[int, object], object]] = None
                ) -> ClosedWindow:
    """One client calling back to back: start call ``i``, wait for its
    result, start the next, for as long as the window is open. ``keep``
    maps a finished result to what the window keeps of it."""
    spans = spans or NoSpans()
    start = clock()
    end = start + seconds
    done, kept = [], []
    i = 0
    while clock() < end:
        with spans.span(span):
            out = call(i)
            wait(out)
        done.append(clock())
        kept.append(keep(i, out) if keep is not None else None)
        i += 1
    return ClosedWindow(start, end, done, kept)


def arrival_schedule(rate_per_s: float, seconds: float, gap_seed: int,
                     seed: int) -> np.ndarray:
    """Arrival times (seconds from the window's start) of a Poisson
    process at ``rate_per_s``. The set of gaps is fixed by ``gap_seed``
    and scaled to fill ``seconds`` exactly; ``seed`` only orders them, so
    every run offers the same number of arrivals at the same gaps."""
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = np.random.default_rng(gap_seed).exponential(1.0 / rate_per_s, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def arrivals(spec: dict, seconds: float, seed: int) -> np.ndarray:
    """Arrival times (seconds from the window's start) of the process that
    a traffic file names, with ``rate_per_s`` requests a second on average
    and the fixed gaps of ``gap_seed`` (``seed`` only orders them):

    * ``"poisson"``: a Poisson process at ``rate_per_s``;
    * ``"on_off"``: bursts. Each ``period_s`` opens with an on phase of
      ``on_share`` of it, in which requests arrive as a Poisson process at
      ``rate_per_s / on_share``; none arrive in the off phase.
    """
    rate, gap_seed = float(spec["rate_per_s"]), int(spec["gap_seed"])
    kind = spec["arrivals"]
    if kind == "poisson":
        return arrival_schedule(rate, seconds, gap_seed, seed % (1 << 32))
    if kind == "on_off":
        period, share = float(spec["period_s"]), float(spec["on_share"])
        if not 0.0 < share <= 1.0:
            raise ValueError(f"on_share {share} is not in (0, 1]")
        on = arrival_schedule(rate / share, seconds * share, gap_seed,
                              seed % (1 << 32))
        k = np.floor(on / (period * share))
        return k * period + (on - k * period * share)
    raise ValueError(f"unknown arrival process {kind!r}")


def mix_sequence(weights, seed: int) -> Callable[[int], int]:
    """The query shape of each call or request: every cycle of
    ``sum(weights)`` holds shape ``k`` ``weights[k]`` times, in an order
    drawn from ``seed`` and the cycle, so every seed asks for the same
    work."""
    pattern = np.repeat(np.arange(len(weights)), weights)
    if pattern.size == 0:
        raise ValueError("a query mix needs a positive weight")
    cycles: dict = {}

    def shape(i: int) -> int:
        c, j = divmod(i, pattern.size)
        if c not in cycles:
            rng = np.random.default_rng([seed % (1 << 64), 11, c])
            cycles[c] = rng.permutation(pattern)
        return int(cycles[c][j])

    return shape


@dataclasses.dataclass
class OpenWindow:
    start: float
    scheduled: np.ndarray        # scheduled arrival of each request (abs)
    requests: List[object]       # request objects, in arrival order
    done: np.ndarray             # when each request's rows were routed back
    flush_start: np.ndarray      # when the flush that served it began
    submitted: np.ndarray        # when the loop handed it to the batcher
    drained: float               # when the last request completed

    def latencies(self) -> np.ndarray:
        """Seconds from scheduled arrival to rows routed back."""
        return self.done - self.scheduled

    def queue_waits(self) -> np.ndarray:
        """Seconds from scheduled arrival to the start of its flush."""
        return self.flush_start - self.scheduled

    def unanswered(self) -> int:
        return int(np.isnan(self.done).sum())


def open_loop(schedule: np.ndarray, make_request: Callable[[int], object],
              batcher, *, clock=time.perf_counter, sleep=time.sleep,
              spans=None, drain_s: float = 60.0) -> OpenWindow:
    """Offer requests at their scheduled times, whatever the batcher is
    doing: a request due while a flush runs is submitted as soon as the
    loop regains control, and its latency still counts from the schedule.
    Between arrivals the loop polls the batcher's deadline and sleeps
    until the next arrival or deadline, and after the last arrival until
    nothing is pending (flushing outright ``drain_s`` past it).

    The batcher records, on each request it flushes, ``flush_start_s``
    (the clock when that flush began)."""
    spans = spans or NoSpans()
    n = len(schedule)
    reqs = [make_request(i) for i in range(n)]
    index = {id(r): i for i, r in enumerate(reqs)}
    done = np.full(n, np.nan)
    flush_start = np.full(n, np.nan)
    submitted = np.full(n, np.nan)
    start = clock()
    sched = start + np.asarray(schedule, float)

    def route(finished):
        t = clock()
        for r in finished:
            i = index.get(id(r))
            if i is None:
                continue
            done[i] = t
            flush_start[i] = getattr(r, "flush_start_s", t)

    i = 0
    give_up = sched[-1] + drain_s if n else start
    while i < n or batcher.pending:
        while i < n and sched[i] <= clock():
            submitted[i] = clock()
            route(batcher.submit(reqs[i]))
            i += 1
        route(batcher.poll())
        if i >= n and batcher.pending and clock() > give_up:
            route(batcher.flush())
        if i >= n and not batcher.pending:
            break
        wake = sched[i] if i < n else give_up + 1e-3
        if batcher.pending:
            oldest = batcher.pending[0].enqueued_s
            # a microsecond past the deadline, so that the poll sees it pass
            wake = min(wake, oldest + batcher.max_wait_ms / 1e3 + 1e-6)
        with spans.span("schedule"):
            delay = wake - clock()
            if delay > 0:
                sleep(delay)
    return OpenWindow(start, sched, reqs, done, flush_start, submitted,
                      float(np.nanmax(done)) if n and not np.isnan(done).all()
                      else clock())

"""The open loop: requests at scheduled times, latency from the schedule
(so a stall counts against every request behind it), and the arrival
schedule's fixed gaps."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np
import pytest

from bench import loops


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Req:
    def __init__(self, i):
        self.i = i
        self.enqueued_s = None


class FakeBatcher:
    """Flushes at ``max_batch`` or on the deadline; each flush takes
    ``cost`` seconds of the clock."""

    def __init__(self, clock, max_batch, max_wait_ms, cost):
        self.clock, self.max_batch = clock, max_batch
        self.max_wait_ms, self.cost = max_wait_ms, cost
        self.pending, self.flushes = [], 0

    def submit(self, r):
        r.enqueued_s = self.clock()
        self.pending.append(r)
        return self.flush() if len(self.pending) >= self.max_batch else []

    def poll(self):
        if self.pending and (self.clock() - self.pending[0].enqueued_s) * 1e3 \
                >= self.max_wait_ms:
            return self.flush()
        return []

    def flush(self):
        batch, self.pending = self.pending, []
        if not batch:
            return []
        for r in batch:
            r.flush_start_s = self.clock()
        self.clock.t += self.cost
        self.flushes += 1
        return batch


def test_latency_counts_from_the_schedule_through_a_stall():
    clock = Clock()
    mb = FakeBatcher(clock, max_batch=64, max_wait_ms=2.0, cost=0.5)
    sched = np.array([0.0, 0.1, 0.2, 0.3])
    w = loops.open_loop(sched, Req, mb, clock=clock, sleep=clock.sleep)
    lat = w.latencies()
    # request 0: due 0, deadline flush at 0.002, done 0.502. Requests 1-3
    # came due during that flush; they are submitted at 0.502 and flushed
    # together when the deadline passes at 0.504, done at 1.004.
    # (deadlines are checked a microsecond after they pass)
    assert lat[0] == pytest.approx(0.502, abs=1e-5)
    assert lat[1:] == pytest.approx([1.004 - 0.1, 1.004 - 0.2, 1.004 - 0.3],
                                    abs=1e-5)
    assert w.queue_waits()[1:] == pytest.approx([0.404, 0.304, 0.204],
                                                abs=1e-5)
    assert w.unanswered() == 0 and mb.flushes == 2
    # the generator was late by the stall, and that is reported
    assert (w.submitted - w.scheduled)[1] == pytest.approx(0.402, abs=1e-5)


def test_full_batches_flush_on_size():
    clock = Clock()
    mb = FakeBatcher(clock, max_batch=2, max_wait_ms=5.0, cost=0.01)
    w = loops.open_loop(np.array([0.0, 0.0, 0.0]), Req, mb, clock=clock,
                        sleep=clock.sleep)
    # two flush on size at 0; the third, submitted at 0.01, on its
    # deadline at 0.015
    assert w.latencies().tolist() == pytest.approx([0.01, 0.01, 0.025],
                                                   abs=1e-5)


def test_a_batcher_that_never_flushes_is_drained():
    clock = Clock()
    mb = FakeBatcher(clock, max_batch=8, max_wait_ms=1e12, cost=0.0)
    w = loops.open_loop(np.array([0.0, 1.0]), Req, mb, clock=clock,
                        sleep=clock.sleep, drain_s=5.0)
    assert w.unanswered() == 0
    assert clock.t == pytest.approx(6.001, abs=1e-5)


def test_arrival_schedule_has_the_same_gaps_in_another_order():
    a = loops.arrival_schedule(20.0, 10.0, gap_seed=7, seed=1)
    b = loops.arrival_schedule(20.0, 10.0, gap_seed=7, seed=2)
    assert len(a) == len(b) == 200
    assert a[0] == 0.0 and a[-1] < 10.0
    ga, gb = np.diff(np.append(a, 10.0)), np.diff(np.append(b, 10.0))
    assert not np.array_equal(ga, gb)
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert ga.sum() == pytest.approx(10.0)


def test_poisson_arrivals_are_the_fixed_gap_schedule():
    spec = {"arrivals": "poisson", "rate_per_s": 20.0, "gap_seed": 7}
    assert np.array_equal(loops.arrivals(spec, 10.0, 3),
                          loops.arrival_schedule(20.0, 10.0, 7, 3))


def test_on_off_bursts_arrive_only_in_on_phases():
    spec = {"arrivals": "on_off", "rate_per_s": 10.0, "gap_seed": 5,
            "period_s": 2.0, "on_share": 0.2}
    a = loops.arrivals(spec, 30.0, 1)
    b = loops.arrivals(spec, 30.0, 2**31 + 9)
    # the mean rate over the window, in bursts at five times it
    assert len(a) == len(b) == 300
    assert np.all(np.diff(a) >= 0) and a[-1] < 30.0
    assert np.all(np.mod(a, 2.0) < 0.4 + 1e-9)
    per_burst = np.bincount((a // 2.0).astype(int), minlength=15)
    assert per_burst.sum() == 300 and per_burst.min() > 0
    assert not np.array_equal(a, b)

    def on_time(t):  # the schedule with its off phases taken out
        k = np.floor(t / 2.0)
        return k * 0.4 + (t - k * 2.0)

    ga, gb = np.diff(np.append(on_time(a), 6.0)), np.diff(np.append(
        on_time(b), 6.0))
    assert np.allclose(np.sort(ga), np.sort(gb))


def test_unknown_arrival_process_fails():
    with pytest.raises(ValueError, match="arrival process"):
        loops.arrivals({"arrivals": "gamma", "rate_per_s": 1.0,
                        "gap_seed": 1}, 10.0, 1)
    with pytest.raises(ValueError, match="on_share"):
        loops.arrivals({"arrivals": "on_off", "rate_per_s": 1.0,
                        "gap_seed": 1, "period_s": 1.0, "on_share": 0.0},
                       10.0, 1)

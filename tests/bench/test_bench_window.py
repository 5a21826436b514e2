"""The closed loop's window arithmetic (draw_s, full_join_s) and the
reservoir that picks the answers to check."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np
import pytest

from bench import loops


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_per_call_seconds_counts_the_call_in_flight_at_the_close():
    clock = Clock()

    def call(i):
        return i

    def wait(_):
        clock.t += 4.0  # every call takes 4 s

    w = loops.closed_loop(call, wait, 10.0, clock=clock)
    # calls start at 100, 104, 108 (< 110); the third ends at 112
    assert w.calls == 3
    assert w.done == [104.0, 108.0, 112.0]
    assert loops.per_call_seconds(w) == pytest.approx(12.0 / 3)


def test_one_call_longer_than_the_window():
    clock = Clock()

    def wait(_):
        clock.t += 25.0

    w = loops.closed_loop(lambda i: i, wait, 10.0, clock=clock)
    assert w.calls == 1
    assert loops.per_call_seconds(w) == pytest.approx(25.0)


def test_keep_records_per_call_results():
    clock = Clock()

    def wait(_):
        clock.t += 3.0

    w = loops.closed_loop(lambda i: i * 10, wait, 7.0, clock=clock,
                          keep=lambda i, out: out + 1)
    assert w.results == [1, 11, 21]


def test_reservoir_is_uniform_and_bounded():
    hits = np.zeros(20)
    for s in range(2000):
        r = loops.Reservoir(3, np.random.default_rng(s))
        for i in range(20):
            r.offer(i, object())
        assert len(r.items) == 3
        for i, _ in r.items:
            hits[i] += 1
    # each of 20 items is kept with probability 3/20
    assert np.all(np.abs(hits / 2000 - 0.15) < 0.04)


def test_nearest_rank():
    assert loops.nearest_rank([4, 1, 3, 2], 0.5) == 2
    assert loops.nearest_rank(list(range(1, 101)), 0.95) == 95
    with pytest.raises(ValueError):
        loops.nearest_rank([], 0.5)


def test_mix_sequence_holds_each_shape_by_its_weight_in_every_cycle():
    a = loops.mix_sequence([3, 1], 2**40 + 1)
    b = loops.mix_sequence([3, 1], 2)
    sa, sb = [a(i) for i in range(400)], [b(i) for i in range(400)]
    for c in range(100):
        assert sorted(sa[4 * c:4 * c + 4]) == [0, 0, 0, 1]
    assert sa != sb
    assert [a(i) for i in range(400)] == sa  # the same seed, the same mix
    one = loops.mix_sequence([1], 5)
    assert {one(i) for i in range(10)} == {0}
    with pytest.raises(ValueError):
        loops.mix_sequence([0, 0], 1)

"""The admission-and-rate rule of `window.py`, on synthetic logs."""

import pytest

from benchmark.window import Window


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def lock_step(seconds, round_s, clients=4, work=10, clock=None):
    """Closed-loop clients that all finish together every ``round_s``."""
    clock = clock or Clock()
    w = Window(seconds, clock=clock)
    live = [c for c in range(clients) if w.submit(c)]
    while live:
        clock.now += round_s
        for c in live:
            w.complete(c, work)
        live = [c for c in live if w.submit(c)]
    return w


def test_a_round_that_would_overrun_is_not_started():
    w = lock_step(seconds=45.0, round_s=12.3)
    assert len(w.completed) == 3 * 4          # a 4th would end at 49.2
    assert w.elapsed == pytest.approx(36.9)
    assert w.rate() == pytest.approx(3 * 40 / 36.9)


@pytest.mark.parametrize("seconds", [37.0, 40.0, 44.0, 48.0, 49.1])
def test_lock_step_rate_does_not_move_with_seconds(seconds):
    """--seconds may move by less than a round: whole rounds over their
    own duration give the same rate."""
    assert lock_step(seconds, 12.3).rate() == pytest.approx(40 / 12.3)


def test_a_slower_parent_still_counts_whole_rounds():
    fast, slow = lock_step(44.0, 12.3), lock_step(44.0, 13.5)
    assert len(fast.completed) == len(slow.completed) == 12
    assert slow.rate() == pytest.approx(fast.rate() * 12.3 / 13.5)


def test_a_request_in_flight_at_the_end_is_waited_for_and_counted():
    clock = Clock()
    w = Window(10.0, clock=clock)
    assert w.submit("a") and w.submit("b")
    clock.now += 4.0
    w.complete("a", 5)
    assert w.submit("a")                 # 4 + 4 <= 10
    clock.now += 4.0
    w.complete("a", 5)
    assert not w.submit("a")             # 8 + 4 > 10
    assert w.in_flight == 1              # b, never completed yet
    clock.now += 5.0                     # b ends at 13, after the window
    w.complete("b", 7)
    assert w.elapsed == pytest.approx(13.0)
    assert w.rate() == pytest.approx(17 / 13.0)


def test_before_its_first_completion_a_lane_is_always_admitted():
    clock = Clock()
    w = Window(1.0, clock=clock)
    assert w.submit(0)
    clock.now += 30.0                    # far longer than the window
    w.complete(0, 3)
    assert not w.submit(0)
    assert w.rate() == pytest.approx(0.1)


def test_training_steps_use_the_slowest_step_so_far():
    clock = Clock()
    w = Window(1.0, clock=clock)
    took = [0.3, 0.25, 0.25, 0.25]
    n = 0
    while w.submit():
        clock.now += took[n]
        w.complete(work=8)
        n += 1
    assert n == 3                # at 0.8 the slowest (0.3) would overrun
    assert w.rate() == pytest.approx(24 / 0.8)


def test_nothing_completed_gives_no_rate_and_failures_no_work():
    w = Window(1.0, clock=Clock())
    with pytest.raises(ValueError):
        w.rate()
    w.submit(0)
    w.abandon(0)
    assert w.in_flight == 0 and w.work == 0

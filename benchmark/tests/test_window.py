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


# -- a group is admitted whole or not at all -----------------------------------

CLIENTS, WORK, ROUND_S = 6, 10, 12.3
# the batcher hands a group's answers over one by one: client c holds its
# answer, and submits again, c milliseconds after client 0
SPREAD_S = (CLIENTS - 1) * 1e-3


def staggered(seconds, grouped):
    """Lock-step rounds of ``ROUND_S`` whose answers arrive a
    millisecond apart; each client submits again on its own answer, with
    its round number as the group or with none.  Returns the window and,
    by round, the clients admitted."""
    clock = Clock()
    w = Window(seconds, clock=clock)
    t_round, r, admitted = clock.now, 0, {}
    live = list(range(CLIENTS))
    while live:
        let_in = []
        for c in live:
            clock.now = t_round + c * 1e-3
            if r:
                w.complete(c, WORK)
            if w.submit(c, group=r if grouped else None):
                let_in.append(c)
        admitted[r] = let_in
        live, r, t_round = let_in, r + 1, t_round + ROUND_S
    return w, admitted


# the line walked across the fourth round's end, a millisecond a case
# (and half a one, to keep off floating-point ties): client c asks for
# round 3 at 3 x ROUND_S + c ms, and its answer is due ROUND_S later
@pytest.mark.parametrize("ms", range(-4, 12))
def test_a_group_is_admitted_whole_or_not_at_all(ms):
    seconds = 4 * ROUND_S + (ms + 0.5) * 1e-3
    w, admitted = staggered(seconds, grouped=True)
    assert all(len(a) in (0, CLIENTS) for a in admitted.values()), admitted
    rounds = 4 if ms >= 0 else 3         # the first asker's answer for all
    assert [len(a) for a in admitted.values()] == \
        [CLIENTS] * rounds + [0]
    assert len(w.completed) == rounds * CLIENTS
    assert w.elapsed == pytest.approx(rounds * ROUND_S + SPREAD_S)
    # whole rounds over their own duration: the lock-step rate, but for
    # the last answers' way out
    assert w.rate() == pytest.approx(CLIENTS * WORK / ROUND_S, rel=2e-4)
    assert w.rate() == pytest.approx(
        rounds * CLIENTS * WORK / (rounds * ROUND_S + SPREAD_S))


@pytest.mark.parametrize("ms", range(-4, 12))
def test_without_a_group_each_lane_is_decided_alone(ms):
    """Today's rule, kept for the lanes that hand no group: the line
    inside a round's resubmissions lets some clients in."""
    seconds = 4 * ROUND_S + (ms + 0.5) * 1e-3
    w, admitted = staggered(seconds, grouped=False)
    last = admitted[3]
    assert last == [c for c in range(CLIENTS) if c <= ms]
    if 0 <= ms < CLIENTS - 1:
        assert 0 < len(last) < CLIENTS       # the group of a few


def test_a_group_is_decided_once_on_the_slowest_piece_of_any_lane():
    clock = Clock()
    w = Window(10.0, clock=clock)
    assert w.submit("a", group=0) and w.submit("b", group=0)
    clock.now += 3.0
    w.complete("a", 1)
    clock.now += 1.0
    w.complete("b", 1)                   # b's piece took 4
    clock.now = 106.5
    assert not w.submit("a", group=1)    # 6.5 + 4 > 10, though a's took 3
    assert not w.submit("b", group=1)
    w2 = Window(10.0, clock=clock)
    clock.now = 100.0
    assert w2.submit("a", group=0) and w2.submit("b", group=0)
    clock.now = 104.0
    w2.complete("a", 1)
    w2.complete("b", 1)
    clock.now = 105.9
    assert w2.submit("a", group=1)       # 5.9 + 4 <= 10: decided
    clock.now = 106.5
    assert w2.submit("b", group=1)       # later, and still the answer
    assert w2.in_flight == 2

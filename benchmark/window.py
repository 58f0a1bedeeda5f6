"""The one admission-and-rate rule, used by every traffic kind.

Work is admitted only while it is predicted to finish inside the window,
and the rate is the work completed over the time from the first
submission to the last completion.  No partial work is ever counted and
no idle tail is ever divided by.

A lane is whatever issues one piece of work at a time: the training loop
is one lane, each closed-loop client is a lane of its own.  The
prediction for a lane is its own slowest completed piece so far; before
its first completion a lane is always admitted.  Work admitted is waited
for and counted even when it ends after ``seconds``: the elapsed time
then runs to its completion.

Lanes that issue their pieces together hand ``submit`` a ``group`` (the
closed loop: its round number).  A group is admitted whole or not at
all: the decision is made once, when the first lane asks for that group,
on the slowest completed piece of ANY lane, and every lane that asks for
the same group later is given that answer.  Decided lane by lane, a
round that ends within milliseconds of the line is let in for some
clients and not for others, and what the few are served as (a smaller
group, another compiled shape) is then drawn by where the line fell in
the round, not by the program under test.  The rule is no barrier: a
lane still submits when its own piece has ended, and it says nothing
about how the scheduler batches what it is handed.

Under a scheduler that runs lock-step rounds this gives whole rounds over
their own duration, whatever ``seconds`` is.  Under a scheduler that
admits work at every step it is the same arithmetic with a short drain at
the end, in which fewer lanes are busy: a small bias against such a
scheduler, noted in PERF.md.
"""

import time


class Window:
    def __init__(self, seconds, clock=time.perf_counter):
        self.seconds = float(seconds)
        self.clock = clock
        self.t0 = None
        self.t_last = None
        self.work = 0
        self.completed = []         # (lane, started, finished, work)
        self._started = {}
        self._slowest = {}
        self._groups = {}           # group -> admitted, decided once

    def _fits(self, now, slowest):
        return slowest is None or now + slowest <= self.t0 + self.seconds

    def submit(self, lane=0, group=None):
        """True when the lane may start its next piece now; the first
        call opens the window.  With a ``group`` the answer is the one
        the group's first asker got."""
        now = self.clock()
        if self.t0 is None:
            self.t0 = now
        if group is None:
            admitted = self._fits(now, self._slowest.get(lane))
        else:
            admitted = self._groups.get(group)
            if admitted is None:
                admitted = self._groups[group] = self._fits(
                    now, max(self._slowest.values(), default=None))
        if not admitted:
            return False
        self._started[lane] = now
        return True

    def complete(self, lane=0, work=0):
        """The lane's piece has ended, worth ``work`` units."""
        now = self.clock()
        started = self._started.pop(lane)
        took = now - started
        self._slowest[lane] = max(self._slowest.get(lane, 0.0), took)
        self.completed.append((lane, started, now, work))
        self.work += work
        self.t_last = now
        return took

    def abandon(self, lane=0):
        """The lane's piece failed: it counts as no work."""
        self._started.pop(lane, None)

    @property
    def in_flight(self):
        return len(self._started)

    @property
    def elapsed(self):
        if self.t_last is None:
            return 0.0
        return self.t_last - self.t0

    def rate(self):
        if not self.completed or self.elapsed <= 0.0:
            raise ValueError("window: nothing completed, no rate")
        return self.work / self.elapsed

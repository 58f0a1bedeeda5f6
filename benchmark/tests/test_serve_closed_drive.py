"""`serve_closed.drive` and what `run_cell` does around it, with the
host's draw taken out: a fake batcher whose answers arrive together and
a fake clock that every submission, draw and wait advances; then the
real tiny engine on the CPU with one round's group forced late."""

import gc
import json
import time
from concurrent.futures import Future

import numpy as np
import pytest

from benchmark import run
from benchmark.cells import Cells
from benchmark.window import Window

from conftest import ROOT, TINY_GPT, TINY_SERVE, write_bench

N, VOCAB, SEED = 4, 128, 2 ** 31 + 11
TRAFFIC = {"clients": N, "prompt_lengths": [3, 5, 9, 16],
           "output_lengths": [2, 3, 5, 8]}
SUBMIT_S, DRAW_S, ROUND_S = 0.45e-3, 0.2e-3, 0.6


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


class FakeBatcher:
    """Answers everything it holds at once, as one group, when the
    driver waits with no limit; ``short`` (round -> how many) makes it
    close that round's first group early."""

    def __init__(self, clock, events, short=None):
        self.clock, self.events = clock, events
        self.queue, self.groups, self.short = [], 0, dict(short or {})

    def submit(self, prompt, new):
        fut = Future()
        self.queue.append((fut, prompt, new))
        self.events.append("s")
        self.clock.now += SUBMIT_S
        return fut

    def serve(self, round_now):
        take = self.short.pop(round_now, len(self.queue))
        group, self.queue = self.queue[:take], self.queue[take:]
        self.clock.now += ROUND_S
        self.groups += 1
        timings = {"bucket": [N, 8 * len(group)],
                   "prefill_us": self.clock.now * 1e6,
                   "collect_us": float(self.groups)}
        for fut, prompt, new in group:
            fut.set_result(dict(timings, tokens=np.zeros(new, np.int32)))


class Serving:
    @staticmethod
    def trace_count():
        return 0


@pytest.fixture
def kind():
    return Cells(ROOT).module("kinds", "serve_closed")


def drive(kind, monkeypatch, seconds, short=None):
    """`drive` on the fakes; returns (result, events, window).  The
    events are "s" a submission and "d" a prompt drawn, in order."""
    clock, events = Clock(), []
    batcher = FakeBatcher(clock, events, short)
    real = kind.prompt_ids

    def drawing(*args):
        events.append("d")
        clock.now += DRAW_S
        return real(*args)

    def wait(pending, timeout):
        if timeout is not None:         # nothing arrives: the batcher works
            clock.now += timeout
            return set()
        batcher.serve(max(r for _, r, _, _ in pending.values()))
        return {fut for fut in pending if fut.done()}

    plan = kind.schedule(TRAFFIC, SEED, 40)
    state = {"batcher": batcher, "plan": plan, "n": N, "vocab": VOCAB,
             "serving": Serving, "pinned": 0,
             "ready": {(c, 0): kind.planned_prompt(SEED, plan, VOCAB, c, 0)
                       for c in range(N)}}
    monkeypatch.setattr(kind, "prompt_ids", drawing)
    monkeypatch.setattr(kind, "_wait", wait)
    window = Window(seconds, clock=clock)
    result = kind.drive(state, window, {"seed": SEED, "log": print})
    return result, "".join(events), window


# one round on the fake clock: N submissions, the silence, N draws, the
# group served
PERIOD_S = N * SUBMIT_S + 0.02 + N * DRAW_S + ROUND_S


def test_no_prompt_is_drawn_inside_a_rounds_resubmissions(kind, monkeypatch):
    assert kind.QUIET_S == 0.02
    result, events, window = drive(kind, monkeypatch, 5 * PERIOD_S + 0.1)
    # every run of submissions is a whole round, every run of draws the
    # next round's prompts: none between a round's first and last submit
    assert events == ("s" * N + "d" * N) * 5
    assert result["watched"] == {"rounds_split": 0, "prompts_drawn_late": 0}
    assert result["faults"] == {"retraces_in_window": 0}
    assert result["attempted"] == len(result["records"]) == 5 * N
    assert result["failed"] == 0
    # each client was handed the prompt the seed gives it for that round
    for rec in result["records"]:
        want = np.random.default_rng(
            [SEED, 2, rec["client"], rec["round"]]).integers(
                0, VOCAB, len(rec["prompt"])).astype(np.int32)
        assert (rec["prompt"] == want).all()


def test_a_missing_prompt_is_drawn_late_and_counted(kind, monkeypatch):
    monkeypatch.setattr(kind, "QUIET_S", None)      # never a silence
    result, events, _ = drive(kind, monkeypatch, 3 * PERIOD_S)
    assert result["watched"]["prompts_drawn_late"] == \
        len(result["records"]) - N
    assert events.startswith("s" * N + "ds" * N)


# the line walked across the fifth round's resubmissions and past them,
# half a submission a case
@pytest.mark.parametrize("step", range(-3, 2 * N + 2))
def test_a_last_round_on_the_line_is_whole_or_not_at_all(
        kind, monkeypatch, step):
    # round 4's first client asks at 4 periods; its answer is due a
    # period (less nothing: the slowest piece so far) later
    seconds = 5 * PERIOD_S + (step + 0.5) * SUBMIT_S / 2
    result, _, window = drive(kind, monkeypatch, seconds)
    served = len(result["records"])
    assert served % N == 0 and served in (4 * N, 5 * N)
    by_round = {}
    for rec in result["records"]:
        by_round.setdefault(rec["round"], []).append(rec["client"])
    assert all(sorted(c) == list(range(N)) for c in by_round.values())
    assert result["watched"]["rounds_split"] == 0
    tokens = sum(TRAFFIC["output_lengths"])
    assert window.rate() == pytest.approx(tokens / PERIOD_S, rel=2e-3)


def test_a_group_closed_early_is_a_round_split(kind, monkeypatch):
    result, _, _ = drive(kind, monkeypatch, 3 * PERIOD_S, short={0: 1})
    # round 0 went out as 1 + 3; the three then ride a round behind
    assert result["watched"]["rounds_split"] >= 1
    whole, _, _ = drive(kind, monkeypatch, 3 * PERIOD_S)
    assert whole["watched"]["rounds_split"] == 0


# -- through run_cell, the real tiny engine on the CPU -------------------------

def _cells(tmp_path):
    # one long prompt: a group without it takes a smaller prefill
    # bucket, which set-up has not warmed
    traffic = dict(TINY_SERVE, prompt_lengths=[16, 3, 3, 3],
                   batcher={"max_delay_ms": 100.0})
    write_bench(str(tmp_path), {"tiny-gpt": TINY_GPT},
                {"tiny-serve": traffic},
                [{"name": "serve", "config": "tiny-gpt",
                  "traffic": "tiny-serve", "chips": 1, "why": "t"}])
    return Cells(str(tmp_path))


def _two_rounds(cells, monkeypatch, late=None):
    """The cell's kind, its plan cut to two rounds; ``late`` (0-based
    submission of round 1) is held back past the batcher's delay."""
    kind = cells.cell("serve")["kind"]
    real = kind.setup

    def setup(ctx):
        state = real(ctx)
        state["plan"] = state["plan"][:2]
        submit, calls = state["batcher"].submit, []

        def held_back(prompt, new):
            if late is not None and len(calls) == N + late:
                time.sleep(0.5)
            calls.append(len(prompt))
            return submit(prompt, new)

        state["batcher"].submit = held_back
        return state

    monkeypatch.setattr(kind, "setup", setup)
    return kind


def test_a_group_forced_late_shows_in_checks(tmp_path, monkeypatch, quiet):
    cells = _cells(tmp_path)
    _two_rounds(cells, monkeypatch, late=1)
    out = run.run_cell(cells, "serve", 5, 30.0, False, platform="cpu",
                       log=quiet[1])
    checks = out["checks"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is False and out["failed"] == 0, quiet[0]
    assert checks["rounds_split"] == [1, None]
    assert checks["compiled_in_window"][0] >= 1
    assert checks["compiled_in_window"][1] == 0
    assert checks["retraces_in_window"][0] >= 1
    assert checks["requests_failed"] == [0, 0]
    assert checks["arrays_off_device"] == [0, 0]
    value, limit = checks["served_token_logit_gap_max"]
    assert value <= limit == 0.05        # the tokens are sound all the same
    assert any("fault: compiled_in_window" in ln for ln in quiet[0])


def test_a_sound_run_reads_every_check_at_its_limit(tmp_path, monkeypatch,
                                                    quiet):
    cells = _cells(tmp_path)
    _two_rounds(cells, monkeypatch)
    out = run.run_cell(cells, "serve", 5, 30.0, False, platform="cpu",
                       log=quiet[1])
    assert out["correct"] is True, quiet[0]
    assert out["attempted"] == 2 * N
    checks = dict(out["checks"])
    assert checks.pop("prompts_drawn_late")[1] is None
    assert checks.pop("served_token_logit_gap_max")[1] == 0.05
    assert checks == {"retraces_in_window": [0, 0],
                      "compiled_in_window": [0, 0],
                      "arrays_off_device": [0, 0],
                      "requests_failed": [0, 0],
                      "rounds_split": [0, None]}
    traced = run.run_cell(cells, "serve", 5, 30.0, True, platform="cpu",
                          log=quiet[1])
    assert list(traced)[-2:] == ["breakdown", "checks"]


def test_a_failed_request_shows_in_checks(tmp_path, monkeypatch, quiet):
    cells = _cells(tmp_path)
    kind = _two_rounds(cells, monkeypatch)
    real = kind.setup

    def setup(ctx):
        state = real(ctx)
        submit, calls = state["batcher"].submit, []

        def refusing(prompt, new):
            calls.append(1)
            if len(calls) == N + 2:
                fut = Future()
                fut.set_exception(RuntimeError("refused"))
                return fut
            return submit(prompt, new)

        state["batcher"].submit = refusing
        return state

    monkeypatch.setattr(kind, "setup", setup)
    out = run.run_cell(cells, "serve", 5, 30.0, False, platform="cpu",
                       log=quiet[1])
    assert out["correct"] is False and out["failed"] == 1
    assert out["checks"]["requests_failed"] == [1, 0]


def test_a_full_collector_pass_runs_before_the_window_opens(
        tmp_path, monkeypatch, quiet):
    cells = _cells(tmp_path)
    kind = _two_rounds(cells, monkeypatch)
    seen = []

    def on_gc(phase, info):
        if phase == "stop":
            seen.append(("gc", info["generation"]))

    real_setup = kind.setup

    def setup(ctx):
        state = real_setup(ctx)
        seen.append(("set-up done", None))
        return state

    class Watched(Window):
        def submit(self, lane=0, group=None):
            seen.append(("submit", lane))
            return super().submit(lane, group)

    monkeypatch.setattr(kind, "setup", setup)
    monkeypatch.setattr(run, "Window", Watched)
    gc.callbacks.append(on_gc)
    try:
        out = run.run_cell(cells, "serve", 5, 30.0, False, platform="cpu",
                           log=quiet[1])
    finally:
        gc.callbacks.remove(on_gc)
    assert out["correct"] is True, quiet[0]
    done = seen.index(("set-up done", None))
    first = next(i for i, e in enumerate(seen) if e[0] == "submit")
    assert ("gc", 2) in seen[done:first], seen[done:first]


def test_main_says_the_checks_last_on_both_streams(monkeypatch, capsys):
    canned = {"correct": False, "attempted": 32, "failed": 0, "metrics": {},
              "device": {"platform": "tpu"},
              "checks": {"served_token_logit_gap_max": [0.004, 0.04],
                         "compiled_in_window": [1, 0],
                         "rounds_split": [1, None]}}
    monkeypatch.setattr(run, "run_cell", lambda *a, **kw: dict(canned))
    assert run.main(["--workload", "gpt2m-serve-chat", "--seed",
                     str(2 ** 31 + 3), "--seconds", "1"]) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert line == canned and list(line)[-1] == "checks"
    assert err.strip().splitlines()[-3:] == [
        "[bench] check served_token_logit_gap_max: 0.004 (limit 0.04)",
        "[bench] check compiled_in_window: 1 (limit 0)",
        "[bench] check rounds_split: 1 (limit None)"]

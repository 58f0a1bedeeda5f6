"""Low-latency serving tier (mxnet_tpu/serving/).

The three claims that make the tier production-shaped, each pinned
here: the request path never retraces after warmup (AOT bucketed
programs), a coalesced batch is bitwise equal to the same requests
served one-by-one (padding can never leak into real rows), and hot
reload swaps weights mid-stream with zero dropped requests (weights are
program arguments, not constants).
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint, serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import gpt
from mxnet_tpu.serving.replica import FrontDoor, ReplicaServer
from mxnet_tpu.test_utils import (UNEQUAL_ANSWERS, cpu_child_env,
                                  jaxpr_loops, serving_host_walk,
                                  serving_dead_rows_keep_their_cache,
                                  serving_unequal_answers)


def _model(seed=7, **kwargs):
    kwargs.setdefault("scan_layers", True)
    kwargs.setdefault("max_length", 16)
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gpt.gpt_tiny(**kwargs)
    net.initialize(init=mx.init.Xavier())
    net(mx.nd.array(np.random.randint(0, 128, (1, 4))
                    .astype(np.float32)))
    return net


def _prompts(n, rng, lo=2, hi=8):
    return [rng.randint(0, 128, rng.randint(lo, hi + 1)).tolist()
            for _ in range(n)]


# -- bitwise coalescing parity -------------------------------------------------

def test_coalesced_batch_bitwise_equals_one_by_one():
    net = _model()
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    rng = np.random.RandomState(3)
    prompts = _prompts(3, rng)
    grouped, timings = eng.serve_group(prompts, 5)
    solo = [eng.serve_group([p], 5)[0][0] for p in prompts]
    for i, (a, b) in enumerate(zip(solo, grouped)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    assert timings["bucket"] == [4, 8]
    assert 0 <= timings["padded_fraction"] < 1

    # ground truth: full recompute, the body that shares nothing with
    # the program
    for p, got in zip(prompts, grouped):
        seed = mx.nd.array(np.asarray([p], np.float32))
        ref = gpt.generate(net, seed, max_new_tokens=5).asnumpy()[0, len(p):]
        np.testing.assert_array_equal(ref.astype(np.int64),
                                      got.astype(np.int64))


def test_decode_attention_is_counted_and_the_tokens_stand():
    """The decode step attends through `ops/cache_attention.py`: on the
    CPU by its plain path over the whole window (share 0.0, every
    position of every row's window read), and a group's tokens are the
    full recompute's, as they were when the step held the contraction
    itself."""
    net = _model(max_length=32)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    rng = np.random.RandomState(11)
    prompts = _prompts(4, rng, lo=3, hi=12)
    outs, timings = eng.serve_group(prompts, [9, 4, 7, 2])
    for p, got, n in zip(prompts, outs, [9, 4, 7, 2]):
        seed = mx.nd.array(np.asarray([p], np.float32))
        ref = gpt.generate(net, seed, max_new_tokens=n).asnumpy()[0, len(p):]
        np.testing.assert_array_equal(ref.astype(np.int64),
                                      got.astype(np.int64))
    assert timings["decode_attn_kernel_share"] == 0.0
    assert timings["decode_attn_window_read_pct"] == 100.0
    assert dict(eng._program.cache_reads[1]) == {("xla", 32, 32): 1}
    # a prefill block attends inside the step: the op is not called
    assert not eng._program.cache_reads[16]
    # one token a request: no decode step ran, so nothing was read
    _, timings = eng.serve_group(prompts, 1)
    assert "decode_attn_window_read_pct" not in timings


@pytest.mark.parametrize("reads,lens,steps,left,want", [
    # two rows, three decode steps, blocks of 256 in a window of 1,024:
    # 29-31 positions are one block, 513-515 three
    ({("kernel", 1024, 256): 1}, [28, 512], 3, None, 100.0 * 4 / 8),
    # a row that crosses a block's edge at its second step
    ({("kernel", 1024, 128): 1}, [127], 2, None, 100.0 * (1 + 2) / 16),
    # the plain path reads the whole window whatever the row holds
    ({("xla", 64, 64): 1}, [1, 40], 5, None, 100.0),
    # MiMo-V2's two kinds of layer: two full layers through the kernel,
    # five rings of one block each
    ({("kernel", 2048, 512): 2, ("xla", 128, 128): 5}, [100, 600], 1, None,
     100.0 * (2 * (512 + 1024) + 5 * 2 * 128) / (2 * 2 * 2048 + 5 * 2 * 128)),
    # nothing is read past the window
    ({("kernel", 256, 128): 1}, [250], 4, None, 100.0),
    # three requests and a pad row, three steps: a row live in two of
    # them (one block each, and one block dead), a row that wanted one
    # token (three blocks today, one a step now), a row live throughout
    # (301-303 positions: two blocks), the pad row (one block a step)
    ({("kernel", 1024, 256): 1}, [28, 512, 300, 1], 3, [2, 0, 3, 0],
     100.0 * (3 + 3 + 3 * 2 + 3) / (4 * 3 * 4)),
    # every row live in every step: what it read before
    ({("kernel", 1024, 256): 1}, [28, 512], 3, [3, 3], 100.0 * 4 / 8),
    # a dead row on the plain path is still the whole window
    ({("xla", 64, 64): 1}, [1, 40], 5, [0, 5], 100.0)])
def test_window_read_pct_follows_lengths_steps_and_blocks(reads, lens, steps,
                                                          left, want):
    from mxnet_tpu.serving.engine import _window_read_pct

    got = _window_read_pct(
        reads, np.asarray(lens, np.int32), steps,
        None if left is None else np.asarray(left, np.int32))
    assert got == pytest.approx(want)


def test_per_request_max_new_tokens_truncates():
    eng = serving.ServingEngine(_model(), batch_buckets=(2,))
    rng = np.random.RandomState(5)
    prompts = _prompts(2, rng)
    outs, _ = eng.serve_group(prompts, [2, 6])
    assert len(outs[0]) == 2 and len(outs[1]) == 6
    # the short request's tokens are a prefix of its solo 6-token run
    full = eng.serve_group([prompts[0]], 6)[0][0]
    np.testing.assert_array_equal(outs[0], full[:2])


# -- rows that want no more token ----------------------------------------------

@pytest.fixture(scope="module")
def unequal():
    eng = serving.ServingEngine(_model(max_length=32), batch_buckets=(4,))
    return eng.warmup(), _prompts(4, np.random.RandomState(13), lo=3, hi=12)


@pytest.mark.parametrize("wants", UNEQUAL_ANSWERS, ids=str)
def test_a_row_that_wants_no_token_changes_nothing(unequal, wants):
    """The decode step is handed which rows still want a token and the
    others attend to nothing and write nothing into the cache: every
    request's tokens are what it gets alone and in a group of equal
    answers; nothing is traced or compiled for it, and the host reads
    what it read."""
    eng, prompts = unequal
    prompts = prompts[:len(wants)]
    pinned = (serving.trace_count(), serving.compile_count())
    serving_dead_rows_keep_their_cache(eng, prompts, [k > 1 for k in wants])
    d0 = serving.dispatch_count()
    timings, live = serving_unequal_answers(eng, prompts, wants)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    # the group, the group of equal answers, each request alone
    steps = max(wants)
    assert serving.dispatch_count() - d0 == 2 * steps + sum(wants)
    assert timings["decode_steps_fed_on_device"] == steps - 1
    assert timings["decode_readback_bytes_per_step"] == 4 * 4
    # the CPU's plain path reads a dead row's whole window too
    assert timings["decode_attn_window_read_pct"] == 100.0


def test_a_sampled_group_is_handed_the_same_mask(unequal):
    """With a temperature the host draws every row's token from each
    program's logits, a dead row's too (one draw a row keeps the
    generator's stream): the rows that still want a token get the
    tokens of the path with the host in every step and no mask."""
    eng, prompts = unequal
    wants = [2, 9, 5, 9]
    want, _ = serving_host_walk(eng, prompts, 9, temperature=0.7,
                                rng=np.random.default_rng(5))
    outs, timings = eng.serve_group(prompts, wants, temperature=0.7,
                                    rng=np.random.default_rng(5))
    for i, k in enumerate(wants):
        np.testing.assert_array_equal(outs[i], want[i, :k])
    assert timings["decode_steps_fed_on_device"] == 0
    assert timings["decode_row_steps_live"] == sum(k - 1 for k in wants)


@pytest.mark.parametrize("live", [None, [True] * 4,
                                  [False, True, False, True]], ids=str)
def test_the_step_without_live_is_the_step_of_every_row(unequal, live):
    """``program.step`` as its direct callers call it, with no ``live``,
    is the step with every row live, bit for bit; a row handed as dead
    leaves every other row's logits and cache rows as they were, writes
    nothing into its own cache rows in any layer (a live row's position
    is written in the last layer too), and its own logits stay
    finite."""
    import jax

    eng, prompts = unequal
    program, B = eng._program, 4
    step = jax.jit(program.step)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    toks = np.zeros((B, 16), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    w = eng._weights
    cache, _ = step(w, eng.init_cache(B), np.zeros(B, np.int32), lens - 1,
                    toks)
    one = np.asarray([[7], [8], [9], [10]], np.int32)
    zero = np.zeros(B, np.int32)
    (ck, cv), want = step(w, cache, lens, zero, one)
    (ck2, cv2), got = step(w, cache, lens, zero, one) if live is None \
        else step(w, cache, lens, zero, one, live=np.asarray(live))
    kept = np.asarray([True] * B if live is None else live)
    for a, b, was in ((ck, ck2, cache[0]), (cv, cv2, cache[1])):
        a, b, was = np.asarray(a), np.asarray(b), np.asarray(was)
        np.testing.assert_array_equal(a[:, kept], b[:, kept])
        np.testing.assert_array_equal(was[:, ~kept], b[:, ~kept])
        assert b[-1][np.arange(B), :, :, lens].any(axis=(1, 2))[kept].all()
    np.testing.assert_array_equal(np.asarray(got)[kept],
                                  np.asarray(want)[kept])
    assert np.isfinite(np.asarray(got)).all()
    if not kept.all():
        assert (np.asarray(got)[~kept] != np.asarray(want)[~kept]).any()


@pytest.mark.parametrize("path", ["rows", "kernel"])
def test_the_cache_write_says_whether_it_was_told_the_live_rows(monkeypatch,
                                                                path):
    """``decode_cache_write_live_share`` is in a group's timings and in
    each request's record, beside ``decode_cache_write_kernel_share``:
    of the decode program's row writes, those that went through the
    kernel and were handed ``live``.  0.0 on the CPU's rows path; with
    the kernel in the program (interpreted here, as on a TPU with no
    mesh) 1.0, and a group of unequal answers gets the rows path's
    tokens.  A step handed no ``live`` (the program's direct callers')
    tallies none."""
    import functools

    import jax

    from mxnet_tpu.ops import cache_write

    net = _model(max_length=32)
    prompts = _prompts(4, np.random.RandomState(13), lo=3, hi=12)
    wants = [2, 9, 5, 9]
    want, _ = serving.ServingEngine(net, batch_buckets=(4,)).serve_group(
        prompts, wants)
    if path == "kernel":
        monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
        monkeypatch.setattr(cache_write, "_write_kernel", functools.partial(
            cache_write._write_kernel, interpret=True))
    share = float(path == "kernel")
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    outs, timings = eng.serve_group(prompts, wants)
    for got, w in zip(outs, want):
        np.testing.assert_array_equal(got, w)
    assert timings["decode_cache_write_kernel_share"] == share
    assert timings["decode_cache_write_live_share"] == share
    told = {"kernel": 8, "kernel_live": 8} if share else {"rows": 8}
    assert dict(eng._program.cache_writes[1]) == told
    serving_dead_rows_keep_their_cache(eng, prompts, [k > 1 for k in wants])
    telemetry.reset()
    batcher = serving.ContinuousBatcher(eng, max_delay_ms=150, max_batch=4)
    try:
        futs = [batcher.submit(p, k) for p, k in zip(prompts, wants)]
        for f in futs:
            f.result(timeout=120)
    finally:
        batcher.close()
    for r in telemetry.recent_requests():
        telemetry.validate_record(r)
        assert r["decode_cache_write_kernel_share"] == share
        assert r["decode_cache_write_live_share"] == share
    zero = np.zeros(4, np.int32)
    jax.eval_shape(eng._program.step, eng._weights, eng.init_cache(4), zero,
                   zero, np.zeros((4, 1), np.int32))
    assert dict(eng._program.cache_writes[1]) == (
        {"kernel": 8} if share else {"rows": 8})


# -- AOT warmup / retrace pin --------------------------------------------------

def test_zero_retraces_after_warmup_across_all_buckets():
    net = _model()
    eng = serving.ServingEngine(net, batch_buckets=(1, 2, 4))
    eng.warmup()
    # (prefill buckets 8, 16 for W=16) + decode, per batch bucket
    assert eng.program_count() == 3 * 3
    pinned = serving.trace_count()
    d0 = serving.dispatch_count()
    rng = np.random.RandomState(11)
    for n in (1, 2, 3, 4):
        eng.serve_group(_prompts(n, rng), 4)
    eng.serve_group(_prompts(2, rng, lo=9, hi=12), 3)  # 16-bucket
    assert serving.trace_count() == pinned, \
        "request path retraced after warmup"
    assert serving.compile_count() >= 9
    assert serving.dispatch_count() > d0


# -- the decode loop stays on the device ---------------------------------------

def test_each_program_returns_the_greedy_ids_and_next_positions():
    """Every served id is `np.argmax` of the logits the same program
    returned, and a greedy group's tokens are those of the path with the
    host in every step."""
    eng = serving.ServingEngine(_model(), batch_buckets=(4,))
    prompts = _prompts(3, np.random.RandomState(21))
    want, _ = serving_host_walk(eng, prompts, 6)
    outs, _ = eng.serve_group(prompts, 6)
    np.testing.assert_array_equal(np.stack(outs), want)


@pytest.mark.parametrize("steps", [1, 2, 3, 7])
def test_greedy_group_is_fed_on_the_device(steps):
    """No host round trip between steps: after warm-up a group
    dispatches its prefill and `steps - 1` decode programs and nothing
    else (no third program a step), every decode step takes the step
    before's ids and positions, and the host reads 4 bytes a row."""
    eng = serving.ServingEngine(_model(), batch_buckets=(4,))
    prompts = _prompts(3, np.random.RandomState(4))
    eng.serve_group(prompts, steps)                 # compiles
    pinned = (serving.trace_count(), serving.compile_count())
    d0 = serving.dispatch_count()
    outs, timings = eng.serve_group(prompts, steps)
    assert serving.dispatch_count() - d0 == 1 + (steps - 1)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    assert timings["decode_steps_fed_on_device"] == steps - 1
    assert timings["decode_readback_bytes_per_step"] == 4 * 4
    assert [len(o) for o in outs] == [steps] * 3
    ts = timings["token_t_us"]
    assert len(ts) == steps and all(a < b for a, b in zip(ts, ts[1:]))
    assert ts[0] > 0


@pytest.mark.parametrize("seed", [0, 1])
def test_sampled_group_draws_on_the_host_from_the_programs_logits(seed):
    """A request with a temperature: the same loop reads each program's
    logits and draws with the caller's generator, token for token the
    stream of the path with the host in every step; no step is fed on
    the device."""
    eng = serving.ServingEngine(_model(), batch_buckets=(4,))
    prompts = _prompts(3, np.random.RandomState(8))
    want, _ = serving_host_walk(eng, prompts, 6, temperature=0.7,
                                rng=np.random.default_rng(seed))
    d0 = serving.dispatch_count()
    outs, timings = eng.serve_group(prompts, 6, temperature=0.7,
                                    rng=np.random.default_rng(seed))
    np.testing.assert_array_equal(np.stack(outs), want)
    assert serving.dispatch_count() - d0 == 6
    assert timings["decode_steps_fed_on_device"] == 0
    assert timings["decode_readback_bytes_per_step"] == 4 * 4 * 128
    greedy, _ = eng.serve_group(prompts, 6)
    assert not np.array_equal(np.stack(greedy), want)


class _TableProgram:
    """A family of one table and no layers: a row's logits are the
    table's row of its last token, the cache counts the programs run
    and the rows they were handed as live."""

    window, vocab = 64, 6

    def __init__(self, table):
        import jax.numpy as jnp

        self._table = jnp.asarray(table, jnp.float32)

    def weights(self):
        return (self._table,)

    def init_cache(self, B):
        import jax.numpy as jnp

        return (jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))

    def step(self, w, cache, pos, last, toks, live=None):
        import jax.numpy as jnp

        tok = jnp.take_along_axis(toks, last[:, None], axis=1)[:, 0]
        handed = 0 if live is None else live.astype(jnp.int32)
        return (cache[0] + 1, cache[1] + handed), w[0][tok]

    def counters(self, cache):
        return {"programs_run": int(np.asarray(cache[0])[0]),
                "live_handed": np.asarray(cache[1]).tolist()}


class _TableModel:
    def __init__(self, table):
        self._table = table

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        return _TableProgram(self._table)


def test_ties_go_to_the_lowest_index_as_numpys_argmax():
    """What the engine adds around a family's step, on a family whose
    logits are written down: equal logits give the first index, a NaN
    counts as the largest, and the chain of tokens is NumPy's."""
    table = np.array([[1, 3, 3, 0, 3, 2],
                      [0, 0, 0, 0, 0, 0],
                      [5, 1, 5, 5, 0, 0],
                      [0, 1, 2, 4, 4, 4],
                      [2, np.nan, 9, np.nan, 0, 0],
                      [7, 7, 7, 7, 7, 8]], np.float32)
    eng = serving.ServingEngine(_TableModel(table), batch_buckets=(4,))
    prompts = [[5, 0], [2], [1, 1, 3], [4]]
    steps = 5
    outs, timings = eng.serve_group(prompts, steps)
    for p, got in zip(prompts, outs):
        tok, want = p[-1], []
        for _ in range(steps):
            tok = int(np.argmax(table[tok]))
            want.append(tok)
        assert got.tolist() == want
    # the family's counters are read once, after the last step
    assert timings["programs_run"] == steps


@pytest.mark.parametrize("temperature", [None, 0.7])
def test_the_decode_steps_are_handed_the_rows_that_want_a_token(
        temperature):
    """Three requests in a bucket of four, wanting 3, 1 and 5 tokens:
    four decode steps are dispatched; decode step j is fed token j, so
    it is handed row i as live when ``j + 1 < wants[i]``: 2, 0 and 4
    steps, and the pad row never.  The prefill is handed no mask.  The
    count of wanted tokens lives on the device: nothing more is read a
    step, no step waits for the host, no program is traced or compiled
    for it.  With a temperature the host draws, and the steps are
    handed the same mask."""
    table = np.arange(36, dtype=np.float32).reshape(6, 6) % 7
    eng = serving.ServingEngine(_TableModel(table), batch_buckets=(4,))
    prompts, wants = [[5, 0], [2], [1, 1, 3]], [3, 1, 5]
    eng.serve_group(prompts, wants)                     # compiles
    pinned = (serving.trace_count(), serving.compile_count())
    d0 = serving.dispatch_count()
    outs, timings = eng.serve_group(
        prompts, wants, temperature=temperature,
        rng=np.random.default_rng(3))
    assert [len(o) for o in outs] == wants
    assert (serving.trace_count(), serving.compile_count()) == pinned
    assert serving.dispatch_count() - d0 == 1 + 4
    assert timings["programs_run"] == 5
    assert timings["live_handed"] == [2, 0, 4, 0]
    assert timings["decode_row_steps"] == 4 * 4
    assert timings["decode_row_steps_live"] == 2 + 0 + 4
    if temperature:
        assert timings["decode_steps_fed_on_device"] == 0
        assert timings["decode_readback_bytes_per_step"] == 4 * 4 * 6
    else:
        assert timings["decode_steps_fed_on_device"] == 4
        assert timings["decode_readback_bytes_per_step"] == 4 * 4
    # one token a request: no decode step, no row-step
    _, timings = eng.serve_group(prompts, 1)
    assert timings["decode_row_steps"] == 0 \
        and timings["decode_row_steps_live"] == 0


# -- continuous batcher --------------------------------------------------------

def test_batcher_coalesces_and_emits_request_records():
    telemetry.reset()
    eng = serving.ServingEngine(_model(), batch_buckets=(4,))
    eng.warmup()
    batcher = serving.ContinuousBatcher(eng, max_delay_ms=150,
                                        max_batch=4)
    try:
        rng = np.random.RandomState(2)
        futs = [batcher.submit(p, 3) for p in _prompts(4, rng)]
        recs = [f.result(timeout=120) for f in futs]
    finally:
        batcher.close()
    assert batcher.requests_served == 4
    # all 4 queued within the 150ms deadline → ONE coalesced group
    assert batcher.groups_served == 1
    for rec in recs:
        assert rec["queue_us"] >= 0
        assert len(rec["tokens"]) == 3
        assert rec["bucket"] == [4, 8]
    requests = telemetry.recent_requests()
    assert len(requests) == 4
    for r in requests:
        telemetry.validate_record(r)
        assert r["generation"] == 0
        # greedy: both decode steps took the step before's ids on the
        # device, and the host read (4, 1) int32 of each program
        assert r["decode_steps_fed_on_device"] == 2
        assert r["decode_readback_bytes_per_step"] == 4 * 4
        # the decode attention's path and reach, as the engine counted
        assert r["decode_attn_kernel_share"] == 0.0
        assert r["decode_attn_window_read_pct"] == 100.0
        # four requests of three tokens in a bucket of four: two decode
        # steps, every row live in both
        assert r["decode_row_steps"] == r["decode_row_steps_live"] == 8


def test_a_compile_collects_what_its_trace_left():
    """`_compile` ends with a full pass of the cyclic collector, so
    that none is left due among the first requests: one is seen inside
    it, and the second generation's counter stands at 0 after it."""
    import gc

    eng = serving.ServingEngine(_model(), batch_buckets=(2,))
    full = []

    def seen(phase, info):
        if phase == "stop" and info["generation"] == 2:
            full.append(info)

    gc.callbacks.append(seen)
    try:
        eng._compile(2, 1)
    finally:
        gc.callbacks.remove(seen)
    assert full and gc.get_count()[2] == 0


def test_batcher_propagates_engine_errors():
    eng = serving.ServingEngine(_model(), batch_buckets=(2,))
    batcher = serving.ContinuousBatcher(eng, max_delay_ms=1)
    try:
        fut = batcher.submit(list(range(30)), 10)  # exceeds W=16
        with pytest.raises(MXNetError, match="cache window"):
            fut.result(timeout=120)
    finally:
        batcher.close()


# -- admission control (no engine compile needed: stub engine) -----------------

class _StubEngine:
    """Engine-shaped stub: blockable, instant, jax-free — isolates the
    batcher's admission/deadline behavior from compile latency."""

    batch_buckets = (1, 2, 4)

    def __init__(self):
        import threading

        self.block = threading.Event()
        self.block.set()

    def serve_group(self, prompts, maxes, temperature=None, rng=None):
        self.block.wait()
        outs = [[1, 2, 3] for _ in prompts]
        timings = {"prefill_us": 10.0, "decode_us_per_token": 1.0,
                   "bucket": [max(len(prompts), 1), 8],
                   "padded_fraction": 0.0, "generation": 0}
        return outs, timings


def test_batcher_sheds_when_queue_full():
    telemetry.reset()
    eng = _StubEngine()
    eng.block.clear()               # engine wedged: queue can only grow
    b = serving.ContinuousBatcher(eng, max_delay_ms=0.0, max_queue=2)
    try:
        futs = [b.submit([1], 2)]
        time.sleep(0.2)             # loop takes it into the blocked serve
        futs += [b.submit([1], 2) for _ in range(2)]  # fills the queue
        with pytest.raises(serving.ServerOverloaded, match="queue full"):
            b.submit([1], 2)
        assert b.shed == 1
        assert telemetry.event_counts().get("queue_full", 0) == 1
        eng.block.set()             # back-pressure released: all served
        for f in futs:
            assert f.result(timeout=30)["tokens"] == [1, 2, 3]
    finally:
        eng.block.set()
        b.close(timeout=30)
    assert b.shed == 1              # shed request never cost a slot


def test_batcher_deadline_exceeded_before_dispatch():
    eng = _StubEngine()
    eng.block.clear()
    b = serving.ContinuousBatcher(eng, max_delay_ms=0.0, max_queue=16)
    try:
        blocker = b.submit([1], 2)          # occupies the engine
        time.sleep(0.05)
        doomed = b.submit([1], 2, deadline_ms=10.0)
        ok = b.submit([1], 2)               # no deadline: must survive
        time.sleep(0.2)                     # deadline passes while queued
        eng.block.set()
        with pytest.raises(serving.DeadlineExceeded):
            doomed.result(timeout=30)
        assert ok.result(timeout=30)["tokens"] == [1, 2, 3]
        assert blocker.result(timeout=30)["tokens"] == [1, 2, 3]
        assert b.deadline_exceeded == 1
    finally:
        eng.block.set()
        b.close(timeout=30)


def test_batcher_idle_blocks_instead_of_spinning():
    """The collector must sit in ONE blocking queue.get while idle —
    the PR 11 loop polled with timeout=0 and burned a core."""
    import queue as queue_mod

    from mxnet_tpu.serving import batcher as batcher_mod

    calls = {"n": 0}

    class CountingQueue(queue_mod.Queue):
        def get(self, block=True, timeout=None):
            calls["n"] += 1
            return super().get(block, timeout)

    orig = batcher_mod.queue.Queue
    batcher_mod.queue.Queue = CountingQueue
    try:
        b = serving.ContinuousBatcher(_StubEngine(), max_delay_ms=1.0)
    finally:
        batcher_mod.queue.Queue = orig
    try:
        time.sleep(0.5)
        assert calls["n"] == 1, \
            f"idle batcher polled the queue {calls['n']} times in 0.5s"
        assert b.submit([1], 2).result(timeout=30)["tokens"] == [1, 2, 3]
    finally:
        b.close(timeout=30)


def test_batcher_close_drains_queued_requests():
    import threading

    eng = _StubEngine()
    eng.block.clear()
    b = serving.ContinuousBatcher(eng, max_delay_ms=0.0, max_queue=16)
    futs = [b.submit([1], 2) for _ in range(4)]
    threading.Timer(0.2, eng.block.set).start()
    b.close(timeout=30)
    for f in futs:
        assert f.result(timeout=1)["tokens"] == [1, 2, 3]


# -- hot reload ----------------------------------------------------------------

def test_hot_reload_mid_stream_zero_dropped_requests(tmp_path):
    telemetry.reset()
    model_a, model_b = _model(seed=1), _model(seed=2)
    ck = checkpoint.AsyncCheckpointer(tmp_path, rank=0, world_size=1)
    ck.save(1, serving.state_for_serving(model_a))
    ck.wait()

    eng = serving.ServingEngine(model_a, batch_buckets=(1, 2))
    rs = ReplicaServer(eng, ckpt_dir=tmp_path, poll_ms=10,
                       max_delay_ms=1)
    rng = np.random.RandomState(9)
    prompts = _prompts(6, rng)
    try:
        pre = [rs.submit(p, 4).result(timeout=120) for p in prompts]
        # step 1 is model A's own weights, so whether the poller's first
        # swap landed yet (generation 0 vs 1) can't change outputs
        assert all(len(r["tokens"]) == 4 for r in pre)

        # commit new weights while the stream keeps flowing; the poller
        # stages them and the batcher swaps BETWEEN groups
        ck.save(2, serving.state_for_serving(model_b))
        ck.wait()
        ck.close()
        deadline = time.monotonic() + 30
        streamed = 0
        while rs.loaded_step != 2:
            assert time.monotonic() < deadline, "reload never landed"
            rs.submit(prompts[streamed % len(prompts)], 2)\
                .result(timeout=120)
            streamed += 1
        post = [rs.submit(p, 4).result(timeout=120) for p in prompts]
    finally:
        rs.close()
    # zero dropped/errored: every future above resolved with tokens
    assert all(len(r["tokens"]) == 4 for r in post)
    # all post-reload requests served by ONE weight generation (the
    # step-1 swap may or may not have landed first: 1 or 2 reloads)
    assert len({r["generation"] for r in post}) == 1
    assert 1 <= rs.reloads <= 2

    # post-reload outputs are REALLY model B's weights
    eng_b = serving.ServingEngine(_model(seed=2), batch_buckets=(1, 2))
    for p, r in zip(prompts, post):
        ref = eng_b.serve_group([p], 4)[0][0]
        np.testing.assert_array_equal(ref, r["tokens"])
    assert telemetry.event_counts().get("serving_reload", 0) >= 1


def test_reload_rejects_incompatible_state():
    eng = serving.ServingEngine(_model(), batch_buckets=(1,))
    gen0 = eng.generation
    with pytest.raises(MXNetError, match="scanned-trunk"):
        eng.reload_from_state({"dense0_weight": np.zeros((2, 2))})
    other = _model(units=16, max_length=16)
    with pytest.raises(MXNetError, match="mismatch"):
        eng.reload_from_state(serving.state_for_serving(other))
    assert eng.generation == gen0  # failed swaps leave weights alone


def test_latest_manifest_step_scans_committed_only(tmp_path):
    assert checkpoint.latest_manifest_step(tmp_path) is None
    for step, committed in ((3, True), (7, False), (5, True)):
        d = tmp_path / f"step_{step:010d}"
        d.mkdir()
        if committed:
            (d / "MANIFEST.json").write_text("{}")
    (tmp_path / "step_junk").mkdir()
    # 7 is a crash orphan (no manifest): invisible
    assert checkpoint.latest_manifest_step(tmp_path) == 5
    assert checkpoint.latest_manifest_step(tmp_path / "absent") is None


# -- front door ----------------------------------------------------------------

class _StubReplica:
    def __init__(self, rank, fail=False, shed=0):
        self.rank = rank
        self.fail = fail
        self.shed = shed        # raise ServerOverloaded this many times
        self.calls = 0

    def submit(self, prompt, max_new_tokens=16, deadline_ms=None,
               trace=None):
        self.calls += 1
        if self.fail:
            raise RuntimeError("replica down")
        if self.shed > 0:
            self.shed -= 1
            raise serving.ServerOverloaded("serving queue full")
        return ("ok", self.rank)

    def close(self, timeout=None):
        pass


def test_front_door_round_robin_and_failover():
    good1, bad, good2 = (_StubReplica(0), _StubReplica(1, fail=True),
                         _StubReplica(2))
    fd = FrontDoor([good1, bad, good2])
    results = [fd.submit([1, 2], 2) for _ in range(6)]
    assert all(r[0] == "ok" for r in results)
    # the failing replica was tried once, failed over, and quarantined
    assert bad.calls == 1
    assert {r.rank for r in fd.alive()} == {0, 2}
    assert good1.calls + good2.calls == 6
    fd2 = FrontDoor([_StubReplica(0, fail=True)])
    with pytest.raises(MXNetError, match="every replica"):
        fd2.submit([1], 1)


def test_front_door_retries_shed_once_without_quarantine():
    # first replica full, second takes it: client never sees the shed
    full, okr = _StubReplica(0, shed=1), _StubReplica(1)
    fd = FrontDoor([full, okr])
    assert fd.submit([1], 1) == ("ok", 1)
    assert {r.rank for r in fd.alive()} == {0, 1}, \
        "a shed is back-pressure, not a failure — no quarantine"
    assert fd.submit([1], 1) == ("ok", 1)   # round-robin unchanged
    assert fd.submit([1], 1) == ("ok", 0)   # ...and 0 drained its queue

    # EVERY replica full: one retry, then the shed reaches the client
    f0, f1, f2 = (_StubReplica(r, shed=9) for r in range(3))
    fd2 = FrontDoor([f0, f1, f2])
    with pytest.raises(serving.ServerOverloaded):
        fd2.submit([1], 1)
    assert f0.calls + f1.calls + f2.calls == 2, \
        "exactly one shed retry — no hammering a saturated fleet"
    assert len(fd2.alive()) == 3


def test_fleet_watcher_claims_freed_chips_and_spawns(tmp_path):
    from mxnet_tpu.distributed import FileKV
    from mxnet_tpu.resilience import announce_freed_chips

    telemetry.reset()
    kv = FileKV(str(tmp_path / "kv"))
    announce_freed_chips(kv, 1, step=12, count=4, addr="host1:0")
    spawned = []

    def spawn(rec):
        spawned.append(rec)
        return _StubReplica(rec["rank"])

    w = serving.FleetWatcher(kv, spawn)
    reps = w.poll_once()
    assert [r.rank for r in reps] == [1]
    assert w.claimed == 1 and len(spawned) == 1
    assert spawned[0]["count"] == 4 and spawned[0]["step"] == 12
    # announcement consumed, claim recorded: a second poll is a no-op
    assert kv.get_json("chips/freed/1") is None
    assert kv.get_json("chips/claimed/1")["rank"] == 1
    assert w.poll_once() == []
    assert telemetry.event_counts().get("serving_replica_spawned") == 1
    assert telemetry.event_counts().get("chips_freed") == 1


# -- tensor-parallel serving ---------------------------------------------------

def test_tp_serving_matches_unsharded(mesh8):
    """Sharded serving through TRANSFORMER_TP_RULES-style placements:
    prefill logits match the unsharded engine to float32 rounding (the
    tp all-reduce associates partial sums differently, so the contract
    is logits-to-rounding — same as _assert_decode_equiv in
    test_model_zoo), and the tp request path is retrace-free."""
    mesh = mesh8(tp=2, dp=4)
    net = _model()
    plain = serving.ServingEngine(net, batch_buckets=(2,))
    tp = serving.ServingEngine(net, batch_buckets=(2,), mesh=mesh)
    rng = np.random.RandomState(13)
    prompts = _prompts(2, rng, lo=4, hi=6)

    toks = np.zeros((2, 8), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    zero = np.zeros(2, np.int32)
    last = np.asarray([len(p) - 1 for p in prompts], np.int32)
    _, ref_lg, _, _ = plain._call(2, 8, plain.init_cache(2), zero, last,
                                  toks)
    _, tp_lg, tp_ids, tp_pos = tp._call(2, 8, tp.init_cache(2), zero, last,
                                        toks)
    np.testing.assert_allclose(np.asarray(tp_lg), np.asarray(ref_lg),
                               rtol=2e-4, atol=1e-5)
    # the next step's inputs come back as the programs take them in:
    # on every chip of the mesh, so a step can be fed the step before's
    for fed in (tp_ids, tp_pos):
        assert fed.sharding.is_equivalent_to(tp._input_sharding(), fed.ndim)
    np.testing.assert_array_equal(
        np.asarray(tp_ids)[:, 0], np.asarray(tp_lg).argmax(-1))
    np.testing.assert_array_equal(np.asarray(tp_pos), last + 1)

    # the full request path runs end-to-end on the mesh, retrace-free
    outs, timings = tp.serve_group(prompts, 4)
    assert [len(o) for o in outs] == [4, 4]
    assert timings["bucket"] == [2, 8]
    pinned = serving.trace_count()
    tp.serve_group(prompts, 4)
    # the count of tokens each row wants is placed where the positions
    # are: a row that ends early changes nothing for itself or the other
    short, timings = tp.serve_group(prompts, [2, 4])
    assert serving.trace_count() == pinned
    for got, want, k in zip(short, outs, [2, 4]):
        np.testing.assert_array_equal(got, want[:k])
    assert (timings["decode_row_steps"],
            timings["decode_row_steps_live"]) == (6, 4)


# -- env knobs -----------------------------------------------------------------

def test_bucket_and_deadline_env_knobs(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_BUCKETS", "2,8,4")
    assert serving.batch_buckets_from_env() == (2, 4, 8)
    monkeypatch.setenv("MXTPU_SERVE_BUCKETS", "bogus")
    assert serving.batch_buckets_from_env() == (1, 2, 4, 8)
    assert serving.prefill_buckets_for(64) == (8, 16, 32, 64)
    assert serving.prefill_buckets_for(48) == (8, 16, 32, 48)
    monkeypatch.setenv("MXTPU_SERVE_MAX_DELAY_MS", "12.5")
    assert serving.max_delay_ms_from_env() == 12.5
    monkeypatch.delenv("MXTPU_SERVE_MAX_DELAY_MS")
    assert serving.max_delay_ms_from_env() == 5.0


def test_capture_cache_size_env(monkeypatch):
    from mxnet_tpu.gluon import captured

    assert captured.capture_cache_size() == 8
    monkeypatch.setenv("MXTPU_CAPTURE_CACHE", "3")
    assert captured.capture_cache_size() == 3
    monkeypatch.setenv("MXTPU_CAPTURE_CACHE", "0")
    assert captured.capture_cache_size() == 1  # floor: never cache-less


def test_capture_cache_eviction_emits_event(monkeypatch):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    telemetry.reset()
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "1")
    monkeypatch.setenv("MXTPU_CAPTURE_CACHE", "1")
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4))
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    rng = np.random.RandomState(0)
    for n in (4, 6):   # two batch shapes, cache capacity 1 → eviction
        x = mx.nd.array(rng.normal(size=(n, 3)).astype(np.float32))
        y = mx.nd.array(rng.randint(0, 4, n).astype(np.float32))
        trainer.train_step(net, loss_fn, x, y)
    assert telemetry.event_counts().get("capture_cache_evict", 0) >= 1


# -- telemetry schema ----------------------------------------------------------

def test_request_record_schema_validates():
    telemetry.reset()
    telemetry.request_record(queue_us=12.0, prefill_us=340.0,
                             decode_us_per_token=55.5, bucket=(4, 16),
                             padded_fraction=0.25, new_tokens=8,
                             generation=2)
    recs = telemetry.recent_requests()
    assert len(recs) == 1
    telemetry.validate_record(recs[0])
    bad = dict(recs[0], bucket=[0, 16])
    with pytest.raises(ValueError, match="bucket"):
        telemetry.validate_record(bad)
    bad = dict(recs[0], padded_fraction=1.5)
    with pytest.raises(ValueError, match="padded_fraction"):
        telemetry.validate_record(bad)


def test_trace_report_requests_section(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    for i in range(5):
        telemetry.request_record(queue_us=10.0 * i, prefill_us=200.0,
                                 decode_us_per_token=40.0,
                                 bucket=(2, 8), padded_fraction=0.1,
                                 new_tokens=4, generation=i % 2)
    telemetry.reset()  # close the sink so the file is flushed
    monkeypatch.delenv("MXTPU_TELEMETRY_PATH")

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    import io

    records, bad = trace_report.read_records(path)
    assert bad == 0 and len(records) == 5
    assert trace_report.validate_all(records) == []
    out = io.StringIO()
    trace_report.report_run("r", records, out)
    text = out.getvalue()
    assert "serving requests:" in text
    assert "decode/token" in text
    assert "2x8:5" in text
    assert "generations served: [0, 1]" in text


# -- CLI smoke -----------------------------------------------------------------

def test_serve_cli_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_child_env()
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "serve.py"),
         "--requests", "4", "--clients", "2", "--new-tokens", "3",
         "--buckets", "1,2"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "served 4 requests" in proc.stdout
    assert "retraces_after_warmup 0" in proc.stdout


# -- reload integrity gate (mxnet_tpu/integrity.py) ----------------------------

def test_reload_rejects_corrupt_checkpoint_and_keeps_serving(tmp_path):
    """A bit-rotted shard must never be swapped in: the poller's
    verify-before-stage gate (per-shard CRC + provenance audit) rejects
    the step ONCE (rejection dedups — a bad file will not un-corrupt),
    emits ``serving_reload_rejected``, and the replica keeps serving on
    its compiled-in weights."""
    telemetry.reset()
    model = _model(seed=1)
    ck = checkpoint.AsyncCheckpointer(tmp_path, rank=0, world_size=1)
    ck.save(1, serving.state_for_serving(model))
    ck.wait()
    ck.close()
    sdir = next(p for p in tmp_path.iterdir()
                if p.name.startswith("step_"))
    shard = next(p for p in sdir.iterdir()
                 if p.name.startswith("shard_"))
    raw = bytearray(shard.read_bytes())
    raw[len(raw) // 2] ^= 0x40
    shard.write_bytes(bytes(raw))

    eng = serving.ServingEngine(model, batch_buckets=(1, 2))
    rs = ReplicaServer(eng, ckpt_dir=tmp_path, poll_ms=10,
                       max_delay_ms=1)
    try:
        deadline = time.monotonic() + 30
        while not telemetry.event_counts().get("serving_reload_rejected"):
            assert time.monotonic() < deadline, "rejection never surfaced"
            time.sleep(0.01)
        time.sleep(0.2)                 # many more poll cycles
        assert rs.loaded_step is None and rs.reloads == 0
        assert telemetry.event_counts()["serving_reload_rejected"] == 1
        # the replica is still healthy on its original weights
        r = rs.submit(_prompts(1, np.random.RandomState(3))[0], 3)\
            .result(timeout=120)
        assert len(r["tokens"]) == 3
    finally:
        rs.close()
    telemetry.reset()


def test_reload_from_state_enforces_attested_fingerprint():
    """``expect_fp`` closes the loop past the per-shard CRCs: the
    restored state is re-fingerprinted and a mismatch with the
    training side's attested fingerprint refuses the swap."""
    from mxnet_tpu import integrity

    telemetry.reset()
    eng = serving.ServingEngine(_model(seed=1), batch_buckets=(1, 2))
    state = serving.state_for_serving(_model(seed=2))
    with pytest.raises(MXNetError, match="fingerprint"):
        eng.reload_from_state(state, step=2, expect_fp=12345)
    assert telemetry.event_counts().get("serving_reload_rejected") == 1
    # the attested fingerprint of the same state swaps cleanly
    eng.reload_from_state(state, step=2,
                          expect_fp=integrity.fingerprint_host(state))
    telemetry.reset()


def test_reload_skips_stale_epoch_manifest(tmp_path, monkeypatch):
    """Epoch fence on the serving side: once a manifest from gang epoch
    E has been served, a newer-STEP manifest stamped with an OLDER
    epoch (a fenced trainer's leftover commit) is rejected — the
    serving weights never roll backwards across a reshape — while a
    same-or-newer-epoch manifest reloads normally."""
    import json

    ev_path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", ev_path)
    telemetry.reset()
    model = _model(seed=1)
    prompt = _prompts(1, np.random.RandomState(3))[0]

    def save(step, epoch):
        ck = checkpoint.AsyncCheckpointer(tmp_path, rank=0,
                                          world_size=1)
        ck.attach_gang(lambda: epoch)
        ck.save(step, serving.state_for_serving(model))
        ck.wait()
        ck.close()

    save(1, 2)
    eng = serving.ServingEngine(model, batch_buckets=(1, 2))
    rs = ReplicaServer(eng, ckpt_dir=tmp_path, poll_ms=10,
                       max_delay_ms=1)
    try:
        deadline = time.monotonic() + 30
        while rs.loaded_step != 1:
            assert time.monotonic() < deadline, "epoch-2 reload lost"
            rs.submit(prompt, 2).result(timeout=120)
        assert rs._served_epoch == 2

        save(2, 1)                      # newer step, OLDER epoch: stale
        deadline = time.monotonic() + 30
        while not telemetry.event_counts().get(
                "serving_reload_rejected"):
            assert time.monotonic() < deadline, \
                "stale-epoch rejection never surfaced"
            time.sleep(0.01)
        time.sleep(0.2)                 # many more poll cycles
        rs.submit(prompt, 2).result(timeout=120)
        assert rs.loaded_step == 1, "stale-epoch manifest was served"
        assert rs._served_epoch == 2

        save(3, 2)                      # same epoch again: reloads
        deadline = time.monotonic() + 30
        while rs.loaded_step != 3:
            assert time.monotonic() < deadline, "epoch-2 reload lost"
            rs.submit(prompt, 2).result(timeout=120)
    finally:
        rs.close()
    telemetry.reset()
    with open(ev_path) as f:
        ev = [json.loads(ln) for ln in f if ln.strip()]
    rejected = [e for e in ev
                if e.get("event") == "serving_reload_rejected"]
    assert rejected and all(
        e["reason"].startswith("stale_epoch") for e in rejected)


# -- the carried KV cache ------------------------------------------------------

def _np_step(w, act, ck, cv, pos, toks):
    """The serving step in NumPy, one slot at a time: row b's block is
    written at ``min(pos[b], W - S)`` (dynamic_update_slice's clamp),
    and slot w is visible to (b, s) when ``w <= pos[b] + s``.  Mutates
    ck/cv (L, B, H, Dh, W) and returns the logits."""
    (tok_e, pos_e, qkvw, qkvb, pwh, pb, f1w, f1b, f2w, f2b,
     g1s, b1s, g2s, b2s, lnf_g, lnf_b) = [np.asarray(a, np.float64)
                                          for a in w]
    L, B, H, Dh, W = ck.shape
    S = toks.shape[1]

    def ln(x, g, b):
        mu = x.mean(-1, keepdims=True)
        return (x - mu) / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * g + b

    def gelu(h):
        return 0.5 * h * (1 + np.tanh(np.sqrt(2 / np.pi) *
                                      (h + 0.044715 * h ** 3)))

    logits = np.zeros((B, S, tok_e.shape[0]))
    for b in range(B):
        at = pos[b] + np.arange(S)
        # a position past the table reads NaN, as jnp.take fills it
        x = tok_e[toks[b]] + np.where((at < W)[:, None],
                                      pos_e[np.minimum(at, W - 1)], np.nan)
        start = min(int(pos[b]), W - S)
        for l in range(L):
            h = ln(x, g1s[l], b1s[l])
            qkv = np.einsum("sc,thdc->sthd", h, qkvw[l]) + qkvb[l]
            for s in range(S):
                ck[l, b, :, :, start + s] = qkv[s, 1]
                cv[l, b, :, :, start + s] = qkv[s, 2]
            attn = np.zeros((S, H, Dh))
            for s in range(S):
                seen = np.arange(W) <= pos[b] + s
                sc = np.einsum("hd,hdw->hw", qkv[s, 0],
                               ck[l, b].astype(np.float64)) * Dh ** -0.5
                sc = np.where(seen[None], sc, -1e30)
                p = np.exp(sc - sc.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                attn[s] = np.einsum("hw,hdw->hd", p,
                                    cv[l, b].astype(np.float64))
            x = x + np.einsum("shd,chd->sc", attn, pwh[l]) + pb[l]
            h = ln(x, g2s[l], b2s[l]) @ f1w[l].T + f1b[l]
            h = gelu(h) if act == "gelu" else np.maximum(h, 0)
            x = x + h @ f2w[l].T + f2b[l]
        logits[b] = ln(x, lnf_g, lnf_b) @ tok_e.T
    return logits


def _cache_walk(eng, lens, S, decode_pos):
    """One prefill then one decode step per entry of ``decode_pos``
    through the engine's programs and through ``_np_step``; returns
    (engine ck, cv, logits), (NumPy ck, cv, logits)."""
    B = len(lens)
    rng = np.random.RandomState(17)
    toks = np.zeros((B, S), np.int32)
    for b, n in enumerate(lens):
        toks[b, :n] = rng.randint(1, 128, n)
    w = [np.asarray(a) for a in eng._weights]
    ck, cv = eng.init_cache(B)
    shape = tuple(ck.shape)
    nk, nv = np.zeros(shape), np.zeros(shape)
    calls = [(np.zeros(B, np.int32), toks)]
    calls += [(np.asarray(p, np.int32),
               rng.randint(1, 128, (B, 1)).astype(np.int32))
              for p in decode_pos]
    last = np.asarray(lens, np.int32) - 1
    for pos, t in calls:
        # the step hands back one position a row: the last real token's
        (ck, cv), lg, _, _ = eng._call(B, t.shape[1], (ck, cv), pos, last,
                                       t)
        want = _np_step(w, eng._program._act, nk, nv, pos, t)[
            np.arange(B), last]
        last = np.zeros(B, np.int32)
    assert tuple(ck.shape) == shape and ck.dtype == cv.dtype
    return (np.asarray(ck), np.asarray(cv), np.asarray(lg)), (nk, nv, want)


@pytest.mark.parametrize("kind,S", [("prefill", 8), ("decode", 1)])
def test_step_carries_the_cache_and_aliases_it(kind, S):
    """The layer loop takes the stacked cache in and hands it out as a
    carry: nothing the loop scans over or stacks up is the cache or a
    layer of it (a scanned input is sliced a layer at a time, a scanned
    output is a new buffer), and the compiled program writes outputs
    0, 1 into the donated arguments 1, 2."""
    import jax

    eng = serving.ServingEngine(_model(num_layers=3), batch_buckets=(4,))
    B = 4
    ck, cv = eng.init_cache(B)
    stack, layer = tuple(ck.shape), tuple(ck.shape[1:])
    jaxpr = jax.make_jaxpr(eng._step[kind])(
        eng._weights, (ck, cv), np.zeros(B, np.int32),
        np.zeros(B, np.int32), np.zeros((B, S), np.int32))
    loops = list(jaxpr_loops(jaxpr.jaxpr))
    assert [e.primitive.name for e in loops] == ["scan"]
    scan = loops[0]
    n_fixed = scan.params["num_consts"] + scan.params["num_carry"]
    carried = [tuple(v.aval.shape) for v in
               scan.invars[scan.params["num_consts"]:n_fixed]]
    assert carried.count(stack) == 2, carried
    for v in scan.invars[n_fixed:] + \
            scan.outvars[scan.params["num_carry"]:]:
        assert tuple(v.aval.shape) != stack, v.aval
    body = scan.params["jaxpr"].jaxpr
    for v in body.outvars[scan.params["num_carry"]:]:
        assert tuple(v.aval.shape) != layer, v.aval

    text = eng._compile(B, S).as_text()
    n_w = len(eng._weights)
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    assert f"{{0}}: ({n_w}, {{}}" in alias, alias
    assert f"{{1}}: ({n_w + 1}, {{}}" in alias, alias
    if kind == "decode":
        assert serving.whole_layer_ops(text, ck.nbytes // ck.shape[0]) == []


@pytest.mark.parametrize("case", ["walk", "clamped"])
def test_cache_holds_exactly_the_rows_written(case):
    """One prefill and three decode steps of a 3-layer engine whose
    rows sit at different positions (a pad row among them): the
    returned cache equals one built slot by slot in NumPy, and every
    slot no step wrote is still zero.  ``clamped``: a write whose start
    would run past the window lands where dynamic_update_slice puts
    it, at ``W - S`` (what it holds there is NaN: the position table has
    no such row)."""
    eng = serving.ServingEngine(_model(num_layers=3), batch_buckets=(4,))
    lens = [3, 7, 5, 1]                # row 3 is a pad row
    steps = [[n + j for n in lens] for j in range(3)]
    if case == "clamped":
        steps.append([16, 20, 15, 99])     # W = 16: rows 0, 1, 3 clamp
    got, want = _cache_walk(eng, lens, 8, steps)
    for g, n, name in zip(got, want, ("ck", "cv", "logits")):
        np.testing.assert_allclose(g, n, rtol=1e-4, atol=1e-5,
                                   err_msg=name)
    ck, cv = got[:2]
    for b, n in enumerate(lens):
        written = np.zeros(16, bool)
        written[:8] = True                         # the prefill block
        written[[min(s[b], 15) for s in steps]] = True
        assert not ck[:, b, :, :, ~written].any()
        assert not cv[:, b, :, :, ~written].any()
        assert (ck[:, b, :, :, written] != 0).all()


def test_tp_cache_holds_exactly_the_rows_written(mesh8):
    """The same walk with the cache sharded on its head axis."""
    eng = serving.ServingEngine(_model(num_layers=3), batch_buckets=(4,),
                                mesh=mesh8(tp=2, dp=4))
    lens = [3, 7, 5, 1]
    steps = [[n + j for n in lens] for j in range(3)]
    got, want = _cache_walk(eng, lens, 8, steps)
    for g, n, name in zip(got, want, ("ck", "cv", "logits")):
        np.testing.assert_allclose(g, n, rtol=2e-4, atol=1e-5,
                                   err_msg=name)
    assert not got[0][:, 0, :, :, 8:].any()    # row 0 wrote slots 0..7
    assert not got[1][:, 1, :, :, 10:].any()   # row 1 wrote up to 9


# a decode program's text cut down to the shapes of what the TPU
# compiler made of the scanned cache (copy.48, the slice and update
# fusions) and of the carried one (the in-place row write, the slice
# read inside the fusion that contracts it); a cache layer is 2048 bytes
_HLO = """HloModule jit_serve_decode, is_scheduled=true

%fused_slice (param_0.1: bf16[3,4,2,16,8], param_1.1: s32[]) -> bf16[1,4,2,16,8] {
  %param_0.1 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.1 = s32[]{:T(128)} parameter(1)
  ROOT %dynamic_slice.87 = bf16[1,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.1, %param_1.1, %param_1.1), dynamic_slice_sizes={1,4,2,16,8}
}

%fused_update (param_0.2: bf16[3,4,2,16,8], param_1.2: bf16[1,4,2,16,8], param_2.2: s32[]) -> bf16[3,4,2,16,8] {
  %param_0.2 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = bf16[1,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} parameter(1)
  %param_2.2 = s32[]{:T(128)} parameter(2)
  ROOT %dynamic_update_slice.7 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0.2, %param_1.2, %param_2.2, %param_2.2)
}

%fused_row_write (param_0.3: bf16[3,4,2,16,8], param_1.3: bf16[1,1,2,1,8], param_2.3: s32[]) -> bf16[3,4,2,16,8] {
  %param_0.3 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.3 = bf16[1,1,2,1,8]{3,4,2,1,0:T(8,128)(2,1)} parameter(1)
  %param_2.3 = s32[]{:T(128)} parameter(2)
  ROOT %dynamic_update_slice.9 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0.3, %param_1.3, %param_2.3, %param_2.3)
}

%fused_scores (param_0.4: bf16[3,4,2,16,8], param_1.4: s32[]) -> f32[4,2,16] {
  %param_0.4 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.4 = s32[]{:T(128)} parameter(1)
  %dynamic_slice.114 = bf16[1,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.4, %param_1.4, %param_1.4), dynamic_slice_sizes={1,4,2,16,8}
  %convert.5 = f32[1,4,2,16,8]{3,4,2,1,0:T(8,128)} convert(%dynamic_slice.114)
  ROOT %reduce.1 = f32[4,2,16]{2,1,0:T(8,128)} reduce(%convert.5, %param_1.4), dimensions={0,4}
}

%body (arg: (bf16[3,4,2,16,8], s32[])) -> (bf16[3,4,2,16,8], s32[]) {
  %arg = (bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)}, s32[]{:T(128)}) parameter(0)
  %gte.0 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=0
  %gte.1 = s32[]{:T(128)} get-tuple-element(%arg), index=1
  %slice_fusion.23 = bf16[1,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} fusion(%gte.0, %gte.1), kind=kLoop, calls=%fused_slice
  %copy.48 = bf16[1,4,2,16,8]{4,3,2,1,0:T(8,128)(2,1)} copy(%slice_fusion.23), metadata={op_name="jit(serve_decode)/while/body/dynamic_slice"}
  %update_fusion.4 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} fusion(%gte.0, %copy.48, %gte.1), kind=kLoop, calls=%fused_update
  %row.1 = bf16[1,1,2,1,8]{3,4,2,1,0:T(8,128)(2,1)} copy(%bitcast.3)
  %row_write.1 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} fusion(%update_fusion.4, %row.1, %gte.1), kind=kLoop, calls=%fused_row_write
  %dynamic-update-slice.16 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%row_write.1, %row.1, %gte.1, %gte.1)
  %scores.1 = f32[4,2,16]{2,1,0:T(8,128)} fusion(%dynamic-update-slice.16, %gte.1), kind=kLoop, calls=%fused_scores
  ROOT %tuple.1 = (bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)}, s32[]{:T(128)}) tuple(%dynamic-update-slice.16, %gte.1)
}

ENTRY %main.1 (ck.1: bf16[3,4,2,16,8]) -> bf16[3,4,2,16,8] {
  %ck.1 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} parameter(0)
  %copy.88 = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} copy(%ck.1)
  ROOT %done = bf16[3,4,2,16,8]{3,4,2,1,0:T(8,128)(2,1)} bitcast(%copy.88)
}
"""


@pytest.mark.parametrize("layer_bytes,want", [
    (2048, ["slice_fusion.23", "copy.48", "update_fusion.4", "copy.88"]),
    (4096, ["copy.88"]),
    (32, ["slice_fusion.23", "copy.48", "update_fusion.4", "row.1",
          "row_write.1", "dynamic-update-slice.16", "copy.88"]),
])
def test_whole_layer_ops_reads_a_recorded_program(layer_bytes, want):
    """What counts is what an instruction materialises: a copy's or a
    slice's result, an update's *update* (in place or not, its result
    is the whole buffer), a fusion's root; a slice read inside the
    fusion that contracts it is no buffer at all."""
    assert serving.whole_layer_ops(_HLO, layer_bytes) == want

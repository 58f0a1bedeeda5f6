"""The serving engine's memory ledger (mxnet_tpu/serving/engine.py,
docs/observability.md "Device memory"): what a bucket's cache reserves
by kind and what a group wrote of it, what each compiled program needs
beside its arguments and what the device says it holds at a group's
end.  All of it host arithmetic over shapes and one ``memory_stats()``
a group: on the CPU, whose ``memory_stats()`` is None, the
``memory_*`` fields are absent, so the chip's answer is played by a
stand-in here.
"""

import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine as mx_engine, serving, telemetry
from mxnet_tpu.gluon.model_zoo import gpt, jamba, mimo_v2
from mxnet_tpu.serving import engine as serving_engine

B = 4
# three prompts of unequal lengths that want unequal answers: the group
# runs 4 decode steps, row 0 is live in all of them, row 1 in one, row
# 2 in none
PROMPTS = ([5, 6, 7], [1, 2, 3, 4, 5, 6], [8, 9])
ANSWERS = [5, 2, 1]
HELD = [3 + 4, 6 + 1, 2]        # positions each row wrote


def _gpt():
    """Two layers of 2 heads of 16, float32, a window of 16: a position
    of a row is 2 x 2 x 16 x 4 B in each of the two stacks."""
    mx.random.seed(7)
    net = gpt.gpt_tiny(scan_layers=True, max_length=16)
    net.initialize(init=mx.init.Xavier())
    net(mx.nd.array(np.zeros((1, 4), np.float32)))
    each = 2 * 2 * 16 * 4
    return net, {"stack": 2 * each * B * 16, "state": 0, "counter": 0,
                 "written": 2 * each * sum(HELD)}


def _mimo():
    """Two full layers (one key head of 24 + 16, a window of 32) and
    five window layers (two key heads, rings of 4 slots: two rows
    wrapped theirs, which counts as full, the third holds 2), the six
    expert layers' counters (all 8 experts held: 8 + 3 ints a phase)
    and the prefill's two."""
    net = mimo_v2.mimo_v2_tiny()
    net.initialize(init=mx.init.Normal(0.02))
    full, ring = 2 * 1 * (24 + 16) * 4, 5 * 2 * (24 + 16) * 4
    counters = 6 * 2 * 11 * 4 + 2 * 4
    return net, {"stack": B * (full * 32 + ring * 4), "state": 0,
                 "counter": counters,
                 "written": full * sum(HELD) + ring * (4 + 4 + 2) + counters}


def _jamba():
    """One attention layer (one key head of 16; the window of 64 in one
    lane block of 128 slots) and four Mamba layers: a row's states are
    4 x 16 x 128 float32 and its tails 4 x 3 x 128, whatever its
    length; seven counters."""
    net = jamba.jamba_tiny(num_layers=5, attn_period=5, attn_offset=2)
    net.initialize(init=mx.init.Normal(0.02))
    each, row = 2 * 1 * 1 * 16 * 4, 4 * 16 * 128 * 4 + 4 * 3 * 128 * 4
    return net, {"stack": each * B * 128, "state": row * B,
                 "counter": 7 * 4,
                 "written": each * sum(HELD) + row * 3 + 7 * 4}


@pytest.fixture(scope="module", params=[_gpt, _mimo, _jamba],
                ids=["gpt", "rings", "states"])
def served(request):
    """(engine, the family's sizes by hand, the group's timings, the
    ``program_memory`` events and ``serve.compile`` spans of its two
    compiles)."""
    net, want = request.param()
    telemetry.reset()
    mx_engine.watch_compiles()
    eng = serving.ServingEngine(net, batch_buckets=(B,))
    _, timings = eng.serve_group(list(PROMPTS), ANSWERS)
    events = [r for r in telemetry._RECENT
              if r.get("event") == "program_memory"]
    spans = [s for s in telemetry.startup_spans()
             if s[0] in ("serve.compile", "startup.engine")]
    yield eng, want, timings, events, spans
    telemetry.reset()


# -- what a cache reserves, and what a group wrote of it -----------------------

def test_reserved_is_the_caches_bytes_by_kind(served):
    eng, want, timings, _, _ = served
    assert timings["cache_bytes_reserved"] \
        == sum(c.nbytes for c in eng.init_cache(B)) \
        == (timings["cache_stack_bytes"] + timings["cache_state_bytes"]
            + timings["cache_counter_bytes"])
    assert {k: timings[f"cache_{k}_bytes"] for k in
            ("stack", "state", "counter")} \
        == {k: want[k] for k in ("stack", "state", "counter")}


def test_written_is_the_rows_prompts_and_answers(served):
    _, want, timings, _, _ = served
    assert timings["cache_bytes_written"] == want["written"]
    assert 0 < timings["cache_bytes_written"] \
        <= timings["cache_bytes_reserved"]


def test_the_weights_are_counted_a_buffer_once(served):
    eng, _, timings, _, spans = served
    buffers = {w.unsafe_buffer_pointer(): w.nbytes for w in eng._weights}
    assert timings["weights_bytes"] == sum(buffers.values())
    assert timings["weights_leaves"] == len(eng._weights)
    assert telemetry.REGISTRY.gauge("memory.weights_bytes").value \
        == timings["weights_bytes"]
    (startup,) = [s for s in spans if s[0] == "startup.engine"]
    assert startup[4]["weights_bytes"] == timings["weights_bytes"]
    # the same leaf twice in the tuple is one buffer
    assert serving_engine._held(list(eng._weights) * 2) \
        == {eng._device: timings["weights_bytes"]}


def test_where_the_buffers_lie_is_no_field(served):
    """A pointer is a host handle on the TPU runtime, new every
    process: it counts a shared weight buffer once and is recorded
    nowhere."""
    _, _, timings, events, spans = served
    for fields in [timings, *events, *(s[4] for s in spans)]:
        assert not [k for k in fields if "addr" in k]
    assert not [k for k in telemetry.MEMORY_FIELDS if "addr" in k]


def test_under_a_mesh_the_bytes_are_one_devices(mesh8, monkeypatch):
    """GPT's stacks lie with their heads over ``tp``: a device holds
    half of each, and of the weights its own shards.  What the first
    device says it holds is held against its own weights, whichever
    device's sum ``weights_bytes`` reports as the largest."""
    net, want = _gpt()
    monkeypatch.setattr(serving_engine, "_device_memory",
                        lambda device: {"memory_in_use_bytes": 10 ** 9})
    eng = serving.ServingEngine(net, batch_buckets=(B,),
                                mesh=mesh8(tp=2, dp=4))
    _, timings = eng.serve_group(list(PROMPTS), ANSWERS)
    assert timings["cache_bytes_reserved"] == want["stack"] // 2
    assert timings["cache_bytes_written"] == want["written"] // 2
    first = min(eng._weights[0].sharding.device_set, key=lambda d: d.id)
    assert eng._device == first
    held = serving_engine._held(eng._weights)
    assert set(held) == set(eng._weights[0].sharding.device_set)
    mine = {}
    for w in eng._weights:
        (shard,) = [s for s in w.addressable_shards if s.device == first]
        mine[shard.data.unsafe_buffer_pointer()] = shard.data.nbytes
    assert held[first] == sum(mine.values()) == eng._weights_here
    assert timings["weights_bytes"] == max(held.values()) \
        < sum(w.nbytes for w in eng._weights)
    assert timings["memory_unaccounted_bytes"] \
        == 10 ** 9 - held[first] - want["stack"] // 2
    # a later device that held more would not enter the first's sum
    eng._ledger["weights_bytes"] += 4096
    assert eng._account_group(B, np.asarray(HELD))[
        "memory_unaccounted_bytes"] == timings["memory_unaccounted_bytes"]


class _Table:
    """A family that states its cache in no `cache_shapes`: a row's
    logits are the table's row of its last token."""

    window, vocab = 16, 6

    def __init__(self):
        import jax.numpy as jnp

        self._table = jnp.eye(6, dtype=jnp.float32)

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        return self

    def weights(self):
        return (self._table,)

    def init_cache(self, B):
        import jax.numpy as jnp

        return (jnp.zeros((B,), jnp.int32),)

    def step(self, w, cache, pos, last, toks, live=None):
        import jax.numpy as jnp

        tok = jnp.take_along_axis(toks, last[:, None], axis=1)[:, 0]
        return (cache[0] + 1,), w[0][tok]


def test_a_cache_of_another_form_reports_neither():
    eng = serving.ServingEngine(_Table(), batch_buckets=(B,))
    outs, timings = eng.serve_group([[1, 2], [3]], 3)
    assert [o.tolist() for o in outs] == [[2, 2, 2], [3, 3, 3]]
    assert not [k for k in timings if k.startswith("cache_bytes")]
    assert timings["weights_bytes"] == 6 * 6 * 4


def test_a_cache_of_another_form_is_held_against_nothing(monkeypatch):
    """What the chip says is passed on; without the cache's bytes the
    ledger names no share of it."""
    monkeypatch.setattr(serving_engine, "_device_memory",
                        lambda device: {"memory_in_use_bytes": 4096})
    eng = serving.ServingEngine(_Table(), batch_buckets=(B,))
    _, timings = eng.serve_group([[1, 2], [3]], 3)
    assert timings["memory_in_use_bytes"] == 4096
    assert "memory_unaccounted_bytes" not in timings


# -- what a compiled program needs ---------------------------------------------

def test_a_program_memory_event_a_compiled_program(served):
    eng, _, timings, events, spans = served
    S = timings["bucket"][1]
    assert [(e["program"], e["B"], e["S"]) for e in events] \
        == [("prefill", B, S), ("decode", B, 1)]
    compiles = [s[4] for s in spans if s[0] == "serve.compile"]
    for event, attrs in zip(events, compiles):
        needs = eng.program_memory[event["B"], event["S"]]
        assert sorted(needs) == sorted(telemetry._PROGRAM_MEMORY)
        for name, value in needs.items():
            assert isinstance(value, int) and value >= 0
            assert event[name] == attrs[name] == value
        # the cache is donated: the program's outputs are its arguments'
        # own buffers, all of the cache at least
        assert needs["alias_size_in_bytes"] \
            >= timings["cache_bytes_reserved"]
        telemetry.validate_record(event)


def test_memory_of_compiled_is_what_the_train_steps_high_water_sums():
    from mxnet_tpu import gluon

    mx.random.seed(3)
    net = gluon.nn.Dense(8, in_units=16)
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.nd.array(np.ones((4, 16), np.float32))
    y = mx.nd.array(np.zeros((4, 8), np.float32))
    trainer.train_step(net, gluon.loss.L2Loss(), x, y)
    step = next(iter(trainer._captured_cache.values()))
    needs = telemetry.memory_of_compiled(step._compiled_for_stats())
    assert sorted(needs) == sorted(telemetry._PROGRAM_MEMORY)
    assert step.memory_high_water() == (
        needs["temp_size_in_bytes"] + needs["argument_size_in_bytes"]
        + needs["output_size_in_bytes"] - needs["alias_size_in_bytes"]) > 0



@pytest.mark.parametrize("analysis", [
    None, type("NoTemporaries", (), {"argument_size_in_bytes": 1,
                                     "output_size_in_bytes": 1})(),
    ValueError("no analysis on this backend")],
    ids=["none", "a_line_missing", "raises"])
def test_a_memory_analysis_that_gives_none_reads_none(analysis):
    """As `CapturedStep.memory_high_water` always took it: whatever the
    compiler does instead of answering, the ledger has no entry and
    nothing raises."""
    class Compiled:
        def memory_analysis(self):
            if isinstance(analysis, Exception):
                raise analysis
            return analysis

    assert telemetry.memory_of_compiled(Compiled()) is None


def test_a_memory_analysis_without_aliases_reads_them_zero():
    class Compiled:
        def memory_analysis(self):
            return type("Plain", (), {"argument_size_in_bytes": 5,
                                      "output_size_in_bytes": 3,
                                      "temp_size_in_bytes": 2})()

    assert telemetry.memory_of_compiled(Compiled()) == {
        "argument_size_in_bytes": 5, "output_size_in_bytes": 3,
        "temp_size_in_bytes": 2, "alias_size_in_bytes": 0,
        "generated_code_size_in_bytes": 0}


# -- what the chip says --------------------------------------------------------

def test_off_the_chip_the_memory_fields_are_absent(served):
    eng, _, timings, events, spans = served
    assert eng._device.memory_stats() is None       # the CPU's answer
    assert not [k for k in timings if k.startswith("memory_")]
    assert not [k for e in events for k in e if k in ("in_use", "peak")]
    assert not [k for s in spans for k in s[4]
                if k.startswith(("in_use", "peak"))]
    outs, again = eng.serve_group(list(PROMPTS), ANSWERS)
    assert [len(o) for o in outs] == ANSWERS
    assert again["cache_bytes_written"] == timings["cache_bytes_written"]


def test_on_a_chip_the_ledger_is_held_against_what_it_says(monkeypatch):
    """The runtime's answer played by a stand-in: in use 1,000,000 B
    more than the engine's weights and the group's cache."""
    net, want = _gpt()
    stats = {"bytes_in_use": 0, "peak_bytes_in_use": 3_000_000,
             "bytes_limit": 16_000_000, "largest_free_block_bytes": 5_000,
             "num_allocs": 77, "bytes_reserved": 0}
    monkeypatch.setattr(
        serving_engine, "_device_memory",
        lambda device: {name: stats[key] for name, key
                        in serving_engine._MEMORY_STATS})
    telemetry.reset()
    eng = serving.ServingEngine(net, batch_buckets=(B,))
    held = eng._ledger["weights_bytes"] + want["stack"]
    stats["bytes_in_use"] = held + 1_000_000
    _, timings = eng.serve_group(list(PROMPTS), ANSWERS)
    assert timings["memory_in_use_bytes"] == held + 1_000_000
    assert timings["memory_unaccounted_bytes"] == 1_000_000
    assert (timings["memory_peak_bytes"], timings["memory_limit_bytes"],
            timings["memory_largest_free_block_bytes"],
            timings["memory_num_allocs"]) \
        == (3_000_000, 16_000_000, 5_000, 77)
    # a ledger that counts a buffer twice reads below zero, unclamped
    stats["bytes_in_use"] = held - 4096
    _, timings = eng.serve_group(list(PROMPTS), ANSWERS)
    assert timings["memory_unaccounted_bytes"] == -4096
    spans = {s[0]: s[4] for s in telemetry.startup_spans()
             if s[0] in ("serve.compile", "startup.engine")}
    assert spans["startup.engine"]["peak"] == 3_000_000
    assert {"in_use_before", "peak_before", "in_use"} \
        <= set(spans["startup.engine"])
    assert spans["serve.compile"]["peak"] == 3_000_000
    assert "in_use" in spans["serve.compile"]
    assert telemetry.REGISTRY.gauge("memory.unaccounted_bytes").value \
        == -4096
    telemetry.reset()


def test_telemetry_off_turns_the_ledger_off(monkeypatch):
    net, _ = _gpt()
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")

    def never(*_):
        raise AssertionError("the ledger is off")

    monkeypatch.setattr(serving_engine, "_held", never)
    monkeypatch.setattr(serving_engine, "_device_memory", never)
    monkeypatch.setattr(telemetry, "memory_of_compiled", never)
    eng = serving.ServingEngine(net, batch_buckets=(B,))
    outs, timings = eng.serve_group(list(PROMPTS), ANSWERS)
    assert [len(o) for o in outs] == ANSWERS
    assert not [k for k in timings if k in telemetry.MEMORY_FIELDS]
    assert eng.program_memory == {} and eng._cache_ledgers == {B: None}
    eng._swap(eng._weights)
    # an engine made without a ledger serves on without one
    monkeypatch.setenv("MXTPU_TELEMETRY", "1")
    outs, timings = eng.serve_group(list(PROMPTS), ANSWERS)
    assert [len(o) for o in outs] == ANSWERS
    assert not [k for k in timings if k in telemetry.MEMORY_FIELDS]


# -- the records ---------------------------------------------------------------

def test_the_fields_reach_the_futures_record_and_the_request_record(served):
    eng, want, timings, _, _ = served
    telemetry.reset()
    batcher = serving.ContinuousBatcher(eng, max_delay_ms=150, max_batch=B)
    try:
        futures = [batcher.submit(p, k) for p, k in zip(PROMPTS, ANSWERS)]
        records = [f.result(timeout=120) for f in futures]
    finally:
        batcher.close()
    carried = [k for k in telemetry.MEMORY_FIELDS if k in timings]
    assert {"weights_bytes", "cache_bytes_reserved",
            "cache_bytes_written"} <= set(carried)
    requests = telemetry.recent_requests()
    assert len(requests) == len(records) == 3
    for rec, request in zip(records, requests):
        telemetry.validate_record(request)
        for k in carried:
            assert k in rec and k in request
        assert rec["cache_bytes_written"] \
            == request["cache_bytes_written"] == want["written"]
    group = [s for s in telemetry.startup_spans() if s[0] == "serve.group"]
    assert group[-1][4]["cache_bytes_written"] == want["written"]
    assert group[-1][4]["cache_bytes_reserved"] \
        == timings["cache_bytes_reserved"]


def test_the_validator_holds_the_fields_to_numbers():
    telemetry.reset()
    telemetry.request_record(queue_us=1.0, prefill_us=2.0,
                             decode_us_per_token=3.0, bucket=(4, 8),
                             padded_fraction=0.5, cache_bytes_written=10,
                             memory_unaccounted_bytes=-5)
    (rec,) = telemetry.recent_requests()
    telemetry.validate_record(rec)
    for bad in ({"cache_bytes_written": -1}, {"memory_in_use_bytes": "9"},
                {"weights_leaves": True}):
        with pytest.raises(ValueError, match=next(iter(bad))):
            telemetry.validate_record(dict(rec, **bad))
    telemetry.reset()


# -- what it costs -------------------------------------------------------------

def test_the_group_end_accounting_costs_microseconds(served):
    """Alone, on the GPT engine: under 100 us a group here (15 on an
    idle core; the benchmark's shortest round is 620,000)."""
    eng = served[0]
    held = np.asarray(HELD)
    best = float("inf")
    for _ in range(5):          # the least of five: neighbours burst
        t0 = time.perf_counter()
        for _ in range(200):
            eng._account_group(B, held)
        best = min(best, (time.perf_counter() - t0) / 200)
    assert best < 100e-6, f"{best * 1e6:.1f} us a group"


# -- the report ----------------------------------------------------------------

def test_trace_report_has_a_memory_section(tmp_path, monkeypatch):
    """From a log alone: the weights, the cache by kind and the share
    written over the groups, each program's temporaries, what the chip
    held at a group's end, and the phase that made the peak."""
    import io
    import os
    import sys

    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    telemetry.event("program_memory", program="prefill", B=4, S=8,
                    argument_size_in_bytes=3_000_000_000,
                    output_size_in_bytes=1_000_000_000,
                    alias_size_in_bytes=1_000_000_000,
                    temp_size_in_bytes=2_500_000_000,
                    generated_code_size_in_bytes=0,
                    in_use=3_000_000_000, peak=5_000_000_000)
    for group, written in ((1.0, 250_000_000), (2.0, 750_000_000)):
        for _ in range(2):      # two requests of each group
            telemetry.request_record(
                queue_us=1.0, prefill_us=group, decode_us_per_token=3.0,
                bucket=(4, 8), padded_fraction=0.5, collect_us=group,
                weights_bytes=2_000_000_000, weights_leaves=16,
                cache_bytes_reserved=1_000_000_000,
                cache_stack_bytes=900_000_000,
                cache_state_bytes=99_999_000, cache_counter_bytes=1_000,
                cache_bytes_written=written,
                memory_in_use_bytes=3_300_000_000,
                memory_peak_bytes=5_000_000_000,
                memory_limit_bytes=16_000_000_000,
                memory_largest_free_block_bytes=9_000_000_000,
                memory_unaccounted_bytes=300_000_000)
    telemetry.reset()       # closes the sink
    monkeypatch.delenv("MXTPU_TELEMETRY_PATH")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    records, bad = trace_report.read_records(path)
    assert bad == 0 and trace_report.validate_all(records) == []
    out = io.StringIO()
    trace_report.report_run("r", records, out)
    text = out.getvalue()
    assert "  memory:" in text
    assert "weights 2.000 GB in 16 leaves" in text
    assert "cache of bucket 4x8: 1.000 GB reserved" in text
    assert "50.00 % of it written over 2 groups" in text
    assert "prefill 4x8" in text and "2.500 GB" in text
    assert "unaccounted 0.300 GB (9.09 %)" in text
    # the compile already read the peak the groups end on
    assert "peak 5.000 GB, first read at the end of: the compile of " \
           "prefill 4x8" in text
    # in the process itself the engine's span says what was there before
    out = io.StringIO()
    trace_report.report_memory(
        [r for r in records if r.get("event") == "program_memory"],
        [r for r in records if r["type"] == "request"], out,
        spans=[("startup.engine", 0.0, 1.0, 0,
                {"peak_before": 5_000_000_000, "peak": 5_000_000_000})])
    assert "first read at the end of: before the engine" in out.getvalue()

"""The start-up timeline (PR 34): the first spans of a process's life are
kept with both clock reads and their thread, JAX's own compile events
enter as spans by program name, and the paths where a process's first
seconds go (import, parameters, engine, trainer, compiles) each close a
span of a documented name (docs/observability.md, "Process start-up").
"""

import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, gluon, profiler, serving, telemetry
from mxnet_tpu.gluon.model_zoo import gpt
from mxnet_tpu.test_utils import cpu_child_env

PROMPTS = [np.arange(3), np.arange(5), np.arange(9)]

# what a fresh process does: import, one jitted function twice with the
# in-memory caches dropped between (so the second asks the persistent
# cache), then everything it kept on one line
CHILD = r"""
import json, sys, time
a = time.perf_counter()
import mxnet_tpu
b = time.perf_counter()
subpackages = sorted(m for m in ("mxnet_tpu.gluon", "mxnet_tpu.serving",
                                 "mxnet_tpu.ops", "jax") if m in sys.modules)
import jax, jax.numpy as jnp
from mxnet_tpu import engine, telemetry
first = telemetry.startup_spans()[:2]
cache_dir = engine.ensure_compile_cache()
def probe(x):
    return jnp.sin(x) * 3 + 1
f = jax.jit(probe)
f(jnp.ones(7)).block_until_ready()
jax.clear_caches()
f(jnp.ones(7)).block_until_ready()
print(json.dumps({
    "around_import": [a, b], "subpackages": subpackages, "first": first,
    "cache_dir": cache_dir, "spans": telemetry.startup_spans(),
    "counters": telemetry.REGISTRY.snapshot(),
    "events": telemetry.event_counts()}))
"""


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    """A fresh process with a compile cache of its own (the CPU backend
    persists only where the variable is set: engine.py)."""
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    out = subprocess.run(
        [sys.executable, "-c", CHILD], capture_output=True, text=True,
        timeout=300, env=cpu_child_env(
            JAX_COMPILATION_CACHE_DIR=cache,
            JAX_ENABLE_COMPILATION_CACHE="true"))
    assert out.returncode == 0, out.stderr[-2000:]
    return dict(json.loads(out.stdout.splitlines()[-1]), cache=cache)


@pytest.fixture
def fresh():
    """An empty timeline, and the compile listeners on."""
    telemetry.reset()
    telemetry.REGISTRY.reset()
    engine.watch_compiles()
    yield
    telemetry.reset()
    telemetry.REGISTRY.reset()


def _named(prefix):
    return [s for s in telemetry.startup_spans()
            if s[0] == prefix or (prefix.endswith(".")
                                  and s[0].startswith(prefix))]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


# -- a fresh process -----------------------------------------------------------

def test_the_import_span_is_first_and_covers_the_subpackages(child):
    before, (name, t0, t1, _thread, attrs) = child["first"]
    a, b = child["around_import"]
    assert name == "startup.import" and attrs is None
    assert a <= t0 <= t1 <= b
    # what the process did before the package's first line has a span of
    # its own, from the process's start (/proc) to the import's
    assert before[0] == "startup.before_import" and before[2] == t0
    assert 0.0 < a - before[1] < 60.0
    # jax and every subpackage came in under it: next to nothing of the
    # import statement's time lies outside the span
    assert child["subpackages"] == ["jax", "mxnet_tpu.gluon",
                                    "mxnet_tpu.ops", "mxnet_tpu.serving"]
    assert (t1 - t0) > 0.95 * (b - a)


def test_the_second_compile_reads_the_cache_and_the_counters_agree(child):
    assert child["cache_dir"] == child["cache"]
    probes = [s for s in child["spans"] if s[0] == "compile.backend"
              and s[4]["program"] == "jit_probe"]
    assert [s[4]["cache"] for s in probes] == ["miss", "hit"]
    backend = [s for s in child["spans"] if s[0] == "compile.backend"]
    hits = sum(s[4]["cache"] == "hit" for s in backend)
    misses = sum(s[4]["cache"] == "miss" for s in backend)
    c = child["counters"]
    assert c["compile.programs"] == len(backend) == hits + misses
    assert c["compile.cache_hits"] == hits >= 1
    assert c["compile.cache_misses"] == misses >= 1
    assert c["compile.seconds"] == pytest.approx(
        sum(s[2] - s[1] for s in backend), rel=1e-6)
    assert child["events"]["compile"] == len(backend)


def test_the_backend_span_is_under_the_cache_setup(child):
    backend = [s for s in child["spans"] if s[0] == "startup.backend"]
    assert len(backend) == 1 and backend[0][2] >= backend[0][1]


# -- the store -----------------------------------------------------------------

def test_a_closed_scope_is_kept_with_both_clock_reads_and_its_thread(fresh):
    before = time.perf_counter()
    with profiler.scope("serve.decode.readback", step=3) as sp:
        pass
    after = time.perf_counter()
    assert telemetry.startup_spans() == [
        ("serve.decode.readback", sp.t0, sp.t1, threading.get_ident(),
         {"step": 3})]
    assert before <= sp.t0 <= sp.t1 <= after
    with profiler.scope("serve.group") as sp:
        sp.set(B=4)
    assert telemetry.startup_spans()[-1][4] == {"B": 4}
    with profiler.scope("serve.finish"):
        pass
    assert telemetry.startup_spans()[-1][4] is None


def test_a_span_keeps_the_thread_that_closed_it(fresh):
    seen = []

    def work():
        seen.append(threading.get_ident())
        with profiler.scope("startup.import.pallas"):
            pass

    t = threading.Thread(target=work)
    t.start()
    t.join()
    (span,) = telemetry.startup_spans()
    assert span[3] == seen[0] != threading.get_ident()


def test_keep_span_enters_the_same_list(fresh):
    with profiler.scope("a"):
        pass
    telemetry.keep_span("compile.backend", 1.0, 2.5, program="jit_f",
                        cache="hit")
    telemetry.keep_span("startup.import", 0.5, 0.75)
    assert [s[0] for s in telemetry.startup_spans()] == [
        "a", "compile.backend", "startup.import"]
    assert telemetry.startup_spans()[1][1:] == (
        1.0, 2.5, threading.get_ident(),
        {"program": "jit_f", "cache": "hit"})
    assert telemetry.startup_spans()[2][4] is None


def test_the_store_stops_at_its_bound_and_stays_as_it_is(
        fresh, monkeypatch):
    assert telemetry.STARTUP_SPANS >= 4096
    monkeypatch.setattr(telemetry, "_STARTUP_ROOM", 8)
    for i in range(11):
        with profiler.scope("serve.decode.sample", step=i):
            pass
    telemetry.keep_span("compile.trace", 0.0, 1.0, program="f")
    spans = telemetry.startup_spans()
    assert [s[4]["step"] for s in spans] == list(range(8))
    # full: no room is left, so a closed scope is one comparison, and
    # nothing counts what came later (a reader that finds the store
    # full knows where the timeline ends)
    assert telemetry._STARTUP_ROOM == 0
    assert telemetry.REGISTRY.snapshot() == {}


def test_after_the_bound_a_closed_scope_allocates_nothing(
        fresh, monkeypatch):
    monkeypatch.setattr(telemetry, "_STARTUP_ROOM", 4)

    def burst(n):
        for i in range(n):
            with profiler.scope("serve.decode.readback", step=i):
                pass

    burst(100)                  # fills the store
    kept = telemetry.startup_spans()
    blocks = sys.getallocatedblocks()
    burst(5000)
    grown = sys.getallocatedblocks() - blocks
    assert telemetry.startup_spans() == kept and len(kept) == 4
    assert grown < 50, f"{grown} blocks kept by 5,000 scopes"


def test_threads_lose_no_span_at_the_bound(fresh, monkeypatch):
    # more threads than cores, switching often: the store fills whole
    # (no span is half in it), and the check-then-append race lets at
    # most one span a thread past the bound
    room, threads, each = 1000, 16, 500
    monkeypatch.setattr(telemetry, "_STARTUP_ROOM", room)

    def work():
        for i in range(each):
            with profiler.scope("serve.decode.sample", step=i):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    kept = telemetry.startup_spans()
    assert room <= len(kept) < room + threads
    assert all(s[0] == "serve.decode.sample" and 0 <= s[4]["step"] < each
               for s in kept)


def test_a_closed_scope_reaches_the_open_step_and_the_timeline(fresh):
    acc = telemetry.step_begin("captured")
    with profiler.scope("captured_step") as sp:
        pass
    telemetry.on_scope("captured_commit", 2e-4)     # a bare duration
    assert acc.scopes == {"captured_step": sp.t1 - sp.t0,
                          "captured_commit": 2e-4}
    telemetry.step_abort(acc)
    assert [s[:3] for s in telemetry.startup_spans()] == [
        ("captured_step", sp.t0, sp.t1)]


def test_telemetry_off_keeps_nothing(fresh, monkeypatch):
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    telemetry.reset()
    with profiler.scope("train_step"):
        pass
    telemetry.keep_span("compile.trace", 0.0, 1.0)
    assert telemetry.startup_spans() == []


def test_reset_clears_the_timeline(fresh):
    with profiler.scope("train_step"):
        pass
    assert telemetry.startup_spans()
    telemetry.reset()
    assert telemetry.startup_spans() == []


@pytest.mark.parametrize("store", ["filling", "full"])
def test_a_scope_still_costs_next_to_nothing(fresh, monkeypatch, store):
    # tests/test_tracing_spans.py's bound, with the store in both states
    best = float("inf")
    for _ in range(40):         # the least of many short bursts: under
        telemetry.reset()       # the suite's load neighbours burst too
        if store == "full":
            monkeypatch.setattr(telemetry, "_STARTUP_ROOM", 1)
        t0 = time.perf_counter()
        for i in range(250):
            with profiler.scope("serve.decode.readback", step=i):
                pass
        best = min(best, (time.perf_counter() - t0) / 250)
    assert len(telemetry.startup_spans()) == (1 if store == "full"
                                              else 250)
    assert best < 5e-6, f"{best * 1e6:.2f} us a scope"


def test_a_kept_span_leaves_the_collector_nothing_to_count(fresh):
    # a survivor a scope (a tuple, a dict) would run a generation-0
    # pass every few hundred scopes, and the full collection those lead
    # to lands in a decode step: the store keeps names and numbers in
    # one flat list, and the collector never runs
    import gc

    passes = []

    def seen(phase, info):
        if phase == "start":
            passes.append(info["generation"])

    for i in range(100):        # whatever the first scopes set up
        with profiler.scope("serve.decode.readback", step=i):
            pass
    gc.collect()
    gc.callbacks.append(seen)
    try:
        for i in range(5000):
            with profiler.scope("serve.decode.readback", step=i):
                pass
            telemetry.keep_span("compile.trace", 0.0, 1.0, program="f")
    finally:
        gc.callbacks.remove(seen)
    assert len(telemetry.startup_spans()) == 10100
    assert passes == []


# -- compile events ------------------------------------------------------------

def test_a_fresh_jit_gives_its_three_spans_by_program(fresh):
    import jax
    import jax.numpy as jnp

    def startup_probe(x):
        return jnp.cos(x) * 2 - 1

    x = jnp.ones(5)             # compiles its own small programs
    telemetry.reset()
    telemetry.REGISTRY.reset()
    jax.jit(startup_probe)(x).block_until_ready()
    # (jnp's own functions are traced inside the probe's trace)
    spans = [s for s in telemetry.startup_spans()
             if "startup_probe" in s[4]["program"]]
    assert [(s[0], s[4]["program"]) for s in spans] == [
        ("compile.trace", "startup_probe"),
        ("compile.lower", "jit_startup_probe"),
        ("compile.backend", "jit_startup_probe")]
    # in order, on this thread, on perf_counter's clock, none inside
    # another; tests run with the persistent cache off
    for a, b in zip(spans, spans[1:]):
        assert a[1] <= a[2] <= b[1] <= b[2] <= time.perf_counter()
    assert {s[3] for s in spans} == {threading.get_ident()}
    assert spans[2][4]["cache"] == "off"
    secs = spans[2][2] - spans[2][1]
    snap = telemetry.REGISTRY.snapshot()
    assert snap["compile.programs"] == 1
    assert snap["compile.seconds"] == pytest.approx(secs)
    assert "compile.cache_hits" not in snap
    assert "compile.cache_misses" not in snap
    # the operator's recompile alarm: the count moved, the record says
    # which program
    assert telemetry.event_counts()["compile"] == 1
    rec = [r for r in telemetry._RECENT if r.get("event") == "compile"][-1]
    assert rec["program"] == "jit_startup_probe" and rec["cache"] == "off"
    assert rec["secs"] == pytest.approx(secs, abs=1e-6)


def test_watch_compiles_registers_once_and_configures_no_cache(fresh):
    import jax
    from jax._src import monitoring

    before = (len(monitoring.get_event_listeners()),
              len(monitoring.get_event_time_span_listeners()),
              jax.config.jax_compilation_cache_dir)
    engine.watch_compiles()
    engine.watch_compiles()
    assert before == (len(monitoring.get_event_listeners()),
                      len(monitoring.get_event_time_span_listeners()),
                      jax.config.jax_compilation_cache_dir)


# -- where set-up's seconds go -------------------------------------------------

def _tiny_gpt():
    net = gpt.GPTModel(vocab_size=128, units=32, num_layers=2, num_heads=2,
                       max_length=64, dropout=0.0, scan_layers=True)
    net.initialize()
    return net


def test_a_serving_engine_gives_its_spans_and_compiles_once(fresh):
    net = _tiny_gpt()
    telemetry.reset()
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    (built,) = _named("startup.engine")
    assert all(_inside(s, built) for s in telemetry.startup_spans())
    eng.serve_group(PROMPTS, 3)
    compiles = _named("serve.compile")
    # beside them each span carries what its program needs on the
    # device and, on a chip, what that held once it was compiled
    # (tests/test_serving_memory.py): those, and nothing else
    needs = set(telemetry._PROGRAM_MEMORY)
    assert [{k: v for k, v in s[4].items()
             if k not in needs | {"in_use", "peak"}}
            for s in compiles] == [
        {"B": 4, "S": 16, "program": "prefill"},
        {"B": 4, "S": 1, "program": "decode"}]
    assert all(needs <= set(s[4]) for s in compiles)
    (prefill_dispatch,) = _named("serve.prefill.dispatch")
    assert _inside(compiles[0], prefill_dispatch)
    for compiled, program in zip(compiles, ("serve_prefill",
                                            "serve_decode")):
        inside = [s for s in _named("compile.") if _inside(s, compiled)]
        got = {(s[0], s[4]["program"]) for s in inside}
        assert {("compile.trace", program),
                ("compile.lower", "jit_" + program),
                ("compile.backend", "jit_" + program)} <= got
    # the second group: every program is there, nothing compiles
    n = len(_named("compile."))
    events = telemetry.event_counts()["compile"]
    eng.serve_group(PROMPTS, 3)
    assert len(_named("compile.")) == n
    assert len(_named("serve.compile")) == 2
    assert telemetry.event_counts()["compile"] == events


def test_warmup_is_its_programs_spans_and_no_event_of_its_own(fresh):
    net = _tiny_gpt()
    eng = serving.ServingEngine(net, batch_buckets=(2,), prefill_floor=32)
    telemetry.reset()
    eng.warmup()
    assert sorted((s[4]["S"], s[4]["program"])
                  for s in _named("serve.compile")) == [
        (1, "decode"), (32, "prefill"), (64, "prefill")]
    assert "serving_warmup" not in telemetry.event_counts()
    assert telemetry.event_counts()["compile"] >= 3


def test_a_trainer_gives_its_spans_and_compiles_in_its_first_step(fresh):
    net = _tiny_gpt()
    net.hybridize()
    telemetry.reset()
    trainer = gluon.Trainer(net.collect_params(), "adamw",
                            {"learning_rate": 1e-3})
    (built,) = _named("startup.trainer")
    assert built[4] is None
    # (the client's start, in the process's first trainer alone)
    assert all(_inside(s, built) for s in _named("startup.backend"))
    loss_fn = gpt.GPTLMLoss()
    x = mx.nd.array(np.random.RandomState(0).randint(
        0, 128, (2, 64)).astype("float32"))
    trainer.train_step(net, loss_fn, x, x, batch_size=1)
    state = _named("startup.optimizer")
    assert len(state) == 1 and state[0][4]["leaves"] == len(
        [p for p in net.collect_params().values() if p.grad_req != "null"])
    (compiled,) = _named("train.compile")
    (dispatch,) = _named("captured_step")
    (whole,) = _named("train_step")
    assert _inside(compiled, dispatch) and _inside(dispatch, whole)
    assert _inside(state[0], whole)
    got = {(s[0], s[4]["program"]) for s in _named("compile.")
           if _inside(s, compiled)}
    assert {("compile.trace", "train_step"),
            ("compile.lower", "jit_train_step"),
            ("compile.backend", "jit_train_step")} <= got
    # a first step also compiles its small eager programs (the key
    # split); from the second step on nothing does
    trainer.train_step(net, loss_fn, x, x, batch_size=1)
    n = len(_named("compile."))
    events = telemetry.event_counts()["compile"]
    for _ in range(2):
        trainer.train_step(net, loss_fn, x, x, batch_size=1)
    assert len(_named("compile.")) == n
    assert telemetry.event_counts()["compile"] == events
    assert len(_named("train.compile")) == 1
    assert len(_named("startup.optimizer")) == 1


def test_initialize_cast_and_set_data_are_parameter_spans(fresh):
    net = gpt.GPTModel(vocab_size=128, units=32, num_layers=2, num_heads=2,
                       max_length=64, dropout=0.0, scan_layers=True)
    params = net.collect_params()
    net.initialize()
    net.cast("bfloat16")
    some = list(params.values())[0]
    value = mx.nd.ones(some.shape, dtype="bfloat16")
    some.set_data(value)
    spans = _named("startup.params")
    assert [s[4]["what"] for s in spans] == ["initialize", "cast",
                                             "set_data"]
    assert spans[0][4]["leaves"] == len(params)
    # the outermost cast is the span; the blocks below it ran inside
    assert spans[1][4]["dtype"] == "bfloat16"
    assert all("bfloat16" in str(p.dtype) for p in params.values())
    assert spans[2][4]["bytes"] == 2 * int(np.prod(some.shape))
    assert np.all(some.data().asnumpy().astype("float32") == 1.0)


# -- tools/trace_report.py -----------------------------------------------------

def _trace_report():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, path


def test_trace_report_splits_a_timeline_into_its_categories():
    import io

    report, _ = _trace_report()
    me, other = 7, 8
    spans = [
        ("startup.before_import", 0.5, 2.0, me, None),
        ("startup.import", 2.0, 6.0, me, None),
        ("startup.import.pallas", 3.0, 5.0, other, None),
        ("compile.backend", 7.0, 8.0, me,
         {"program": "jit_convert_element_type", "cache": "hit"}),
        ("startup.params", 6.0, 9.0, me, {"what": "cast"}),
        ("compile.trace", 13.0, 14.0, me, {"program": "serve_prefill"}),
        ("compile.backend", 15.0, 17.5, me,
         {"program": "jit_serve_prefill", "cache": "miss"}),
        ("serve.compile", 12.5, 18.0, me, {"B": 4, "S": 16}),
        ("serve.prefill.dispatch", 12.0, 20.0, me, None),
        ("some.other.span", 20.0, 21.0, me, None),
        ("train_step", 22.0, 23.0, me, None),
    ]
    secs = report.startup_seconds(spans)
    assert secs == pytest.approx({
        "before_import": 1.5, "import": 4.0, "params": 2.0, "trace_lower": 1.0 + 2.0,
        "compile": 1.0 + 2.5, "warm_run": 2.5 + 1.0,
        "unattributed": 21.0 - 16.0})
    assert sum(secs.values()) == pytest.approx(23.0 - 0.5)
    # only what closed by ``until``, and the rest runs to it
    early = report.startup_seconds(spans, until=10.0)
    assert early["import"] == 4.0 and early["warm_run"] == 0.0
    assert early["unattributed"] == pytest.approx(1.0)
    out = io.StringIO()
    report.report_startup([], out, spans=spans)
    text = out.getvalue()
    assert "start-up (11 spans kept)" in text
    assert "2 backend compiles or cache loads, 3.500 s (1 hit, 1 miss, " \
           "0 off)" in text
    assert text.index("jit_serve_prefill") \
        < text.index("jit_convert_element_type")


def test_trace_report_names_the_programs_of_a_logs_compile_events(
        fresh, monkeypatch, tmp_path):
    import jax
    import jax.numpy as jnp

    _, script = _trace_report()
    path = str(tmp_path / "ev.jsonl")
    x = jnp.ones(3)
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()

    def report_probe(v):
        return jnp.tanh(v) + 2

    jax.jit(report_probe)(x).block_until_ready()
    telemetry.reset()
    r = subprocess.run([sys.executable, script, path, "--validate"],
                       env=cpu_child_env(), capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "1 records validate" in r.stdout
    assert "compiles: 1 backend compiles or cache loads" in r.stdout
    assert "jit_report_probe" in r.stdout and " off" in r.stdout

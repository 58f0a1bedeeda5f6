"""The program's spans on the profiler's clock (PR 24).

A ``jax.profiler`` session that this test opens itself (not
`profiler.start_xla_trace`) must show every span of serving and of the
captured step, nested as docs/observability.md states and with their
attributes; ``timings`` carries the per-step split taken from those
spans' own clock reads; the three compiled programs carry their names
and named scopes; and a `profiler.scope` with no session live costs next
to nothing.
"""

import glob
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, profiler, serving
from mxnet_tpu.gluon import captured
from mxnet_tpu.gluon.model_zoo import gpt

STEPS = 4
PROMPTS = [np.arange(3), np.arange(5), np.arange(9)]

# span -> (parent, attributes it must carry)
SERVE_SPANS = {
    "serve.collect": (None, {"n": 3}),
    "serve.group": (None, {"B": 4, "S": 16, "steps": STEPS,
                           "generation": 0}),
    "serve.prefill.dispatch": ("serve.group", {}),
    "serve.prefill.readback": ("serve.group", {}),
    "serve.decode.sample": ("serve.group", {"step": 0}),
    "serve.decode.dispatch": ("serve.group", {"step": 0}),
    "serve.decode.readback": ("serve.group", {"step": 0}),
    "serve.finish": ("serve.group", {}),
}
TRAIN_SPANS = {
    "train_step": (None, {}),
    "captured_host_prep": ("train_step", {}),
    "captured_keys": ("captured_host_prep", {}),
    "captured_data": ("train_step", {}),
    "captured_step": ("train_step", {}),
    "captured_commit": ("train_step", {}),
    "guard_readback": ("train_step", {}),
}


def _tiny_gpt():
    net = gpt.GPTModel(vocab_size=128, units=32, num_layers=2, num_heads=2,
                       max_length=64, dropout=0.0, scan_layers=True)
    net.initialize()
    return net


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One tiny served group and one tiny captured step under a profiler
    session of the test's own; everything the cases below look at."""
    import jax

    served = _tiny_gpt()
    engine = serving.ServingEngine(served, batch_buckets=(4,))
    engine.serve_group(PROMPTS, STEPS)              # warm-up: compiles
    pinned = serving.trace_count()
    batcher = serving.ContinuousBatcher(engine, max_delay_ms=200)
    trained = _tiny_gpt()
    trained.hybridize()
    trainer = gluon.Trainer(trained.collect_params(), "adamw",
                            {"learning_rate": 1e-3})
    loss_fn = gpt.GPTLMLoss()
    x = mx.nd.array(np.random.RandomState(0).randint(
        0, 128, (2, 64)).astype("float32"))
    for _ in range(2):
        trainer.train_step(trained, loss_fn, x, x, batch_size=1)
    logdir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        futures = [batcher.submit(p, STEPS) for p in PROMPTS]
        records = [f.result(timeout=120) for f in futures]
        trainer.train_step(trained, loss_fn, x, x, batch_size=1)
    finally:
        jax.profiler.stop_trace()
        batcher.close()
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(files[0])
    spans = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "train_step",
                                      "captured_", "guard_")):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    step = captured.get_step(trainer, trained, loss_fn, x, x, 1)
    return {"spans": spans, "records": records, "engine": engine,
            "retraces": serving.trace_count() - pinned,
            "train_hlo": step._compiled_for_stats().as_text()}


@pytest.mark.parametrize("name", list(SERVE_SPANS) + list(TRAIN_SPANS))
def test_span_is_in_the_trace_nested_and_with_its_attrs(traced, name):
    parent, attrs = {**SERVE_SPANS, **TRAIN_SPANS}[name]
    found = traced["spans"].get(name)
    assert found, f"{name} is not in the host plane of the trace"
    start, end, stats = sorted(found)[0]
    for key, value in attrs.items():
        assert stats.get(key) == value, (name, key, stats)
    if parent is not None:
        assert any(ps <= start and end <= pe
                   for ps, pe, _ in traced["spans"][parent]), \
            f"{name} does not lie inside a {parent} span"
    if name.startswith("serve.decode."):
        # one span a step; the last token needs no cache step
        want = STEPS if name.endswith("sample") else STEPS - 1
        assert sorted(s[2]["step"] for s in found) == list(range(want))


def test_timings_carry_the_split_of_a_decode_step(traced):
    for rec in traced["records"]:
        for field in ("collect_us", "finish_us",
                      "decode_sample_us_per_step",
                      "decode_dispatch_us_per_step",
                      "decode_readback_us_per_step",
                      "decode_host_us_per_step", "token_t_us",
                      "decode_steps_fed_on_device",
                      "decode_readback_bytes_per_step",
                      "prefill_us", "decode_us_per_token", "decode_us",
                      "t_prefill0", "t_decode0", "queue_us"):
            assert field in rec, field
        assert len(rec["token_t_us"]) == STEPS
        assert rec["token_t_us"] == sorted(rec["token_t_us"])
        assert rec["token_t_us"][-1] == pytest.approx(rec["decode_us"])
        parts = (rec["decode_sample_us_per_step"]
                 + rec["decode_dispatch_us_per_step"]
                 + rec["decode_readback_us_per_step"])
        # the parts are sums of the same clock reads as the whole
        assert 0 < parts <= rec["decode_us_per_token"]
        assert rec["decode_host_us_per_step"] == pytest.approx(
            rec["decode_sample_us_per_step"]
            + rec["decode_dispatch_us_per_step"])
        assert rec["t_decode0"] - rec["t_prefill0"] == pytest.approx(
            rec["prefill_us"] * 1e-6, abs=1e-4)
        # the request tree takes prefill and decode from those spans
        tree = {s["name"]: s for s in rec["spans"]} if "spans" in rec \
            else None
        assert tree is None or tree["decode"]["dur_us"] == pytest.approx(
            rec["decode_us"], abs=0.1)


@pytest.mark.parametrize("bucket,module,scopes", [
    ((4, 16), "jit_serve_prefill",
     ["serve.embed", "serve.head", "serve.sample"]),
    ((4, 1), "jit_serve_decode",
     ["serve.embed", "serve.attn_qkv", "serve.cache_write", "serve.attn",
      "serve.mlp", "serve.head", "serve.sample"]),
])
def test_serving_programs_carry_their_names_and_scopes(traced, bucket,
                                                       module, scopes):
    hlo = traced["engine"]._programs[bucket].as_text()
    assert hlo.startswith(f"HloModule {module},")
    for scope in scopes:
        assert f"/{scope}/" in hlo, scope


def test_captured_step_carries_its_name_and_scopes(traced):
    hlo = traced["train_hlo"]
    assert hlo.startswith("HloModule jit_train_step,")
    for scope in ("train.forward_backward", "train.guard",
                  "train.optimizer", "attn_qkv", "attn_out", "mlp"):
        assert f"{scope}" in hlo, scope
    assert "/train.optimizer/" in hlo


def test_no_retrace_after_warm_up(traced):
    assert traced["retraces"] == 0


def test_a_scope_with_no_session_live_costs_next_to_nothing():
    best = float("inf")
    for _ in range(5):          # the least of five: neighbours burst
        t0 = time.perf_counter()
        for i in range(1000):
            with profiler.scope("serve.decode.readback", step=i):
                pass
        best = min(best, (time.perf_counter() - t0) / 1000)
    assert best < 5e-6, f"{best * 1e6:.2f} us a scope"

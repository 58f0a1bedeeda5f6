"""`spans.py` and the four readers built on it, on the hand-made trace
``data/hand_spans.xplane.txt`` (microseconds):

    device   jit_serve_decode 0-100 and 200-300, each a ``while.1`` that
             holds fusion.1 (30, under serve.cache_write), copy.2 (20, no
             op_name) and fusion.3 (30, under serve.attn)
    host     serve.group 50-350 > serve.decode.readback 90-130,
             serve.decode.sample 130-150, serve.decode.dispatch 150-205
             > serve.decode.dispatch.put 160-170; and the runtime's own
             np.asarray(jax.Array) 95-125, which is nobody's span

so the device is idle 100-200 and, with a window of 400, for 200 in all.
"""

import os

import pytest

from benchmark import spans
from benchmark.cells import HERE, Cells

from conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "hand_spans.xplane.txt")
US = 1e-6
DECODE = ["serve.embed", "serve.attn_qkv", "serve.cache_write",
          "serve.attn", "serve.mlp", "serve.head"]


def _reader(name):
    return Cells(ROOT).module("readers", name).read


def _run(**over):
    run = {"cell": {"name": "no-such-cell"}, "xplane": DATA,
           "window_s": 400 * US, "records": []}
    run.update(over)
    return run


def test_gaps_are_split_by_intersection_with_the_innermost_span():
    idle = spans.read(DATA)["idle"]
    assert idle == pytest.approx({
        "serve.decode.readback": 30 * US,       # 100-130 of the gap
        "serve.decode.sample": 20 * US,
        "serve.decode.dispatch": 40 * US,       # 150-200 less its child
        "serve.decode.dispatch.put": 10 * US,
        "serve.group": 50 * US})                # 300-350, after the last
    # the gap between the two programs is split whole, nothing twice
    assert sum(v for k, v in idle.items() if k != "serve.group") \
        == pytest.approx(100 * US)
    assert "np.asarray(jax.Array)" not in idle


@pytest.mark.parametrize("want,pct", [
    (["serve.decode.readback", "serve.prefill.readback"], 7.5),
    (["serve.decode.sample", "serve.decode.dispatch", "serve.group"], 27.5),
    (["serve.decode.dispatch."], 2.5),          # a prefix
    ("unattributed", 12.5),                     # 200 idle less 150 owned
])
def test_span_idle_parts_sum_to_the_idle_total(want, pct):
    read = _reader("span_idle")
    assert read(_run(), {"spans": want}) == pytest.approx(pct)
    if want == "unattributed":
        parts = [read(_run(), {"spans": [s]}) for s in
                 ("serve.group", "serve.decode.")]
        total = 100.0 * (400 - 200) / 400       # what device_idle reads
        assert sum(parts) + pct == pytest.approx(total)


def test_device_time_by_scope_counts_each_operation_once():
    tr = spans.read(DATA)
    ops = tr["programs"]["jit_serve_decode"]
    # the while's own time is what its body's operations leave
    assert ops == pytest.approx({"while.1": 40 * US, "fusion.1": 60 * US,
                                 "copy.2": 40 * US, "fusion.3": 60 * US})
    by_scope, none, total = spans.scope_seconds(tr, "jit_serve_decode",
                                                DECODE)
    assert total == pytest.approx(tr["busy_s"]) == pytest.approx(200 * US)
    assert sum(by_scope.values()) + none == pytest.approx(total)
    assert spans.scope_seconds(tr, "jit_train_step", DECODE) is None


@pytest.mark.parametrize("scope,pct", [
    ("serve.cache_write", 30.0), ("serve.attn", 30.0), ("serve.mlp", 0.0),
    ("unscoped", 40.0)])
def test_scope_shares_sum_to_100(scope, pct):
    read = _reader("scope_share")
    params = {"program": "jit_serve_decode", "scopes": DECODE}
    assert read(_run(), dict(params, scope=scope)) == pytest.approx(pct)
    shares = [read(_run(), dict(params, scope=s))
              for s in DECODE + ["unscoped"]]
    assert sum(shares) == pytest.approx(100.0)


def test_two_programs_may_each_hold_an_operation_of_one_name():
    from jax.profiler import ProfileData

    meta = " ".join(
        'event_metadata { key: %d value { id: %d name: "%%fusion.5 = '
        'f32[%d] fusion()" stats { metadata_id: 1 str_value: "%s" } } }'
        % (i, i, i, path) for i, path in (
            (1, "jit(serve_prefill)/while/body/serve.attn/dot_general:"),
            (2, "jit(serve_decode)/while/body/serve.cache_write/scatter:")))
    text = ('planes { name: "/device:TPU:0" %s stat_metadata { key: 1 '
            'value { id: 1 name: "tf_op" } } }' % meta)
    paths = spans._metadata_paths(
        ProfileData.text_proto_to_serialized_xspace(text))
    assert spans.under(paths["jit_serve_prefill"]["fusion.5"], "serve.attn")
    assert spans.under(paths["jit_serve_decode"]["fusion.5"],
                       "serve.cache_write")


def test_a_scope_is_found_under_transformations():
    path = "jit(train_step)/jit(main)/train.forward_backward/" \
           "transpose(jvp(flash))/pallas_call"
    assert spans.under(path, "flash")
    assert spans.under(path, "train.forward_backward")
    assert not spans.under(path, "train.optimizer")
    assert not spans.under("jit(f)/flash_like/add", "flash")


def test_record_list_percentile_reads_the_gaps_once_a_group():
    read = _reader("record_list_percentile")
    params = {"field": "token_t_us", "q": 95, "scale": 0.001,
              "once_per": "t_decode0"}
    recs = [{"t_decode0": 1.0, "token_t_us": [0.0, 1000.0, 3000.0]},
            {"t_decode0": 1.0, "token_t_us": [0.0, 1000.0, 3000.0]},
            {"t_decode0": 2.0, "token_t_us": [5.0, 4005.0]},
            {"t_decode0": 3.0, "token_t_us": [7.0]}]
    assert read(_run(records=recs), params) == pytest.approx(4.0)
    assert read(_run(records=recs), dict(params, q=50)) \
        == pytest.approx(2.0)


def test_step_span_reads_the_windows_last_step_records():
    from mxnet_tpu import telemetry

    read = _reader("step_span")
    telemetry.reset()
    for i, dispatch in enumerate([9000.0, 1000.0, 3000.0, 2000.0]):
        acc = telemetry.step_begin(path="captured")
        telemetry.on_scope("captured_step", dispatch * 1e-6)
        telemetry.on_scope("captured_commit", 50e-6)
        telemetry.on_scope("captured_data", 25e-6)
        telemetry.step_end(acc, step=i)
    run = _run(records=[{}] * 3)        # the window's three steps
    assert read(run, {"buckets": ["dispatch"], "q": 50, "scale": 0.001}) \
        == pytest.approx(2.0)
    assert read(run, {"buckets": ["host_prep", "data"], "q": 50,
                      "scale": 0.001}) == pytest.approx(0.075)
    telemetry.reset()
    assert read(run, {"buckets": ["dispatch"], "q": 50}) is None


@pytest.mark.parametrize("reader,params", [
    ("span_idle", {"spans": ["serve.collect"]}),
    ("span_idle", {"spans": "unattributed"}),
    ("scope_share", {"program": "jit_serve_decode", "scopes": DECODE,
                     "scope": "unscoped"}),
    ("record_list_percentile", {"field": "token_t_us", "q": 95}),
    ("step_span", {"buckets": ["no_such_bucket"], "q": 50}),
])
def test_nothing_to_read_gives_none(reader, params, tmp_path):
    """No trace of this process (a ``--trace 0`` run, the layout absent),
    or records without the field: None, never an exception, as the parent
    of the PR that brings a metric has to answer."""
    read = _reader(reader)
    run = _run(xplane=None, records=[{"queue_us": 1.0}])
    assert read(run, params) is None
    assert spans.find("no-such-cell", root=str(tmp_path)) is None


def test_a_trace_without_the_programs_names_gives_none(tmp_path):
    """The parent's trace: operations and host events, none of them the
    program's: every reader on it reads nothing."""
    old = os.path.join(os.path.dirname(DATA), "v5e_serve_decode.xplane.txt")
    if not os.path.isfile(old):
        pytest.skip("no recorded trace beside the tests")
    run = _run(xplane=old)
    assert _reader("span_idle")(run, {"spans": "unattributed"}) is None
    assert _reader("scope_share")(run, {
        "program": "jit_serve_decode", "scopes": DECODE,
        "scope": "unscoped"}) is None


def test_find_takes_the_newest_trace_of_this_process(tmp_path):
    import time

    cell, now = "a-cell", time.time()
    for seed, name in ((1, "old"), (2, "new")):
        d = tmp_path / "benchmark_out" / cell / f"seed{seed}-trace1" \
            / "trace" / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        (d / f"{name}.xplane.pb").write_bytes(b"")
        os.utime(d / f"{name}.xplane.pb", (now + seed, now + seed))
    newest = spans.find(cell, root=str(tmp_path))
    assert newest is not None and newest.endswith("new.xplane.pb")
    stale = os.path.getmtime(newest) - 10 ** 6      # before this process
    for dirpath, _, files in os.walk(tmp_path):
        for f in files:
            os.utime(os.path.join(dirpath, f), (stale, stale))
    assert spans.find(cell, root=str(tmp_path)) is None


def test_every_new_metric_names_a_reader_and_its_params():
    cells = Cells(ROOT)
    for name in ("decode_host_ms_per_step_p50", "serve_token_gap_ms_p95",
                 "idle_readback_pct.serve", "idle_host_pct.serve",
                 "idle_collect_pct.serve", "idle_unattributed_pct.serve",
                 "decode_cache_write_pct", "decode_unscoped_pct",
                 "train_dispatch_ms_p50.lm", "train_host_prep_ms_p50.lm",
                 "idle_unattributed_pct.lm", "train_flash_pct.lm",
                 "train_optimizer_pct.lm"):
        desc, read = cells.reader(name)
        assert os.path.dirname(read.__code__.co_filename) \
            == os.path.join(HERE, "readers")
        assert desc["params"]

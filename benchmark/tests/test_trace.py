"""The reduction of `trace.py`: on hand-made intervals, on a hand-made
trace with collectives, and on the small trace recorded on the v5e that
is kept in ``data/``."""

import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_length_subtract():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert u == [(0, 3), (5, 8), (10, 11)]
    assert trace.length(u) == 7
    assert trace.subtract([(0, 12)], u) == [(3, 5), (8, 10), (11, 12)]
    assert trace.subtract(u, [(2, 6), (10.5, 20)]) == [
        (0, 2), (6, 8), (10, 10.5)]
    assert trace.subtract(u, []) == u


def test_short_names():
    assert trace.short("%copy.50 = bf16[1,16]{1,0} copy(bf16[1,16] %x)") \
        == "copy.50"
    assert trace.short("jit_step(123)") == "jit_step(123)"
    assert trace.clean("np.asarray(jax.Array)") == "np.asarray_jax.Array_"


def _plane(name, lines):
    metas, out, ids = {}, [], 0
    for line_name, events in lines:
        body = []
        for ev_name, start_us, dur_us in events:
            mid = metas.setdefault(ev_name, len(metas) + 1)
            body.append("events { metadata_id: %d offset_ps: %d "
                        "duration_ps: %d }" % (mid, start_us * 10 ** 6,
                                               dur_us * 10 ** 6))
        ids += 1
        out.append('lines { id: %d name: "%s" timestamp_ns: 0 %s }'
                   % (ids, line_name, " ".join(body)))
    meta = " ".join('event_metadata { key: %d value { id: %d name: "%s" } }'
                    % (i, i, n) for n, i in metas.items())
    return 'planes { name: "%s" %s %s }' % (name, " ".join(out), meta)


def test_collectives_and_gap_naming_on_a_hand_made_trace(tmp_path):
    """Two chips.  Chip 0: fusion 0-100 us, all-gather 80-200 (exposed
    100-200), fusion 300-400.  Chip 1: all-reduce 0-50 alone, fusion
    50-400.  The host reads back during chip 0's gap at 200-300."""
    text = "\n".join([
        _plane("/device:TPU:0", [
            ("XLA Ops", [("%fusion.1 = f32[] fusion()", 0, 100),
                         ("%all-gather.3 = f32[] all-gather()", 80, 120),
                         ("%fusion.2 = f32[] fusion()", 300, 100)]),
            ("XLA Modules", [("jit_step(1)", 0, 200),
                             ("jit_step(1)", 300, 100)])]),
        _plane("/device:TPU:1", [
            ("XLA Ops", [("%all-reduce.7 = f32[] all-reduce()", 0, 50),
                         ("%fusion.1 = f32[] fusion()", 50, 350)])]),
        _plane("/host:CPU", [
            ("python3", [("$builtins len", 190, 120),
                         ("train_step", 0, 400),
                         ("np.asarray(jax.Array)", 205, 90)])])])
    path = tmp_path / "t.txt"
    path.write_text(text)
    r = trace.reduce(str(path))
    c0, c1 = r["chips"]
    assert c0["busy_s"] == pytest.approx(300e-6)
    assert c1["busy_s"] == pytest.approx(400e-6)
    assert r["busy_s"] == pytest.approx(350e-6)
    assert c0["collective_s"] == pytest.approx(120e-6)
    assert c0["collective_exposed_s"] == pytest.approx(100e-6)
    assert c1["collective_exposed_s"] == pytest.approx(50e-6)
    assert r["collective_exposed_s"] == pytest.approx(75e-6)
    # the 100 us gap on chip 0 goes to the innermost host event that
    # covers most of it, never to the interpreter's own '$' events
    assert r["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert r["idle_gaps"][0][1] == pytest.approx(100e-6)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((100e-6 + 350e-6) / 2)
    assert r["modules"]["jit_step(1)"] == pytest.approx([100e-6, 200e-6])


RECORDED = os.path.join(DATA, "v5e_serve_decode.xplane.txt")


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded trace beside the tests")
def test_the_recorded_v5e_trace():
    """0.1 s of gpt2m-serve-chat's decode on the v5e (PR 23), cut to a
    few hundred events a line: the numbers below were read off it once,
    by this code and by a brute-force sweep (here again)."""
    r = trace.reduce(RECORDED)
    assert len(r["chips"]) == 1
    chip = r["chips"][0]
    assert chip["busy_s"] > 0 and r["collective_s"] == 0
    # brute force: sample the span on a fine grid and count covered points
    data = trace.load(RECORDED)
    ops = [ln for p in data.planes if p.name == "/device:TPU:0"
           for ln in p.lines if ln.name == "XLA Ops"][0]
    ev = sorted((e.start_ns, e.start_ns + e.duration_ns)
                for e in ops.events)
    lo, hi = ev[0][0], max(e for _, e in ev)
    step = (hi - lo) / 20000
    covered, j, reach = 0, 0, lo
    for i in range(20000):
        t = lo + (i + 0.5) * step
        while j < len(ev) and ev[j][0] <= t:
            reach = max(reach, ev[j][1])
            j += 1
        covered += reach > t
    assert chip["busy_s"] == pytest.approx(covered * step * 1e-9, rel=2e-3)
    span = chip["last_s"] - chip["first_s"]
    assert span == pytest.approx((hi - lo) * 1e-9)
    idle = 1.0 - chip["busy_s"] / span
    assert 0.0 < idle < 1.0
    named = dict(r["idle_gaps"])
    assert sum(named.values()) == pytest.approx(span - chip["busy_s"],
                                                rel=1e-6)
    assert all(not k.startswith("$") for k in named)
    assert len(r["device_ops"]) == 10

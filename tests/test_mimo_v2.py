"""MiMo-V2 family (gluon/model_zoo/mimo_v2.py) against its plain
reference (benchmark/references/mimo_v2.py), tiny, float32, on the CPU:
the uncached forward, the cached step through `ServingEngine` with its
two kinds of cache, the dropless share of the experts, and the engine's
pins for the new family."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx                                      # noqa: E402
from mxnet_tpu import serving                               # noqa: E402
from mxnet_tpu.base import MXNetError                       # noqa: E402
from mxnet_tpu.gluon.model_zoo import _decoder_ops, mimo_v2  # noqa: E402
from mxnet_tpu.ops import moe                               # noqa: E402
from mxnet_tpu.test_utils import (                          # noqa: E402
    UNEQUAL_ANSWERS, _padded_group, serving_dead_rows_keep_their_cache,
    serving_host_walk as _walk, serving_unequal_answers)

from benchmark import program, weights                      # noqa: E402
from benchmark.references import mimo_v2 as ref             # noqa: E402

WINDOW = 4


def _config(**over):
    """The tiny member's sizes under the source's keys (hidden 64, 4
    query heads, 1 and 2 key/value heads, dqk 24, dv 16, rotary 8,
    window 4, full-w-w-w-w-w-full, dense then 8 experts top-2)."""
    cfg = {"hidden_size": 64, "num_hidden_layers": 7,
           "hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0],
           "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
           "num_attention_heads": 4, "num_key_value_heads": 1,
           "swa_num_key_value_heads": 2, "rope_theta": 1e7,
           "swa_rope_theta": 1e4, "head_dim": 24, "v_head_dim": 16,
           "partial_rotary_factor": 0.334, "sliding_window": WINDOW,
           "attention_value_scale": 0.707, "intermediate_size": 96,
           "moe_intermediate_size": 32, "n_routed_experts": 8,
           "router_experts": 8, "num_experts_per_tok": 2,
           "vocab_size": 96, "layernorm_epsilon": 1e-5,
           # wide enough that every term of a layer shows in the logits
           "initializer_range": 0.2}
    cfg.update(over)
    return cfg


def _net(cfg, seed=5, change=None, **kw):
    """(net, reference parameters): the tiny model with the reference's
    seeded leaves; ``change(values)`` edits them first."""
    held = cfg.get("experts_held")
    net = mimo_v2.mimo_v2_tiny(
        experts_held=held, rope_theta=cfg["rope_theta"],
        swa_rope_theta=cfg["swa_rope_theta"], **kw)
    net.initialize(init=mx.init.Zero())
    spec = ref.param_spec(cfg)
    values = dict(weights.make(seed, spec, "float32"))
    if change is not None:
        change(values)
    leaves = program.match_leaves(spec, list(net.collect_params().keys()))
    for leaf, _, _ in spec:
        net.collect_params()[leaves[leaf]].set_data(values[leaf])
    return net, values


def _ref_logits(values, ids, cfg):
    import jax.numpy as jnp

    return np.asarray(ref.logits(values, jnp.asarray(ids), cfg))


# -- 1. the uncached forward ---------------------------------------------------

def _never_packs(monkeypatch, tile):
    """A small tile, and a `packing` that fails: for what hands
    `_block_layer` no lengths and must keep its rows."""
    def packing(*args):
        raise AssertionError("the row path packs nothing")

    monkeypatch.setattr(mimo_v2, "_TILE", tile)
    monkeypatch.setattr(_decoder_ops, "packing", packing)


@pytest.mark.parametrize("T,chunk,tile", [
    (13, 4096, None), (16, 16, None), (3, 4096, None),
    (13, 4096, 4), (16, 16, 4)])
def test_forward_equals_the_reference(T, chunk, tile, monkeypatch):
    """`hybrid_forward` over whole sequences (a length that is no
    multiple of the attention block; rows worked off in chunks; one
    shorter than a block).  It hands no lengths and keeps its rows
    whatever the prefill's tile."""
    if tile:
        _never_packs(monkeypatch, tile)
    cfg = _config()
    net, values = _net(cfg, prefill_chunk_tokens=chunk)
    ids = np.random.RandomState(0).randint(0, 96, (4, T))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    want = _ref_logits(values, ids, cfg)
    assert got.shape == want.shape == (4, T, 96)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


# -- 2. prefill, then decode through both caches -------------------------------

@pytest.mark.parametrize("chunk", [4096, 16])
def test_serving_equals_the_reference_at_every_served_position(chunk):
    """A group mixing prompts shorter than the window, equal to it and
    several windows long, decoded until every ring has wrapped at least
    twice: the cached step's logits are the reference's full forward at
    each served position, and `serve_group` serves the same tokens."""
    cfg = _config()
    net, values = _net(cfg, prefill_chunk_tokens=chunk)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 96, n).tolist()
               for n in (2, WINDOW, 3 * WINDOW + 1)]
    steps = 3 * WINDOW
    toks, logits = _walk(eng, prompts, steps)
    for i, p in enumerate(prompts):
        full = np.asarray(list(p) + list(toks[i, :-1]))[None]
        want = _ref_logits(values, full, cfg)[0, len(p) - 1:]
        np.testing.assert_allclose(logits[i], want, atol=3e-4, rtol=1e-4,
                                   err_msg=f"prompt of {len(p)}")
    outs, timings = eng.serve_group(prompts, steps)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o, toks[i])
    # every pair a real token makes is held here (all 8 experts are):
    # 2 a token a layer, 6 expert layers; the pad row has one token,
    # and wants none: it is dead from the first decode step
    real = sum(len(p) for p in prompts) + 1
    assert timings["moe_pairs_prefill"] == real * 2 * 6
    assert timings["moe_pairs_decode"] == 3 * 2 * 6 * (steps - 1)
    assert timings["moe_rows_computed_decode"] \
        >= timings["moe_pairs_decode"]
    assert 1 <= timings["moe_experts_hit_per_step"] <= 8
    assert timings["moe_load_max_over_mean"] >= 1.0


# -- 3. the share ties to the model --------------------------------------------

@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The 8 experts of a layer held as ``shares`` shares: what the
    program's op gives for each share, added up, is the uncut
    reference's whole layer."""
    import jax.numpy as jnp

    cfg = _config()
    z = ref.sizes(cfg)
    values = weights.make(7, ref.param_spec(cfg), "float32")
    u = jnp.asarray(np.random.RandomState(2).normal(size=(3, 11, 64)),
                    jnp.float32)
    parts = ref._jitted(ref._key(z), ref.product)
    want = np.asarray(ref.moe_layer(u, values, 3, z, parts, held=(0, 8)))
    per = 8 // shares
    total, pairs = 0.0, 0
    for s in range(shares):
        at = slice(s * per, (s + 1) * per)
        y, stats = moe.moe_share_ffn(
            u, values["l3_router_weight"], values["l3_router_bias"],
            values["l3_experts_gate_up_weight"][at],
            values["l3_experts_down_weight"][at], k=2,
            experts_lo=s * per, output_stats=True)
        total = total + np.asarray(y)
        pairs += int(np.asarray(stats)[:per].sum())
    assert pairs == 3 * 11 * 2          # each assignment in one share
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)


# -- 4. dropless under imbalance -----------------------------------------------

@pytest.mark.parametrize("pass_rows", [None, 4])
def test_nothing_is_dropped_when_every_token_goes_to_one_expert(pass_rows):
    """A correction bias that sends every token to the one expert held
    here: every token's assignment is computed (``moe_pairs`` is the
    token count, a layer), in as many passes as the buffer needs, and
    the served logits are the reference's."""
    cfg = _config(experts_held=[2, 1], n_routed_experts=1)

    def force(values):
        for name in values:
            if name.endswith("router_bias"):
                values[name] = values[name].at[2].set(10.0)

    net, values = _net(cfg, change=force, moe_pass_rows=pass_rows)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 96, n).tolist() for n in (9, 3, 16, 5)]
    toks, logits = _walk(eng, prompts, 4)
    for i, p in enumerate(prompts):
        full = np.asarray(list(p) + list(toks[i, :-1]))[None]
        want = _ref_logits(values, full, cfg)[0, len(p) - 1:]
        np.testing.assert_allclose(logits[i], want, atol=3e-4, rtol=1e-4)
    _, timings = eng.serve_group(prompts, 4)
    tokens = sum(len(p) for p in prompts)
    assert timings["moe_pairs_prefill"] == tokens * 6
    assert timings["moe_pairs_decode"] == 4 * 6 * 3
    # one expert holds all the load
    assert timings["moe_experts_hit_per_step"] == 1.0
    if pass_rows:
        # 33 pairs a layer through a buffer of 4 rows: 9 passes
        assert timings["moe_rows_computed_prefill"] == 6 * 9 * pass_rows


def test_the_op_drops_nothing_past_its_buffer():
    """`held_experts_ffn` alone, every pair on one expert and a buffer
    of 5 rows for 40 pairs."""
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    w13 = jnp.asarray(rng.normal(size=(2, 16, 24)) * 0.3, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(2, 12, 16)) * 0.3, jnp.float32)
    chosen = jnp.stack([jnp.full((40,), 5), jnp.full((40,), 0)], 1)
    wts = jnp.full((40, 2), 0.5, jnp.float32)
    y, stats = moe.held_experts_ffn(x, chosen, wts, w13, w2, experts_lo=4,
                                    pass_rows=5)
    h = np.asarray(x) @ np.asarray(w13[1])
    h = h[:, :12] / (1 + np.exp(-h[:, :12])) * h[:, 12:]
    np.testing.assert_allclose(np.asarray(y), 0.5 * h @ np.asarray(w2[1]),
                               atol=1e-5)
    assert list(np.asarray(stats)) == [0, 40, 40]


# -- 5. the engine's pins for the new family -----------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = _config()
    net, values = _net(cfg)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    return cfg, net, values, eng


def test_no_retrace_after_warmup(served):
    _, _, _, eng = served
    eng.warmup()
    pinned = serving.trace_count()
    rng = np.random.RandomState(5)
    for lens in ((2, 9), (16, 3, 1, 7), (4,)):
        eng.serve_group([rng.randint(0, 96, n).tolist() for n in lens], 5)
    assert serving.trace_count() == pinned
    assert eng.program_count() == len(eng.prefill_buckets) + 1


@pytest.mark.parametrize("kind,S", [("prefill", 8), ("decode", 1)])
def test_the_four_stacks_alias_their_inputs(served, kind, S):
    """Every array of the cache is written into its donated argument,
    and the decode program moves no layer-sized piece of any stack."""
    _, _, _, eng = served
    B = 4
    text = eng._compile(B, S).as_text()
    n_w = len(eng._weights)
    cache = eng.init_cache(B)
    assert len(cache) == 6
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(len(cache)):
        assert f"{{{i}}}: ({n_w + i}, {{}}" in alias, (i, alias)
    if kind == "decode":
        for c in cache[:4]:
            assert serving.whole_layer_ops(
                text, c.nbytes // c.shape[0]) == []


@pytest.mark.parametrize("tile", [None, 4])
def test_a_coalesced_group_is_bitwise_the_requests_served_alone(
        served, tile, monkeypatch):
    """Also in tiles smaller than a chunk's tokens: a request alone,
    beside three pad rows of one token, lies in other tiles than in its
    group."""
    eng = served[3] if tile is None else _tiled(monkeypatch, tile)[1]
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 96, n).tolist() for n in (3, 11, WINDOW, 7)]
    toks, logits = _walk(eng, prompts, 10)
    for i, p in enumerate(prompts):
        t1, l1 = _walk(eng, [p], 10)
        np.testing.assert_array_equal(t1[0], toks[i])
        np.testing.assert_array_equal(l1[0], logits[i])


# -- 5b. the prefill works its real tokens only --------------------------------

def _tiled(monkeypatch, tile, **kw):
    """(net, engine) whose prefill packs in tiles of ``tile``: the tile
    is read while a program is traced, so the engine is the test's
    own."""
    monkeypatch.setattr(mimo_v2, "_TILE", tile)
    net, _ = _net(_config(), **kw)
    return net, serving.ServingEngine(net, batch_buckets=(4,))


def _prefilled(eng, prompts):
    """One prefill through the engine's own program: (cache, logits)."""
    B, n, toks = _padded_group(eng, prompts)
    cache, logits, *_ = eng._call(B, toks.shape[1], eng.init_cache(B),
                                  np.zeros(B, np.int32), n - 1, toks)
    return cache, np.asarray(logits)


# a bucket of 4 x 16: a row of one token, a full row, and (two rows a
# chunk) a second chunk whose 16 tokens end on the edge of a tile of 4
# or 8.  (tile, tokens a row chunk, the positions its tiles work)
LENS = (1, 16, 7, 9)
TILED = [(4, 32, 20 + 16), (8, 32, 24 + 16), (8, 16, 8 + 16 + 8 + 16),
         (3, 32, 18 + 18), (512, 32, 32 + 32)]


@pytest.mark.parametrize("tile,chunk,worked", TILED)
def test_the_packed_prefill_equals_the_row_layout(tile, chunk, worked,
                                                  monkeypatch):
    """Ragged lengths across row chunks, the token-wise products in
    tiles of the packed block (the family's own tile holds a chunk
    whole): the logits, the full layers' keys and values up to each
    row's last token, the rings and every expert counter are what the
    row layout gives (`_block_layer` handed no lengths: the forward
    pass's path), and the two counters of the packing are the prompts'
    own sums, in whole tiles a chunk."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 96, n).tolist() for n in LENS]
    net, eng = _tiled(monkeypatch, tile, prefill_chunk_tokens=chunk)
    cache, logits = _prefilled(eng, prompts)
    counts = eng._program.counters(cache)

    packed = mimo_v2._block_layer
    monkeypatch.setattr(
        mimo_v2, "_block_layer",
        lambda z, i, p, x, pos, held=None: packed(z, i, p, x, pos))
    _never_packs(monkeypatch, tile)
    rows = serving.ServingEngine(net, batch_buckets=(4,))
    want_cache, want = _prefilled(rows, prompts)
    want_counts = rows._program.counters(want_cache)

    np.testing.assert_allclose(logits, want, atol=2e-5, rtol=1e-5)
    for got, ref_ in zip(cache[:2], want_cache[:2]):
        for r, n in enumerate(LENS):
            np.testing.assert_allclose(
                np.asarray(got)[:, r, :, :, :n],
                np.asarray(ref_)[:, r, :, :, :n], atol=2e-5, rtol=1e-5)
    for got, ref_ in zip(cache[2:4], want_cache[2:4]):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_),
                                   atol=2e-5, rtol=1e-5)
    for name, n in want_counts.items():
        if name.startswith("moe_"):
            assert counts[name] == n, name
    assert want_counts["moe_pairs_prefill"] == sum(LENS) * 2 * 6
    assert counts["prefill_positions"] == sum(LENS)
    assert counts["prefill_positions_worked"] == worked
    assert counts["prefill_positions_padded"] == worked - sum(LENS)
    assert counts["prefill_tokens_padded_pct"] == pytest.approx(
        100.0 * (1 - sum(LENS) / worked))


def test_the_prefills_counters_reach_a_groups_timings(served):
    """At the family's own tile a block of 4 x 16 is one tile, worked
    whole: every position of the bucket, of which the prompts' are real
    (a pad row holds one token); a decode step adds nothing."""
    _, _, _, eng = served
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, 96, n).tolist() for n in (5, 13)]
    _, timings = eng.serve_group(prompts, 3)
    S = timings["bucket"][1]
    assert timings["prefill_positions"] == 5 + 13 + 2
    assert timings["prefill_positions_worked"] == 4 * S
    assert timings["prefill_tokens_padded_pct"] == pytest.approx(
        100.0 * (1 - 20 / (4 * S)))


def test_only_the_prefill_packs(monkeypatch):
    """The path is taken where the rows' lengths are given: the prefill
    program packs each layer's row chunks, the decode step (one token a
    row) never enters it, whatever the tile."""
    _, eng = _tiled(monkeypatch, 4)
    calls = []
    packing = _decoder_ops.packing
    monkeypatch.setattr(
        _decoder_ops, "packing",
        lambda *a: calls.append(a[1:]) or packing(*a))
    eng._compile(4, 1)
    assert calls == []
    eng._compile(4, 8)
    # a layer's row chunk is traced twice (its shapes, then the loop)
    # where the bucket is cut, once where a chunk holds it
    assert calls == [(8, 4)] * 7


@pytest.mark.parametrize("steps", [1, 2, 9])
def test_a_greedy_group_is_fed_on_the_device(served, steps):
    """The second family through the same wrapper: a greedy group's
    tokens are those of the path with the host in every step (rings
    wrapped twice at 9 steps), it dispatches its prefill and
    ``steps - 1`` decode programs and no third one, every decode step
    takes the step before's ids, and the host reads 4 bytes a row."""
    _, _, _, eng = served
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, 96, n).tolist() for n in (2, WINDOW, 13)]
    want, _ = _walk(eng, prompts, steps)
    pinned = (serving.trace_count(), serving.compile_count())
    d0 = serving.dispatch_count()
    outs, timings = eng.serve_group(prompts, steps)
    assert serving.dispatch_count() - d0 == 1 + (steps - 1)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    np.testing.assert_array_equal(np.stack(outs), want)
    assert timings["decode_steps_fed_on_device"] == steps - 1
    assert timings["decode_readback_bytes_per_step"] == 4 * 4
    ts = timings["token_t_us"]
    assert len(ts) == steps and all(a < b for a, b in zip(ts, ts[1:]))
    # the family's counters are still read once, after the last step:
    # the three rows that want a token, not the pad row
    assert timings["moe_pairs_decode"] == 3 * 2 * 6 * (steps - 1)


@pytest.mark.parametrize("wants", UNEQUAL_ANSWERS, ids=str)
def test_a_row_that_wants_no_token_changes_nothing(served, wants):
    """The decode step is handed which rows still want a token: the
    others attend to nothing and write nothing (full layers and rings
    alike) and go to no
    expert, every request's tokens are what it gets alone and in a
    group of equal answers, and the experts' counters are the live
    row-steps' (all 8 experts are held: 2 pairs a token in each of the
    6 expert layers)."""
    _, _, _, eng = served
    eng.warmup()
    rng = np.random.RandomState(10)
    prompts = [rng.randint(0, 96, n).tolist()
               for n in (3, 11, WINDOW, 7)[:len(wants)]]
    pinned = (serving.trace_count(), serving.compile_count())
    timings, live = serving_unequal_answers(eng, prompts, wants)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    assert timings["moe_pairs_decode"] == len(live) * 2 * 6
    assert timings["moe_rows_computed_decode"] \
        >= timings["moe_pairs_decode"]
    assert 1 <= timings["moe_experts_hit_per_step"] <= 8
    # and in every layer of every stack a finished row's cache rows are
    # what they were
    serving_dead_rows_keep_their_cache(eng, prompts, [k > 1 for k in wants])


def test_a_sampled_group_draws_on_the_host(served):
    """With a temperature and a seeded generator the tokens are those
    of the path with the host in every step, draw for draw; the host
    reads each program's logits and no step is fed on the device."""
    _, _, _, eng = served
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 96, n).tolist() for n in (5, 2, 11)]
    want, _ = _walk(eng, prompts, 7, temperature=1.3,
                    rng=np.random.default_rng(12))
    d0 = serving.dispatch_count()
    outs, timings = eng.serve_group(prompts, 7, temperature=1.3,
                                    rng=np.random.default_rng(12))
    np.testing.assert_array_equal(np.stack(outs), want)
    assert serving.dispatch_count() - d0 == 7
    assert timings["decode_steps_fed_on_device"] == 0
    assert timings["decode_readback_bytes_per_step"] == 4 * 4 * 96


def test_a_mesh_is_refused_and_reload_goes_through_weights(served):
    cfg, net, _, eng = served
    with pytest.raises(MXNetError, match="one chip"):
        serving.ServingEngine(net, batch_buckets=(4,), mesh=object())
    prompts = [[1, 2, 3, 4, 5], [7, 8]]
    before, _ = eng.serve_group(prompts, 4)
    other, _ = _net(cfg, seed=9)
    eng.reload_from_model(other)
    pinned = serving.trace_count()
    after, _ = eng.serve_group(prompts, 4)
    assert serving.trace_count() == pinned and eng.generation == 1
    fresh, _ = serving.ServingEngine(
        other, batch_buckets=(4,)).serve_group(prompts, 4)
    for a, f in zip(after, fresh):
        np.testing.assert_array_equal(a, f)
    assert any((a != b).any() for a, b in zip(after, before))
    with pytest.raises(MXNetError, match="checkpoint-state convention"):
        eng.reload_from_state(serving.state_for_serving(other))
    eng.reload_from_model(net)
    with pytest.raises(MXNetError, match="incompatible model"):
        eng.reload_from_model(_net(_config(experts_held=[0, 4],
                                           n_routed_experts=4))[0])


# -- 6. the sink and the two rotary bases matter -------------------------------

def _served_logits(change=None, **over):
    cfg = _config(**over)
    net, _ = _net(cfg, change=change)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    prompts = [[5, 9, 2], list(range(1, 12))]
    return _walk(eng, prompts, 6)[1]


def test_the_sink_changes_the_output():
    def no_sink(values):
        for name in values:
            if name.endswith("sink_bias"):
                values[name] = values[name] - 30.0     # exp(-30): gone

    base = _served_logits()
    assert np.abs(_served_logits(change=no_sink) - base).max() > 1e-2


@pytest.mark.parametrize("key", ["rope_theta", "swa_rope_theta"])
def test_each_rotary_base_changes_the_output(key):
    base = _served_logits()
    assert np.abs(_served_logits(**{key: 50.0}) - base).max() > 1e-3

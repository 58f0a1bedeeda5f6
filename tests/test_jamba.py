"""Jamba (gluon/model_zoo/jamba.py) against its plain reference
(benchmark/references/jamba.py), tiny, on the CPU: (a) the uncached
forward, (b) the cached step through `ServingEngine` with mixed prompt
lengths in one padded bucket, (c) the carried state and tail: what the
prefill leaves is the reference's, and zeroing either between prefill
and decode fails the comparison by a stated margin, as does a token 256
positions back under the configuration's seeding, (d) the counters and
``live``, (e) a float8 control for the bfloat16 tolerance, (f) the
packed prefill against the row layout's, in tiles and across row chunks,
and the engine's pins for the family."""

import json
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
from mxnet_tpu.gluon.model_zoo import jamba                 # noqa: E402
from mxnet_tpu.test_utils import (                          # noqa: E402
    UNEQUAL_ANSWERS, _padded_group, serving_dead_rows_keep_their_cache,
    serving_host_walk as _walk, serving_unequal_answers)

from benchmark import program, weights                      # noqa: E402
from benchmark.references import jamba as ref               # noqa: E402

# float32 on both sides, products in another order (the program's
# running softmax and its scan against the reference's plain ones):
# logits that reach 3 agree to 1e-5
ATOL, RTOL = 2e-4, 1e-4
# bfloat16 against the float32 reference at these sizes: weights, tails,
# cached keys and values and every product's operands are rounded to 8
# bits of mantissa, and logits of size 2.5 move by up to 0.103 over the
# served positions below (two seeds: 0.043-0.103); two and a half times
# that.  The float8 control moves them by 0.97-1.96 (test (e)).  (At the
# family's 14 layers the readings are 0.12-0.43 and 2.1-3.6: the depth
# amplifies, as the published model's 28 do)
BF16_ATOL = 0.25
# what zeroing the carried state, or the tail, must move a served logit
# by at the least (float32): the readings over two sets of prompts are
# 2.4-3.1 for the state and 2.3-3.3 for the tail, of logits of size 2.5;
# ATOL is 2e-4
ZEROED_MARGIN = 1.0

with open(os.path.join(ROOT, "benchmark", "configs", "jamba2-3b.json")) as f:
    PUBLISHED = json.load(f)

# five layers, the attention layer in the middle: Mamba layers in front
# of it and behind it (the family's tiny member has 14, as a period of
# the published model; its programs take three times as long to compile)
LAYERS = dict(num_layers=5, attn_period=5, attn_offset=2)
LM, LA = 4, 1


def _config(**over):
    """The tiny member's sizes under the source's keys: hidden 64, five
    layers with the attention layer at 2, four heads over one, 128
    channels, 16 states, a dt rank of 4; the state seeded as the
    published configuration seeds it."""
    cfg = {"hidden_size": 64, "num_hidden_layers": 5,
           "num_attention_heads": 4, "num_key_value_heads": 1,
           "intermediate_size": 96, "vocab_size": 96,
           "rms_norm_eps": 1e-6, "hidden_act": "silu",
           "tie_word_embeddings": True, "mamba_expand": 2,
           "mamba_d_state": 16, "mamba_dt_rank": 4, "mamba_d_conv": 4,
           "mamba_conv_bias": True, "mamba_proj_bias": False,
           "attn_layer_period": 5, "attn_layer_offset": 2,
           "num_experts": 1,
           # wide enough at 64 units that every term shows
           "initializer_range": 0.1, "seeded": dict(PUBLISHED["seeded"])}
    cfg.update(over)
    return cfg


def _net(cfg, seed=5, dtype="float32", **kw):
    """(net, reference parameters): the tiny model with the reference's
    seeded leaves."""
    net = jamba.jamba_tiny(dtype=dtype, **LAYERS, **kw)
    net.initialize(init=mx.init.Zero())
    spec = ref.param_spec(cfg)
    values = dict(weights.make(seed, spec, dtype))
    leaves = program.match_leaves(spec, list(net.collect_params().keys()))
    for leaf, _, _ in spec:
        net.collect_params()[leaves[leaf]].set_data(values[leaf])
    return net, values


def _ref_logits(values, ids, cfg, prod=ref.product):
    import jax.numpy as jnp

    return np.asarray(ref.logits(values, jnp.asarray(ids), cfg, prod))


def _served_want(values, cfg, prompts, toks, prod=ref.product):
    """The reference's full forward at every served position."""
    return [_ref_logits(values, np.asarray(list(p) + list(toks[i, :-1]))[
        None], cfg, prod)[0, len(p) - 1:] for i, p in enumerate(prompts)]


# a row of one token, one shorter than the convolution, and two that
# end inside the bucket of 64
LENS = (1, 3, 21, 40)


def _prompts(seed=1, lens=LENS):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 96, n).tolist() for n in lens]


@pytest.fixture(scope="module")
def served():
    cfg = _config()
    net, values = _net(cfg)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    return cfg, net, values, eng


# -- (a) the uncached forward --------------------------------------------------

def test_forward_equals_the_reference(served):
    cfg, net, values, _ = served
    ids = np.random.RandomState(0).randint(0, 96, (2, 37))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    want = _ref_logits(values, ids, cfg)
    assert got.shape == want.shape == (2, 37, 96)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)


def test_the_leaves_are_the_references_and_the_layers_of_their_kind(served):
    cfg, net, values, _ = served
    assert ref.kinds(cfg) == net._sizes.kinds == ["ssm"] * 2 + ["attn"] \
        + ["ssm"] * 2
    assert ref.kinds(PUBLISHED).count("attn") == 2 and [
        i for i, k in enumerate(ref.kinds(PUBLISHED)) if k == "attn"] \
        == [7, 21]
    assert {n: tuple(s) for n, s, _ in ref.param_spec(cfg)} \
        == dict(net._sizes.leaves())
    inits = {n: i for n, _, i in ref.param_spec(cfg)}
    assert inits["a_log_weight"] == "uniform:5" \
        and inits["d_weight"] == "ones" and inits["dt_bias"] == "zeros"
    with pytest.raises(ValueError, match="no leaf"):
        ref.param_spec(dict(cfg, seeded={"nope": "ones"}))
    with pytest.raises(ValueError, match="dense"):
        ref.sizes(dict(cfg, num_experts=16))


# -- (b) prefill, then decode through the states -------------------------------

def test_serving_equals_the_reference_at_every_served_position(served):
    """Mixed prompt lengths in one bucket, padded on the right: the scan
    stops at each row's length, so the state and tail the decode steps
    start from are the reference's."""
    cfg, _, values, eng = served
    prompts = _prompts()
    toks, logits = _walk(eng, prompts, 6)
    for i, want in enumerate(_served_want(values, cfg, prompts, toks)):
        np.testing.assert_allclose(logits[i], want, atol=ATOL, rtol=RTOL)


def test_a_coalesced_group_is_bitwise_the_requests_served_alone(served):
    _, _, _, eng = served
    prompts = _prompts(seed=6, lens=(17, 2, 32, 30))
    toks, logits = _walk(eng, prompts, 4)
    for i, p in enumerate(prompts):
        t1, l1 = _walk(eng, [p], 4)
        np.testing.assert_array_equal(t1[0], toks[i])
        np.testing.assert_array_equal(l1[0], logits[i])


# -- (c) the carried state and tail --------------------------------------------

def _prefill(eng, prompts):
    B, lens, toks = _padded_group(eng, prompts)
    zero = np.zeros(B, np.int32)
    cache, _, ids, pos = eng._call(B, toks.shape[1], eng.init_cache(B),
                                   zero, lens - 1, toks)
    return B, cache, ids, pos


def test_the_prefill_leaves_the_references_state_and_tail(served):
    """In the first Mamba layer, the one behind the attention layer and
    the last: a row shorter than the convolution and one that ends
    inside the bucket."""
    import jax.numpy as jnp

    cfg, _, values, eng = served
    prompts = _prompts(seed=2, lens=(2, 21))
    _, cache, _, _ = _prefill(eng, prompts)
    states, tails = np.asarray(cache[2]), np.asarray(cache[3])
    assert states.shape == (LM, 4, 16, 128) and states.dtype == np.float32
    assert tails.shape == (LM, 4, 3 * 128)
    z = ref.sizes(cfg)
    step = ref._jitted(tuple(sorted(z.items())), ref.product)
    for r, p in enumerate(prompts):
        x = jnp.asarray(values["embed_weight"])[np.asarray(p)[None]]
        for i, kind in enumerate(ref.kinds(cfg)):
            leaves = ref.layer_leaves(values, cfg, i)
            if i in (0, 3, 4):
                m = ref.kinds(cfg)[:i].count("ssm")
                _, h, a = ref.mamba(ref._rms_norm(x, leaves["ln1_gamma"],
                                                  z["eps"]), leaves, z,
                                    ref.product)
                np.testing.assert_allclose(states[m, r], np.asarray(h[0]).T,
                                           atol=ATOL, rtol=RTOL)
                # the last three real inputs, zeros before the first
                want = np.zeros((3, 128), np.float32)
                n = min(3, len(p))
                want[3 - n:] = np.asarray(a[0, len(p) - n:])
                np.testing.assert_allclose(tails[m, r].reshape(3, 128),
                                           want, atol=ATOL, rtol=RTOL)
            x = step[kind](x, leaves)


@pytest.mark.parametrize("zeroed", [2, 3], ids=["state", "tail"])
def test_a_zeroed_state_or_tail_fails_the_comparison(served, zeroed):
    """The seeding keeps the state alive: a decode step that starts from
    an emptied state, or an emptied tail, puts out logits further from
    the reference than any tolerance of this file."""
    import jax.numpy as jnp

    cfg, _, values, eng = served
    prompts = _prompts(seed=3, lens=(9, 21, 40, 33))
    B, cache, ids, pos = _prefill(eng, prompts)
    cache = list(cache)
    cache[zeroed] = jnp.zeros_like(cache[zeroed])
    _, logits, *_ = eng._call(B, 1, tuple(cache), pos, np.zeros(B, np.int32),
                              ids)
    toks = np.asarray(ids)
    want = [_ref_logits(values, np.asarray(list(p) + [toks[i, 0]])[None],
                        cfg)[0, -1] for i, p in enumerate(prompts)]
    moved = [float(np.abs(np.asarray(logits[i]) - w).max())
             for i, w in enumerate(want)]
    assert min(moved) > ZEROED_MARGIN, moved


def test_a_token_256_positions_back_moves_the_logits():
    """Under the published configuration's seeding of ``A_log`` a logit
    moves by well over any limit of a cell when a token 256 positions
    back changes (through the attention layer alone, with ``A = -1`` at
    every state, it moves by a twentieth of that)."""
    rng = np.random.RandomState(4)
    ids = rng.randint(0, 96, (1, 320))
    other = ids.copy()
    other[0, 63] = (other[0, 63] + 17) % 96
    moved = {}
    for name, seeded in (("seeded", PUBLISHED["seeded"]), ("unseeded", {})):
        cfg = _config(seeded=seeded)
        values = dict(weights.make(5, ref.param_spec(cfg), "float32"))
        moved[name] = float(np.abs(
            _ref_logits(values, ids, cfg)[0, 319]
            - _ref_logits(values, other, cfg)[0, 319]).max())
    assert moved["seeded"] > 0.2 and moved["seeded"] > 5 * moved["unseeded"], \
        moved


# -- (d) the counters, and rows that want no token -----------------------------

@pytest.mark.parametrize("wants", UNEQUAL_ANSWERS, ids=str)
def test_a_row_that_wants_no_token_changes_nothing(served, wants):
    """The decode step is handed which rows still want a token: the
    others attend to nothing, write no position and keep their state and
    tail bit for bit; every request's tokens are what it gets alone and
    in a group of equal answers; the counters are the live row-steps'."""
    _, _, _, eng = served
    eng.warmup()
    prompts = _prompts(seed=10, lens=(2, 8, 23, 5)[:len(wants)])
    pinned = (serving.trace_count(), serving.compile_count())
    timings, live = serving_unequal_answers(eng, prompts, wants)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    n = [len(p) for p in prompts]
    assert timings["ssm_row_updates_decode"] == LM * len(live)
    assert timings["attn_positions_decode"] == \
        LA * sum(n[i] + j + 1 for i, j in live)
    # a pad row holds one token; the plain scan walks the whole bucket
    pad = 4 - len(prompts)
    S = timings["bucket"][1]
    assert timings["ssm_positions_prefill"] == LM * (sum(n) + pad)
    assert timings["ssm_positions_scanned_prefill"] == LM * 4 * S
    assert timings["ssm_scan_padded_pct"] == pytest.approx(
        100.0 * (1 - (sum(n) + pad) / (4 * S)))
    assert timings["attn_pairs_prefill"] == LA * (
        sum(k * (k + 1) // 2 for k in n) + pad)
    # on the CPU the plain paths ran
    assert timings["decode_state_update_kernel_share"] == 0.0
    assert timings["decode_state_update_live_share"] == 0.0
    assert timings["prefill_state_scan_kernel_share"] == 0.0
    assert eng._program.state_updates[1] == {"plain": LM * 4}
    serving_dead_rows_keep_their_cache(eng, prompts, [k > 1 for k in wants])


# -- (e) bfloat16 inside a tolerance that float8 fails -------------------------

@pytest.fixture(scope="module")
def served_bf16():
    import jax.numpy as jnp

    cfg = _config()
    net, values = _net(cfg, dtype="bfloat16")
    eng = serving.ServingEngine(net, batch_buckets=(4,),
                                dtype=jnp.bfloat16)
    prompts = _prompts()
    toks, logits = _walk(eng, prompts, 6)
    return cfg, values, prompts, toks, logits


def test_serving_in_bfloat16_stays_within_its_tolerance(served_bf16):
    cfg, values, prompts, toks, logits = served_bf16
    worst = max(float(np.abs(logits[i] - want).max()) for i, want in
                enumerate(_served_want(values, cfg, prompts, toks)))
    assert worst < BF16_ATOL, worst


def test_the_float8_reference_fails_the_bfloat16_tolerance(served_bf16):
    cfg, values, prompts, toks, _ = served_bf16
    full = _served_want(values, cfg, prompts, toks)
    low = _served_want(values, cfg, prompts, toks, ref.low_precision)
    worst = max(float(np.abs(a - b).max()) for a, b in zip(low, full))
    assert worst > 3 * BF16_ATOL, worst


# -- (f) the prefill works its real tokens only ---------------------------------

def _row_layout_prefill(net, toks, lens):
    """The prefill in the row layout, every product over the whole
    padded block (R, S, .), from the family's own pieces: (logits at
    each row's last token, states, tails, keys, values)."""
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo import _decoder_ops as ops
    from mxnet_tpu.ops import ssm

    z = net._sizes
    w = dict(zip(net._names, net.decoder_program().weights()))
    lens = jnp.asarray(lens, jnp.int32)
    x = jnp.take(w["embed_weight"], jnp.asarray(toks), axis=0)
    states, tails, keys, values = [], [], [], []
    for i, kind in enumerate(z.kinds):
        g1, j = w["ln1_gamma"][i], z.place[i]
        if kind == "ssm":
            p = jamba._of_layer(w, jamba._SSM_LEAVES, j)
            a, gate = jamba._ssm_in(z, p, g1, x)
            c, tail = ssm.causal_conv_rows(a, p["conv_weight"].T,
                                           p["conv_bias"], lens)
            dt, Bm, Cm = jamba._ssm_x(z, p, c)
            A, D = jamba._ssm_consts(p)
            y, h = ssm.selective_scan_rows(c, dt, A, Bm, Cm, D, lens)
            x = jamba._ssm_out(z, p, x, y, gate)
            states.append(h), tails.append(tail)
        else:
            p = jamba._of_layer(w, jamba._ATTN_LEAVES, j)
            q, k, v = (jamba._heads(z, t)
                       for t in jamba._attn_in(z, p, g1, x))
            a = jamba._block_attention(z, q, k, v, lens)
            x = x + ops.mm("bhsd,chd->bsc", a, p["o_weight"].reshape(
                -1, z.num_heads, z.head_dim))
            keys.append(k.swapaxes(2, 3)), values.append(v.swapaxes(2, 3))
        x = jamba._mlp(z, w, i, x)
    last = jnp.take_along_axis(x, (lens - 1)[:, None, None], axis=1)[:, 0]
    return tuple(np.asarray(t) for t in (
        jamba._head(z, w, last), jnp.stack(states), jnp.stack(tails),
        jnp.stack(keys), jnp.stack(values)))


# two rows a chunk of the bucket of 4 x 64, tiles of 16 packed tokens:
# 41 tokens in 3 tiles of the first chunk's 8, 81 in 6 of the second's
TILED = dict(tile=16, chunk=128, lens=(1, 40, 17, 64), worked=(3 + 6) * 16)


@pytest.fixture
def tiled(monkeypatch):
    monkeypatch.setattr(jamba, "_TILE", TILED["tile"])
    net, _ = _net(_config(), prefill_chunk_tokens=TILED["chunk"])
    return net, serving.ServingEngine(net, batch_buckets=(4,))


def test_the_packed_prefill_equals_the_row_layouts_across_two_row_chunks(
        tiled):
    """Ragged lengths, two row chunks, the token-wise products in tiles
    of the packed block: the logits, the states, the tails and every
    real position's cached keys and values are the row layout's, and
    the counters the prompts' own sums."""
    net, eng = tiled
    lens = TILED["lens"]
    prompts = _prompts(seed=12, lens=lens)
    B, n, toks = _padded_group(eng, prompts)
    assert (B, toks.shape[1]) == (4, 64)
    cache, logits, _, _ = eng._call(B, 64, eng.init_cache(B),
                                    np.zeros(B, np.int32), n - 1, toks)
    want = _row_layout_prefill(net, toks, n)
    np.testing.assert_allclose(np.asarray(logits), want[0], atol=ATOL,
                               rtol=RTOL)
    np.testing.assert_allclose(np.asarray(cache[2]), want[1], atol=ATOL,
                               rtol=RTOL)
    np.testing.assert_allclose(np.asarray(cache[3]), want[2], atol=ATOL,
                               rtol=RTOL)
    for got, rows in ((np.asarray(cache[0]), want[3]),
                      (np.asarray(cache[1]), want[4])):
        for r, k in enumerate(lens):
            np.testing.assert_allclose(got[:, r, :, :, :k], rows[:, r, :, :, :k],
                                       atol=ATOL, rtol=RTOL)
    counts = eng._program.counters(cache)
    assert counts["prefill_positions"] == sum(lens)
    assert counts["prefill_positions_worked"] == TILED["worked"]
    assert counts["prefill_positions_padded"] == TILED["worked"] - sum(lens)
    assert counts["prefill_tokens_padded_pct"] == pytest.approx(
        100.0 * (1 - sum(lens) / TILED["worked"]))
    assert counts["ssm_positions_prefill"] == LM * sum(lens)


def test_a_packed_group_in_tiles_is_bitwise_the_requests_served_alone(tiled):
    """A request alone lies in other tiles, beside three pad rows of one
    token, than in its group: its tokens and logits are the same bits."""
    _, eng = tiled
    prompts = _prompts(seed=13, lens=TILED["lens"])
    toks, logits = _walk(eng, prompts, 3)
    for i, p in enumerate(prompts):
        t1, l1 = _walk(eng, [p], 3)
        np.testing.assert_array_equal(t1[0], toks[i])
        np.testing.assert_array_equal(l1[0], logits[i])


def test_the_prefills_counters_where_one_tile_holds_the_block(served):
    """At the family's own tile a block of 4 x 64 is one tile, worked
    whole: every position of the bucket, of which the prompts' are
    real (a pad row holds one token)."""
    _, _, _, eng = served
    prompts = _prompts(seed=14, lens=(5, 33))
    _, timings = eng.serve_group(prompts, 2)
    S = timings["bucket"][1]
    assert timings["prefill_positions"] == 5 + 33 + 2
    assert timings["prefill_positions_worked"] == 4 * S
    assert timings["prefill_tokens_padded_pct"] == pytest.approx(
        100.0 * (1 - 40 / (4 * S)))


# -- the engine's pins for the seventh family ----------------------------------

def test_the_caches_shapes_no_retrace_a_mesh_refused_and_reload(served):
    cfg, net, _, eng = served
    eng.warmup()
    cache = eng.init_cache(4)
    assert [c.shape for c in cache] == [
        (LA, 4, 1, 16, 128), (LA, 4, 1, 16, 128), (LM, 4, 16, 128),
        (LM, 4, 3 * 128), (7,)]
    assert cache[2].dtype == np.float32
    pinned = serving.trace_count()
    eng.serve_group(_prompts(seed=8), 3)
    assert serving.trace_count() == pinned
    with pytest.raises(MXNetError, match="one chip"):
        serving.ServingEngine(net, batch_buckets=(4,), mesh=object())
    other, _ = _net(cfg, seed=9)
    before, _ = eng.serve_group([[1, 2, 3, 4, 5]], 4)
    eng.reload_from_model(other)
    after, _ = eng.serve_group([[1, 2, 3, 4, 5]], 4)
    assert serving.trace_count() == pinned and (before[0] != after[0]).any()
    eng.reload_from_model(net)
    with pytest.raises(MXNetError, match="incompatible model"):
        eng.reload_from_model(jamba.jamba_tiny(**dict(LAYERS,
                                                      attn_offset=3)))

"""Granite 4.0-H (gluon/model_zoo/granite_hybrid.py) against its plain
reference (benchmark/references/granite_hybrid.py), tiny, on the CPU:
(a) the uncached forward, (b) the cached step through `ServingEngine`
with rows of unequal length in one padded bucket and a second group
after the first, (c) the four chips' shares of a layer add up to the
uncut layer (the share served throughout is experts 4 and 5 of 8), (d) the state lives and the multipliers bite: a token 256
positions back, an emptied state or tail, and ``r``, ``a`` or ``s`` set
to 1 each move a logit by a stated margin, (e) the counters and
``live``, (f) a float8 control for the bfloat16 tolerance, (g) the
packed prefill in tiles and across row chunks, and the engine's pins
for the family."""

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
from mxnet_tpu.gluon.model_zoo import granite_hybrid as gh  # noqa: E402
from mxnet_tpu.test_utils import (                          # noqa: E402
    UNEQUAL_ANSWERS, _padded_group, serving_dead_rows_keep_their_cache,
    serving_host_walk as _walk, serving_unequal_answers)

from benchmark import program, weights                      # noqa: E402
from benchmark.references import granite_hybrid as ref     # noqa: E402

# float32 on both sides, products in another order (the program's
# running softmax, its grouped products and its scan against the
# reference's plain ones): logits that reach 0.15 agree to 1e-6
ATOL, RTOL = 2e-5, 1e-4
# bfloat16 against the float32 reference at these sizes: weights, tails,
# cached keys and values and every product's operands are rounded to 8
# bits of mantissa, a routing choice among 8 can flip, and logits that
# spread by 0.04 move by up to 0.0016 over the served positions below
# (two seeds: 0.0014, 0.0016); three times that.  The float8 control
# moves them by 0.024-0.035 (test (f))
BF16_ATOL = 0.005
# what each perturbation of (d) must move a logit by at the least
# (float32; ATOL is 2e-5; the logits spread by 0.04): the readings over
# two seeds are 0.010-0.022 for a token 256 positions back, of which
# 0.0021-0.0023 through the states alone (the attention layer's way out
# zeroed), 0.05-0.25 for an emptied state or tail, 0.15 for r, 0.05 for
# a and 0.33 for s
MARGIN = {"token": 0.001, "zeroed": 0.02, "r": 0.05, "a": 0.02, "s": 0.1}

with open(os.path.join(ROOT, "benchmark", "configs",
                       "granite-4.0-h-small-ep4.json")) as f:
    PUBLISHED = json.load(f)

LM, LA, HELD = 4, 1, 2
# the share served below: experts 4 and 5 of 8 (a first expert that is
# not 0: the held experts are found by their place in the router)
LO = 4
# the tiny member's draws: as the published configuration's, for a
# width of 64 (its `assumed` says why each leaf is drawn as it is)
SEEDED = {"a_log_weight": "uniform:6", "conv_weight": "uniform:0.5",
          "embed_weight": "normal:0.02", "q_weight": "normal:0.5",
          "k_weight": "normal:0.5", "v_weight": "normal:0.3",
          "experts_down_weight": "normal:0.5",
          "shared_down_weight": "normal:0.3"}


def _config(**over):
    """The tiny member's sizes under the source's keys: hidden 64, five
    layers with the attention layer at 2, four Mamba-2 heads of 8
    channels over 16 states, four query heads over two, eight experts of
    24 of which two are held, three a token, a shared expert of 48."""
    cfg = {"hidden_size": 64, "num_hidden_layers": 5,
           "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "intermediate_size": 24, "shared_intermediate_size": 48,
           "num_local_experts": HELD, "router_experts": 8,
           "experts_held": [LO, HELD], "num_experts_per_tok": 3,
           "vocab_size": 96, "rms_norm_eps": 1e-5, "hidden_act": "silu",
           "tie_word_embeddings": True, "mamba_expand": 0.5,
           "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
           "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_conv_bias": True,
           "mamba_proj_bias": False, "attention_bias": False,
           "position_embedding_type": "nope", "embedding_multiplier": 12.0,
           "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
           "logits_scaling": 4.0,
           # wide enough at 64 units that every term shows
           "initializer_range": 0.1, "seeded": dict(SEEDED)}
    cfg.update(over)
    return cfg


def _net(cfg, seed=5, dtype="float32", **kw):
    """(net, reference parameters): the tiny model with the reference's
    seeded leaves."""
    net = gh.granite_hybrid_tiny(
        dtype=dtype, experts_held=tuple(cfg["experts_held"]),
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"], **kw)
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
    """The reference's full forward at every served position (one
    compiled length: what lies right of a position does not reach
    it)."""
    ids = np.zeros((4, 64), np.int64)
    for i, p in enumerate(prompts):
        ids[i, :len(p) + toks.shape[1] - 1] = list(p) + list(toks[i, :-1])
    out = _ref_logits(values, ids, cfg, prod)
    return [out[i, len(p) - 1:len(p) - 1 + toks.shape[1]]
            for i, p in enumerate(prompts)]


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
    # one prefill program: every prompt in the bucket of 64
    eng = serving.ServingEngine(net, batch_buckets=(4,), prefill_floor=64)
    return cfg, net, values, eng


# -- (a) the uncached forward --------------------------------------------------

def test_forward_equals_the_reference(served):
    cfg, net, values, _ = served
    ids = np.random.RandomState(0).randint(0, 96, (4, 64))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    want = _ref_logits(values, ids, cfg)
    assert got.shape == want.shape == (4, 64, 96)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)


def test_the_leaves_are_the_references_and_the_published_count(served):
    cfg, net, _, _ = served
    assert ref.kinds(cfg) == net._sizes.kinds == ["ssm"] * 2 + ["attn"] \
        + ["ssm"] * 2
    assert {n: tuple(s) for n, s, _ in ref.param_spec(cfg)} \
        == dict(net._sizes.leaves())
    # the published configuration: one period, attention at 5, the
    # issue's count, its draws as `seeded` gives them
    assert [i for i, k in enumerate(ref.kinds(PUBLISHED)) if k == "attn"] \
        == [5] and len(ref.kinds(PUBLISHED)) == 10
    spec = ref.param_spec(PUBLISHED)
    assert sum(int(np.prod(s)) for _, s, _ in spec) == 2_955_758_208
    inits = {n: i for n, _, i in spec}
    assert all(inits[k] == v for k, v in PUBLISHED["seeded"].items())
    assert inits["d_weight"] == "ones" and inits["dt_bias"] == "zeros"
    kw = PUBLISHED["program"]["kwargs"]
    big = gh.GraniteHybridModel(**kw)            # no parameter allocated
    assert {n: tuple(s) for n, s, _ in spec} == dict(big._sizes.leaves())
    with pytest.raises(ValueError, match="no leaf"):
        ref.param_spec(dict(cfg, seeded={"nope": "ones"}))
    with pytest.raises(ValueError, match="one group"):
        ref.sizes(dict(cfg, mamba_n_groups=2))
    with pytest.raises(MXNetError, match="experts_held"):
        gh.granite_hybrid_tiny(experts_held=(7, 2))


# -- (b) prefill, then decode through the states -------------------------------

def test_serving_equals_the_reference_at_every_served_position(served):
    """Rows of unequal length in one bucket, padded on the right: the
    scan stops at each row's length, so the states and tails the decode
    steps start from are the reference's; and a second group after the
    first starts from an empty cache again."""
    cfg, _, values, eng = served
    for seed, lens in ((1, LENS), (6, (17, 2, 32))):
        prompts = _prompts(seed, lens)
        toks, logits = _walk(eng, prompts, 6)
        for i, want in enumerate(_served_want(values, cfg, prompts, toks)):
            np.testing.assert_allclose(logits[i], want, atol=ATOL,
                                       rtol=RTOL)


def test_a_coalesced_group_is_bitwise_the_requests_served_alone(served):
    _, _, _, eng = served
    prompts = _prompts(seed=6, lens=(17, 2, 32, 30))
    toks, logits = _walk(eng, prompts, 4)
    for i, p in enumerate(prompts):
        t1, l1 = _walk(eng, [p], 4)
        np.testing.assert_array_equal(t1[0], toks[i])
        np.testing.assert_array_equal(l1[0], logits[i])


# -- (c) the share is the model's ----------------------------------------------

@pytest.mark.parametrize("shares,i,kind", [(4, 1, "ssm"), (2, 2, "attn")])
def test_the_shares_add_up_to_the_uncut_layer(shares, i, kind):
    """The 8 routed experts of a layer held as ``shares`` shares: the
    mixer once, what the program's ops give for each share's held
    experts, added up, and the shared expert **counted once**, are the
    uncut reference's whole layer, a Mamba layer and the attention
    layer; counting the shared expert in every share is not."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe

    uncut = _config(num_local_experts=8, experts_held=[0, 8])
    z = ref.sizes(uncut)
    values = weights.make(7, ref.param_spec(uncut), "float32")
    x = jnp.asarray(np.random.RandomState(2).normal(size=(3, 11, 64)),
                    jnp.float32)
    per = 8 // shares
    p = ref.layer_leaves(values, uncut, i)
    # the mixer is traced once for both (op by op it compiles each op)
    want, x1 = jax.jit(lambda x: (
        ref.layer(x, p, kind, z, ref.product),
        ref.mixer(x, p, kind, z, ref.product)[0]))(x)
    want = np.asarray(want)
    u = ref._rms_norm(x1, p["ln2_gamma"], z["eps"]).reshape(33, 64)
    chosen, gates = moe.softmax_topk_route(u, p["router_weight"], 3)
    once = moe.swiglu_ffn(u, p["shared_gate_weight"],
                          p["shared_up_weight"], p["shared_down_weight"])
    total, pairs = once, 0
    for s in range(shares):
        at = slice(s * per, (s + 1) * per)
        y, stats = moe.held_experts_ffn(
            u, chosen, gates, p["experts_gate_up_weight"][at],
            p["experts_down_weight"][at], experts_lo=s * per)
        total, pairs = total + y, pairs + int(stats[:per].sum())
    assert pairs == 33 * 3              # each assignment in one share
    got = np.asarray(x1) + z["r"] * np.asarray(total).reshape(3, 11, 64)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)
    every = got + z["r"] * (shares - 1) * np.asarray(once).reshape(
        3, 11, 64)
    assert np.abs(every - want).max() > 1e-2


# -- (d) the state lives and the multipliers bite ------------------------------

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
    assert states.shape == (LM, 4, 32, 16) and states.dtype == np.float32
    assert tails.shape == (LM, 4, 3 * 64)
    z = ref.sizes(cfg)
    step = ref._jitted(tuple(sorted(z.items())), ref.product)
    for r, p in enumerate(prompts):
        x = z["e"] * jnp.asarray(values["embed_weight"])[
            np.asarray(p)[None]]
        for i, kind in enumerate(ref.kinds(cfg)):
            leaves = ref.layer_leaves(values, cfg, i)
            if i in (0, 3, 4):
                m = ref.kinds(cfg)[:i].count("ssm")
                _, h, a = ref.mamba(ref._rms_norm(x, leaves["ln1_gamma"],
                                                  z["eps"]), leaves, z,
                                    ref.product)
                np.testing.assert_allclose(
                    states[m, r], np.asarray(h[0]).reshape(32, 16),
                    atol=ATOL, rtol=RTOL)
                # the last three real inputs, zeros before the first
                want = np.zeros((3, 64), np.float32)
                n = min(3, len(p))
                want[3 - n:] = np.asarray(a[0, len(p) - n:])
                np.testing.assert_allclose(tails[m, r].reshape(3, 64),
                                           want, atol=ATOL, rtol=RTOL)
            x = step[kind](x, leaves)


@pytest.mark.parametrize("zeroed", [2, 3], ids=["state", "tail"])
def test_a_zeroed_state_or_tail_fails_the_comparison(served, zeroed):
    """The seeding keeps the state alive: a decode step that starts from
    emptied states, or emptied tails, puts out logits further from the
    reference than any tolerance of this file."""
    import jax.numpy as jnp

    cfg, _, values, eng = served
    prompts = _prompts(seed=3, lens=(9, 21, 40, 33))
    B, cache, ids, pos = _prefill(eng, prompts)
    cache = list(cache)
    cache[zeroed] = jnp.zeros_like(cache[zeroed])
    _, logits, *_ = eng._call(B, 1, tuple(cache), pos, np.zeros(B, np.int32),
                              ids)
    toks = np.asarray(ids)
    want = [w[-1] for w in _served_want(
        values, cfg, prompts, np.concatenate([toks[:4], toks[:4]], axis=1))]
    moved = [float(np.abs(np.asarray(logits[i]) - w).max())
             for i, w in enumerate(want)]
    assert min(moved) > MARGIN["zeroed"], moved


@pytest.mark.parametrize("what", ["token", "r", "a", "s"])
def test_a_far_token_and_each_multiplier_move_the_logits(what):
    """A token 256 positions back reaches the last logits through the
    slow heads' states: with the attention layer's way out zeroed it
    still moves them, and with ``A = -1`` at every head besides it moves
    nothing; the residual and attention multipliers and the logits'
    scaling set to 1 each move them by well over any tolerance of this
    file."""
    rng = np.random.RandomState(4)
    ids = rng.randint(0, 96, (1, 320))
    cfg = _config()
    values = dict(weights.make(5, ref.param_spec(cfg), "float32"))
    base = _ref_logits(values, ids, cfg)[0, 319]
    if what == "token":
        other = ids.copy()
        other[0, 63] = (other[0, 63] + 17) % 96
        moved = float(np.abs(_ref_logits(values, other, cfg)[0, 319]
                             - base).max())
        by_state = {}
        for name, seeded in (("slow", dict(SEEDED, o_weight="zeros")),
                             ("fast", dict(SEEDED, o_weight="zeros",
                                           a_log_weight="zeros"))):
            c = _config(seeded=seeded)
            v = dict(weights.make(5, ref.param_spec(c), "float32"))
            by_state[name] = float(np.abs(
                _ref_logits(v, other, c)[0, 319]
                - _ref_logits(v, ids, c)[0, 319]).max())
        assert by_state["slow"] > MARGIN["token"] \
            and by_state["fast"] < by_state["slow"] / 10, by_state
    else:
        key = {"r": "residual_multiplier", "a": "attention_multiplier",
               "s": "logits_scaling"}[what]
        moved = float(np.abs(_ref_logits(values, ids, dict(cfg, **{key: 1.0}))[
            0, 319] - base).max())
    assert moved > MARGIN[what], moved


# -- (e) the counters, and rows that want no token -----------------------------

@pytest.mark.parametrize("wants", UNEQUAL_ANSWERS, ids=str)
def test_a_row_that_wants_no_token_changes_nothing(served, wants):
    """The decode step is handed which rows still want a token: the
    others attend to nothing, go to no expert, write no position and
    keep their states and tails bit for bit; every request's tokens are
    what it gets alone and in a group of equal answers; the counters are
    the live row-steps'."""
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
    assert timings["attn_pairs_prefill"] == LA * (
        sum(k * (k + 1) // 2 for k in n) + pad)
    # every layer routes every real token to three of eight experts, of
    # which two are held: no more pairs than tokens x 2, and a step's
    # rows that are not live make none
    tokens = (LM + LA) * (sum(n) + pad)
    assert 0 < timings["moe_pairs_prefill"] <= 2 * tokens
    assert 0 < timings["moe_pairs_decode"] <= 2 * (LM + LA) * len(live)
    assert timings["moe_rows_computed_decode"] >= timings["moe_pairs_decode"]
    # on the CPU the plain paths ran
    assert timings["decode_state_update_kernel_share"] == 0.0
    assert timings["prefill_state_scan_kernel_share"] == 0.0
    assert eng._program.state_updates[1] == {"plain": LM * 4}
    serving_dead_rows_keep_their_cache(eng, prompts, [k > 1 for k in wants])


# -- (f) bfloat16 inside a tolerance that float8 fails -------------------------

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


# -- (g) the prefill works its real tokens only ---------------------------------

# two rows a chunk of the bucket of 4 x 64, tiles of 16 packed tokens:
# 41 tokens in 3 tiles of the first chunk's 8, 77 in 5 of the second's
TILED = dict(tile=16, chunk=128, lens=(1, 40, 17, 60), worked=(3 + 5) * 16)


@pytest.fixture
def tiled(monkeypatch):
    monkeypatch.setattr(gh, "_TILE", TILED["tile"])
    cfg = _config()
    net, values = _net(cfg, prefill_chunk_tokens=TILED["chunk"])
    return cfg, values, serving.ServingEngine(net, batch_buckets=(4,))


def test_the_packed_prefill_in_tiles_across_two_row_chunks(tiled):
    """Ragged lengths, two row chunks, the token-wise products in tiles
    of the packed block and the experts on the packed block whole: the
    logits and the served tokens' are the reference's, a request alone
    (in other tiles, beside three pad rows) gets the same bits, and the
    counters are the prompts' own sums."""
    cfg, values, eng = tiled
    lens = TILED["lens"]
    prompts = _prompts(seed=12, lens=lens)
    toks, logits = _walk(eng, prompts, 3)
    for i, want in enumerate(_served_want(values, cfg, prompts, toks)):
        np.testing.assert_allclose(logits[i], want, atol=ATOL, rtol=RTOL)
    t1, l1 = _walk(eng, prompts[1:2], 3)       # alone
    np.testing.assert_array_equal(t1[0], toks[1])
    np.testing.assert_array_equal(l1[0], logits[1])
    _, timings = eng.serve_group(prompts, 2)
    assert timings["prefill_positions"] == sum(lens)
    assert timings["prefill_positions_worked"] == TILED["worked"]
    assert timings["prefill_tokens_padded_pct"] == pytest.approx(
        100.0 * (1 - sum(lens) / TILED["worked"]))
    assert timings["ssm_positions_prefill"] == LM * sum(lens)


# -- the engine's pins for the eighth family -----------------------------------

def test_the_caches_shapes_no_retrace_a_mesh_refused_and_reload(served):
    cfg, net, _, eng = served
    eng.warmup()
    cache = eng.init_cache(4)
    assert [c.shape for c in cache] == [
        (LA, 4, 2, 16, 128), (LA, 4, 2, 16, 128), (LM, 4, 32, 16),
        (LM, 4, 3 * 64), (LM + LA, 2, HELD + 3), (7,)]
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
        eng.reload_from_model(gh.granite_hybrid_tiny(
            residual_multiplier=1.0))
    # the published cell's cache, as shapes: 5.29 GB at 128 rows
    big = gh.GraniteHybridModel(**PUBLISHED["program"]["kwargs"])
    stacks, states, counters = big.decoder_program().cache_shapes(128)
    assert [s for s, _ in stacks] == [(1, 128, 8, 128, 768)] * 2
    assert [s for s, _ in states] == [(9, 128, 8192, 128), (9, 128, 25344)]
    assert [s for s, _ in counters] == [(10, 2, 21), (7,)]

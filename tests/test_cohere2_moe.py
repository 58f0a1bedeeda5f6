"""Command A+'s language model (gluon/model_zoo/cohere2_moe.py) against
its plain reference (benchmark/references/cohere2_moe.py), tiny, on the
CPU: the uncached forward, the cached step through `ServingEngine` with
rings that wrap in prefill and again in decode, the shares of an expert
layer with attention, the shared experts and the norm counted once, what
position means to each kind of layer, a prefilled ring that wraps, and
the engine's pins for the family.  One engine for the module."""

import copy
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx                                      # noqa: E402
from mxnet_tpu import serving                               # noqa: E402
from mxnet_tpu.base import MXNetError                       # noqa: E402
from mxnet_tpu.gluon.model_zoo import _decoder_ops as ops   # noqa: E402
from mxnet_tpu.gluon.model_zoo import cohere2_moe as cm     # noqa: E402
from mxnet_tpu.ops import cache_write                       # noqa: E402
from mxnet_tpu.test_utils import (                          # noqa: E402
    UNEQUAL_ANSWERS, serving_dead_rows_keep_their_cache,
    serving_host_walk as _walk, serving_unequal_answers)

from benchmark import program, weights                      # noqa: E402
from benchmark.references import cohere2_moe as ref         # noqa: E402

# float32 on both sides, products and the softmax's sums in another order
# (the kernel's running maximum over tiles, the ring's slots out of
# order, the shared experts as one product where the reference makes
# four): logits that reach 2 agree to 2e-5; ten times that.  bfloat16
# where float32 is stated moves them by 5e-3 and more
# (test_bfloat16_where_float32_is_stated_fails)
ATOL, RTOL = 2e-4, 1e-4
WINDOW = 8


def _config(**over):
    """The tiny member's sizes under the source's keys: hidden 64, 8
    query heads over 2 key heads of 16, three window layers (8
    positions) and a full one, 8 experts top-2 of width 32 of which
    experts 2-3 are held, 2 shared experts."""
    cfg = {"hidden_size": 64, "num_hidden_layers": 4,
           "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
           "num_attention_heads": 8, "num_key_value_heads": 2,
           "head_dim": 16, "sliding_window": WINDOW, "rope_theta": 50000.0,
           "rotary_pct": 1, "position_embedding_type": "rope_gptj",
           "intermediate_size": 32, "num_experts": 2, "router_experts": 8,
           "experts_held": [2, 2], "num_experts_per_tok": 2,
           "num_shared_experts": 2,
           "shared_expert_combination_strategy": "average",
           "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
           "first_k_dense_replace": 0, "use_parallel_block": True,
           "use_qk_norm": False, "attention_bias": False,
           "use_gated_activation": True, "hidden_act": "silu",
           "tie_word_embeddings": True, "logit_scale": 1,
           "layer_norm_eps": 1e-5, "vocab_size": 96,
           # wide enough that every term of a layer shows in the logits
           "initializer_range": 0.2,
           "seeded": {"embed_weight": "normal:1.0",
                      "ln_gamma": "normal:1.0"}}
    cfg.update(over)
    return cfg


def _net(cfg, seed=5, dtype="float32", **kw):
    """(net, reference parameters): the tiny model with the reference's
    seeded leaves."""
    net = cm.cohere2_moe_tiny(experts_held=cfg["experts_held"],
                              dtype=dtype, **kw)
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


@pytest.fixture(scope="module")
def served():
    cfg = _config()
    # a bucket of 32 positions is prefilled a row at a time, one of 16
    # two rows at a time, one of 8 with every row at once
    net, values = _net(cfg, prefill_chunk_tokens=32)
    return cfg, net, values, serving.ServingEngine(net, batch_buckets=(4,))


# -- (a) the uncached forward --------------------------------------------------

def test_forward_equals_the_reference(served, monkeypatch):
    """`hybrid_forward` over whole sequences 3.5 times the window,
    token-wise products cut along S; the reference in pieces smaller
    than the sequence.  (Sequences shorter than the window: (b).)"""
    cfg, net, values, _ = served
    T = 28
    monkeypatch.setattr(net._sizes, "token_chunk", 4)
    for name, n in (("TOKEN_CHUNK", 14), ("QUERY_BLOCK", 7),
                    ("FFN_TILE", 16)):
        monkeypatch.setattr(ref, name, n)
    ids = np.random.RandomState(0).randint(0, 96, (3, T))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    want = _ref_logits(values, ids, cfg)
    assert got.shape == want.shape == (3, T, 96)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)


def test_bfloat16_where_float32_is_stated_fails(served):
    """The tolerance is a float32 one: the same reference with both
    operands of every product rounded to bfloat16 is 25 times ATOL and
    more from itself in float32."""
    import jax.numpy as jnp

    cfg, _, values, _ = served
    ids = np.random.RandomState(0).randint(0, 96, (1, 28))
    low = _ref_logits(values, ids, cfg, lambda spec, a, b: ref.product(
        spec, *(x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))))
    assert np.abs(low - _ref_logits(values, ids, cfg)).max() > 25 * ATOL


# -- (b) prefill, then decode through the rings --------------------------------

LENS = (8, 16, 28, 3)       # 1x, 2x and 3.5x the window, and under it
STEPS = 12


def _pairs(lens, steps):
    """(window, full) query-key pairs a layer of a prefill of ``lens``
    and of ``steps`` decode steps after it."""
    band = lambda n: sum(min(i + 1, WINDOW) for i in range(n))
    tri = lambda n: n * (n + 1) // 2
    return ((sum(band(n) for n in lens), sum(tri(n) for n in lens)),
            (sum(min(n + j + 1, WINDOW) for n in lens for j in range(steps)),
             sum(n + j + 1 for n in lens for j in range(steps))))


def test_serving_equals_the_reference_at_every_served_position(served):
    """A group of unequal rows at 1, 2 and 3.5 times the window and one
    under it, decoded 12 steps: rings wrap in the prefill and again in
    the decode steps (slots out of order), a ring fills in the decode
    steps (3 -> 8), and the cached step's logits are the reference's
    full forward at each served position (prefilled a row at a time:
    `test_no_retrace_after_warmup` serves buckets that take every row at
    once); `serve_group` serves the same tokens and counts the pairs it
    was asked to score."""
    cfg, net, values, eng = served
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 96, n).tolist() for n in LENS]
    toks, logits = _walk(eng, prompts, STEPS)
    for i, p in enumerate(prompts):
        full = np.asarray(list(p) + list(toks[i, :-1]))[None]
        want = _ref_logits(values, full, cfg)[0, len(p) - 1:]
        np.testing.assert_allclose(logits[i], want, atol=ATOL, rtol=RTOL,
                                   err_msg=f"prompt of {len(p)}")
    outs, timings = eng.serve_group(prompts, STEPS)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o, toks[i])
    (w_pre, f_pre), (w_dec, f_dec) = _pairs(LENS, STEPS - 1)
    assert timings["attn_window_pairs_prefill"] == 3 * w_pre
    assert timings["attn_window_pairs_causal_prefill"] == 3 * f_pre
    assert timings["attn_full_pairs_prefill"] == f_pre
    assert timings["attn_window_pairs_decode"] == 3 * w_dec
    assert timings["attn_window_pairs_causal_decode"] == 3 * f_dec
    assert timings["attn_full_pairs_decode"] == f_dec
    # the router's choices that fall to experts 2-3, of 2 a token and
    # layer over 8 experts: some, and never more than one a held expert
    assert 0 < timings["moe_pairs_prefill"] <= sum(LENS) * 2 * 4
    assert 0 < timings["moe_pairs_decode"] <= 4 * 2 * 4 * (STEPS - 1)
    assert timings["prefill_attn_kernel_share"] == 1.0
    # on the CPU both ops take their XLA paths
    assert timings["decode_cache_write_kernel_share"] == 0.0
    assert timings["decode_attn_kernel_share"] == 0.0
    assert timings["decode_attn_window_read_pct"] == 100.0


@pytest.mark.parametrize("wants", UNEQUAL_ANSWERS, ids=str)
def test_a_row_that_wants_no_token_changes_nothing(served, wants):
    """Unequal answers in a group: a row that is done attends to
    nothing, goes to no routed expert and writes nothing into the
    rings or the full stacks, every request's tokens are
    what it gets alone and in a group of equal answers, and the
    counters are the live row-steps'."""
    _, _, _, eng = served
    rng = np.random.RandomState(10)
    lens = (2, 8, 23, 5)[:len(wants)]
    prompts = [rng.randint(0, 96, n).tolist() for n in lens]
    timings, live = serving_unequal_answers(eng, prompts, wants)
    assert timings["attn_full_pairs_decode"] == \
        sum(lens[i] + j + 1 for i, j in live)
    assert timings["attn_window_pairs_decode"] == \
        3 * sum(min(lens[i] + j + 1, WINDOW) for i, j in live)
    # and in every layer of every stack a finished row's cache rows are
    # what they were
    serving_dead_rows_keep_their_cache(eng, prompts, [k > 1 for k in wants])


# -- (c) the share ties to the model -------------------------------------------

@pytest.mark.parametrize("shares", [4, 2])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """A layer's 8 routed experts held as ``shares`` shares of
    ``router_experts / n``: what the program's layer gives each share's
    routed experts, added up, with attention, the shared experts and the
    norm **counted once**, is the uncut reference's whole layer;
    counting them in every share is not."""
    import jax.numpy as jnp

    cfg = _config(num_experts=8, experts_held=[0, 8])
    z_ref = ref.sizes(cfg)
    values = weights.make(7, ref.param_spec(cfg), "float32")
    parts = ref._jitted(ref._key(z_ref), ref.product)
    x = jnp.asarray(np.random.RandomState(2).normal(size=(3, 11, 64)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(11)[None], (3, 11))
    want = np.asarray(ref.layer([x], values, 1, z_ref, parts)[0])
    per = 8 // shares
    whole = cm.cohere2_moe_tiny()._sizes

    layer = {n: values[f"l1_{n}"] for n in cm._LAYER_LEAVES}

    @jax.jit
    def share(lo, p):
        """(the layer's output, the routed experts' part alone, the
        pairs computed) of the share that holds experts ``lo ..``."""
        z = copy.copy(whole)
        z.experts_held = (lo, per)
        out, _, _, stats = cm._block_layer(z, "window", p, x, pos, None,
                                           None)
        h = cm._norm(z, p, x)
        routed, _ = cm._experts(z, p, h, cm._experts_front(z, p, h)[1],
                                None, jnp.zeros_like(x))
        return out, routed, jnp.sum(stats[:per])

    def held(s):
        at = slice(s * per, (s + 1) * per)
        return dict(layer,
                    experts_gate_up_weight=layer["experts_gate_up_weight"][at],
                    experts_down_weight=layer["experts_down_weight"][at])

    outs = [[np.asarray(a) for a in share(s * per, held(s))]
            for s in range(shares)]
    assert sum(int(n) for _, _, n in outs) == 3 * 11 * 2    # each pair once
    total = outs[0][0] + sum(routed for _, routed, _ in outs[1:])
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=1e-4)
    if shares > 1:
        every = sum(out for out, _, _ in outs) - (shares - 1) * np.asarray(x)
        assert np.abs(every - want).max() > 1e-2


def test_holding_other_experts_gives_other_logits(served):
    """The fixture holds experts 2-3 of 8 under the whole router, and
    (a) and (b) hold program and reference to the same share; which
    experts a chip holds is part of the result."""
    cfg, net, values, _ = served
    ids = np.random.RandomState(3).randint(0, 96, (2, 20))
    other = _ref_logits(values, ids, _config(experts_held=[5, 2]))
    assert np.abs(other - _ref_logits(values, ids, cfg)).max() > 1e-2


# -- (d) what a position means to each kind of layer ---------------------------

def test_positions_are_relative_on_window_layers_and_nothing_on_full_ones(
        served, monkeypatch):
    """Shift every position by a constant: a full layer's output is
    bitwise the same (it takes no position), a window layer's the same
    to rounding (the rotation is relative).  The two pairings are not
    interchangeable under given weights: a window layer rotated in
    halves gives another output, a full layer the same."""
    import jax.numpy as jnp

    _, net, values, _ = served
    z = net._sizes
    x = jnp.asarray(np.random.RandomState(4).normal(size=(2, 24, 64)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))

    def layer(kind, i, pos):
        p = {n: values[f"l{i}_{n}"] for n in cm._LAYER_LEAVES}
        return cm._block_layer(z, kind, p, x, pos, None, None)[0]

    def out(kind, i, pos, run=jax.jit(layer, static_argnums=(0, 1))):
        return np.asarray(run(kind, i, pos))

    full, window = out("full", 3, pos), out("window", 0, pos)
    np.testing.assert_array_equal(out("full", 3, pos + 1000), full)
    # angles of 1,000 radians in float32: sin and cos to 1e-4
    np.testing.assert_allclose(out("window", 0, pos + 1000), window,
                               atol=5e-3)
    assert np.abs(out("window", 0, pos + 1000) - window).max() < \
        0.01 * np.abs(window - np.asarray(x)).max()
    rope = ops.rope
    monkeypatch.setattr(ops, "rope", lambda *a, pairs, **kw: rope(
        *a, pairs="halves", **kw))
    # another function, so traced anew
    halves = jax.jit(lambda *a: layer(*a), static_argnums=(0, 1))
    assert np.abs(out("window", 0, pos, halves) - window).max() > 0.05
    np.testing.assert_array_equal(out("full", 3, pos, halves), full)


def test_the_two_pairings_of_the_rotation():
    """`_decoder_ops.rope`: interleaved pairs are the halves' rotation
    of the dimensions reordered (evens, then odds), at the same
    frequencies; the reference's rotation is the interleaved one."""
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(5).normal(size=(2, 3, 7, 16)),
                    jnp.float32)
    pos = jnp.asarray(np.random.RandomState(6).randint(0, 5000, (2, 7)))
    got = np.asarray(ops.rope(x, pos, 50000.0, 16, pairs="interleaved"))
    order = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    halves = np.asarray(ops.rope(x[..., order], pos, 50000.0, 16))
    np.testing.assert_allclose(got[..., order], halves, atol=1e-6)
    z = {"D": 16, "theta": 50000.0}
    for b in range(2):      # the reference rotates (B, T, .., D) from t0
        for t in range(7):
            want = ref.rotate(x[b:b + 1, :, t:t + 1].swapaxes(1, 2),
                              int(pos[b, t]), z)
            np.testing.assert_allclose(got[b, :, t], np.asarray(want)[0, 0],
                                       atol=2e-4)
    with pytest.raises(ValueError, match="halves"):
        ops.rope(x, pos, 50000.0, 16, pairs="pairs")


# -- (g) a prefilled ring that wraps -------------------------------------------

@pytest.mark.parametrize("S,W", [(32, 8), (16, 16), (8, 16)])
def test_a_prefilled_ring_holds_each_rows_last_positions(S, W):
    """`cache_write.write_ring`: slot s of row b's ring holds the
    latest position below the row's length that is congruent to s, for
    rows shorter than the ring, as long, and several times as long (the
    kept positions then lie in two pieces of the block), into layer 1 of
    a stack of 3 at row 1 of 4; nothing else is touched."""
    import jax.numpy as jnp

    lengths = [n for n in (1, W - 1, W, W + 3, 2 * W + 5, S) if n <= S]
    R = len(lengths)
    rng = np.random.RandomState(S + W)
    news = [jnp.asarray(rng.normal(size=(R, K, D, S)), jnp.float32)
            for K, D in ((2, 4), (1, 6))]
    stacks = [jnp.asarray(rng.normal(size=(3, R + 2, n.shape[1], n.shape[2],
                                           W)), jnp.float32) for n in news]
    import collections

    tally = collections.Counter()
    out = cache_write.write_ring(stacks, news, 1, jnp.asarray(lengths),
                                 tally=tally, row=1)
    assert tally["rows"] == 2 * R
    for c, c0, new in zip(out, stacks, news):
        want = np.asarray(c0).copy()
        for b, n in enumerate(lengths):
            for s in range(W):
                p = n - 1 - (n - 1 - s) % W
                if p >= 0:
                    want[1, 1 + b, :, :, s] = np.asarray(new)[b, :, :, p]
        got = np.asarray(c)
        live = np.zeros(got.shape, bool)
        for b, n in enumerate(lengths):
            live[1, 1 + b, :, :, :min(n, W)] = True
        untouched = np.ones(got.shape, bool)
        untouched[1, 1:1 + R] = False
        np.testing.assert_array_equal(got[live], want[live])
        np.testing.assert_array_equal(got[untouched], want[untouched])


# -- the engine's pins for the sixth family ------------------------------------

def test_no_retrace_after_warmup(served):
    _, _, _, eng = served
    eng.warmup()
    pinned = serving.trace_count()
    rng = np.random.RandomState(5)
    for lens in ((2, 9), (16, 3, 1, 40), (4,)):
        eng.serve_group([rng.randint(0, 96, n).tolist() for n in lens], 5)
    assert serving.trace_count() == pinned
    assert eng.program_count() == len(eng.prefill_buckets) + 1


@pytest.mark.parametrize("kind,S", [("prefill", 16), ("decode", 1)])
def test_the_four_stacks_alias_their_inputs(served, kind, S):
    """Two kinds of cache: full stacks the window long, rings the
    layer's window long; every array of the cache is written into its
    donated argument, and the decode program moves no layer-sized piece
    of any stack."""
    _, _, _, eng = served
    B = 4
    text = eng._compile(B, S).as_text()
    n_w = len(eng._weights)
    cache = eng.init_cache(B)
    assert [c.shape for c in cache] == [
        (1, B, 2, 16, 64)] * 2 + [(3, B, 2, 16, WINDOW)] * 2 + [
            (4, 2, 5), (4, 2, 2)]
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(len(cache)):
        assert f"{{{i}}}: ({n_w + i}, {{}}" in alias, (i, alias)
    if kind == "decode":
        assert serving.whole_layer_ops(
            text, cache[2].nbytes // cache[2].shape[0]) == []


def test_the_weights_are_the_parameters_own_buffers(served):
    _, net, _, eng = served
    assert "head_weight" not in net._names      # the head is the embedding
    for name, a in zip(net._names, eng._weights):
        assert a is getattr(net, name).data()._data, name


def test_a_coalesced_group_is_bitwise_the_requests_served_alone(served):
    """Rows do not see each other, whatever row chunk they fall in."""
    _, _, _, eng = served
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 96, n).tolist() for n in (17, 21, 32, 30)]
    toks, logits = _walk(eng, prompts, 10)
    for i, p in enumerate(prompts[:2]):
        t1, l1 = _walk(eng, [p], 10)
        np.testing.assert_array_equal(t1[0], toks[i])
        np.testing.assert_array_equal(l1[0], logits[i])


@pytest.mark.parametrize("steps", [1, 9])
def test_a_greedy_group_is_fed_on_the_device(served, steps):
    _, _, _, eng = served
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, 96, n).tolist() for n in (2, 8, 23)]
    want, _ = _walk(eng, prompts, steps)
    pinned = (serving.trace_count(), serving.compile_count())
    d0 = serving.dispatch_count()
    outs, timings = eng.serve_group(prompts, steps)
    assert serving.dispatch_count() - d0 == 1 + (steps - 1)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    np.testing.assert_array_equal(np.stack(outs), want)
    assert timings["decode_steps_fed_on_device"] == steps - 1
    assert timings["decode_readback_bytes_per_step"] == 4 * 4


def test_a_mesh_is_refused_and_reload_goes_through_weights(served):
    cfg, net, _, eng = served
    with pytest.raises(MXNetError, match="one chip"):
        serving.ServingEngine(net, batch_buckets=(4,), mesh=object())
    with pytest.raises(MXNetError, match="layer_types"):
        cm.cohere2_moe_tiny(layer_types=["window", "linear"])
    prompts = [[1, 2, 3, 4, 5], list(range(7, 30))]
    before, _ = eng.serve_group(prompts, 4)
    other, _ = _net(cfg, seed=9)
    eng.reload_from_model(other)
    pinned = serving.trace_count()
    after, _ = eng.serve_group(prompts, 4)
    assert serving.trace_count() == pinned and eng.generation == 1
    assert any((a != b).any() for a, b in zip(after, before))
    eng.reload_from_model(net)
    with pytest.raises(MXNetError, match="incompatible model"):
        eng.reload_from_model(cm.cohere2_moe_tiny(window=16))

"""Ouro's looped language model (gluon/model_zoo/ouro.py) against its
plain reference (benchmark/references/ouro.py), tiny, on the CPU: (a) the
uncached forward, (b) the cached step through `ServingEngine` with a
cache slot for every (loop step, layer), (c) the loop itself (another
number of passes gives other logits; each slot holds its own pass's
keys), (d) the exit rule and its histogram, (e) the counters, (f) a
float8 control for the bfloat16 tolerance, and the engine's pins for the
family.  (g), the programs compiled for a described v5e at the published
widths, is in tests/test_cache_write.py with every other such compile
(one worker describes the chip)."""

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
from mxnet_tpu.gluon.model_zoo import ouro                  # noqa: E402
from mxnet_tpu.test_utils import (                          # noqa: E402
    UNEQUAL_ANSWERS, serving_dead_rows_keep_their_cache,
    serving_host_walk as _walk, serving_unequal_answers)

from benchmark import program, weights                      # noqa: E402
from benchmark.references import ouro as ref                # noqa: E402

# float32 on both sides, products in another order (the program's one
# qkv product and its running softmax against the reference's plain
# one): logits that reach 5 agree to 2e-5
ATOL, RTOL = 2e-4, 1e-4
# bfloat16 against the float32 reference at the tiny member's sizes with
# matrices normal(0.1) and a unit embedding (`_bf16_config`): weights,
# cached keys and values and every product's operands are rounded to 8
# bits of mantissa, so logits of size 3 move by up to 0.023 over the
# served positions below (two seeds); three times that.  The float8
# control moves them by 0.42 and more (test (f)).  At normal(0.2) the
# scores spread by 2.6, the softmax is sharp and the same rounding moves
# a logit of size 6 by 0.26
BF16_ATOL = 0.07

L, T = 2, 3


def _config(**over):
    """The tiny member's sizes under the source's keys (hidden 64, 4
    heads of 16, a feed-forward of 96, 2 layers run 3 times)."""
    cfg = {"hidden_size": 64, "num_hidden_layers": L,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "head_dim": 16, "intermediate_size": 96, "total_ut_steps": T,
           "early_exit_threshold": 1.0, "vocab_size": 96,
           "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
           "hidden_act": "silu", "tie_word_embeddings": False,
           "rope_scaling": None, "use_sliding_window": False,
           # wide enough that the scores spread and every term shows
           "initializer_range": 0.2}
    cfg.update(over)
    return cfg


def _net(cfg, seed=5, dtype="float32", **kw):
    """(net, reference parameters): the tiny model with the reference's
    seeded leaves."""
    net = ouro.ouro_tiny(
        num_layers=cfg["num_hidden_layers"],
        kv_heads=cfg["num_key_value_heads"],
        loop_steps=cfg["total_ut_steps"],
        exit_threshold=cfg["early_exit_threshold"], dtype=dtype, **kw)
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


LENS = (3, 8, 21, 40)


def _prompts(seed=1, lens=LENS):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 96, n).tolist() for n in lens]


# -- (a) the uncached forward --------------------------------------------------

@pytest.mark.parametrize("n,kv_heads", [(20, 4), (5, 4), (20, 2), (130, 1)])
def test_forward_equals_the_reference(n, kv_heads):
    """`hybrid_forward`, all ``T`` passes, at every position; plain
    multi-head as published and with fewer key heads than query heads;
    shorter and longer than the flash kernel's 128-position tile."""
    cfg = _config(num_key_value_heads=kv_heads)
    net, values = _net(cfg)
    ids = np.random.RandomState(0).randint(0, 96, (3, n))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    want = _ref_logits(values, ids, cfg)
    assert got.shape == want.shape == (3, n, 96)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)


# -- (b) prefill, then decode through the T L slots ----------------------------

@pytest.mark.parametrize("kv_heads", [4, 2])
def test_serving_equals_the_reference_at_every_served_position(kv_heads):
    """A group of unequal rows, decoded 12 steps: the cached step's
    logits are the reference's full forward at each served position, and
    `serve_group` serves the same tokens."""
    cfg = _config(num_key_value_heads=kv_heads)
    net, values = _net(cfg)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    prompts, steps = _prompts(), 12
    toks, logits = _walk(eng, prompts, steps)
    for i, want in enumerate(_served_want(values, cfg, prompts, toks)):
        np.testing.assert_allclose(logits[i], want, atol=ATOL, rtol=RTOL,
                                   err_msg=f"prompt of {LENS[i]}")
    outs, _ = eng.serve_group(prompts, steps)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o, toks[i])


# -- (c) the loop is real ------------------------------------------------------

def test_another_number_of_passes_gives_other_logits():
    """The same weights run once, twice and three times over."""
    cfg = _config()
    _, values = _net(cfg)
    ids = np.random.RandomState(0).randint(0, 96, (2, 12))
    by_steps = []
    for steps in (1, 2, 3):
        c = _config(total_ut_steps=steps)
        net, _ = _net(c)
        got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
        np.testing.assert_allclose(got, _ref_logits(values, ids, c),
                                   atol=ATOL, rtol=RTOL)
        by_steps.append(got)
    assert np.abs(by_steps[0] - by_steps[2]).max() > 0.5
    assert np.abs(by_steps[1] - by_steps[2]).max() > 0.5


def test_every_slot_holds_its_own_passs_keys_and_values():
    """After a prefill, slot ``t L + l`` of the stacks holds the
    reference's rotated keys and its values of loop step ``t``, layer
    ``l``, each row's to its length: no slot shared, none skipped, and
    no two passes alike."""
    import jax.numpy as jnp

    cfg = _config()
    net, values = _net(cfg)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    prompts = _prompts()
    B, S = 4, 64
    toks = np.zeros((B, S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lens = np.asarray(LENS, np.int32)
    cache, *_ = eng._call(B, S, eng.init_cache(B), np.zeros(B, np.int32),
                          lens - 1, toks)
    ck, cv = np.asarray(cache[0]), np.asarray(cache[1])
    assert ck.shape == cv.shape == (T * L, B, 4, 16, 64)
    for i, p in enumerate(prompts):
        _, _, kept = ref.passes(values, jnp.asarray([p]), cfg,
                                keep_keys=True)
        for t in range(T):
            for l in range(L):
                k, v = (np.asarray(a)[0].transpose(1, 2, 0)
                        for a in kept[t][l])        # (K, d, n)
                slot = t * L + l
                np.testing.assert_allclose(ck[slot, i, :, :, :len(p)], k,
                                           atol=2e-5, rtol=1e-4)
                np.testing.assert_allclose(cv[slot, i, :, :, :len(p)], v,
                                           atol=2e-5, rtol=1e-4)
    row = ck[:, 3, :, :, :LENS[3]]
    for a in range(T * L):
        for b in range(a):
            assert np.abs(row[a] - row[b]).max() > 0.1, (a, b)


# -- (d) the exit rule ---------------------------------------------------------

def _gated_config(threshold):
    # gates spread over (0, 1): rows of one batch leave at different
    # steps under a threshold of 0.5
    return _config(early_exit_threshold=threshold,
                   seeded={"exit_weight": "normal:0.3",
                           "exit_bias": "normal:0.5"})


def test_the_exit_rule_on_given_gates():
    import jax.numpy as jnp

    gates = jnp.asarray([[0.6, 0.2, 0.2, 0.0, 0.5],
                         [0.9, 0.5, 0.1, 0.0, 0.0],
                         [0.0, 0.0, 0.0, 0.0, 0.0]], jnp.float32)
    # cumulative: 0.6 | 0.2, 0.6 | 0.2, 0.28 | 0 | 0.5 (at the threshold)
    want = [0, 1, 2, 2, 0]
    np.testing.assert_array_equal(ref.exit_step(gates, 0.5), want)
    z = ouro.ouro_tiny(exit_threshold=0.5)._sizes
    np.testing.assert_array_equal(ouro._exit_step(z, gates), want)
    z1 = ouro.ouro_tiny(exit_threshold=1.0)._sizes
    np.testing.assert_array_equal(ouro._exit_step(z1, gates), [2] * 5)
    assert np.asarray(ouro._exit_step(
        z1, jnp.asarray([[1.0], [0.3], [0.3]]))).tolist() == [0]


def test_rows_of_one_batch_leave_at_different_steps():
    """Under a threshold of 0.5 with a seeded gate: the served logits
    are the reference's at each position's own exit step, and the
    histogram counts the rows by the step the reference gives them."""
    import jax.numpy as jnp

    cfg = _gated_config(0.5)
    net, values = _net(cfg)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    prompts, steps = _prompts(seed=2), 6
    toks, logits = _walk(eng, prompts, steps)
    hist = {"prefill": [0] * T, "decode": [0] * T}
    for i, p in enumerate(prompts):
        full = jnp.asarray([list(p) + list(toks[i, :-1])])
        _, gates = ref.passes(values, full, cfg)
        left = np.asarray(ref.exit_step(gates, 0.5))[0, len(p) - 1:]
        hist["prefill"][left[0]] += 1
        for t in left[1:]:
            hist["decode"][t] += 1
        # no gate at a served position lies near the threshold's edge
        np.testing.assert_allclose(
            logits[i], _ref_logits(values, np.asarray(full), cfg)[
                0, len(p) - 1:], atol=ATOL, rtol=RTOL)
    assert sum(n > 0 for n in hist["prefill"]) + \
        sum(n > 0 for n in hist["decode"]) >= 4, hist
    _, timings = eng.serve_group(prompts, steps)
    assert timings["loop_exit_step_prefill"] == hist["prefill"]
    assert timings["loop_exit_step_decode"] == hist["decode"]
    # the same weights under the published threshold: other logits, and
    # every row leaves at the last step
    late, _ = _net(_gated_config(1.0))
    eng1 = serving.ServingEngine(late, batch_buckets=(4,))
    _, logits1 = _walk(eng1, prompts, 2)
    assert np.abs(logits1 - logits[:, :2]).max() > 0.1
    _, timings = eng1.serve_group(prompts, steps)
    assert timings["loop_exit_step_prefill"] == [0, 0, 4]
    assert timings["loop_exit_step_decode"] == [0, 0, 4 * (steps - 1)]


# -- (e) the counters ----------------------------------------------------------

@pytest.mark.parametrize("steps", [1, 2, 9])
def test_the_counters_of_a_served_group(steps):
    """``T`` passes a program execution; the positions read over all
    ``T L`` slots, every row of the bucket in the prefill and every row
    that wants a token in a decode step (the pad row wants none); and
    the group is fed on the device like every family's."""
    net, _ = _net(_config())
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    prompts = _prompts(lens=(2, 8, 23))
    want, _ = _walk(eng, prompts, steps)
    pinned = (serving.trace_count(), serving.compile_count())
    d0 = serving.dispatch_count()
    outs, timings = eng.serve_group(prompts, steps)
    assert serving.dispatch_count() - d0 == 1 + (steps - 1)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    np.testing.assert_array_equal(np.stack(outs), want)
    assert timings["decode_steps_fed_on_device"] == steps - 1
    assert timings["loop_passes_prefill"] == T
    assert timings["loop_passes_decode"] == T * (steps - 1)
    lens = (2, 8, 23, 1)            # the pad row holds one dummy token
    assert timings["attn_positions_prefill"] == \
        T * L * sum(n * (n + 1) // 2 for n in lens)
    assert timings["attn_positions_decode"] == \
        T * L * sum(n + j + 1 for n in lens[:3] for j in range(steps - 1))
    assert sum(timings["loop_exit_step_decode"]) == 3 * (steps - 1)
    # on the CPU both cache ops take their XLA paths; the block's
    # attention is the flash forward kernel, interpreted
    if steps > 1:
        assert timings["decode_cache_write_kernel_share"] == 0.0
        assert timings["decode_attn_kernel_share"] == 0.0
    assert timings["prefill_attn_kernel_share"] == 1.0


# -- (f) bfloat16 inside a tolerance that float8 fails -------------------------

@pytest.fixture(scope="module")
def served_bf16():
    import jax.numpy as jnp

    cfg = _config(initializer_range=0.1,
                  seeded={"embed_weight": "normal:1.0"})
    net, values = _net(cfg, dtype="bfloat16")
    eng = serving.ServingEngine(net, batch_buckets=(4,),
                                dtype=jnp.bfloat16)
    prompts = _prompts()
    toks, logits = _walk(eng, prompts, 12)
    return cfg, values, prompts, toks, logits


def test_serving_in_bfloat16_stays_within_its_tolerance(served_bf16):
    cfg, values, prompts, toks, logits = served_bf16
    worst = max(float(np.abs(logits[i] - want).max()) for i, want in
                enumerate(_served_want(values, cfg, prompts, toks)))
    assert worst < BF16_ATOL, worst


def test_the_float8_reference_fails_the_bfloat16_tolerance(served_bf16):
    """The reference with both operands of every product through float8
    is further from the float32 reference, at the same prompts and
    served tokens, than the tolerance the bfloat16 program meets."""
    cfg, values, prompts, toks, _ = served_bf16
    full = _served_want(values, cfg, prompts, toks)
    low = _served_want(values, cfg, prompts, toks, ref.low_precision)
    worst = max(float(np.abs(a - b).max()) for a, b in zip(low, full))
    assert worst > 5 * BF16_ATOL, worst


# -- the engine's pins for the fifth family ------------------------------------

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
    for lens in ((2, 9), (16, 3, 1, 40), (4,)):
        eng.serve_group([rng.randint(0, 96, n).tolist() for n in lens], 5)
    assert serving.trace_count() == pinned
    assert eng.program_count() == len(eng.prefill_buckets) + 1


@pytest.mark.parametrize("kind,S", [("prefill", 16), ("decode", 1)])
def test_the_stacks_alias_their_inputs(served, kind, S):
    """A cache deeper than the weights: ``T L`` slots of keys and as
    many of values; every array of the cache is written into its donated
    argument through both loops, and the decode program moves no
    slot-sized piece of a stack."""
    _, net, _, eng = served
    B = 4
    text = eng._compile(B, S).as_text()
    n_w = len(eng._weights)
    cache = eng.init_cache(B)
    assert [c.shape for c in cache] == [(T * L, B, 4, 16, 64)] * 2 + \
        [(2, 2 + T)]
    assert net.qkv_weight.shape[0] == L
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(len(cache)):
        assert f"{{{i}}}: ({n_w + i}, {{}}" in alias, (i, alias)
    if kind == "decode":
        assert serving.whole_layer_ops(
            text, cache[0].nbytes // cache[0].shape[0]) == []


def test_one_layer_body_and_the_weights_held_once(served):
    """The step's jaxpr holds one loop over the passes with one scan
    over the layers in it, whose body has the one attention call: the
    layer body is traced once, and the weights enter the program as
    ``L`` stacked layers, not ``T L``."""
    import jax

    from mxnet_tpu.test_utils import jaxpr_loops

    _, net, _, eng = served
    program_ = eng._program
    B = 4
    args = (eng._weights, eng.init_cache(B), np.zeros(B, np.int32),
            np.zeros(B, np.int32), np.zeros((B, 1), np.int32))
    jaxpr = jax.make_jaxpr(program_.step)(*args)
    loops = list(jaxpr_loops(jaxpr.jaxpr))
    assert [eq.primitive.name for eq in loops] == ["scan", "scan"]
    assert [eq.params["length"] for eq in loops] == [T, L]
    assert dict(program_.cache_reads[1]) == {("xla", 64, 64): 1}
    assert dict(program_.cache_writes[1]) == {"rows": 2 * B}
    for name, a in zip(net._names, eng._weights):
        assert a is getattr(net, name).data()._data, name


def test_a_coalesced_group_is_bitwise_the_requests_served_alone(served):
    _, _, _, eng = served
    prompts = _prompts(seed=6, lens=(17, 21, 32, 30))
    toks, logits = _walk(eng, prompts, 10)
    for i, p in enumerate(prompts):
        t1, l1 = _walk(eng, [p], 10)
        np.testing.assert_array_equal(t1[0], toks[i])
        np.testing.assert_array_equal(l1[0], logits[i])


@pytest.mark.parametrize("wants", UNEQUAL_ANSWERS, ids=str)
def test_a_row_that_wants_no_token_changes_nothing(served, wants):
    """The decode step is handed which rows still want a token: the
    others attend to nothing and write into none of the ``T L`` slots,
    every request's tokens are what it gets
    alone and in a group of equal answers, and the counters are the
    live row-steps' (the positions read in all ``T L`` slots, the rows
    that left at each step) while the passes stay ``T`` a step."""
    _, _, _, eng = served
    eng.warmup()
    prompts = _prompts(seed=10, lens=(2, 8, 23, 5)[:len(wants)])
    pinned = (serving.trace_count(), serving.compile_count())
    timings, live = serving_unequal_answers(eng, prompts, wants)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    assert timings["attn_positions_decode"] == \
        T * L * sum(len(prompts[i]) + j + 1 for i, j in live)
    assert timings["loop_exit_step_decode"] == [0] * (T - 1) + [len(live)]
    assert timings["loop_passes_decode"] == T * (max(wants) - 1)
    # and in every layer of every stack a finished row's cache rows are
    # what they were
    serving_dead_rows_keep_their_cache(eng, prompts, [k > 1 for k in wants])


def test_a_mesh_is_refused_and_reload_goes_through_weights(served):
    cfg, net, _, eng = served
    with pytest.raises(MXNetError, match="one chip"):
        serving.ServingEngine(net, batch_buckets=(4,), mesh=object())
    prompts = [[1, 2, 3, 4, 5], list(range(7, 30))]
    before, _ = eng.serve_group(prompts, 4)
    other, _ = _net(cfg, seed=9)
    eng.reload_from_model(other)
    pinned = serving.trace_count()
    after, _ = eng.serve_group(prompts, 4)
    assert serving.trace_count() == pinned and eng.generation == 1
    assert any((a != b).any() for a, b in zip(after, before))
    eng.reload_from_model(net)
    # another number of passes is another program: the signature says so
    with pytest.raises(MXNetError, match="incompatible model"):
        eng.reload_from_model(_net(_config(total_ut_steps=2))[0])

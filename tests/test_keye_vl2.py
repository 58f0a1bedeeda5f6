"""Keye-VL-2.0's language model (gluon/model_zoo/keye_vl2.py) against its
plain reference (benchmark/references/keye_vl2.py), tiny, float32, on the
CPU: the uncached forward, the cached step through `ServingEngine` with
its three kinds of stack, the selected sets themselves, controls that
show the indexer matters, the dropless share of the experts, the
threshold search against a sort, and the engine's pins for the family."""

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
from mxnet_tpu.gluon.model_zoo import keye_vl2              # noqa: E402
from mxnet_tpu.ops import indexed_attention, moe            # noqa: E402
from mxnet_tpu.test_utils import (                          # noqa: E402
    UNEQUAL_ANSWERS, serving_dead_rows_keep_their_cache,
    serving_host_walk as _walk, serving_unequal_answers)

from benchmark import program, weights                      # noqa: E402
from benchmark.references import keye_vl2 as ref            # noqa: E402

TOPK = 8
# float32 on both sides, products in another order: the forward agrees
# to 1e-5 of logits that reach 6; the cached step adds the cache's own
# order of sums
ATOL, RTOL = 2e-4, 1e-4


def _config(**over):
    """The tiny member's sizes under the source's keys (hidden 64, 4
    query and 2 key/value heads of 16, an indexer of 2 heads of 8,
    top-8, 3 layers, 8 experts top-2 of width 32)."""
    cfg = {"hidden_size": 64, "num_hidden_layers": 3,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "rope_theta": 1e7,
           "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                         "indexer_num_kv_heads": 1, "topk": TOPK,
                         "q_chunk_size": 512, "kv_chunk_size": 512},
           "mlp_only_layers": [], "decoder_sparse_step": 1,
           "moe_intermediate_size": 32, "num_experts": 8,
           "router_experts": 8, "num_experts_per_tok": 2,
           "vocab_size": 96, "rms_norm_eps": 1e-6,
           # wide enough that every term of a layer shows in the logits
           "initializer_range": 0.2}
    cfg.update(over)
    return cfg


def _net(cfg, seed=5, **kw):
    """(net, reference parameters): the tiny model with the reference's
    seeded leaves."""
    net = keye_vl2.keye_vl2_tiny(
        experts_held=cfg.get("experts_held"), **kw)
    net.initialize(init=mx.init.Zero())
    spec = ref.param_spec(cfg)
    values = dict(weights.make(seed, spec, "float32"))
    leaves = program.match_leaves(spec, list(net.collect_params().keys()))
    for leaf, _, _ in spec:
        net.collect_params()[leaves[leaf]].set_data(values[leaf])
    return net, values


def _ref_logits(values, ids, cfg):
    import jax.numpy as jnp

    return np.asarray(ref.logits(values, jnp.asarray(ids), cfg))


# -- (a) the uncached forward --------------------------------------------------

@pytest.mark.parametrize("T,chunk", [(48, 4096), (48, 48), (5, 4096)])
def test_forward_equals_the_reference(T, chunk):
    """`hybrid_forward` over whole sequences: six times ``topk`` long
    (rows whole, and worked off one at a time) and shorter than it."""
    cfg = _config()
    net, values = _net(cfg, prefill_chunk_tokens=chunk)
    ids = np.random.RandomState(0).randint(0, 96, (3, T))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    want = _ref_logits(values, ids, cfg)
    assert got.shape == want.shape == (3, T, 96)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)


# -- (b) prefill, then decode through the three stacks -------------------------

@pytest.mark.parametrize("chunk", [4096, 64])
def test_serving_equals_the_reference_at_every_served_position(chunk):
    """A group mixing prompts under ``topk``, at it and several times
    over it, decoded 12 steps: the cached step's logits are the
    reference's full forward at each served position, and `serve_group`
    serves the same tokens and counts the keys it read."""
    cfg = _config()
    net, values = _net(cfg, prefill_chunk_tokens=chunk)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    rng = np.random.RandomState(1)
    lens = (3, TOPK, 21, 40)
    prompts = [rng.randint(0, 96, n).tolist() for n in lens]
    steps = 12
    toks, logits = _walk(eng, prompts, steps)
    for i, p in enumerate(prompts):
        full = np.asarray(list(p) + list(toks[i, :-1]))[None]
        want = _ref_logits(values, full, cfg)[0, len(p) - 1:]
        np.testing.assert_allclose(logits[i], want, atol=ATOL, rtol=RTOL,
                                   err_msg=f"prompt of {len(p)}")
    outs, timings = eng.serve_group(prompts, steps)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o, toks[i])
    # live keys: every position up to a query's own, a layer; selected:
    # ``topk`` of them at most, more only on exact ties
    live = 3 * sum(n * (n + 1) // 2 for n in lens)
    assert timings["attn_keys_live_prefill"] == live
    least = 3 * sum(min(t + 1, TOPK) for n in lens for t in range(n))
    assert least <= timings["attn_keys_selected_prefill"] < live
    live = 3 * sum(n + j + 1 for n in lens for j in range(steps - 1))
    assert timings["attn_keys_live_decode"] == live
    least = 3 * sum(min(n + j + 1, TOPK) for n in lens
                    for j in range(steps - 1))
    assert least <= timings["attn_keys_selected_decode"] < live
    # every pair a real token makes is held here (all 8 experts are)
    assert timings["moe_pairs_prefill"] == sum(lens) * 2 * 3
    assert timings["moe_pairs_decode"] == 4 * 2 * 3 * (steps - 1)
    assert 1 <= timings["moe_experts_hit_per_step"] <= 8


# -- (c) the selected sets themselves ------------------------------------------

def _program_selection(net, values, x, i, last):
    """Layer ``i``'s selection as the program's prefill kernel makes it
    on the layer input ``x`` (B, S, C): bool (B, S, S); and as its
    decode path makes it for the last query against the same keys."""
    import jax.numpy as jnp

    z = net._sizes
    p = {n: values[n][i] for n in keye_vl2._LAYER_LEAVES}
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    u, _, _, _ = keye_vl2._qkv(z, p, x, pos)
    qi, ki, w = keye_vl2._index(z, p, u, pos)
    kit = ki.swapaxes(1, 2)
    mask = indexed_attention.select_prefill(qi, w, kit, last, z.topk)
    at = jnp.asarray(last)
    rows = jnp.arange(B)
    index = indexed_attention.index_scores_decode(
        qi[rows, :, at], w[rows, at], kit)
    live = jnp.arange(S)[None, :] <= at[:, None]
    return (np.asarray(mask) != 0,
            np.asarray(indexed_attention.select_topk(index, live, z.topk)))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_the_selected_sets_are_the_references(layer):
    """Every query's set S[t], from the prefill kernel and, for each
    row's last query, from the decode path: equal to the reference's
    sort-based selection, position for position (float32 on both sides:
    no score is within rounding of its threshold at this seed)."""
    import jax.numpy as jnp

    cfg = _config()
    net, values = _net(cfg)
    T = 48
    ids = np.random.RandomState(2).randint(0, 96, (3, T))
    x, want = ref.selections(values, jnp.asarray(ids), cfg)[layer]
    want = np.asarray(want)
    last = np.asarray([T - 1, 30, 5], np.int32)
    got, got_decode = _program_selection(net, values, x, layer, last)
    for b, n in enumerate(last):
        np.testing.assert_array_equal(got[b, :n + 1], want[b, :n + 1])
        np.testing.assert_array_equal(got_decode[b], want[b, n])
    # a query reads at least topk keys once it sees so many (more only
    # where scores tie at the threshold: with two indexer heads a score
    # is exactly 0 wherever both ReLUs are shut), one below it all it
    # can see
    counts = got[0].sum(axis=-1)
    assert (counts >= np.minimum(np.arange(T) + 1, TOPK)).all()
    np.testing.assert_array_equal(counts[:TOPK], np.arange(TOPK) + 1)


# -- (d) the indexer matters ---------------------------------------------------

def _last_topk(qi, w, ki, t0, z, prod):
    import jax.numpy as jnp

    t = t0 + jnp.arange(qi.shape[1])[:, None]
    s = jnp.arange(ki.shape[1])[None, :]
    return jnp.broadcast_to(((s <= t) & (s > t - z["topk"]))[None],
                            (qi.shape[0],) + t.shape[:1] + s.shape[1:])


_SELECT = ref.select


def _weights_ignored(qi, w, ki, t0, z, prod):
    import jax.numpy as jnp

    return _SELECT(qi, jnp.ones_like(w), ki, t0, z, prod)


@pytest.mark.parametrize("control", [_last_topk, _weights_ignored])
def test_a_reference_without_the_indexers_choice_is_far(control,
                                                        monkeypatch):
    """Controls for (a)'s tolerance: a reference that attends to the
    last ``topk`` positions, or that ranks with the indexer's per-head
    weights ignored, is far outside the tolerance the program meets."""
    cfg = _config()
    net, values = _net(cfg)
    ids = np.random.RandomState(0).randint(0, 96, (3, 48))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    ref._jitted.cache_clear()
    monkeypatch.setattr(ref, "select", control)
    try:
        other = _ref_logits(values, ids, cfg)
    finally:
        monkeypatch.undo()
        ref._jitted.cache_clear()
    assert np.abs(got - other).max() > 100 * ATOL
    np.testing.assert_allclose(got, _ref_logits(values, ids, cfg),
                               atol=ATOL, rtol=RTOL)


# -- (e) the share ties to the model -------------------------------------------

@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The 8 experts of a layer held as ``shares`` shares (4: the
    ``experts_held`` (0, 2), (2, 2), (4, 2), (6, 2)): what the program's
    ops give for each share, added up, is the uncut reference's whole
    layer."""
    import jax.numpy as jnp

    cfg = _config()
    z = ref.sizes(cfg)
    values = weights.make(7, ref.param_spec(cfg), "float32")
    u = jnp.asarray(np.random.RandomState(2).normal(size=(3, 11, 64)),
                    jnp.float32)
    parts = ref._jitted(ref._key(z), ref.product)
    want = np.asarray(ref.moe_layer(u, values, 1, z, parts, held=(0, 8)))
    per = 8 // shares
    chosen, wts = moe.softmax_topk_route(
        u.reshape(33, 64), values["router_weight"][1], 2)
    total, pairs = 0.0, 0
    for s in range(shares):
        at = slice(s * per, (s + 1) * per)
        y, stats = moe.held_experts_ffn(
            u.reshape(33, 64), chosen, wts,
            values["experts_gate_up_weight"][1, at],
            values["experts_down_weight"][1, at], experts_lo=s * per)
        total = total + np.asarray(y).reshape(3, 11, 64)
        pairs += int(np.asarray(stats)[:per].sum())
    assert pairs == 3 * 11 * 2          # each assignment in one share
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)


def test_a_share_serves_what_the_reference_gives_the_same_share():
    cfg = _config(experts_held=[2, 4], num_experts=4)
    net, values = _net(cfg)
    ids = np.random.RandomState(3).randint(0, 96, (2, 20))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    np.testing.assert_allclose(got, _ref_logits(values, ids, cfg),
                               atol=ATOL, rtol=RTOL)


# -- (f) the threshold search against a sort -----------------------------------

def _by_sort(scores, live, k):
    s = np.where(live, scores, -np.inf)
    kth = np.sort(s, axis=-1)[..., max(s.shape[-1] - k, 0)]
    return (s >= kth[..., None]) & live


def _adversarial(name):
    rng = np.random.RandomState(4)
    W, k = 40, 8
    scores = rng.normal(size=(6, W)).astype(np.float32)
    live = np.ones((6, W), bool)
    if name == "ties_at_the_threshold":
        scores = np.round(scores * 2) / 2      # a dozen values, many ties
    elif name == "all_equal":
        scores[:] = 0.25
    elif name == "zeros_of_both_signs":
        scores = np.where(rng.rand(6, W) < 0.5, 0.0, -0.0
                          ).astype(np.float32)
        scores[:, :3] = [1.0, -1.0, 2.0]
    elif name == "minus_inf_padding":
        scores[:, 10:] = -np.inf
    elif name == "fewer_than_k_live":
        live[:, 5:] = False
    elif name == "exactly_k_live":
        live[:, k:] = False
    elif name == "negative_scores":
        scores = -np.abs(scores) - 1.0
    elif name == "huge_and_tiny":
        scores[:, ::2] *= 1e30
        scores[:, 1::2] *= 1e-30
    return scores, live, k


@pytest.mark.parametrize("name", [
    "random", "ties_at_the_threshold", "all_equal", "zeros_of_both_signs",
    "minus_inf_padding", "fewer_than_k_live", "exactly_k_live",
    "negative_scores", "huge_and_tiny"])
def test_the_threshold_search_equals_a_sort(name):
    import jax.numpy as jnp

    scores, live, k = _adversarial(name)
    got = np.asarray(indexed_attention.select_topk(
        jnp.asarray(scores), jnp.asarray(live), k))
    np.testing.assert_array_equal(got, _by_sort(scores, live, k))


@pytest.mark.parametrize("k", [1, 3, 64])
def test_the_search_finds_the_kth_largest_key(k):
    import jax.numpy as jnp

    x = np.random.RandomState(5).normal(size=(7, 33)).astype(np.float32)
    keys = indexed_attention.sortable_key(jnp.asarray(x))
    thr = indexed_attention.kth_key(
        lambda c: jnp.sum(keys >= c, axis=-1, keepdims=True,
                          dtype=jnp.int32), k, (7, 1))
    order = np.sort(np.asarray(keys), axis=-1)
    want = order[:, 33 - k] if k <= 33 else np.full(7, -2 ** 31)
    np.testing.assert_array_equal(np.asarray(thr)[:, 0], want)
    # the keys sort as the floats do
    np.testing.assert_array_equal(np.argsort(np.asarray(keys), axis=-1,
                                             kind="stable"),
                                  np.argsort(x, axis=-1, kind="stable"))


@pytest.mark.parametrize("case", ["all_equal", "padding_rows"])
def test_the_prefill_kernel_on_degenerate_scores(case):
    """Zero queries score every key 0: every live key ties at the
    threshold and all are kept.  A row whose last token lies in the
    first block leaves later query blocks empty."""
    import jax.numpy as jnp

    B, Hi, S, di = 2, 2, 256, 8
    rng = np.random.RandomState(6)
    qi = np.zeros((B, Hi, S, di), np.float32) if case == "all_equal" \
        else rng.normal(size=(B, Hi, S, di)).astype(np.float32)
    ki = rng.normal(size=(B, di, S)).astype(np.float32)
    w = rng.normal(size=(B, S, Hi)).astype(np.float32)
    last = np.asarray([S - 1, 40], np.int32)
    mask = np.asarray(indexed_attention.select_prefill(
        jnp.asarray(qi), jnp.asarray(w), jnp.asarray(ki),
        jnp.asarray(last), TOPK)) != 0
    t, s = np.arange(S)[:, None], np.arange(S)[None, :]
    if case == "all_equal":
        np.testing.assert_array_equal(mask[0], s <= t)
    else:
        assert not mask[1, 128:].any()          # a block past the row
        score = np.einsum("hqd,ds->qhs", qi[1], ki[1])
        index = (w[1][:, :, None] * np.maximum(score, 0)).sum(1)
        want = _by_sort(index, s <= t, TOPK)
        np.testing.assert_array_equal(mask[1, :41], want[:41])


# -- (g) the engine's pins for the third family --------------------------------

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


def test_the_prefill_kernel_share_reaches_the_batchers_record(served):
    """The prefill program's attention under the selection is the one
    flash forward call a scanned layer makes
    (`pallas_attention.flash_attention_forward(keep=)`, interpreted
    here): ``prefill_attn_kernel_share`` is 1.0 in a served group's
    timings and in each request's record, as Kimi-K2's, Ouro's and
    Command A+'s is, and the decode program's tally holds no such call
    (it attends through `cache_attention.attend_rows`)."""
    from mxnet_tpu import telemetry

    _, _, _, eng = served
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 96, n) for n in (11, 5)]
    _, timings = eng.serve_group(prompts, 2)
    assert timings["bucket"] == [4, 16]
    assert timings["prefill_attn_kernel_share"] == 1.0
    assert dict(eng._program.block_attends[16]) == {"kernel": 1}
    assert not eng._program.block_attends[1]
    telemetry.reset()
    batcher = serving.ContinuousBatcher(eng, max_delay_ms=150, max_batch=4)
    try:
        futs = [batcher.submit(p, 2) for p in prompts]
        for f in futs:
            f.result(timeout=120)
    finally:
        batcher.close()
    requests = telemetry.recent_requests()
    assert len(requests) == 2
    for r in requests:
        telemetry.validate_record(r)
        assert r["prefill_attn_kernel_share"] == 1.0


@pytest.mark.parametrize("kind,S", [("prefill", 16), ("decode", 1)])
def test_the_three_stacks_alias_their_inputs(served, kind, S):
    """Every array of the cache is written into its donated argument,
    and the decode program moves no layer-sized piece of any stack:
    keys, values or the indexer's keys."""
    _, _, _, eng = served
    B = 4
    text = eng._compile(B, S).as_text()
    n_w = len(eng._weights)
    cache = eng.init_cache(B)
    assert len(cache) == 5
    assert [c.shape for c in cache[:3]] == [
        (3, B, 2, 16, 64), (3, B, 2, 16, 64), (3, B, 1, 8, 64)]
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(len(cache)):
        assert f"{{{i}}}: ({n_w + i}, {{}}" in alias, (i, alias)
    if kind == "decode":
        for c in cache[:3]:
            assert serving.whole_layer_ops(
                text, c.nbytes // c.shape[0]) == []


def test_the_weights_are_the_parameters_own_buffers(served):
    _, net, _, eng = served
    for name, a in zip(net._names, eng._weights):
        assert a is getattr(net, name).data()._data, name


def test_a_coalesced_group_is_bitwise_the_requests_served_alone(served):
    """Rows do not see each other.  The prompts share a prefill bucket
    (32): the prefill kernels' blocks follow the bucket below 512
    positions, and another block is another order of sums."""
    _, _, _, eng = served
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 96, n).tolist() for n in (17, 21, 32, 30)]
    toks, logits = _walk(eng, prompts, 10)
    for i, p in enumerate(prompts):
        t1, l1 = _walk(eng, [p], 10)
        np.testing.assert_array_equal(t1[0], toks[i])
        np.testing.assert_array_equal(l1[0], logits[i])


@pytest.mark.parametrize("steps", [1, 2, 9])
def test_a_greedy_group_is_fed_on_the_device(served, steps):
    """The third family through the same wrapper: a greedy group's
    tokens are those of the path with the host in every step, it
    dispatches its prefill and ``steps - 1`` decode programs, every
    decode step takes the step before's ids, and the host reads 4 bytes
    a row."""
    _, _, _, eng = served
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, 96, n).tolist() for n in (2, TOPK, 23)]
    want, _ = _walk(eng, prompts, steps)
    pinned = (serving.trace_count(), serving.compile_count())
    d0 = serving.dispatch_count()
    outs, timings = eng.serve_group(prompts, steps)
    assert serving.dispatch_count() - d0 == 1 + (steps - 1)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    np.testing.assert_array_equal(np.stack(outs), want)
    assert timings["decode_steps_fed_on_device"] == steps - 1
    assert timings["decode_readback_bytes_per_step"] == 4 * 4
    # the three rows that want a token, not the pad row
    assert timings["moe_pairs_decode"] == 3 * 2 * 3 * (steps - 1)


@pytest.mark.parametrize("wants", UNEQUAL_ANSWERS, ids=str)
def test_a_row_that_wants_no_token_changes_nothing(served, wants):
    """The decode step is handed which rows still want a token: the
    others attend to nothing, go to no expert and write nothing into
    the three stacks, every request's tokens are what it gets alone and
    in a group of equal answers, and the counters are the live
    row-steps': the keys attention could read
    and read, 3 layers, and 2 pairs a token a layer (all 8 experts are
    held)."""
    _, _, _, eng = served
    eng.warmup()
    rng = np.random.RandomState(10)
    prompts = [rng.randint(0, 96, n).tolist()
               for n in (2, TOPK, 23, 5)[:len(wants)]]
    pinned = (serving.trace_count(), serving.compile_count())
    timings, live = serving_unequal_answers(eng, prompts, wants)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    held = [len(prompts[i]) + j + 1 for i, j in live]
    assert timings["attn_keys_live_decode"] == 3 * sum(held)
    assert 3 * sum(min(n, TOPK) for n in held) \
        <= timings["attn_keys_selected_decode"] \
        <= timings["attn_keys_live_decode"]
    assert timings["moe_pairs_decode"] == len(live) * 2 * 3
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
    with pytest.raises(MXNetError, match="checkpoint-state convention"):
        eng.reload_from_state(serving.state_for_serving(other))
    eng.reload_from_model(net)
    with pytest.raises(MXNetError, match="incompatible model"):
        eng.reload_from_model(_net(_config(experts_held=[0, 4],
                                           num_experts=4))[0])


def test_the_rotary_base_and_the_qk_norm_change_the_output():
    def served_logits(net):
        eng = serving.ServingEngine(net, batch_buckets=(4,))
        return _walk(eng, [[5, 9, 2], list(range(1, 20))], 4)[1]

    cfg = _config()
    net, values = _net(cfg)
    base = served_logits(net)
    assert np.abs(served_logits(_net(cfg, rope_theta=50.0)[0])
                  - base).max() > 1e-3
    for leaf in ("q_norm_gamma", "index_k_norm_beta"):
        other, _ = _net(cfg)
        p = getattr(other, leaf)
        p.set_data(p.data() + 0.5)
        assert np.abs(served_logits(other) - base).max() > 1e-3, leaf

"""Kimi-K2's language model (gluon/model_zoo/kimi_k2.py) against its plain
reference (benchmark/references/kimi_k2.py), tiny, on the CPU: the
uncached forward, the cached step through `ServingEngine` with its one
latent stack, the absorbed decode path against the expanded one, the
shares of an expert layer with the shared expert counted once, YaRN's
table at the published numbers, a float8 control for the bfloat16
tolerance, the scaled router, and the engine's pins for the family."""

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
from mxnet_tpu.gluon.model_zoo import _decoder_ops as ops   # noqa: E402
from mxnet_tpu.gluon.model_zoo import kimi_k2               # noqa: E402
from mxnet_tpu.ops import cache_attention, moe              # noqa: E402
from mxnet_tpu.test_utils import (                          # noqa: E402
    UNEQUAL_ANSWERS, serving_dead_rows_keep_their_cache,
    serving_host_walk as _walk, serving_unequal_answers)

from benchmark import program, weights                      # noqa: E402
from benchmark.references import kimi_k2 as ref             # noqa: E402

# float32 on both sides, products in another order: logits that reach 3
# agree to 1e-5; the cached step adds the absorbed path's other order of
# sums (q W_uk first, then the latents, where the reference expands the
# latents first)
ATOL, RTOL = 2e-4, 1e-4
# bfloat16 against the float32 reference, at the tiny member's sizes with
# matrices normal(0.05) (`_bf16_config`): weights and the cached latents
# are rounded to 8 bits of mantissa and the absorbed path rounds q W_uk
# where the reference rounds nothing, so logits of size 1.5 move by up to
# 0.008 over the served positions below; four times that.  The float8
# control moves them by 0.46 and more (test (f)).  At normal(0.2) the
# same rounding flips a router's choice at one served position in 48 and
# the expert it swaps in moves a logit by 1.7: wider than any control
BF16_ATOL = 0.03

_PUBLISHED_ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                   "mscale": 1, "mscale_all_dim": 1,
                   "original_max_position_embeddings": 4096, "type": "yarn"}


def _config(**over):
    """The tiny member's sizes under the source's keys (hidden 64, 4
    heads of 16 + 8 rotated dimensions and values of 12, latents of 24
    and 16, a dense layer of 96 and 2 expert layers of 8 experts top-2
    of width 32 with a shared expert, YaRN by 8 from 8 positions)."""
    cfg = {"hidden_size": 64, "num_hidden_layers": 3,
           "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "n_shared_experts": 1, "n_routed_experts": 8,
           "router_experts": 8, "num_experts_per_tok": 2,
           "routed_scaling_factor": 2.5, "first_k_dense_replace": 1,
           "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
           "scoring_func": "sigmoid", "norm_topk_prob": True,
           "rope_theta": 50000.0,
           "rope_scaling": dict(_PUBLISHED_ROPE, factor=8,
                                original_max_position_embeddings=8),
           "vocab_size": 96, "rms_norm_eps": 1e-5,
           # wide enough that every term of a layer shows in the logits
           "initializer_range": 0.2,
           # a bias that moves the choice (the published cut seeds zeros)
           "seeded": {"router_bias": "normal:0.3"}}
    cfg.update(over)
    return cfg


def _net(cfg, seed=5, dtype="float32", **kw):
    """(net, reference parameters): the tiny model with the reference's
    seeded leaves."""
    net = kimi_k2.kimi_k2_tiny(experts_held=cfg.get("experts_held"),
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


@pytest.fixture
def small_pieces(monkeypatch):
    """The reference's cuts set below the tiny member's sizes: every
    loop over chunks, head groups, query blocks and tiles runs more than
    once."""
    for name, n in (("TOKEN_CHUNK", 16), ("QUERY_BLOCK", 8),
                    ("HEAD_GROUP", 3), ("FFN_TILE", 40)):
        monkeypatch.setattr(ref, name, n)


# -- (a) the uncached forward --------------------------------------------------

@pytest.mark.parametrize("T,tokens", [(48, 16), (48, 4096), (5, 16)])
def test_forward_equals_the_reference(T, tokens):
    """`hybrid_forward` over whole sequences six times the original
    length YaRN stretches from (token-wise products cut along S, and
    whole) and shorter than it."""
    cfg = _config()
    net, values = _net(cfg, token_chunk=tokens)
    ids = np.random.RandomState(0).randint(0, 96, (3, T))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    want = _ref_logits(values, ids, cfg)
    assert got.shape == want.shape == (3, T, 96)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)


def test_the_references_cuts_change_nothing(small_pieces):
    cfg = _config()
    values = dict(weights.make(5, ref.param_spec(cfg), "float32"))
    ids = np.random.RandomState(0).randint(0, 96, (2, 40))
    pieces = _ref_logits(values, ids, cfg)
    for name in ("TOKEN_CHUNK", "QUERY_BLOCK", "HEAD_GROUP", "FFN_TILE"):
        setattr(ref, name, 4096)
    np.testing.assert_allclose(pieces, _ref_logits(values, ids, cfg),
                               atol=2e-5, rtol=1e-5)


# -- (b) prefill, then decode through the latent stack -------------------------

@pytest.mark.parametrize("rows", [16384, 64])
def test_serving_equals_the_reference_at_every_served_position(rows):
    """A group of unequal rows (under, at and several times over the
    original length), decoded 12 steps: the cached step's logits are the
    reference's full forward at each served position, prefilled with
    every row at once and a row at a time, and `serve_group` serves the
    same tokens and counts the positions it attended to."""
    cfg = _config()
    net, values = _net(cfg, prefill_chunk_tokens=rows)
    eng = serving.ServingEngine(net, batch_buckets=(4,))
    rng = np.random.RandomState(1)
    lens = (3, 8, 21, 40)
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
    assert timings["attn_latent_positions_prefill"] == \
        3 * sum(n * (n + 1) // 2 for n in lens)
    assert timings["attn_latent_positions_decode"] == \
        3 * sum(n + j + 1 for n in lens for j in range(steps - 1))
    # every pair a real token makes is held here (all 8 experts are), in
    # both expert layers
    assert timings["moe_pairs_prefill"] == sum(lens) * 2 * 2
    assert timings["moe_pairs_decode"] == 4 * 2 * 2 * (steps - 1)
    assert 1 <= timings["moe_experts_hit_per_step"] <= 8
    # on the CPU both ops take their XLA paths
    assert timings["decode_cache_write_kernel_share"] == 0.0
    assert timings["decode_attn_kernel_share"] == 0.0


def _bf16_config():
    return _config(initializer_range=0.05, seeded={})


def _served_bf16(cfg):
    net, values = _net(cfg, dtype="bfloat16")
    import jax.numpy as jnp

    eng = serving.ServingEngine(net, batch_buckets=(4,),
                                dtype=jnp.bfloat16)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 96, n).tolist() for n in (3, 8, 21, 40)]
    toks, logits = _walk(eng, prompts, 12)
    fulls = [np.asarray(list(p) + list(toks[i, :-1]))[None]
             for i, p in enumerate(prompts)]
    return values, prompts, fulls, logits


def test_serving_in_bfloat16_stays_within_its_tolerance():
    cfg = _bf16_config()
    values, prompts, fulls, logits = _served_bf16(cfg)
    worst = 0.0
    for i, p in enumerate(prompts):
        want = _ref_logits(values, fulls[i], cfg)[0, len(p) - 1:]
        worst = max(worst, float(np.abs(logits[i] - want).max()))
    assert worst < BF16_ATOL, worst


# -- (f) the control for (b)'s bfloat16 tolerance ------------------------------

def test_the_float8_reference_fails_the_bfloat16_tolerance():
    """The reference with both operands of every product through float8
    is further from the float32 reference, at the same prompts and
    served tokens, than the tolerance the bfloat16 program meets."""
    cfg = _bf16_config()
    values, prompts, fulls, _ = _served_bf16(cfg)
    worst = 0.0
    for i, p in enumerate(prompts):
        want = _ref_logits(values, fulls[i], cfg)[0, len(p) - 1:]
        low = _ref_logits(values, fulls[i], cfg,
                          ref.low_precision)[0, len(p) - 1:]
        worst = max(worst, float(np.abs(low - want).max()))
    assert worst > 10 * BF16_ATOL, worst


# -- (c) the absorbed path equals the expanded one -----------------------------

@pytest.mark.parametrize("layer,short", [(0, False), (0, True), (1, True)],
                         ids=["l0-whole", "l0-to-lengths", "l1-to-lengths"])
def test_the_absorbed_path_equals_the_expanded_one(layer, short):
    """One layer's attention for each row's last query: W_uk folded into
    the query and W_uv into the output over the cached latents, against
    keys and values expanded by head through the flash forward kernel
    (in blocks of 8; ``short``: each row to its own length, short of S
    for two of the three, and zero past it); and both against the
    reference's layer."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention

    cfg = _config()
    net, values = _net(cfg)
    z = net._sizes
    p = {n: ref.leaf(values, n, layer) for n in kimi_k2._ATTN_LEAVES}
    B, S = 3, 32
    x = jnp.asarray(np.random.RandomState(3).normal(size=(B, S, 64)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    last = np.asarray([S - 1, 20, 4])
    cq, latent = kimi_k2._down(z, p, x, pos)
    q, k, v = kimi_k2._expanded(z, p, cq, latent, pos)
    a = pallas_attention.flash_attention_forward(
        q, k, v, jnp.asarray(last + 1) if short else None, scale=1.0,
        block_q=8, block_k=8)
    assert a.shape == (B, z.num_heads, S, z.v_dim)
    if short:
        for b, n in enumerate(last):
            assert not np.asarray(a[b, :, n + 1:]).any()
    expanded = np.asarray(x + ops.mm(
        "bhsd,chd->bsc", a, p["o_weight"].reshape(-1, z.num_heads,
                                                  z.v_dim)))
    rows = jnp.arange(B)
    at = jnp.asarray(last)
    qa = kimi_k2._absorbed_query(z, p, cq[rows, at][:, None], at[:, None])
    stack = latent.swapaxes(1, 2)[None, :, None]        # (1, B, 1, ., S)
    out = cache_attention.attend_rows(qa, stack, None, 0, at + 1,
                                      leading=z.kv_rank)
    absorbed = np.asarray(kimi_k2._absorbed_out(
        z, p, x[rows, at][:, None], out))[:, 0]
    parts = ref._jitted(ref._key(ref.sizes(cfg)), ref.product)
    want = np.asarray(ref.attention_layer([x], values, layer,
                                          ref.sizes(cfg), parts)[0])
    for b, n in enumerate(last):
        np.testing.assert_allclose(absorbed[b], expanded[b, n], atol=2e-5,
                                   rtol=1e-4)
        np.testing.assert_allclose(absorbed[b], want[b, n], atol=2e-5,
                                   rtol=1e-4)


def test_the_prefill_kernel_share_reaches_the_batchers_record():
    """Every attention call of the prefill program inside its block
    (layer 0's and the scanned layers' one) goes through the flash
    forward kernel, interpreted here: ``prefill_attn_kernel_share`` is
    1.0 in a served group's timings and in each request's record, and
    the decode program's tally holds no such call."""
    from mxnet_tpu import telemetry

    net, _ = _net(_config())
    eng = serving.ServingEngine(net, batch_buckets=(2,))
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 96, n) for n in (11, 5)]
    _, timings = eng.serve_group(prompts, 2)
    assert timings["bucket"] == [2, 16]
    assert timings["prefill_attn_kernel_share"] == 1.0
    assert dict(eng._program.block_attends[16]) == {"kernel": 2}
    assert not eng._program.block_attends[1]
    telemetry.reset()
    batcher = serving.ContinuousBatcher(eng, max_delay_ms=150, max_batch=2)
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


# -- (d) the share ties to the model -------------------------------------------

@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The 8 routed experts of a layer held as ``shares`` shares: what
    the program's op gives for each share's routed experts, added up,
    and the shared expert **counted once**, is the uncut reference's
    whole layer; counting the shared expert in every share is not."""
    import jax.numpy as jnp

    cfg = _config()
    z = ref.sizes(cfg)
    values = weights.make(7, ref.param_spec(cfg), "float32")
    u = jnp.asarray(np.random.RandomState(2).normal(size=(3, 11, 64)),
                    jnp.float32)
    parts = ref._jitted(ref._key(z), ref.product)
    want = np.asarray(ref.moe_layer(u, values, 1, z, parts, held=(0, 8)))
    per = 8 // shares
    shared = tuple(values[f"shared_{m}_weight"][0]
                   for m in ("gate", "up", "down"))

    def share(s, with_shared):
        at = slice(s * per, (s + 1) * per)
        return moe.moe_share_ffn(
            u, values["router_weight"][0], values["router_bias"][0],
            values["experts_gate_up_weight"][0, at],
            values["experts_down_weight"][0, at], k=2, experts_lo=s * per,
            scale=2.5, output_stats=True,
            shared=shared if with_shared else None)

    total, pairs = 0.0, 0
    for s in range(shares):
        y, stats = share(s, with_shared=s == 0)
        total = total + np.asarray(y)
        pairs += int(np.asarray(stats)[:per].sum())
    assert pairs == 3 * 11 * 2          # each assignment in one share
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)
    if shares > 1:
        every = sum(np.asarray(share(s, True)[0]) for s in range(shares))
        assert np.abs(every - want).max() > 1e-2


def test_a_share_serves_what_the_reference_gives_the_same_share():
    cfg = _config(experts_held=[2, 4], n_routed_experts=4)
    net, values = _net(cfg)
    ids = np.random.RandomState(3).randint(0, 96, (2, 20))
    got = net(mx.nd.array(ids.astype(np.float32))).asnumpy()
    np.testing.assert_allclose(got, _ref_logits(values, ids, cfg),
                               atol=ATOL, rtol=RTOL)


# -- (e) YaRN at the published numbers -----------------------------------------

def test_yarns_table_for_the_published_numbers():
    cfg = _config(qk_nope_head_dim=128, qk_rope_head_dim=64,
                  rope_scaling=_PUBLISHED_ROPE)
    z = ref.sizes(cfg)
    assert ref.yarn_range(z) == (8, 20)
    want = ref.yarn_frequencies(z)
    assert want[0] == 1.0
    np.testing.assert_allclose(want[31], 50000 ** (-62 / 64) / 64,
                               rtol=1e-12)
    # pair 8 keeps its frequency, pair 20 takes a 64th, pair 14 is half
    # way between
    f = 50000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(want[:9], f[:9], rtol=1e-12)
    np.testing.assert_allclose(want[20:], f[20:] / 64, rtol=1e-12)
    np.testing.assert_allclose(want[14], f[14] * (0.5 + 0.5 / 64),
                               rtol=1e-12)
    np.testing.assert_allclose(ref.softmax_scale(z), 0.144680, rtol=5e-6)
    # the program's table and scale are the reference's
    got = ops.yarn_frequencies(64, 50000.0, 64.0, 4096, 32, 1)
    np.testing.assert_allclose(got, want.astype(np.float32), rtol=1e-7)
    net = kimi_k2.kimi_k2_tiny(nope_dim=128, rope_dim=64, rope_factor=64.0,
                               rope_original_length=4096)
    np.testing.assert_allclose(net._sizes.softmax_scale, 0.144680,
                               rtol=5e-6)
    assert net._sizes.rope_mscale == 1.0


def test_the_table_and_the_scale_change_the_output():
    """Contexts past the original length: plain rotary frequencies, or a
    scale without YaRN's factor, serve other logits."""
    def served_logits(net):
        eng = serving.ServingEngine(net, batch_buckets=(4,))
        return _walk(eng, [[5, 9, 2], list(range(1, 40))], 4)[1]

    cfg = _config()
    base = served_logits(_net(cfg)[0])
    assert np.abs(served_logits(_net(cfg, rope_factor=1.0)[0])
                  - base).max() > 1e-3
    assert np.abs(served_logits(_net(cfg, mscale_all_dim=0.0)[0])
                  - base).max() > 1e-3


# -- (g) the scaled router -----------------------------------------------------

def test_the_routed_scaling_factor():
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.normal(size=(33, 64)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(8, 64)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.normal(size=(8,)) * 0.3, jnp.float32)
    chosen, w = moe.sigmoid_topk_route(x, wr, b, 2, scale=2.827)
    np.testing.assert_allclose(np.asarray(w).sum(axis=-1), 2.827,
                               rtol=1e-6)
    c1, w1 = moe.sigmoid_topk_route(x, wr, b, 2, scale=1.0)
    c0, w0 = moe.sigmoid_topk_route(x, wr, b, 2)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(c0))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w0))
    np.testing.assert_allclose(np.asarray(w), 2.827 * np.asarray(w0),
                               rtol=1e-6)


# -- the engine's pins for the fourth family -----------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = _config()
    net, values = _net(cfg, prefill_chunk_tokens=32)
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
def test_the_latent_stack_aliases_its_input(served, kind, S):
    """One stack with no heads and no second one for the values: ``L x B
    x (kv_rank + rope_dim) x W`` elements; every array of the cache is
    written into its donated argument (the prefill a row chunk at a
    time), and the decode program moves no layer-sized piece of it."""
    _, _, _, eng = served
    B = 4
    text = eng._compile(B, S).as_text()
    n_w = len(eng._weights)
    cache = eng.init_cache(B)
    assert [c.shape for c in cache] == [(3, B, 1, 24, 64), (2, 2, 11),
                                        (3, 2)]
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(len(cache)):
        assert f"{{{i}}}: ({n_w + i}, {{}}" in alias, (i, alias)
    if kind == "decode":
        assert serving.whole_layer_ops(
            text, cache[0].nbytes // cache[0].shape[0]) == []


def test_the_weights_are_the_parameters_own_buffers(served):
    _, net, _, eng = served
    for name, a in zip(net._names, eng._weights):
        assert a is getattr(net, name).data()._data, name


def test_a_coalesced_group_is_bitwise_the_requests_served_alone(served):
    """Rows do not see each other, whatever row chunk they fall in."""
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
    # the three rows that want a token, not the pad row
    assert timings["moe_pairs_decode"] == 3 * 2 * 2 * (steps - 1)


@pytest.mark.parametrize("wants", UNEQUAL_ANSWERS, ids=str)
def test_a_row_that_wants_no_token_changes_nothing(served, wants):
    """The decode step is handed which rows still want a token: the
    others attend to nothing, go to no routed expert and write nothing
    into the latent stack, every
    request's tokens are what it gets alone and in a group of equal
    answers, and the counters are the live row-steps': the latent
    positions attended to, 3 layers, and 2 pairs a token in each of the
    2 expert layers (all 8 experts are held)."""
    _, _, _, eng = served
    eng.warmup()
    rng = np.random.RandomState(10)
    prompts = [rng.randint(0, 96, n).tolist()
               for n in (2, 8, 23, 5)[:len(wants)]]
    pinned = (serving.trace_count(), serving.compile_count())
    timings, live = serving_unequal_answers(eng, prompts, wants)
    assert (serving.trace_count(), serving.compile_count()) == pinned
    assert timings["attn_latent_positions_decode"] == \
        3 * sum(len(prompts[i]) + j + 1 for i, j in live)
    assert timings["moe_pairs_decode"] == len(live) * 2 * 2
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
    with pytest.raises(MXNetError, match="incompatible model"):
        eng.reload_from_model(_net(_config(experts_held=[0, 4],
                                           n_routed_experts=4))[0])

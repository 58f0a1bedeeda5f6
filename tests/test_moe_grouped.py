"""`ops/moe.py`'s grouped product: the kernel (interpreted here) against
``lax.ragged_dot`` and against a loop over the groups; a pass's way out
by the walk of the stream's token tiles (interpreted) against XLA's
scatter-add; and `held_experts_ffn` on its paths.  (The compiles for a
described v5e are in tests/test_cache_write.py, with every other.)"""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from mxnet_tpu.ops import moe

# (`_ROWS`, rows of the buffer, rows of each group): the groups lie one
# after another from row 0, what is past them is padding; a tile is
# `_ROWS` rows, twice that in the last case (`_row_tile`)
CASES = {
    "every_pair_on_one_expert": (8, 32, (0, 0, 32, 0)),
    "a_group_over_three_tiles": (8, 32, (5, 18, 3)),
    "empty_groups_between": (8, 32, (0, 7, 0, 0, 9, 0, 4)),
    "no_pair": (8, 16, (0, 0, 0)),
    "the_buffer_full": (8, 32, (10, 10, 12)),
    "one_tile": (16, 16, (3, 0, 6)),
    "groups_end_on_tile_edges": (8, 32, (8, 16, 0, 8)),
    "the_last_tiles_empty": (8, 64, (2, 1, 9)),
}


def _operands(P, sizes, L=3, K=24, N=40, dtype=jnp.float32, seed=0):
    n = len(sizes)
    ks = jax.random.split(jax.random.key(seed), 2)
    x = jax.random.normal(ks[0], (P, K), dtype)
    w = jax.random.normal(ks[1], (L, n, K, N), dtype)
    hi = jnp.cumsum(jnp.array(sizes, jnp.int32))
    return x, w, hi - jnp.array(sizes, jnp.int32), hi


def _by_kernel(x, w, lo, hi, layer):
    L, n, K, N = w.shape
    walk = moe._walk(lo, hi, layer * n, x.shape[0])
    return moe._grouped_kernel_call(x, w.reshape(L * n, K, N), walk,
                                    interpret=True)


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_equals_ragged_dot_and_a_loop_over_the_groups(
        name, monkeypatch):
    """The rows of each group times that group's matrix of layer 1 of a
    stack of three, to float32 rounding of the sums over K; and the walk
    visits each (tile, group) that holds a row once, in order, in arrays
    sized for the most a pass can need."""
    rows, P, sizes = CASES[name]
    monkeypatch.setattr(moe, "_ROWS", rows)
    tm = moe._row_tile(P, len(sizes))
    assert tm == (2 * rows if name == "the_last_tiles_empty" else rows)
    x, w, lo, hi = _operands(P, sizes)
    total = int(hi[-1])
    got = np.asarray(_by_kernel(x, w, lo, hi, 1))
    assert got.shape == (P, w.shape[-1]) and got.dtype == np.float32
    want = np.asarray(lax.ragged_dot(x, w[1], hi - lo,
                                     preferred_element_type=jnp.float32))
    np.testing.assert_allclose(got[:total], want[:total], atol=1e-5)
    for g, (a, b) in enumerate(zip(np.asarray(lo), np.asarray(hi))):
        np.testing.assert_allclose(
            got[a:b], np.asarray(x[a:b]) @ np.asarray(w[1, g]), atol=1e-5)
    first, visits, group, tile, _, _ = (np.asarray(a) for a in moe._walk(
        lo, hi, len(sizes), P))
    pairs = [(t, g) for g, (a, b) in enumerate(zip(np.asarray(lo),
                                                   np.asarray(hi)))
             for t in range(a // tm, -(-b // tm)) if b > a]
    assert first[0] == len(sizes) and visits[0] == len(pairs)
    assert len(group) == P // tm + len(sizes) - 1
    assert list(zip(tile[:len(pairs)], group[:len(pairs)])) == pairs


@pytest.mark.parametrize("layer", ["int", "traced"])
def test_the_layer_offset_reads_that_layers_slice_of_the_stack(
        layer, monkeypatch):
    """Given ``layer`` the block index is ``layer * n + e`` into the
    stack seen as ``(L n, ...)``: the product over that layer's slice,
    for an int and for a traced scalar, at bfloat16 operands."""
    monkeypatch.setattr(moe, "_ROWS", 16)
    x, w, lo, hi = _operands(32, (4, 0, 20, 3), L=4, dtype=jnp.bfloat16)
    for l in range(4):
        got = _by_kernel(x, w, lo, hi, l) if layer == "int" else jax.jit(
            _by_kernel)(x, w, lo, hi, jnp.int32(l))
        want = lax.ragged_dot(x, w[l], hi - lo,
                              preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(got)[:27],
                                   np.asarray(want)[:27], atol=2e-5,
                                   rtol=1e-5)


def _on_the_kernel_path(monkeypatch, rows, poison=False, tokens=8):
    """`held_experts_ffn` as a TPU would run it, both kernels
    interpreted, at ``rows`` rows a tile and ``tokens`` tokens a tile of
    the stream.  ``poison``: the rows of the grouped kernel's result
    that no visit wrote (past the last pair's tile, and past the last
    pair in it) hold NaN, as a buffer on the chip may."""
    real, way_out = moe._grouped_kernel_call, moe._combine_kernel_call

    def interpreted(x, w, walk):
        out = real(x, w, walk, interpret=True)
        if poison:
            out = jnp.where((jnp.arange(x.shape[0]) < walk[5][-1])[:, None],
                            out, jnp.nan)
        return out

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe, "_ROWS", rows)
    monkeypatch.setattr(moe, "_TOKENS", tokens)
    monkeypatch.setattr(moe, "_grouped_kernel_call", interpreted)
    monkeypatch.setattr(
        moe, "_combine_kernel_call",
        lambda y, o, w, tok: way_out(y, o, w, tok, interpret=True))


def _routed(T, k, n, M, F, dtype, experts=None, seed=0):
    """T tokens routed to k of ``experts`` experts of which experts
    2 .. 2 + n are held."""
    ks = jax.random.split(jax.random.key(seed), 5)
    experts = experts or n + 4
    x = jax.random.normal(ks[0], (T, M), jnp.float32)
    w13 = (jax.random.normal(ks[1], (n, M, 2 * F), jnp.float32)
           * M ** -0.5).astype(dtype)
    w2 = (jax.random.normal(ks[2], (n, F, M), jnp.float32)
          * F ** -0.5).astype(dtype)
    _, chosen = lax.top_k(jax.random.normal(ks[3], (T, experts)), k)
    weights = jax.nn.softmax(jax.random.normal(ks[4], (T, k)), axis=-1)
    return x, chosen.astype(jnp.int32), weights, w13, w2


@pytest.mark.parametrize("rows,pass_rows", [(8, 16), (16, 64)])
def test_rows_past_the_pairs_stay_out_of_the_sum(rows, pass_rows,
                                                  monkeypatch):
    """The kernel writes no row past a pass's last pair, so what the
    buffer held stays there, NaN perhaps: `held_experts_ffn` keeps it
    out with a select, and its sum and counts are the plain path's, with
    a buffer that takes several passes, one that is mostly empty, and
    tokens that are padding."""
    x, chosen, weights, w13, w2 = _routed(24, 3, 4, 128, 128, jnp.float32)
    valid = jnp.arange(24) % 5 != 0
    add_to = jnp.ones((24, 128), jnp.float32)

    def run(**kw):
        return moe.held_experts_ffn(
            x, chosen, weights, w13, w2, experts_lo=2, valid=valid,
            pass_rows=pass_rows, add_to=add_to, **kw)

    tally = collections.Counter()
    want, stats0 = run(tally=tally)
    assert dict(tally) == {"plain": 1, "combine_plain": 1}
    _on_the_kernel_path(monkeypatch, rows, poison=True)
    assert moe._fits(pass_rows, 128, 128, 4, jnp.float32)
    assert moe._combine_fits(24, pass_rows, 128)
    got, stats1 = run(tally=tally)
    assert dict(tally) == {"plain": 1, "combine_plain": 1, "kernel": 1,
                           "combine_kernel": 1}
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(stats0), np.asarray(stats1))
    pairs = int(np.asarray(stats1)[:4].sum())
    assert 0 < pairs and stats1[4] == -(-pairs // pass_rows) * pass_rows


# each expert family's published hidden width and expert width
# (benchmark/configs), cut in the number of experts held only
FAMILIES = {
    "mimo": (4096, 2048), "keye": (2048, 768), "kimi": (7168, 2048),
    "cmda": (4096, 4096), "granite": (4096, 768),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_both_paths_agree_at_the_published_widths(family, monkeypatch):
    """`held_experts_ffn`'s sum and counts by the kernel (its tiles as
    the chip's but for 16 rows a tile) equal the plain path's to float32
    rounding, the experts in a stack by layer and the layer a traced
    scalar, as `_decoder_ops.experts_of_layer` hands them."""
    M, F = FAMILIES[family]
    x, chosen, weights, w13, w2 = _routed(16, 2, 2, M, F, jnp.float32,
                                          experts=5)
    want, stats0 = moe.held_experts_ffn(x, chosen, weights, w13, w2,
                                        experts_lo=2)
    _on_the_kernel_path(monkeypatch, 16)
    assert moe._fits(32, M, F, 2, jnp.float32) \
        and moe._fits(256, M, F, 16, jnp.bfloat16)
    tally = collections.Counter()
    # layer 1 of a stack of two, whose layer 0 is the same experts in
    # the other order
    got, stats1 = jax.jit(lambda l, *a: moe.held_experts_ffn(
        *a, experts_lo=2, layer=l, tally=tally))(
            jnp.int32(1), x, chosen, weights,
            jnp.stack([w13[::-1], w13]), jnp.stack([w2[::-1], w2]))
    assert dict(tally) == {"kernel": 1, "combine_kernel": 1}
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6 * scale * M ** 0.5)
    np.testing.assert_array_equal(np.asarray(stats0), np.asarray(stats1))
    assert int(np.asarray(stats1)[:2].sum()) > 0


def test_what_the_kernel_takes():
    """`_fits` on what it can see: lane-aligned widths, a buffer of whole
    row tiles of whole packed sublanes, a 128-column block inside the
    budget; `_tiles`: 128 rows (a shorter buffer whole; 256 where the
    buffer holds as many for every expert) and the widest aligned column
    block under `_WEIGHT_BLOCK`, K never cut."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    for P, M, F, n in ((256, 4096, 768, 18), (1024, 4096, 2048, 16),
                       (128, 2048, 768, 16), (64, 7168, 2048, 12),
                       (4096, 4096, 4096, 8)):
        assert moe._fits(P, M, F, n, bf16)
    assert not moe._fits(256, 4096 + 64, 768, 18, bf16)   # a ragged lane
    assert not moe._fits(256, 4096, 24, 18, bf16)
    assert not moe._fits(200, 4096, 768, 18, bf16)   # 128 does not divide
    assert not moe._fits(8, 4096, 768, 18, bf16)     # half a packed tile
    assert moe._fits(8, 4096, 768, 18, f32)
    assert not moe._fits(256, 1 << 16, 768, 18, f32)      # a block too deep
    assert moe._tiles(256, 4096, 1536, 18, 2) == (128, 768)
    assert moe._tiles(256, 4096, 4096, 16, 2) == (128, 1024)
    assert moe._tiles(1024, 768, 4096, 18, 2) == (128, 4096)
    assert moe._tiles(64, 7168, 4096, 12, 2) == (64, 512)
    assert moe._tiles(4096, 7168, 4096, 12, 2) == (256, 512)
    assert moe._tiles(4096, 4096, 8192, 8, 2) == (256, 1024)
    assert moe._tiles(16, 24, 40, 3, 4) == (16, 40)


def test_a_served_group_by_the_kernel_equals_the_plain_paths(monkeypatch):
    """A family whose experts are stacked by layer (Keye-VL-2.0's, two
    layers at lane-aligned widths) through `ServingEngine` with the
    kernel in both programs (interpreted here, as on a TPU): the tokens
    and the experts' counters are the plain path's, the decode and the
    prefill program each tallied their scanned layer's call by path, and
    ``moe_grouped_kernel_share`` is in the group's timings and in each
    request's record: 0.0 on the CPU's own path, 1.0 with the kernel; and
    so is ``moe_combine_kernel_share`` for the passes' way out."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.gluon.model_zoo import keye_vl2

    net = keye_vl2.KeyeVL2Model(
        vocab_size=96, units=128, num_layers=2, num_heads=2, kv_heads=1,
        head_dim=16, index_heads=2, index_dim=8, topk=4, expert_hidden=128,
        router_experts=8, experts_per_token=2, experts_held=[2, 4],
        max_length=32, grad_req="null")
    net.initialize(init=mx.init.Normal(0.2))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 96, n) for n in (11, 5, 3, 8)]
    plain = serving.ServingEngine(net, batch_buckets=(8,))
    want, t0 = plain.serve_group(prompts, 4)
    assert t0["moe_grouped_kernel_share"] == 0.0
    assert t0["moe_combine_kernel_share"] == 0.0
    assert dict(plain._program.grouped_products[1]) == {
        "plain": 1, "combine_plain": 1}
    _on_the_kernel_path(monkeypatch, 8)
    eng = serving.ServingEngine(net, batch_buckets=(8,))
    got, t1 = eng.serve_group(prompts, 4)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert t1["moe_grouped_kernel_share"] == 1.0
    assert t1["moe_combine_kernel_share"] == 1.0
    S = t1["bucket"][1]
    by_kernel = {"kernel": 1, "combine_kernel": 1}
    assert dict(eng._program.grouped_products[1]) == by_kernel
    assert dict(eng._program.grouped_products[S]) == by_kernel
    for key in ("moe_pairs_prefill", "moe_pairs_decode",
                "moe_rows_computed_prefill", "moe_rows_computed_decode",
                "moe_experts_hit_per_step", "moe_load_max_over_mean"):
        assert t1[key] == t0[key], key
    assert t1["moe_pairs_prefill"] > 0 and t1["moe_pairs_decode"] > 0
    telemetry.reset()
    batcher = serving.ContinuousBatcher(eng, max_delay_ms=150, max_batch=4)
    try:
        futs = [batcher.submit(p, 4) for p in prompts]
        for f in futs:
            f.result(timeout=120)
    finally:
        batcher.close()
    requests = telemetry.recent_requests()
    assert len(requests) == 4
    for r in requests:
        telemetry.validate_record(r)
        assert r["moe_grouped_kernel_share"] == 1.0
        assert r["moe_combine_kernel_share"] == 1.0


# -- a pass's way out, by token tile -------------------------------------------

# (tokens of the stream, rows of the buffer, the rows' tokens in buffer
# order): tiles of 8 tokens and 8 rows; the rows past those listed, and
# a listed row whose token is T, are dead and hold NaN
WAYS_OUT = {
    "a_token_with_several_rows": (32, 16, (3, 9, 3, 30, 3, 9, 17, 3, 2)),
    "every_row_on_one_token": (16, 16, (5,) * 16),
    "a_pass_mostly_empty": (64, 32, (40, 7)),
    "no_pair": (16, 16, ()),
    "the_buffer_full": (24, 16, (23, 0, 8, 15, 16, 7, 1, 22, 9, 9, 0, 23,
                                 12, 4, 20, 11)),
    "a_decode_call": (8, 32, (1, 6, 1, 3, 6, 6, 0, 2, 5, 1, 7, 7, 3)),
    "a_stream_of_64_buffers": (1024, 16, (1000, 3, 512, 513, 3, 77, 1023,
                                          640, 640, 0)),
    "tokens_in_expert_order": (64, 24, (1, 18, 40, 63, 0, 18, 19, 41, 5, 18,
                                        62, 63, 2, 40)),
    "dead_rows_between": (8, 24, (1, 8, 8, 3) + (8,) * 8 + (1, 8, 5)),
    "dead_rows_between_in_a_long_stream": (64, 24, (9, 64, 64, 3) + (64,) * 8
                                           + (9, 64, 50)),
}


def _way_out_operands(T, P, toks, M=128, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    tok = jnp.array(tuple(toks) + (T,) * (P - len(toks)), jnp.int32)
    y = jax.random.normal(ks[0], (T, M), jnp.float32)
    o = jnp.where((tok < T)[:, None],
                  jax.random.normal(ks[1], (P, M), jnp.float32), jnp.nan)
    w = jax.random.uniform(ks[2], (P,), jnp.float32)
    return y, o, w, tok


@pytest.mark.parametrize("name", list(WAYS_OUT))
def test_the_way_out_by_token_tile_equals_the_scatter_add(name, monkeypatch):
    """`_combine_kernel_call` (interpreted, tiles of 8 tokens and 8
    rows, the dead rows NaN) adds what `_combine_plain` adds, to float32
    rounding of a token's sum; a token tile no row falls in keeps what
    it held to the bit; and the walk visits each (token tile, row tile)
    that shares a sorted row once, in order, in arrays sized for the
    most a pass can need."""
    T, P, rows = WAYS_OUT[name]
    toks = [t for t in rows if t < T]
    monkeypatch.setattr(moe, "_ROWS", 8)
    monkeypatch.setattr(moe, "_TOKENS", 8)
    assert moe._combine_fits(T, P, 128)
    assert moe._combine_tiles(T, P, 128) == (8, 8, 128)
    y, o, w, tok = _way_out_operands(T, P, rows)
    want = np.asarray(moe._combine_plain(y, o, w, tok))
    got = np.asarray(moe._combine_kernel_call(y, o, w, tok, interpret=True))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-6)
    hit = {t // 8 for t in toks}
    for t in range(T // 8):
        if t not in hit:
            np.testing.assert_array_equal(got[8 * t:8 * t + 8],
                                          np.asarray(y)[8 * t:8 * t + 8])
    assert bool(toks) == bool((got != np.asarray(y)).any())
    sorted_toks = sorted(toks)
    visits, ttile, rtile = (np.asarray(a) for a in moe._token_walk(
        jnp.array(sorted_toks + [T] * (P - len(toks)), jnp.int32), T, 8, 8))
    pairs = sorted({(t // 8, r // 8) for r, t in enumerate(sorted_toks)})
    assert visits[0] == len(pairs)
    if T == 8:    # one tile: the rows as they lie, to the last live one
        unsorted = moe._token_walk(tok, T, 8, 8)
        assert unsorted[0][0] == max(
            [r // 8 + 1 for r, t in enumerate(rows) if t < T], default=0)
    assert len(ttile) == len(rtile) == P // 8 + min(P, T // 8) - 1
    assert list(zip(ttile[:len(pairs)], rtile[:len(pairs)])) == pairs
    assert ttile.max() < T // 8 and rtile.max() < P // 8


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_way_out_at_the_published_widths(family, monkeypatch):
    """The way out at each expert family's hidden width, its columns cut
    as the chip cuts them (`_combine_tiles`: the widest lane-aligned
    divisor under `_STREAM_BLOCK`), several tokens with rows in both
    row tiles: the scatter-add's sum."""
    M = FAMILIES[family][0]
    monkeypatch.setattr(moe, "_ROWS", 8)
    monkeypatch.setattr(moe, "_TOKENS", 16)
    monkeypatch.setattr(moe, "_STREAM_BLOCK", 16 * 4 * 1024)
    tn = moe._combine_tiles(32, 16, M)[2]
    assert tn == {2048: 1024, 4096: 1024, 7168: 1024}[M] and M % tn == 0
    y, o, w, tok = _way_out_operands(
        32, 16, (31, 2, 17, 2, 9, 9, 30, 2, 16, 31, 0), M=M, seed=M)
    want = moe._combine_plain(y, o, w, tok)
    got = moe._combine_kernel_call(y, o, w, tok, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("T,k,pass_rows", [
    (8, 4, 32),       # a decode call: fewer tokens than rows
    (16, 3, 16),      # a token's rows in two passes
    (1024, 1, 16),    # a stream of 64 buffers
    (40, 2, 24),      # padding tokens, a last pass mostly empty
])
def test_held_experts_by_both_kernels_equal_the_plain_paths(
        T, k, pass_rows, monkeypatch):
    """`held_experts_ffn` with the products and the way out by their
    kernels (interpreted; the products' buffer poisoned past the pairs)
    against the plain paths: the sum to float32 rounding, the counts
    equal as integers, the tokens that are padding and the token tiles
    no pair falls in holding ``add_to`` to the bit, and ``tally`` told
    both paths once a call."""
    x, chosen, weights, w13, w2 = _routed(T, k, 4, 128, 128, jnp.float32,
                                          seed=T)
    valid = jnp.arange(T) % 7 != 3
    if T == 1024:     # few real tokens in a long stream
        valid = valid & (jnp.arange(T) % 97 < 2)
    add_to = jax.random.normal(jax.random.key(1), (T, 128), jnp.float32)

    def run(tally):
        return moe.held_experts_ffn(
            x, chosen, weights, w13, w2, experts_lo=2, valid=valid,
            pass_rows=pass_rows, add_to=add_to, tally=tally)

    tally = collections.Counter()
    want, stats0 = run(tally)
    _on_the_kernel_path(monkeypatch, 8, poison=True)
    got, stats1 = run(tally)
    assert dict(tally) == {"plain": 1, "combine_plain": 1, "kernel": 1,
                           "combine_kernel": 1}
    np.testing.assert_array_equal(np.asarray(stats0), np.asarray(stats1))
    assert stats1.dtype == jnp.int32 and int(stats1[:4].sum()) > 0
    assert int(stats1[4]) >= (2 if T == 16 else 1) * pass_rows
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, atol=1e-5)
    local = np.asarray(chosen) - 2
    routed = (((local >= 0) & (local < 4)).any(axis=1)) & np.asarray(valid)
    assert routed.any() and not routed.all()
    np.testing.assert_array_equal(got[~routed], np.asarray(add_to)[~routed])


def test_what_the_way_out_takes():
    """`_combine_fits` on what it can see (a lane-aligned width, whole
    tiles of tokens and of rows, whole sublanes) and `_combine_tiles` at
    the ten shapes the five cells' programs hand it: 128 tokens by 128
    rows (a shorter stream or buffer whole) and the widest aligned
    column tile whose tile of the stream is a megabyte at the most."""
    shapes = {      # (T, P, M): tiles
        (16384, 4096, 7168): (128, 128, 1792),      # Kimi's prefill
        (262144, 65536, 2048): (128, 128, 2048),    # Keye's
        (16384, 4096, 4096): (128, 128, 2048),      # Command A+'s
        (4096, 1024, 4096): (128, 128, 2048),       # Granite's, MiMo's
        (128, 256, 4096): (128, 128, 2048),         # Granite's decode
        (64, 256, 4096): (64, 128, 4096),           # MiMo's
        (16, 128, 2048): (16, 128, 2048),           # Keye's
        (8, 64, 7168): (8, 64, 7168),               # Kimi's
        (8, 64, 4096): (8, 64, 4096),               # Command A+'s
    }
    for shape, tiles in shapes.items():
        assert moe._combine_fits(*shape), shape
        assert moe._combine_tiles(*shape) == tiles, shape
    assert not moe._combine_fits(4096, 1024, 4096 + 64)   # a ragged lane
    assert not moe._combine_fits(200, 1024, 4096)    # 128 does not divide
    assert not moe._combine_fits(4096, 200, 4096)
    assert not moe._combine_fits(4, 64, 4096)        # half a sublane tile
    assert moe._combine_fits(8, 8, 128)

"""`ops/pallas_attention.py`: the flash kernels (interpreted here)
against the dense oracle at the blocks the shape gives and at forced
small ones, what their products are fed, and the block rule; the
forward-only entry with a value width of its own and a length a row,
its window and its key heads fewer than query heads (Command A+'s
prefill), and that training's call and the entry without a window
lower to the grids they had.  The
compiles for a described v5e are in `tests/test_cache_write.py` (one
worker loads the TPU's library)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_attention as pa

BF16, F32 = jnp.bfloat16, jnp.float32

# (T, D, causal, dtype): the training cell's shape, BERT's, a length
# that is lane-aligned and no multiple of 256, heads of 128 over two
# grid blocks, and float32 inputs
CASES = [
    (1024, 64, True, BF16),
    (512, 64, False, BF16),
    (384, 64, True, BF16),
    (2048, 128, True, BF16),
    (256, 64, True, F32),
]
# forced blocks a case: several tiles a row, one the diagonal cuts, one
# wholly above it (causal), and blocks that differ for queries and keys
FORCED = {
    1024: [(128, 128), (256, 512)],
    512: [(128, 128), (256, 128)],
    384: [(128, 128), (128, 384)],
    2048: [(256, 256), (1024, 512)],
    256: [(128, 128), (128, 256)],
}
# test_flash_attention_grad's tolerances (tests/test_parallel.py) for
# float32; a bfloat16 result carries its own rounding besides (half a
# unit in the last of 8 bits) and that of p and ds before their products
TOL = {F32: {"fwd": (2e-4, 2e-5), "bwd": (2e-3, 2e-4)},
       BF16: {"fwd": (2e-3 + 2.0 ** -8, 2e-3), "bwd": (2e-3 + 2.0 ** -7,
                                                      2e-2)}}


def _inputs(T, D, dtype, seed=0, heads=2):
    keys = jax.random.split(jax.random.key(seed + T), 4)
    return [jax.random.normal(k, (1, heads, T, D), dtype) for k in keys]


def _oracle(q, k, v, w, causal):
    """The dense reference in float32 on the inputs' own values."""
    scale = q.shape[-1] ** -0.5
    q, k, v, w = (x.astype(F32) for x in (q, k, v, w))

    def loss(q, k, v):
        return jnp.sum(pa._dense_ref(q, k, v, causal, scale) * w)

    return (pa._dense_ref(q, k, v, causal, scale),
            jax.grad(loss, argnums=(0, 1, 2))(q, k, v))


def _kernel(q, k, v, w, causal, **blocks):
    def loss(q, k, v):
        out = pa.flash_attention(q, k, v, causal=causal, **blocks)
        return jnp.sum(out.astype(F32) * w.astype(F32))

    return (pa.flash_attention(q, k, v, causal=causal, **blocks),
            jax.grad(loss, argnums=(0, 1, 2))(q, k, v))


def _cases():
    for T, D, causal, dtype in CASES:
        for blocks in [None] + FORCED[T]:
            name = "x".join(map(str, blocks)) if blocks else "default"
            yield pytest.param(
                T, D, causal, dtype, blocks,
                id=f"T{T}-D{D}-{'causal' if causal else 'full'}-"
                   f"{jnp.dtype(dtype).name}-{name}")


@pytest.mark.parametrize("T,D,causal,dtype,blocks", list(_cases()))
def test_forward_and_gradients_match_the_dense_oracle(T, D, causal, dtype,
                                                      blocks):
    q, k, v, w = _inputs(T, D, dtype)
    kw = dict(zip(("block_q", "block_k"), blocks)) if blocks else {}
    out, grads = _kernel(q, k, v, w, causal, **kw)
    ref, ref_grads = _oracle(q, k, v, w, causal)
    assert out.dtype == dtype and out.shape == q.shape
    rtol, atol = TOL[dtype]["fwd"]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=rtol, atol=atol)
    rtol, atol = TOL[dtype]["bwd"]
    for got, want, x, name in zip(grads, ref_grads, (q, k, v), "qkv"):
        assert got.dtype == x.dtype and got.shape == x.shape
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want), rtol=rtol,
            atol=atol, err_msg=f"d{name}")


# -- the forward-only entry: a value width of its own, a length a row ----------

# each batch row its own length: none, one position, a block's edge, one
# past it, the middle of a sub-tile, all
LENGTHS = [0, 1, 128, 129, 300, 512]


def _wide_inputs(D, Dv, dtype, T=512, rows=len(LENGTHS), heads=2):
    keys = jax.random.split(jax.random.key(D + Dv), 3)
    return [jax.random.normal(k, (rows, heads, T, d), dtype)
            for k, d in zip(keys, (D, D, Dv))]


@pytest.mark.parametrize("blocks", [None, (128, 128), (256, 128)],
                         ids=["default", "128x128", "256x128"])
@pytest.mark.parametrize("given", [False, True], ids=["whole", "lengths"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("D,Dv", [(192, 128), (32, 16)])
def test_forward_entry_matches_the_dense_oracle(D, Dv, dtype, given, blocks):
    """`flash_attention_forward` with values narrower than keys (Kimi's
    192 / 128 and a small pair; keys no multiple of 128 wide are padded
    inside): the dense oracle's result, (B, H, T, Dv); with ``lengths``
    each row equals the oracle below its length and is exactly zero at
    and past it, whatever the blocks (several key blocks a step through
    the two buffers, a step's first block handed on by the step before,
    also over rows and query blocks that walk nothing)."""
    q, k, v = _wide_inputs(D, Dv, dtype)
    kw = dict(zip(("block_q", "block_k"), blocks)) if blocks else {}
    lengths = jnp.asarray(LENGTHS, jnp.int32) if given else None
    out = jax.jit(lambda q, k, v, n: pa.flash_attention_forward(
        q, k, v, n, scale=D ** -0.5, **kw))(q, k, v, lengths)
    assert out.dtype == dtype and out.shape == v.shape
    ref = np.asarray(pa._dense_ref(*(x.astype(F32) for x in (q, k, v)),
                                   True, D ** -0.5))
    out = np.asarray(out, np.float32)
    rtol, atol = TOL[dtype]["fwd"]
    for b, n in enumerate(LENGTHS if given else [512] * len(LENGTHS)):
        np.testing.assert_allclose(out[b, :, :n], ref[b, :, :n], rtol=rtol,
                                   atol=atol, err_msg=f"row of {n}")
        assert not out[b, :, n:].any(), f"row of {n}"


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T,lengths", [(8, (8, 3, 0)), (64, (64, 1, 40)),
                                       (200, (200, 128, 129))])
def test_forward_entry_takes_a_block_shorter_than_its_tiles(T, lengths,
                                                            dtype):
    """A serving engine's small buckets (8-64 positions; any ``T`` that
    is no multiple of 128): the entry pads the block with positions past
    every row's length, the kernel works whole tiles as it must on the
    TPU, and the result is cut back: the dense oracle's below each
    row's length, zero at and past it, (B, H, T, Dv)."""
    D, Dv = 192, 128
    q, k, v = _wide_inputs(D, Dv, dtype, T=T, rows=len(lengths))
    fn = jax.jit(lambda n: pa.flash_attention_forward(q, k, v, n,
                                                      scale=D ** -0.5))
    call, = _pallas_calls(jax.make_jaxpr(fn)(jnp.asarray(lengths)).jaxpr, [])
    assert [x.aval.shape[1:] for x in call.invars[1:]] == [
        (pa.lane_tiles(T), 256)] * 2 + [(pa.lane_tiles(T), Dv)]
    ref = np.asarray(pa._dense_ref(*(x.astype(F32) for x in (q, k, v)),
                                   True, D ** -0.5))
    rtol, atol = TOL[dtype]["fwd"]
    for given in (jnp.asarray(lengths, jnp.int32), None):
        out = fn(given) if given is not None else jax.jit(
            lambda: pa.flash_attention_forward(q, k, v, scale=D ** -0.5))()
        assert out.dtype == dtype and out.shape == v.shape
        out = np.asarray(out, np.float32)
        for b, n in enumerate(lengths if given is not None
                              else (T,) * len(lengths)):
            np.testing.assert_allclose(out[b, :, :n], ref[b, :, :n],
                                       rtol=rtol, atol=atol,
                                       err_msg=f"row of {n}")
            assert not out[b, :, n:].any(), f"row of {n}"


def _masked_ref(q, k, v, scale, seen):
    """The dense masked softmax in float32 over the keys ``seen`` marks
    (it broadcasts against (B, H, T, T)), query head h over key head
    ``h // group``; a query that sees no key comes out zero."""
    group = q.shape[1] // k.shape[1]
    q, k, v = (x.astype(F32) for x in (q, k, v))
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1),
                  0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def _band_ref(q, k, v, scale, window):
    """Query i over keys ``i - window + 1 .. i``."""
    i = jnp.arange(q.shape[2])
    return _masked_ref(q, k, v, scale, (i[None, :] <= i[:, None])
                       & (i[None, :] > i[:, None] - window))


@pytest.mark.parametrize("T,blocks", [(128, None), (384, 128), (512, None)])
def test_forward_entry_with_twenty_query_heads_over_one_key_head(T, blocks):
    """Jamba2-3B's attention layers: 20 query heads read the one key
    head of 128 where it lies (a group that is no power of two; the
    widest before it was 16), each row to its own length, at the blocks
    `_block_sizes` gives and at forced blocks of 128."""
    D, lengths = 128, (T, max(1, (5 * T) // 8), 1)
    keys = jax.random.split(jax.random.key(T), 3)
    q, k, v = (jax.random.normal(kk, (3, h, T, D), F32)
               for kk, h in zip(keys, (20, 1, 1)))
    out = np.asarray(jax.jit(lambda q, k, v, n: pa.flash_attention_forward(
        q, k, v, n, scale=D ** -0.5, block_q=blocks, block_k=blocks))(
        q, k, v, jnp.asarray(lengths, jnp.int32)))
    assert out.shape == (3, 20, T, D)
    i = jnp.arange(T)
    ref = np.asarray(_masked_ref(q, k, v, D ** -0.5,
                                 i[None, :] <= i[:, None]))
    rtol, atol = TOL[F32]["fwd"]
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(out[b, :, :n], ref[b, :, :n], rtol=rtol,
                                   atol=atol, err_msg=f"row of {n}")
        assert not out[b, :, n:].any(), f"row of {n}"


def _window_cases():
    for T in (128, 384, 1000):
        for window in (1, 100, 128, 300, T, 2 * T):
            for D, Dv, group in ((128, 128, 2), (256, 128, 1)):
                yield pytest.param(T, window, D, Dv, group,
                                   id=f"T{T}-w{window}-D{D}x{Dv}-g{group}")


@pytest.mark.parametrize("T,window,D,Dv,group", list(_window_cases()))
def test_forward_entry_with_a_window_matches_the_masked_softmax(
        T, window, D, Dv, group):
    """`flash_attention_forward(window=)` against a dense masked
    softmax, in blocks of 128 so that a query block of a long row walks
    several key blocks and starts past those behind its band: windows
    of one position, inside a block, a block, across blocks, the whole
    row and more; ragged lengths; Command A+'s heads (128 / 128, two
    query heads a key head, read where they lie) and Kimi's widths
    (256 / 128); a length that is no multiple of a tile.  float32
    inputs: the tolerance is the forward's float32 one, which a
    bfloat16 product (2**-8) fails by an order of magnitude."""
    lengths = (T, max(1, (5 * T) // 8))
    keys = jax.random.split(jax.random.key(T + window), 3)
    q, k, v = (jax.random.normal(kk, (2, h, T, d), F32)
               for kk, h, d in zip(keys, (2 * group, 2, 2), (D, D, Dv)))
    out = jax.jit(lambda q, k, v, n: pa.flash_attention_forward(
        q, k, v, n, scale=D ** -0.5, window=window, block_q=128,
        block_k=128))(q, k, v, jnp.asarray(lengths, jnp.int32))
    assert out.shape == (2, 2 * group, T, Dv)
    ref = np.asarray(_band_ref(q, k, v, D ** -0.5, window))
    out = np.asarray(out)
    rtol, atol = TOL[F32]["fwd"]
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(out[b, :, :n], ref[b, :, :n], rtol=rtol,
                                   atol=atol, err_msg=f"row of {n}")
        assert not out[b, :, n:].any(), f"row of {n}"


def test_a_window_walks_only_the_blocks_of_its_band():
    """What the band saves is not a mask over work done anyway: keys
    wholly behind a query block's band are never read.  NaNs planted in
    every key and value block that no query of a row's last query block
    can see (and zero queries elsewhere would hide nothing: the whole
    row is live) leave that block's output finite and equal to the
    oracle's; with no window the same NaNs reach every query."""
    T, w, D = 1024, 200, 128
    keys = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (1, 1, T, D), F32) for kk in keys)
    ref = np.asarray(_band_ref(q, k, v, D ** -0.5, w))
    # the last query block (896..1023) sees keys 697..1023: blocks 5-7
    hole = jnp.arange(T)[None, None, :, None] < 5 * 128
    kn, vn = (jnp.where(hole, jnp.nan, x) for x in (k, v))
    fn = jax.jit(lambda q, k, v, window: pa.flash_attention_forward(
        q, k, v, scale=D ** -0.5, window=window, block_q=128, block_k=128),
        static_argnums=3)
    out = np.asarray(fn(q, kn, vn, w))
    rtol, atol = TOL[F32]["fwd"]
    np.testing.assert_allclose(out[0, 0, 896:], ref[0, 0, 896:], rtol=rtol,
                               atol=atol)
    assert np.isnan(np.asarray(fn(q, kn, vn, None))[0, 0, 896:]).all()


def test_no_window_builds_the_kernel_it_built_before():
    """`flash_attention_forward(window=None)` with a key head a query
    head is Kimi-K2's and Ouro's prefill call: the kernel has the
    operands and the grid it had before the window existed (lengths, q,
    k, v; heads x query blocks), its body is told of no window and no
    group, and its jaxpr is the one a window at least as long as the
    row walks less of, never more: no equation is added for a band that
    is not there.  With a window the operands and the grid stay the
    same: the band is in the walk's bounds, not in a new operand or
    grid axis.  (The compiled text of both families' tiny prefill was
    compared with the parent's once, CHANGES.md PR 39.)"""
    H, T, D = 4, 512, 128
    x = jax.ShapeDtypeStruct((1, H, T, D), BF16)
    n = jax.ShapeDtypeStruct((1,), jnp.int32)

    def call(**kw):
        found, = _pallas_calls(jax.make_jaxpr(
            lambda q, k, v, n: pa.flash_attention_forward(
                q, k, v, n, scale=1.0, block_q=128, block_k=128, **kw))(
                    x, x, x, n).jaxpr, [])
        return found

    plain, banded = call(), call(window=200)
    for c in (plain, banded):
        mapping = c.params["grid_mapping"]
        assert mapping.grid == (H, T // 128)
        assert mapping.num_index_operands == 1
        assert [v.aval.shape for v in c.invars] == [(1,)] + [(H, T, D)] * 3
    assert str(plain.params["jaxpr"]) == str(call(window=None).params["jaxpr"])
    assert len(_eqns(plain.params["jaxpr"], None, [])) < len(
        _eqns(banded.params["jaxpr"], None, []))
    # a key head for every two query heads: half the rows of k and v,
    # still no further operand
    half = jax.ShapeDtypeStruct((1, H // 2, T, D), BF16)
    grouped, = _pallas_calls(jax.make_jaxpr(
        lambda q, k, v, n: pa.flash_attention_forward(
            q, k, v, n, scale=1.0, window=200))(x, half, half, n).jaxpr, [])
    assert [v.aval.shape for v in grouped.invars] == [
        (1,), (H, T, D), (H // 2, T, D), (H // 2, T, D)]


def test_no_mask_builds_the_kernels_the_parent_built():
    """`flash_attention_forward(keep=None)` is Kimi-K2's, Ouro's and
    Command A+'s prefill call, and training's `_flash_call` shares the
    forward body: since the body learnt a selection mask (PR 43) the
    plain, the windowed and the grouped call, with and without a
    window, and training's forward and backward have the grid, the
    operands and the number of equations they had on the parent (counted
    there, commit c7ab047, at these shapes).  The mask is one more
    operand, one more scratch and the heads of a key head in one step
    only where a caller hands one."""
    H, T, D = 4, 512, 128
    x = jax.ShapeDtypeStruct((1, H, T, D), BF16)
    half = jax.ShapeDtypeStruct((1, H // 2, T, D), BF16)
    n = jax.ShapeDtypeStruct((1,), jnp.int32)

    def calls(fn, *args):
        return [(c.params["grid_mapping"].grid,
                 [v.aval.shape for v in c.invars],
                 len(_eqns(c.params["jaxpr"], None, [])))
                for c in _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr, [])]

    def entry(**kw):
        return lambda q, k, v, n: pa.flash_attention_forward(
            q, k, v, n, scale=1.0, block_q=128, block_k=128, **kw)

    whole, halved = (H, T, D), (H // 2, T, D)
    for kv, shapes, plain, banded in ((x, [whole] * 3, 352, 520),
                                      (half, [whole, halved, halved], 412,
                                       580)):
        assert calls(entry(), x, kv, kv, n) == [
            ((H, T // 128), [(1,)] + shapes, plain)]
        assert calls(entry(keep=None, window=200), x, kv, kv, n) == [
            ((H, T // 128), [(1,)] + shapes, banded)]
    rows = (H, 8, T)
    assert calls(jax.grad(lambda q, k, v: jnp.sum(pa.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128).astype(F32)),
        (0, 1, 2)), x, x, x) == [
            ((H, T // 128, T // 128), [whole] * 3, 132),
            ((H, T // 128, T // 128), [whole] * 4 + [rows] * 2, 131)]
    # and with a mask: one more operand, a key head's heads a step
    masked, = calls(entry(keep=jnp.zeros((1, T, T), jnp.int8)), x, half,
                    half, n)
    assert masked[:2] == ((H // 2, T // 128),
                          [(1,), (H // 2, 2, T, D), halved, halved,
                           (1, T, T)])


KEPT_T = 384
KEPT_LENGTHS = (1, 127, 128, 129, KEPT_T, 0)


def _kept_mask(kind, lengths, T):
    """(B, T, T) int8, causal, at least one key a query.  ``late``: a
    query marks its own position and the 15 before it only, so a query
    past the first block marks nothing in the first sub-tiles it
    visits.  ``not_self``: every second earlier key and never its own
    position (but query 0, which has no other).  ``topk``: what
    `indexed_attention.select_prefill` writes for seeded indexer
    projections, the 32 best of each query's earlier positions, each
    row to its last position.  ``causal``: every earlier key."""
    t, s = np.arange(T)[:, None], np.arange(T)[None, :]
    if kind == "topk":
        from mxnet_tpu.ops import indexed_attention
        keys = jax.random.split(jax.random.key(7), 3)
        B = len(lengths)
        return indexed_attention.select_prefill(
            jax.random.normal(keys[0], (B, 2, T, 8), F32),
            jax.random.normal(keys[1], (B, T, 2), F32),
            jax.random.normal(keys[2], (B, 8, T), F32),
            jnp.asarray(lengths, jnp.int32) - 1, 32)
    keep = {"late": (s <= t) & (s > t - 16),
            "not_self": ((s < t) & (s % 2 == 0)) | ((t == 0) & (s == 0)),
            "causal": s <= t}[kind]
    return jnp.asarray(np.broadcast_to(keep, (len(lengths), T, T)),
                       jnp.int8)


@pytest.mark.parametrize("kind", ["late", "not_self", "topk", "causal"])
@pytest.mark.parametrize("group", [8, 1], ids=["8-heads-a-key-head",
                                               "a-key-head-a-head"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_forward_entry_under_a_selection_mask(dtype, group, kind):
    """`flash_attention_forward(keep=)` (Keye-VL-2.0's prefill) against
    the dense softmax over the marked keys, in blocks of 128 so that a
    query block walks several key blocks, each with its window of the
    mask: rows of 1, 127, 128, 129, all and no positions; a key head's
    eight query heads in one step and a head of its own; a query whose
    first visited sub-tiles hold none of its selection and one that
    does not select itself (their sums take exp(0) of what they do not
    see and are wiped by the first key they do); the selection kernel's
    own top-k mask, which marks keys for the padding queries of a row's
    last block too.  Queries at and past a row's length come out zero."""
    T, D, lengths = KEPT_T, 128, KEPT_LENGTHS
    keys = jax.random.split(jax.random.key(group + len(kind)), 3)
    Hk = 1 if group > 1 else 2
    q, k, v = (jax.random.normal(kk, (len(lengths), h, T, D), dtype)
               for kk, h in zip(keys, (group * Hk, Hk, Hk)))
    keep = _kept_mask(kind, lengths, T)
    out = jax.jit(lambda q, k, v, n, m: pa.flash_attention_forward(
        q, k, v, n, scale=D ** -0.5, keep=m, block_q=128, block_k=128))(
            q, k, v, jnp.asarray(lengths, jnp.int32), keep)
    assert out.dtype == dtype and out.shape == q.shape
    ref = np.asarray(_masked_ref(q, k, v, D ** -0.5,
                                 (keep != 0)[:, None]))
    out = np.asarray(out, np.float32)
    rtol, atol = TOL[dtype]["fwd"]
    if dtype == BF16:
        # a softmax over 16 keys rounds few, large p to 8 bits: a unit
        # in the last place of a value near 1
        atol = 2.0 ** -7
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(out[b, :, :n], ref[b, :, :n], rtol=rtol,
                                   atol=atol, err_msg=f"row of {n}")
        assert not out[b, :, n:].any(), f"row of {n}"


def test_what_the_entries_refuse():
    q, k, v = _wide_inputs(32, 16, F32, T=128, rows=1)
    with pytest.raises(ValueError, match="one width"):
        pa.flash_attention(q, k, v, causal=True)
    with pytest.raises(ValueError, match="must divide"):
        pa.flash_attention_forward(q, k, v, scale=1.0, block_q=96)
    # a head's width cannot be told from the zeros it arrives padded with
    with pytest.raises(TypeError, match="scale"):
        pa.flash_attention_forward(q, k, v)
    with pytest.raises(ValueError, match="own position"):
        pa.flash_attention_forward(q, k, v, scale=1.0, window=0)
    with pytest.raises(ValueError, match="do not divide"):
        pa.flash_attention_forward(jnp.concatenate([q, q[:, :1]], axis=1),
                                   k, v, scale=1.0)
    # a mask is a row of keys for every query of every batch row, int8
    for keep in (jnp.ones((1, 128, 64), jnp.int8),
                 jnp.ones((1, 128, 128), bool)):
        with pytest.raises(ValueError, match="keep is int8"):
            pa.flash_attention_forward(q, k, v, scale=1.0, keep=keep)
    with pytest.raises(ValueError, match="its own band"):
        pa.flash_attention_forward(q, k, v, scale=1.0, window=8,
                                   keep=jnp.ones((1, 128, 128), jnp.int8))


def _pallas_calls(jaxpr, found):
    return _eqns(jaxpr, "pallas_call", found)


@pytest.mark.parametrize("shape,grid", [((4, 16, 1024, 64), (64, 1, 1)),
                                        ((2, 8, 4096, 128), (16, 4, 4))],
                         ids=["train-cell", "2x8x4096x128"])
def test_equal_widths_and_no_length_lower_to_the_parents_grid(shape, grid):
    """Training's call (equal widths, no length) has the grid, the
    block shapes and the sub-tiles it had before the forward body took
    a value width and a length: blocks of 1,024, sub-tiles of 512, no
    scalar prefetch, and index maps that are the grid indices as they
    are (no clamp)."""
    B, H, T, D = shape
    assert pa._block_sizes(T, D, BF16, "fwd") == (1024, 1024)
    assert pa._block_sizes(T, D, BF16, "fwd", D) == (1024, 1024)
    assert pa._sub_tiles(1024, 1024) == (512, 512)
    x = jax.ShapeDtypeStruct(shape, BF16)
    fwd, = _pallas_calls(jax.make_jaxpr(lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True))(x, x, x).jaxpr, [])
    mapping = fwd.params["grid_mapping"]
    assert mapping.grid == grid
    assert mapping.num_index_operands == 0
    blocks = [tuple(int(getattr(d, "block_size", d) or 1)
                    for d in m.block_shape) for m in mapping.block_mappings]
    assert blocks == [(1, 1024, D)] * 4 + [(1, 8, 1024)]      # q k v o lse
    for m in mapping.block_mappings:
        assert not m.index_map_jaxpr.jaxpr.eqns     # (b, i, 0), (b, j, 0)


def test_the_forward_entry_is_a_grid_over_query_blocks():
    """Kimi-K2.6's prefill shape: 1,024 steps a call (64 heads x 16
    query blocks of 1,024) where the grid over key blocks too would be
    16,384; the lengths are the scalar prefetch, q and o come in blocks
    and k and v stay where they are; keys of 192 arrive 256 wide."""
    H, T = 64, 16384
    call, = _pallas_calls(jax.make_jaxpr(
        lambda q, k, v, n: pa.flash_attention_forward(q, k, v, n, scale=1.0))(
            *(jax.ShapeDtypeStruct((1, H, T, d), BF16) for d in (192, 192,
                                                                 128)),
            jax.ShapeDtypeStruct((1,), jnp.int32)).jaxpr, [])
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (H, T // 1024)
    assert mapping.num_index_operands == 1
    assert [v.aval.shape for v in call.invars] == [
        (1,), (H, T, 256), (H, T, 256), (H, T, 128)]
    assert pa._block_sizes(T, 256, BF16, "fwd", 128) == (1024, 1024)


def _eqns(jaxpr, primitive, found):
    """Every equation of that primitive (None: of any) in a jaxpr and
    the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if primitive is None or eqn.primitive.name == primitive:
            found.append(eqn)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _eqns(sub, primitive, found)
    return found


def _products(jaxpr, found):
    return _eqns(jaxpr, "dot_general", found)


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bfloat16", "float32"])
def test_products_take_the_inputs_dtype_and_accumulate_in_float32(dtype):
    """No operand is cast up in front of a product: bfloat16 inputs go
    into the MXU as bfloat16, float32 inputs as float32 (the type
    follows the input's, it is not forced down), two products forward
    and five backward, each accumulated in float32."""
    q, k, v, w = _inputs(256, 64, dtype)

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, causal=True).astype(F32))

    for fn, count in ((lambda q, k, v: pa.flash_attention(
            q, k, v, causal=True), 2), (jax.grad(loss, (0, 1, 2)), 2 + 5)):
        dots = _products(jax.make_jaxpr(fn)(q, k, v).jaxpr, [])
        # the causal walk traces a sub-tile twice: unmasked and masked
        assert len(dots) == 2 * count
        for eqn in dots:
            assert [x.aval.dtype for x in eqn.invars] == [dtype, dtype]
            assert eqn.outvars[0].aval.dtype == F32
            assert eqn.params["preferred_element_type"] == F32


@pytest.mark.parametrize("T,D,causal", [(1024, 64, True), (512, 64, False),
                                        (384, 64, True), (512, 128, True)])
def test_bfloat16_products_equal_float32_fed_ones(monkeypatch, T, D,
                                                  causal):
    """Feeding the products bfloat16 lowers no precision: a product of
    two bfloat16 values is exact in the float32 accumulator, so the
    kernels give what they give when every operand is cast to float32
    in front of its product (the parent's feed), up to the order of
    float32 additions.  The float32 logsumexp agrees to float32
    rounding.  The bfloat16 results are equal but for the few values (a
    probability or a ds that fell on the other side of a rounding
    boundary moves its sums by a bfloat16 unit of one term): under one
    in a hundred, none by more than a unit in the last place of the
    tensor's largest value."""
    q, k, v, w = _inputs(T, D, BF16, seed=1)
    scale = D ** -0.5

    def run():
        out, lse = pa._flash_call(q, k, v, causal, scale)
        return (out, lse) + pa._flash_bwd_call(q, k, v, out, lse, w,
                                               causal, scale)

    fed_bf16 = run()
    dot = pa._dot
    monkeypatch.setattr(pa, "_dot", lambda a, b, dims: dot(
        a.astype(F32), b.astype(F32), dims))
    fed_f32 = run()
    np.testing.assert_allclose(np.asarray(fed_bf16[1]),
                               np.asarray(fed_f32[1]), rtol=1e-6, atol=1e-6)
    for got, want in zip(fed_bf16[:1] + fed_bf16[2:],
                         fed_f32[:1] + fed_f32[2:]):
        assert got.dtype == want.dtype == BF16
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert (got != want).mean() < 0.01
        assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max()


@pytest.mark.parametrize("D", [64, 128])
def test_block_sizes_divide_every_aligned_length(D):
    """For every lane-aligned length up to 8,192, both kernels and both
    operand types: blocks that divide the length, lane-aligned and no
    larger than the rule's cap, sub-tiles that divide the blocks, and a
    grid step's VMEM need inside the budget the rule reckons with."""
    for T in range(pa._LANE, 8192 + 1, pa._LANE):
        for dtype in (BF16, F32):
            for kernel in ("fwd", "bwd"):
                bq, bk = pa._block_sizes(T, D, dtype, kernel)
                assert T % bq == 0 and T % bk == 0, (T, bq, bk)
                assert bq % pa._LANE == 0 and bk % pa._LANE == 0
                assert max(bq, bk) <= pa._MAX_BLOCK
                sq, sk = pa._sub_tiles(bq, bk)
                assert bq % sq == 0 and bk % sk == 0
                assert sq % pa._LANE == 0 and sk % pa._LANE == 0
                assert pa._vmem_bytes(T, D, dtype, kernel, bq,
                                      bk)[0] <= pa._VMEM_BUDGET
    # the largest block wins: the cell's length is one block a head
    assert pa._block_sizes(1024, 64, BF16, "fwd") == (1024, 1024)
    assert pa._block_sizes(1024, 64, BF16, "bwd") == (1024, 1024)
    assert pa._block_sizes(384, 64, BF16, "fwd") == (384, 384)
    assert pa._block_sizes(1152, 64, BF16, "fwd") == (384, 384)
    # off the lane grid (interpret mode only): the length itself
    assert pa._block_sizes(192, 16, F32, "fwd") == (192, 192)
    assert pa._sub_tiles(192, 192) == (192, 192)

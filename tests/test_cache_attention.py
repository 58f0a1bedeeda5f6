"""`ops/cache_attention.py`: the per-row kernel (interpreted here)
against the plain path, the masked contraction over the whole window.
The kernel's compiles for a described v5e are in tests/test_cache_write.py
(one worker describes the chip)."""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import cache_attention

# (K, G, D, Dv, W, a selection mask, a sink): the three families' heads
# at their real widths.  GPT's two heads of 64 are worked side by side;
# MiMo-V2's keys are 192 wide beside values of 128; Keye-VL-2.0 reads
# under its selection.
SHAPES = {
    "gpt": (16, 1, 64, 64, 384, False, False),
    "mimo_full": (4, 16, 192, 128, 256, False, True),
    "keye": (4, 8, 128, 128, 512, True, False),
    "keye_sunk": (4, 8, 128, 128, 256, True, True),
    "small_heads": (4, 2, 16, 16, 256, False, False),   # four side by side
    # Jamba2-3B's attention layers: 20 query heads over one key head of
    # 128 (a group that is no power of two: 32 rows of a tile, 12 of
    # them padding), a window of 704 in stacks of 768 slots
    "jamba": (1, 20, 128, 128, 768, False, False),
}
# lengths that differ by row and sit on the edges of a 128-lane block;
# "dead": rows that want no token (length 0: their first block, all of
# it masked) among rows that do and at both ends, so that the copy a
# row's last block starts for the next row is awaited by an empty row,
# and an empty row's one block starts the copy of a row of two (the
# latent shapes take the first three and a whole window)
LENGTHS = {"edges": [1, 127, 128, 129], "whole": [None, 0, 130, 256],
           "dead": [0, 130, 0, 0, 256, 0]}
TOL = {"float32": 2e-6, "bfloat16": 4e-3}


def _case(name, dtype, lengths, seed=0, L=3, lanes=128):
    """(``name``: one of SHAPES, or such a tuple.)  Stacks whose every position past a row's last block is NaN, and
    what lies between the row's length and its block's end is large:
    the first must never be read, the second never weigh."""
    K, G, D, Dv, W, masked, sunk = SHAPES.get(name, name)
    B = len(lengths)
    n = np.asarray([W if x is None else x for x in lengths], np.int32)
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, K, G, D) * D ** -0.5, dtype)
    ck, cv = rng.randn(L, B, K, D, W), rng.randn(L, B, K, Dv, W)
    at = np.arange(W)[None, :]
    ends = np.maximum(-(-n // lanes), 1) * lanes
    for c in (ck, cv):
        np.putmask(c, np.broadcast_to(
            (at >= n[:, None])[None, :, None, None, :], c.shape), 50.0)
        np.putmask(c, np.broadcast_to(
            (at >= ends[:, None])[None, :, None, None, :], c.shape), np.nan)
    mask = jnp.asarray(rng.rand(B, W) < 0.3) if masked else None
    sink = jnp.asarray(rng.randn(K, G), jnp.float32) if sunk else None
    return (q, jnp.asarray(ck, dtype), jnp.asarray(cv, dtype),
            jnp.asarray(n), mask, sink)


def _plain(q, ck, cv, l, n, mask, sink):
    """The plain path over a layer with the never-read positions made
    finite: it multiplies them by a weight of zero."""
    clean = lambda c: jnp.nan_to_num(c[l].astype(jnp.float32), nan=0.0
                                     ).astype(c.dtype)
    return cache_attention._attend_xla(q, clean(ck), clean(cv), n, mask,
                                       sink)


@pytest.mark.parametrize("lengths", list(LENGTHS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_kernel_gives_what_the_plain_path_gives(name, dtype, lengths):
    """Row b reads the lane blocks that hold its first ``lengths[b]``
    positions and nothing beyond (NaN there would reach the output),
    what its last block holds past the length does not weigh, a row that
    sees nothing (length 0, or a mask that keeps none of its positions)
    comes out zero, and the result is the plain path's up to
    the rounding of a running softmax."""
    q, ck, cv, n, mask, sink = _case(name, jnp.dtype(dtype), LENGTHS[lengths])
    l = 1
    got = jax.jit(lambda *a: cache_attention._attend_kernel(
        *a, 128, interpret=True))(q, ck, cv, l, n, mask, sink)
    want = _plain(q, ck, cv, l, n, mask, sink)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL[dtype], rtol=TOL[dtype])
    for b, held in enumerate(np.asarray(n)):
        if held == 0:
            assert not np.asarray(got)[b].any()


@pytest.mark.parametrize("lanes", [128, 256])
def test_blocks_of_more_than_one_lane_block(lanes):
    """The block a step brings in is chosen from the stacks' shape;
    whatever it is, the result stands."""
    q, ck, cv, n, mask, sink = _case("keye", jnp.bfloat16,
                                     [1, 300, None, 129], lanes=lanes)
    got = jax.jit(lambda *a: cache_attention._attend_kernel(
        *a, lanes, interpret=True))(q, ck, cv, 2, n, mask, sink)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain(q, ck, cv, 2, n, mask, sink)),
                               atol=4e-3, rtol=4e-3)


def test_a_traced_layer_index_reads_that_layer():
    """GPT's and Keye's layer loops hand the kernel their scan index."""
    q, ck, cv, n, mask, sink = _case("gpt", jnp.bfloat16, [5, 130, 1, 384])

    def walk(attend):
        return jax.lax.scan(lambda _, l: (None, attend(l)), None,
                            jnp.arange(ck.shape[0], dtype=jnp.int32))[1]

    got = jax.jit(lambda: walk(lambda l: cache_attention._attend_kernel(
        q, ck, cv, l, n, None, None, 128, interpret=True)))()
    for l in range(ck.shape[0]):
        np.testing.assert_allclose(
            np.asarray(got[l]), np.asarray(_plain(q, ck, cv, l, n, None, None)),
            atol=4e-3, rtol=4e-3)


@pytest.mark.parametrize("K,D,Dv,r", [(16, 64, 64, 2), (4, 192, 128, 1),
                                      (4, 128, 128, 1), (4, 16, 16, 4),
                                      (3, 32, 32, 3), (1, 64, 64, 1)])
def test_heads_side_by_side_fill_a_tile(K, D, Dv, r):
    assert cache_attention.heads_a_tile(K, D, Dv) == r


@pytest.mark.parametrize("K,D,Dv,W,lanes", [
    (16, 64, 64, 1024, 128), (4, 128, 128, 16384, 512),
    (4, 192, 128, 2048, 256), (16, 64, 64, 384, 128),
    (8, 192, 128, 128, 128), (1, 64, 64, 65536, 4096),
    (1, 128, 128, 768, 256)])
def test_block_lanes_follow_the_stacks(K, D, Dv, W, lanes):
    """Longer windows and narrower positions take larger blocks (the
    three cells' stacks first: the sizes measured best on the v5e), a
    divisor of W, at least one lane block."""
    sds = lambda d: jax.ShapeDtypeStruct((2, 4, K, d, W), jnp.bfloat16)
    assert cache_attention.block_lanes(sds(D), sds(Dv)) == lanes


@pytest.mark.parametrize("W,mesh,tpu,path", [
    (256, None, True, "kernel"), (256, "a mesh", True, "xla"),
    (256, None, False, "xla"), (128, None, True, "xla"),
    (16, None, True, "xla")])
def test_attend_rows_picks_its_path_on_what_it_sees(monkeypatch, W, mesh,
                                                    tpu, path):
    """The kernel where the platform is a TPU, no mesh is given and the
    window is more than one lane block (a ring of 128 is one block: there
    is nothing to stop short of); the plain path everywhere else.  One
    query position a row is the op's signature: a prefill block never
    calls it.  The tally is told the path and the block, once a call."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(4, 2, 2, 16), jnp.float32)
    ck = jnp.asarray(rng.randn(2, 4, 2, 16, W), jnp.float32)
    cv = jnp.asarray(rng.randn(2, 4, 2, 8, W), jnp.float32)
    n = jnp.asarray([1, 5, W, 3], jnp.int32)
    monkeypatch.setattr(cache_attention, "_on_tpu", lambda: tpu)
    took = []
    monkeypatch.setattr(
        cache_attention, "_attend_kernel",
        lambda q, ck, cv, l, n, mask, sink, lanes, leading=None:
        took.append(lanes)
        or jnp.zeros(q.shape[:3] + cv.shape[3:4]))
    tally = collections.Counter()
    out = cache_attention.attend_rows(q, ck, cv, 1, n, mesh=mesh, tally=tally)
    assert out.shape == (4, 2, 2, 8)
    if path == "kernel":
        lanes = cache_attention.block_lanes(ck, cv)
        assert took == [lanes] and dict(tally) == {("kernel", W, lanes): 1}
    else:
        assert took == [] and dict(tally) == {("xla", W, W): 1}
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(cache_attention._attend_xla(q, ck[1], cv[1], n, None,
                                                   None)), atol=1e-6)


# -- values that are the leading rows of their keys -----------------------------
#
# Kimi-K2's latent stack: one head (K 1) that 64 query heads share, 576
# rows a position of which the first 512 are also the values (4.5 lane
# tiles: not a multiple of 128), and a small one of the same build.
LATENT = {"kimi_latent": (64, 576, 512, 256), "small": (4, 24, 16, 384)}


@pytest.mark.parametrize("lengths", list(LENGTHS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(LATENT))
def test_values_that_are_the_keys_leading_rows(name, dtype, lengths):
    """No stack of values: the kernel brings a block in once and takes
    its first ``Dv`` rows for values; the plain path slices the same
    rows.  Both equal the plain path given those rows as a stack of
    their own."""
    G, D, Dv, W = LATENT[name]
    q, ck, _, n, _, _ = _case((1, G, D, D, W, False, False),
                              jnp.dtype(dtype), LENGTHS[lengths][:3] + [W])
    l = 2
    got = jax.jit(lambda q, ck, n: cache_attention._attend_kernel(
        q, ck, None, l, n, None, None, 128, interpret=True, leading=Dv))(
            q, ck, n)
    want = _plain(q, ck, ck[:, :, :, :Dv], l, n, None, None)
    assert got.shape == want.shape == (4, 1, G, Dv)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL[dtype], rtol=TOL[dtype])
    clean = jnp.nan_to_num(ck.astype(jnp.float32)).astype(ck.dtype)
    tally = collections.Counter()
    plain = cache_attention.attend_rows(q, clean, None, l, n, leading=Dv,
                                        tally=tally)
    assert dict(tally) == {("xla", W, W): 1}
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(want))


def test_block_lanes_count_a_shared_position_once():
    """8 x 16,384 positions of 576 rows read once: blocks of 1,024 (a
    second stack of 512 rows would make them 512)."""
    sds = lambda d: jax.ShapeDtypeStruct((5, 8, 1, d, 16384), jnp.bfloat16)
    assert cache_attention.block_lanes(sds(576), None) == 1024
    assert cache_attention.block_lanes(sds(576), sds(512)) == 512


def test_the_plain_path_is_the_softmax_it_says():
    """Said without the op: a row's softmax over its first ``n``
    positions the mask keeps, the sink one more key of no value."""
    q, ck, cv, n, mask, sink = _case("keye_sunk", jnp.float32, [3, 40, None, 0])
    want = np.zeros(q.shape[:3] + cv.shape[3:4], np.float32)
    ck0, cv0 = np.nan_to_num(np.asarray(ck[0])), np.nan_to_num(np.asarray(cv[0]))
    for b in range(q.shape[0]):
        keep = (np.arange(ck.shape[-1]) < int(n[b])) & np.asarray(mask[b])
        for k in range(q.shape[1]):
            s = np.asarray(q[b, k]) @ ck0[b, k][:, keep]           # (G, n)
            s = np.concatenate([s, np.asarray(sink[k])[:, None]], axis=1)
            p = np.exp(s - s.max(axis=1, keepdims=True))
            p = p / p.sum(axis=1, keepdims=True)
            want[b, k] = p[:, :-1] @ cv0[b, k][:, keep].T
    want[np.asarray(n) == 0] = 0.0
    got = _plain(q, ck, cv, 0, n, mask, sink)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)

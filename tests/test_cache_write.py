"""`ops/cache_write.py`: the in-place kernel (interpreted here) against
the plain path, one ``dynamic_update_slice`` a row.  Also this file's:
every compile for a described v5e (this kernel, `ops/cache_attention.py`'s,
`ops/indexed_attention.py`'s and `ops/pallas_attention.py`'s), so that one
worker loads the TPU's library."""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import cache_write

# (L, B, the stacks' K, W, the stacks' D): GPT's pair and MiMo's two
# kinds, a ring among them (W = the window, written at ``pos % W``), and
# Keye-VL-2.0's three stacks of differing head count and width (keys and
# values beside the indexer's one narrow head), at test widths
SHAPES = {
    "gpt": (3, 4, (4, 4), 384, (16, 16)),
    "mimo_full": (2, 4, (2, 2), 256, (24, 16)),
    "mimo_ring": (3, 4, (4, 4), 128, (24, 16)),
    "narrow": (3, 4, (2, 2), 16, (8, 8)),   # a window under one lane block
    "keye": (3, 4, (2, 2, 1), 256, (16, 16, 8)),
    "one_head_beside_four": (2, 4, (4, 1), 128, (128, 64)),
    # Kimi-K2's latent stack: one stack, no heads, 4.5 lane tiles wide
    "kimi_latent": (2, 4, (1,), 256, (576,)),
}


def _case(name, dtype, seed=0):
    L, B, Ks, W, Ds = SHAPES[name]
    rng = np.random.RandomState(seed)
    stacks = tuple(jnp.asarray(rng.randn(L, B, K, D, W), dtype)
                   for K, D in zip(Ks, Ds))
    news = tuple(jnp.asarray(rng.randn(B, K, D, 1), dtype)
                 for K, D in zip(Ks, Ds))
    return stacks, news, W


def _bits(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize("layer", ["first", "middle", "last"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_kernel_writes_what_the_rows_path_writes(name, dtype, layer):
    """Rows at different positions, a pad row at 0 among them: the lane
    block's first and last lane, the next block's first, the window's
    last slot, and one past the window (clipped to ``W - 1``, where
    dynamic_update_slice's clamp puts it).  The kernel's stacks equal
    the plain path's bit for bit, and so every slot that no row wrote is
    what it was."""
    stacks, news, W = _case(name, jnp.dtype(dtype))
    L = stacks[0].shape[0]
    l = {"first": 0, "middle": L // 2, "last": L - 1}[layer]
    for at in ([0, 127, 128, W - 1], [W + 5, 1, 0, W]):
        pos = jnp.asarray(at, jnp.int32)
        starts = pos % W if name == "mimo_ring" else pos
        got = jax.jit(lambda s, n, p: cache_write._write_kernel(
            s, n, l, p, interpret=True))(stacks, news, starts)
        want = tuple(cache_write._write_by_rows(c, n, l, starts)
                     for c, n in zip(stacks, news))
        for g, w, c, n in zip(got, want, stacks, news):
            assert g.dtype == c.dtype and g.shape == c.shape
            np.testing.assert_array_equal(_bits(g), _bits(w))
            # and said without the plain path: layer l, row b, one slot
            slot = np.clip(np.asarray(starts), 0, W - 1)
            touched = np.zeros(c.shape, bool)
            for b, s in enumerate(slot):
                touched[l, b, :, :, s] = True
                np.testing.assert_array_equal(
                    _bits(g)[l, b, :, :, s], _bits(n)[b, :, :, 0])
            np.testing.assert_array_equal(_bits(g)[~touched],
                                          _bits(c)[~touched])


# which of four rows still want a token
LIVE = {
    "all": [True, True, True, True],
    "none": [False, False, False, False],
    "leading_dead": [False, False, True, True],
    "trailing_dead": [True, True, False, False],
    "alternating": [True, False, True, False],
    "one_live": [False, False, True, False],
}


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("name", list(SHAPES))
def test_a_row_that_is_not_live_writes_nothing(monkeypatch, name, live):
    """A decode step's write told which rows still want a token: the
    kernel's stacks equal the rows path's bit for bit, as the CPU
    builds that path (a condition a row) and as a TPU does (a select
    between the new value and the position as it was); the stacks of a
    row that is not live are the input's in every layer, whatever its
    new values hold (NaN here) and wherever it stands; a live row's are
    what the write told of no row gives it."""
    stacks, news, W = _case(name, jnp.bfloat16, seed=2)
    keep = np.asarray(LIVE[live])
    news = tuple(jnp.where(keep[:, None, None, None], n, jnp.nan)
                 for n in news)
    pos = jnp.asarray([W + 5, 127, 128 % W, W - 1], jnp.int32)
    starts = pos % W if name == "mimo_ring" else pos
    l = stacks[0].shape[0] - 1
    got = jax.jit(lambda s, n, p, v: cache_write._write_kernel(
        s, n, l, p, v, interpret=True))(stacks, news, starts,
                                        jnp.asarray(keep))

    def by_rows():
        return tuple(cache_write._write_by_rows(c, n, l, starts, 0,
                                                jnp.asarray(keep))
                     for c, n in zip(stacks, news))

    want = by_rows()
    monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
    selected = by_rows()
    every = cache_write._write_kernel(stacks, news, l, starts,
                                      interpret=True)
    for g, w, t, c, e in zip(got, want, selected, stacks, every):
        assert g.dtype == c.dtype and g.shape == c.shape
        g, w, t, c, e = _bits(g), _bits(w), _bits(t), _bits(c), _bits(e)
        assert not np.isnan(g).any()
        np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(t, w)
        np.testing.assert_array_equal(g[:, ~keep], c[:, ~keep])
        np.testing.assert_array_equal(g[:, keep], e[:, keep])


@pytest.mark.parametrize("path", ["kernel", "rows"])
@pytest.mark.parametrize("S", [2, 8])
def test_live_is_a_decode_steps(monkeypatch, path, S):
    """A prefill has no row to skip: ``live`` with a block of more than
    one position is refused, on either path."""
    stacks, news, W = _case("narrow", jnp.float32)
    news = tuple(jnp.repeat(n, S, axis=-1) for n in news)
    monkeypatch.setattr(cache_write, "_on_tpu", lambda: path == "kernel")
    with pytest.raises(ValueError, match="decode step"):
        cache_write.write_rows(stacks, news, 0, jnp.zeros(4, jnp.int32),
                               live=jnp.ones(4, bool))


def _pallas_call(fn, *args):
    eqn, = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"]
    return eqn


@pytest.mark.parametrize("name", ["keye", "gpt", "kimi_latent"])
def test_told_of_no_row_it_is_the_same_kernel(name):
    """One kernel whether the call is told the live rows or not: one
    invocation a call, the layer and the positions its two prefetched
    scalars, the stacks whole and aliased to their outputs, the new
    rows as the program leaves them, and the same body.  What differs
    is the positions handed it: -1 where a row is not live."""
    stacks, news, W = _case(name, jnp.bfloat16)
    pos = jnp.asarray([5, 130, 0, 255], jnp.int32)
    B, n = pos.shape[0], len(stacks)

    def call(*live):
        return _pallas_call(lambda s, n, p: cache_write._write_kernel(
            s, n, 1, p, *live, interpret=True), stacks, news, pos)

    plain, told = call(), call(jnp.asarray([True, False, True, False]))
    for eqn in (plain, told):
        mapping = eqn.params["grid_mapping"]
        assert mapping.grid == (1,) and mapping.num_index_operands == 2
        assert [tuple(v.aval.shape) for v in eqn.invars] == \
            [(1,), (B,)] + [tuple(c.shape) for c in stacks] + \
            [tuple(x.shape[:3]) for x in news]
        assert dict(eqn.params["input_output_aliases"]) == {
            2 + i: i for i in range(n)}
    assert str(plain.params["jaxpr"]) == str(told.params["jaxpr"])
    assert str(plain.params["grid_mapping"]) == \
        str(told.params["grid_mapping"])
    every = cache_write._write_kernel(stacks, news, 1, pos, interpret=True)
    ones = cache_write._write_kernel(stacks, news, 1, pos,
                                     jnp.ones(B, bool), interpret=True)
    for e, o in zip(every, ones):
        np.testing.assert_array_equal(_bits(e), _bits(o))


def test_a_traced_layer_index_writes_that_layer():
    """GPT's layer loop hands the kernel its scan index."""
    stacks, news, W = _case("gpt", jnp.bfloat16, seed=1)
    pos = jnp.asarray([5, 130, 0, 383], jnp.int32)

    def walk(write):
        def body(carry, l):
            return write(carry, l), None
        return jax.lax.scan(body, stacks,
                            jnp.arange(stacks[0].shape[0], dtype=jnp.int32))[0]

    got = walk(lambda s, l: cache_write._write_kernel(s, news, l, pos,
                                                      interpret=True))
    want = walk(lambda s, l: tuple(
        cache_write._write_by_rows(c, n, l, pos) for c, n in zip(s, news)))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("S,mesh,tpu,live,path", [
    (1, None, True, None, "kernel"), (8, None, True, None, "rows"),
    (1, "a mesh", True, None, "rows"), (1, None, False, None, "rows"),
    (1, None, True, "alternating", "kernel"),
    (1, "a mesh", True, "alternating", "rows"),
    (1, None, False, "one_live", "rows")])
def test_write_rows_picks_its_path_on_what_it_sees(monkeypatch, S, mesh,
                                                   tpu, live, path):
    """The kernel where the block is one position, the platform a TPU
    and no mesh is given; one write a row everywhere else.  The tally
    is told which, once a row and a stack, and which of the kernel's
    were handed ``live``; the kernel is handed it as it came."""
    stacks, news, W = _case("narrow", jnp.float32)
    news = tuple(jnp.repeat(n, S, axis=-1) for n in news)
    pos = jnp.asarray([0, 3, 7, 2], jnp.int32)
    keep = np.asarray(LIVE[live or "all"])
    monkeypatch.setattr(cache_write, "_on_tpu", lambda: tpu)
    took = []
    monkeypatch.setattr(
        cache_write, "_write_kernel",
        lambda s, n, l, p, live: took.append(live) or s)
    tally = collections.Counter()
    out = cache_write.write_rows(
        stacks, news, 1, pos, mesh=mesh, tally=tally,
        live=None if live is None else jnp.asarray(keep))
    assert len(out) == len(stacks)
    assert len(took) == (path == "kernel")
    want = {path: 4 * len(stacks)}
    if path == "kernel":
        assert (took[0] is None) == (live is None)
        if live is not None:
            want["kernel_live"] = 4 * len(stacks)
    assert dict(tally) == want
    if path == "rows":
        for g, c, n in zip(out, stacks, news):
            for b, p in enumerate(np.asarray(pos)):
                np.testing.assert_array_equal(
                    _bits(g)[1, b, :, :, p:p + S],
                    _bits(n)[b] if keep[b] else _bits(c)[1, b, :, :, p:p + S])
            assert (_bits(g)[0] == _bits(c)[0]).all()


@pytest.mark.parametrize("name,row", [("kimi_latent", 2), ("keye", 1),
                                      ("kimi_latent", None)])
def test_a_prefill_writes_its_rows_at_a_row_offset(name, row):
    """A prefill that works its rows off a few at a time: two rows'
    blocks of 5 positions go to rows ``row, row + 1`` of the stacks
    (traced, as a loop over row chunks hands it over; None: from the
    first), each at its own position, and nothing else is touched."""
    stacks, _, W = _case(name, jnp.float32)
    rng = np.random.RandomState(1)
    news = tuple(jnp.asarray(rng.randn(2, c.shape[2], c.shape[3], 5),
                             c.dtype) for c in stacks)
    pos = jnp.asarray([0, 9], jnp.int32)
    tally = collections.Counter()
    if row is None:
        got = cache_write.write_rows(stacks, news, 1, pos, tally=tally)
    else:
        got = jax.jit(lambda s, n, p, r: cache_write.write_rows(
            s, n, 1, p, tally=tally, row=r))(stacks, news, pos,
                                             jnp.int32(row))
    assert dict(tally) == {"rows": 2 * len(stacks)}
    for g, c, n in zip(got, stacks, news):
        want = np.array(_bits(c))
        for b, p in enumerate(np.asarray(pos)):
            want[1, (row or 0) + b, :, :, p:p + 5] = _bits(n)[b]
        np.testing.assert_array_equal(_bits(g), want)


def test_a_row_offset_keeps_a_decode_write_off_the_kernel(monkeypatch):
    """The kernel's grid is the stacks' rows from the first."""
    stacks, news, _ = _case("kimi_latent", jnp.float32)
    monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
    tally = collections.Counter()
    cache_write.write_rows(stacks, tuple(n[:2] for n in news), 0,
                           jnp.asarray([3, 4], jnp.int32), tally=tally,
                           row=1)
    assert dict(tally) == {"rows": 2}


# -- compiled for the chip, without the chip -----------------------------------

@pytest.fixture(scope="module")
def v5e():
    """Four described v5e chips: Mosaic and XLA:TPU compile for them
    here, nothing runs (only this file's worker loads the TPU's
    library)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:      # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e[0])


@pytest.mark.parametrize("told", [False, True], ids=["every_row", "live"])
def test_the_rows_path_compiles_for_four_v5es_in_place(v5e, monkeypatch,
                                                       told):
    """A `tp` engine's decode write (GPT-2 medium's stacks, their heads
    over four chips, the layout pinned shard by shard as
    `gluon/model_zoo/gpt.py` pins it): the rows path, told of the live
    rows and not, compiles for the chips with the stacks aliased to
    their outputs and no temporary of a layer's size, so no chip copies
    its shard of a stack for a row that is not live."""
    from jax.experimental.layout import with_layout_constraint
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu import serving
    from mxnet_tpu.parallel.sharding import serving_cache_sharding

    monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
    L, B, K, D, W = 24, 16, 16, 64, 1024
    mesh = Mesh(np.array(v5e).reshape(4), ("tp",))
    heads = serving_cache_sharding(mesh)

    def sds(shape, dtype=jnp.bfloat16, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    stack = sds((L, B, K, D, W), spec=heads.spec)
    pin = jax.jit(lambda x: x).lower(stack).compile(
        ).input_formats[0][0].layout
    keep_layout = jax.shard_map(
        lambda c: with_layout_constraint(c, pin), mesh=mesh,
        in_specs=heads.spec, out_specs=heads.spec)
    tally = collections.Counter()

    def step(stacks, news, pos, live):
        def body(c, l):
            out = cache_write.write_rows(
                c, news, l, pos, mesh=mesh, tally=tally,
                live=live if told else None)
            return tuple(keep_layout(o) for o in out), None
        return jax.lax.scan(body, stacks, jnp.arange(L, dtype=jnp.int32))[0]

    new = sds((B, K, D, 1), spec=P(None, "tp"))
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        (stack, stack), (new, new), sds((B,), jnp.int32),
        sds((B,), jnp.bool_)).compile()
    assert dict(tally) == {"rows": 2 * B}
    text = compiled.as_text()
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    assert "{0}: (0, {}" in alias and "{1}: (1, {}" in alias, alias
    layer = B * K // 4 * D * W * 2      # a chip's shard of one layer
    assert serving.whole_layer_ops(text, layer) == []
    assert compiled.memory_analysis().temp_size_in_bytes < layer


@pytest.mark.parametrize("name,L,B,Ks,Ds,W", [
    ("gpt2_medium", 24, 16, (16, 16), (64, 64), 1024),
    ("mimo_full", 2, 64, (4, 4), (192, 128), 2048),
    ("mimo_ring", 5, 64, (8, 8), (192, 128), 128),
    ("keye_vl2", 6, 16, (4, 4, 1), (128, 128, 64), 16384),
    ("kimi_k2", 5, 8, (1,), (576,), 16384),
    ("ouro", 6, 8, (16, 16), (128, 128), 512),
    ("cmda_ring", 3, 8, (8, 8), (128, 128), 4096)])
@pytest.mark.parametrize("told", [False, True], ids=["every_row", "live"])
def test_kernel_compiles_for_a_v5e_in_place(one_chip, told, name, L, B, Ks,
                                            Ds, W):
    """At the cells' real widths Mosaic takes the kernel (interpret mode
    cannot say), told of the live rows and not, the stacks are aliased
    to their outputs, and the compiled program copies no layer of them:
    it holds no temporary of a stack's size."""
    from mxnet_tpu import serving

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(stacks, news, pos, live):
        def body(c, l):
            return cache_write._write_kernel(
                c, news, l, pos, live if told else None), None
        return jax.lax.scan(body, stacks, jnp.arange(L, dtype=jnp.int32))[0]

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        tuple(sds((L, B, K, D, W)) for K, D in zip(Ks, Ds)),
        tuple(sds((B, K, D, 1)) for K, D in zip(Ks, Ds)),
        sds((B,), jnp.int32), sds((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(len(Ds)):
        # a single stack is the program's whole output, not a tuple's part
        out = f"{{{i}}}" if len(Ds) > 1 else "{}"
        assert f"{out}: ({i}, {{}}" in alias, alias
    layer = B * min(K * D for K, D in zip(Ks, Ds)) * W * 2
    assert serving.whole_layer_ops(text, layer) == []
    assert compiled.memory_analysis().temp_size_in_bytes < layer


@pytest.mark.parametrize("name,L,B,K,G,D,Dv,W,masked,sunk", [
    ("gpt2_medium", 24, 16, 16, 1, 64, 64, 1024, False, False),
    ("mimo_full", 2, 64, 4, 16, 192, 128, 2048, False, False),
    # the rule keeps a one-block ring on the plain path; the kernel
    # itself takes it, sink and all
    ("mimo_ring", 5, 64, 8, 8, 192, 128, 128, False, True),
    ("keye_vl2", 6, 16, 4, 8, 128, 128, 16384, True, False)])
def test_attention_kernel_compiles_for_a_v5e(one_chip, name, L, B, K, G, D,
                                             Dv, W, masked, sunk):
    """`ops/cache_attention.py`'s kernel at the cells' real widths, in a
    layer loop with a traced layer index as the decode programs call it:
    Mosaic takes it (GPT's heads two side by side, MiMo-V2's 192-wide
    keys, Keye-VL-2.0's mask), and the compiled program copies no layer
    of the stacks it is given whole: it holds no temporary of a layer's
    size.  Kept in this file: one worker describes the chip."""
    from mxnet_tpu import serving
    from mxnet_tpu.ops import cache_attention

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, ck, cv, n, mask, sink):
        lanes = cache_attention.block_lanes(ck, cv)

        def body(acc, l):
            return acc + cache_attention._attend_kernel(
                q, ck, cv, l, n, mask if masked else None,
                sink if sunk else None, lanes), None
        return jax.lax.scan(body, jnp.zeros((B, K, G, Dv), jnp.float32),
                            jnp.arange(L, dtype=jnp.int32))[0]

    compiled = jax.jit(step).lower(
        sds((B, K, G, D)), sds((L, B, K, D, W)), sds((L, B, K, Dv, W)),
        sds((B,), jnp.int32), sds((B, W), jnp.bool_),
        sds((K, G), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer = B * K * min(D, Dv) * W * 2
    assert serving.whole_layer_ops(text, layer) == []
    assert compiled.memory_analysis().temp_size_in_bytes < layer
    assert compiled.out_info.shape == (B, K, G, Dv)


def test_the_latent_stacks_kernel_compiles_for_a_v5e(one_chip):
    """`ops/cache_attention.py`'s kernel over Kimi-K2's latent stack at
    the cell's real sizes (one head of 576 rows, 64 query heads, values
    the first 512 rows, blocks of 1,024 positions): Mosaic takes the
    4.5-tile contraction and the slice of the key buffer, there is no
    second stack, and the program holds no temporary of a layer's size."""
    from mxnet_tpu import serving
    from mxnet_tpu.ops import cache_attention

    L, B, G, D, Dv, W = 5, 8, 64, 576, 512, 16384

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, ck, n):
        lanes = cache_attention.block_lanes(ck, None)

        def body(acc, l):
            return acc + cache_attention._attend_kernel(
                q, ck, None, l, n, None, None, lanes, leading=Dv), None
        return jax.lax.scan(body, jnp.zeros((B, 1, G, Dv), jnp.float32),
                            jnp.arange(L, dtype=jnp.int32))[0]

    compiled = jax.jit(step).lower(sds((B, 1, G, D)), sds((L, B, 1, D, W)),
                                   sds((B,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer = B * Dv * W * 2
    assert serving.whole_layer_ops(text, layer) == []
    assert compiled.memory_analysis().temp_size_in_bytes < layer
    assert compiled.out_info.shape == (B, 1, G, Dv)


@pytest.mark.parametrize("kernel", ["select", "attend"])
def test_the_selection_kernels_compile_for_a_v5e(one_chip, monkeypatch,
                                                 kernel):
    """Keye-VL-2.0's two prefill kernels at the cell's real sizes, one
    row of 16,384 positions: the selection (`ops/indexed_attention.py`:
    Mosaic takes the query block's 8 MB of keys in VMEM and the int8
    mask) and attention under it, the flash forward body with the mask
    an operand (`ops/pallas_attention.py::flash_attention_forward(keep=)`:
    32 query heads over 4 key heads, a key head's eight heads a step in
    blocks of 512, so a grid of 4 x 32 steps where the kernel this file
    compiled until PR 43 had 16,384; the step's 20 MB of VMEM are asked
    for).  Interpret mode cannot say.  Kept in this file: one worker
    describes the chip."""
    from mxnet_tpu.ops import indexed_attention, pallas_attention

    monkeypatch.setattr(indexed_attention, "_use_interpret", lambda: False)
    monkeypatch.setattr(pallas_attention, "_use_interpret", lambda: False)
    S, bf = 16384, jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if kernel == "select":
        fn = lambda qi, w, ki, last: indexed_attention.select_prefill(
            qi, w, ki, last, 2048)
        args = (sds((1, 16, S, 64)), sds((1, S, 16), jnp.float32),
                sds((1, 64, S)), sds((1,), jnp.int32))
        out = (1, S, S)
    else:
        fn = lambda q, k, v, n, keep: \
            pallas_attention.flash_attention_forward(q, k, v, n, scale=1.0,
                                                     keep=keep)
        args = (sds((1, 32, S, 128)), sds((1, 4, S, 128)),
                sds((1, 4, S, 128)), sds((1,), jnp.int32),
                sds((1, S, S), jnp.int8))
        out = (1, 32, S, 128)
        call, = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        block_q, block_k = pallas_attention._block_sizes(
            S, 128, bf, "fwd", 128, True, 8)
        assert (block_q, block_k) == (512, 512)
        assert call.params["grid_mapping"].grid == (4, S // block_q)
        assert [v.aval.shape for v in call.invars] == [
            (1,), (4, 8, S, 128), (4, S, 128), (4, S, 128), (1, S, S)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == out


@pytest.mark.parametrize("name,BH,T,D,causal", [
    ("gpt2m_train_t1024", 64, 1024, 64, True),
    ("bert_base_b32", 384, 512, 64, False),   # set the parent's 256 cap
    ("long_heads_of_128", 16, 4096, 128, True)])
def test_the_flash_kernels_compile_for_a_v5e(one_chip, monkeypatch, name,
                                             BH, T, D, causal):
    """`ops/pallas_attention.py`'s forward and backward at the blocks the
    shape gives (`tests/test_pallas_attention.py` holds their results,
    interpreted): Mosaic takes both inside the scoped VMEM it grants by
    default (no limit is asked for), the training cell's length is one
    grid step a head, and every operand of the two custom calls is
    bfloat16 but the float32 logsumexp and delta rows: nothing is cast
    up in front of a kernel.  Kept in this file: one worker describes
    the chip."""
    import re

    from mxnet_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((1, BH, T, D), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = pa.flash_attention(q, k, v, causal=causal)
        return jnp.sum(out.astype(jnp.float32))

    lowered = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(x, x, x)
    calls = {}
    for line in lowered.as_text().split("\n"):
        if "@tpu_custom_call" in line:
            kernel = re.search(r'kernel_name = "(\w+)"', line).group(1)
            calls[kernel] = re.findall(r"tensor<([\dx]+)x(\w+)>",
                                       line.split(" : (")[-1])
    wide, rows = f"{BH}x{T}x{D}", f"{BH}x8x{T}"
    assert calls == {
        "_fwd_kernel": [(wide, "bf16")] * 4 + [(rows, "f32")],
        "_bwd_kernel": ([(wide, "bf16")] * 4 + [(rows, "f32")] * 2
                        + [(wide, "bf16")] * 3)}
    for kernel in ("fwd", "bwd"):
        bq, bk = pa._block_sizes(T, D, jnp.bfloat16, kernel)
        assert bq == bk == min(T, 1024)
        assert sum(pa._vmem_bytes(T, D, jnp.bfloat16, kernel, bq,
                                  bk)) <= pa._VMEM_DEFAULT
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert [o.shape for o in compiled.out_info[1]] == [(1, BH, T, D)] * 3


def test_the_prefill_flash_kernel_compiles_for_a_v5e(one_chip, monkeypatch):
    """`flash_attention_forward` at Kimi-K2.6's prefill shape (one row a
    chunk, 64 heads, a bucket of 16,384, keys 192 wide arriving padded
    to 256 and values 128, the row's length traced): Mosaic takes the
    kernel that brings its own key blocks at blocks of 1,024 inside the
    default scoped VMEM, bfloat16 in and out, the length its scalar
    prefetch, and no logsumexp is written; keys that arrive 192 wide
    are padded in front of the same call."""
    import re

    from mxnet_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    H, T, D, Dv = 64, 16384, 256, 128

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert pa._block_sizes(T, D, jnp.bfloat16, "fwd", Dv) == (1024, 1024)
    assert sum(pa._vmem_bytes(T, D, jnp.bfloat16, "fwd", 1024, 1024,
                              Dv)) <= pa._VMEM_DEFAULT
    for width in (D, 192):
        lowered = jax.jit(lambda q, k, v, n: pa.flash_attention_forward(
            q, k, v, n, scale=1.0)).lower(
                sds((1, H, T, width)), sds((1, H, T, width)),
                sds((1, H, T, Dv)), sds((1,), jnp.int32))
        call, = [line for line in lowered.as_text().split("\n")
                 if "@tpu_custom_call" in line]
        assert re.search(r'kernel_name = "(\w+)"',
                         call).group(1) == "_fwd_rows_kernel"
        assert re.findall(r"tensor<([\dx]+)x(\w+)>",
                          call.split(" : (")[-1]) == [
            ("1", "i32"), (f"{H}x{T}x{D}", "bf16"), (f"{H}x{T}x{D}", "bf16"),
            (f"{H}x{T}x{Dv}", "bf16"), (f"{H}x{T}x{Dv}", "bf16")]
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (1, H, T, Dv)


@pytest.mark.parametrize("S,D,Dv", [(8, 256, 128), (64, 256, 128),
                                    (200, 256, 128), (128, 64, 64)])
def test_the_prefill_flash_kernel_compiles_at_a_small_bucket(
        one_chip, monkeypatch, S, D, Dv):
    """A serving engine's small prefill buckets (its floor is 8) at
    Kimi-K2.6's widths, eight rows a chunk: a block shorter than the
    kernel's 128-position tiles is padded in front of the call and cut
    back behind it, so Mosaic takes it whatever ``S``; the blocks are
    the kernel's own choice (128 here).  And heads of 64, whose rows
    Mosaic's copies refuse: keys and values both arrive padded."""
    import re

    from mxnet_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    B, H, T = 8, 64, pa.lane_tiles(S)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(lambda q, k, v, n: pa.flash_attention_forward(
        q, k, v, n, scale=1.0)).lower(
            sds((B, H, S, D)), sds((B, H, S, D)), sds((B, H, S, Dv)),
            sds((B,), jnp.int32))
    call, = [line for line in lowered.as_text().split("\n")
             if "@tpu_custom_call" in line]
    assert re.findall(r"tensor<([\dx]+)x(\w+)>", call.split(" : (")[-1]) == [
        (f"{B}", "i32")] + [
        (f"{B * H}x{T}x{pa.lane_tiles(d)}", "bf16") for d in (D, D, Dv, Dv)]
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (B, H, S, Dv)


@pytest.mark.parametrize("S", [8, 64])
def test_kimis_block_layer_compiles_at_a_small_bucket(one_chip, monkeypatch,
                                                      S):
    """Kimi-K2's prefill layer (`kimi_k2._block_layer`: the expanded
    heads, the flash forward kernel to each row's length, the dense
    feed-forward) at the buckets a default `ServingEngine` warms up
    (its prefill floor is 8), at `chip_smoke.kimi_small`'s widths:
    XLA:TPU and Mosaic take it, the kernel in it."""
    import chip_smoke
    from mxnet_tpu.gluon.model_zoo import kimi_k2
    from mxnet_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    z = kimi_k2.KimiK2Model(**chip_smoke.kimi_small().kwargs)._sizes
    B = 8

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {n: sds(z.shape_of("l0_" + n))
         for n in kimi_k2._ATTN_LEAVES + kimi_k2._DENSE_LEAVES}
    compiled = jax.jit(
        lambda p, x, pos, n: kimi_k2._block_layer(z, p, x, pos, n)[:2]).lower(
            p, sds((B, S, z.units), jnp.float32), sds((B, S), jnp.int32),
            sds((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert [o.shape for o in compiled.out_info] == [
        (B, S, z.units), (B, S, z.latent)]


@pytest.mark.parametrize("S", [1, 512])
def test_ouros_programs_compile_for_a_v5e_at_the_published_widths(
        one_chip, monkeypatch, S):
    """Ouro-2.6B's decode step and its 8 x 512 prefill
    (`gluon/model_zoo/ouro.py::OuroProgram.step`, the sizes of
    benchmark/configs/ouro-2.6b.json: 48 stacked layers of weights, 192
    cache slots) lower and compile for a described v5e with the kernels
    in them: both 3.2 GB stacks are written into their donated
    arguments through the two nested loops, with a traced slot index,
    and neither program's buffer assignment holds a copy of a stack
    (the decode program's temporaries are under one slot's 16.8 MB; the
    prefill's are the block's own activations)."""
    import json
    import os

    from mxnet_tpu.gluon.model_zoo import ouro
    from mxnet_tpu.ops import cache_attention, pallas_attention as pa

    monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
    monkeypatch.setattr(cache_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        kwargs = json.load(f)["program"]["kwargs"]
    net = ouro.OuroModel(**kwargs)        # no parameter is allocated
    z, B, W = net._sizes, 8, kwargs["max_length"]
    program = ouro.OuroProgram(net, jnp.bfloat16)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots = z.loop_steps * z.num_layers
    assert (slots, z.num_layers) == (192, 48)
    stack = sds((slots, B, z.kv_heads, z.head_dim, W))
    # the format a donated stack arrives in, as `init_cache` reads it
    # off an allocated one on the chip
    program._layouts = [jax.jit(lambda x: x).lower(stack).compile(
        ).input_formats[0][0]] * 2
    weights = tuple(sds(z.shape_of(n)) for n in net._names)
    assert weights[net._names.index("qkv_weight")].shape[0] == 48
    # the decode step as the engine compiles it: handed the live rows
    live = (sds((B,), jnp.bool_),) if S == 1 else ()
    compiled = jax.jit(program.step, donate_argnums=(1,)).lower(
        weights, (stack, stack, sds((2, 2 + z.loop_steps), jnp.uint32)),
        sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B, S), jnp.int32), *live).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(3):
        assert f"{{{i}}}: ({len(weights) + i}, {{}}" in alias, alias
    slot_bytes = B * z.kv_heads * z.head_dim * W * 2
    stack_bytes = slots * slot_bytes
    assert stack_bytes == 3_221_225_472
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * stack_bytes
    if S == 1:
        assert mem.temp_size_in_bytes < slot_bytes
        assert dict(program.cache_writes[1]) == {"kernel": 2 * B,
                                                 "kernel_live": 2 * B}
        assert dict(program.cache_reads[1]) == {("kernel", W, 128): 1}
    else:
        assert mem.temp_size_in_bytes < stack_bytes // 8
        assert dict(program.block_attends[S]) == {"kernel": 1}
    assert compiled.out_info[1].shape == (B, kwargs["vocab_size"])


@pytest.mark.parametrize("S", [1, 16384])
def test_command_a_plus_programs_compile_for_a_v5e_at_the_published_widths(
        one_chip, monkeypatch, S):
    """Command A+'s decode step and its 8 x 16,384 prefill
    (`gluon/model_zoo/cohere2_moe.py::Cohere2MoeProgram.step`, the sizes
    of benchmark/configs/command-a-plus-ep16.json) lower and compile for
    a described v5e with the kernels in them: the rings of 4,096 go
    through the row-write and the per-row attention kernels (16 query
    heads a key head, 32 lane blocks a ring), the prefill through the
    flash forward kernel with 8 key heads under 128 query heads and, on
    the window layers, ``window=4096``; all four stacks are written into
    their donated arguments (the prefill a row at a time), and the
    decode program holds no temporary of a ring layer's size."""
    import json
    import os

    from mxnet_tpu.gluon.model_zoo import cohere2_moe
    from mxnet_tpu.ops import cache_attention, moe, pallas_attention as pa

    monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
    monkeypatch.setattr(cache_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "command-a-plus-ep16.json")) as f:
        kwargs = json.load(f)["program"]["kwargs"]
    net = cohere2_moe.Cohere2MoeModel(**kwargs)   # no parameter allocated
    z, B, W = net._sizes, 8, kwargs["max_length"]
    program = cohere2_moe.Cohere2MoeProgram(net, jnp.bfloat16)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    full = sds((1, B, z.kv_heads, z.head_dim, W))
    ring = sds((3, B, z.kv_heads, z.head_dim, z.window))
    assert (z.window, z.groups, W) == (4096, 16, 16384)
    # the formats donated stacks arrive in, as `init_cache` reads them
    # off allocated ones on the chip
    program._layouts = [jax.jit(lambda x: x).lower(c).compile(
        ).input_formats[0][0] for c in (full, full, ring, ring)]
    weights = tuple(sds(shape) for _, shape in z.leaves())
    assert sum(int(np.prod(w.shape)) for w in weights) == 3_122_679_808
    # the decode step as the engine compiles it: handed the live rows
    live = (sds((B,), jnp.bool_),) if S == 1 else ()
    compiled = jax.jit(program.step, donate_argnums=(1,)).lower(
        weights, (full, full, ring, ring, sds((4, 2, 11), jnp.int32),
                  sds((4, 2, 2), jnp.uint32)),
        sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B, S), jnp.int32), *live).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(6):
        assert f"{{{i}}}: ({len(weights) + i}, {{}}" in alias, alias
    # the four layers' held experts by the grouped product's kernel,
    # their passes into the stream by the walk of its token tiles
    assert dict(program.grouped_products[S]) == {"kernel": 4,
                                                 "combine_kernel": 4}
    assert not [line for line in text.split("\n")
                if "scatter" in line and "serve.moe.experts" in line]
    assert "ragged" not in text
    ring_layer = B * z.kv_heads * z.head_dim * z.window * 2
    mem = compiled.memory_analysis()
    if S == 1:
        assert mem.temp_size_in_bytes < ring_layer
        assert dict(program.cache_writes[1]) == {"kernel": 4 * 2 * B,
                                                 "kernel_live": 4 * 2 * B}
        assert dict(program.cache_reads[1]) == {
            ("kernel", 4096, 256): 3, ("kernel", W, 512): 1}
    else:
        # one row's stream, queries, attention output and a token
        # chunk's shared-expert hidden, not eight rows'
        assert mem.temp_size_in_bytes < 4 << 30
        assert dict(program.block_attends[S]) == {"kernel": 4}
    assert compiled.out_info[1].shape == (B, kwargs["vocab_size"])


@pytest.mark.parametrize("kernel", ["scan", "update"])
def test_the_state_space_kernels_compile_for_a_v5e(one_chip, kernel):
    """`ops/ssm.py`'s chunked scan at a row chunk of Jamba2-3B's prefill
    (16 rows x 512 positions x 5,120 channels, 16 states: grid 16 x 10
    x 4) and its one-position update at the cell's 128 rows, in place
    on all 26 layers' states (1.09 GB, aliased to the output)."""
    from mxnet_tpu.ops import ssm

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, S, E, N, L, B = 16, 512, 5120, 16, 26, 128
    if kernel == "scan":
        compiled = jax.jit(ssm._scan_kernel_call).lower(
            sds((R, S, E)), sds((R, S, E)), sds((N, E)), sds((R, S, N)),
            sds((R, S, N)), sds((E,)), sds((R,), jnp.int32)).compile()
        assert [tuple(o.shape) for o in compiled.out_info] == [
            (R, S, E), (R, N, E)]
    else:
        compiled = jax.jit(
            lambda st, *a: ssm._update_kernel_call(st, 3, *a),
            donate_argnums=(0,)).lower(
            sds((L, B, N, E)), sds((B, E)), sds((B, E)), sds((N, E)),
            sds((B, N)), sds((B, N)), sds((E,)),
            sds((B,), jnp.bool_)).compile()
        assert compiled.memory_analysis().alias_size_in_bytes \
            == L * B * N * E * 4
    assert "tpu_custom_call" in compiled.as_text()


def test_jambas_decode_step_compiles_for_a_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """Jamba2-3B's decode step
    (`gluon/model_zoo/jamba.py::JambaProgram.step`, the sizes of
    benchmark/configs/jamba2-3b.json) lowers and compiles for a
    described v5e with the kernels in it: the 26 Mamba layers'
    one-position update (`ops/ssm.py`), the two attention layers' row
    write and per-row attention (20 query heads over one key head,
    stacks of 768 slots in blocks of 256); the two stacks, the states
    and the tails are written into their donated arguments, and the
    program holds no temporary near the states' size.  (The 128 x 512
    prefill compiles the same way in 50 s, with 16 rows a chunk and
    `state_updates[512] == {"kernel": 26 * 16}`: the chip runs hold
    it, not this file.)"""
    import json
    import os

    from mxnet_tpu.gluon.model_zoo import jamba
    from mxnet_tpu.ops import cache_attention, pallas_attention as pa, ssm

    monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
    monkeypatch.setattr(cache_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        kwargs = json.load(f)["program"]["kwargs"]
    net = jamba.JambaModel(**kwargs)      # no parameter allocated
    z, B = net._sizes, 128
    program = jamba.JambaProgram(net, jnp.bfloat16)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stacks, states, counters = program.cache_shapes(B)
    assert [s for s, _ in stacks] == [(2, B, 1, 128, 768)] * 2
    assert [s for s, _ in states] == [(26, B, 16, 5120), (26, B, 15360)]
    cache = tuple(sds(s, d or jnp.bfloat16)
                  for s, d in stacks + states + counters)
    # the formats donated arrays arrive in, as `init_cache` reads them
    # off allocated ones on the chip
    program._layouts = [jax.jit(lambda x: x).lower(c).compile(
        ).input_formats[0][0] for c in cache[:4]]
    weights = tuple(sds(shape) for _, shape in z.leaves())
    assert sum(int(np.prod(w.shape)) for w in weights) == 3_029_337_472
    compiled = jax.jit(program.step, donate_argnums=(1,)).lower(
        weights, cache, sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B, 1), jnp.int32), sds((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(5):
        assert f"{{{i}}}: ({len(weights) + i}, {{}}" in alias, alias
    mem = compiled.memory_analysis()
    # the logits (128, 65,536) float32 are 33.5 MB; a layer's states
    # 41.9 MB, all layers' 1.09 GB
    assert mem.temp_size_in_bytes < 128 << 20
    assert dict(program.state_updates[1]) == {
        "kernel": 26 * B, "kernel_live": 26 * B}
    assert dict(program.cache_writes[1]) == {"kernel": 2 * 2 * B,
                                             "kernel_live": 2 * 2 * B}
    assert dict(program.cache_reads[1]) == {("kernel", 768, 256): 2}
    assert compiled.out_info[1].shape == (B, kwargs["vocab_size"])


@pytest.mark.parametrize("kernel", ["scan", "scan_float32", "update"])
def test_the_mamba2_kernels_compile_for_a_v5e(one_chip, kernel):
    """`ops/ssm.py`'s chunked Mamba-2 scan at a row chunk of Granite
    4.0-H's prefill (8 rows x 512 positions, 128 heads of 64 over 128
    states: grid 8 x 8 x 4, the products' operands bfloat16 as served
    and float32 as tested) and its one-position update at the cell's
    128 rows, in place on all 9 layers' states (4.83 GB, aliased to the
    output, no temporary)."""
    from mxnet_tpu.ops import ssm

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, S, H, P, N, L, B = 8, 512, 128, 64, 128, 9, 128
    assert ssm._mamba2_scan_fits(H, P, N) and ssm._mamba2_update_fits(H * P, N)
    if kernel != "update":
        import functools

        compiled = jax.jit(functools.partial(
            ssm._mamba2_scan_kernel_call,
            operands=None if kernel == "scan_float32" else jnp.bfloat16)
        ).lower(sds((R, S, H, P)), sds((R, S, H)), sds((H,)),
                sds((R, S, N)), sds((R, S, N)), sds((H,)),
                sds((R,), jnp.int32)).compile()
        assert [tuple(o.shape) for o in compiled.out_info] == [
            (R, S, H, P), (R, H * P, N)]
    else:
        compiled = jax.jit(
            lambda st, *a: ssm._mamba2_update_kernel_call(st, 3, *a),
            donate_argnums=(0,)).lower(
            sds((L, B, H * P, N)), sds((B, H, P)), sds((B, H)), sds((H,)),
            sds((B, N)), sds((B, N)), sds((H,)),
            sds((B,), jnp.bool_)).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == L * B * H * P * N * 4
        assert mem.temp_size_in_bytes < 64 << 20
    assert "tpu_custom_call" in compiled.as_text()


def _grouped_calls(jaxpr):
    """(column tiles, the most visits the walk's arrays hold, groups) of
    each grouped product's Pallas call in ``jaxpr`` and in what it
    calls, in order: the grid's first bound, and the shapes of the
    scalar operands behind its second (the pass's own count of
    visits)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and "moe_grouped" in str(
                eqn.params.get("name_and_src_info", "")) + str(
                    eqn.params.get("name", "")):
            mapping = eqn.params["grid_mapping"]
            assert mapping.num_dynamic_grid_bounds == 1
            first, visits, group, tile, lo, hi = (
                v.aval.shape for v in eqn.invars[1:7])
            assert first == visits == (1,) and group == tile and lo == hi
            out.append((mapping.grid[0], group[0], lo[0]))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out.extend(_grouped_calls(sub))
    return out


# (M, F, n held, layers in the stack, a decode step's buffer, a prefill
# pass's): the five expert families' cells (benchmark/configs) and the
# smallest the smoke serves
GROUPED = {
    "mimo": (4096, 2048, 16, 1, 256, 1024),
    "keye": (2048, 768, 16, 6, 128, 4096),
    "kimi": (7168, 2048, 12, 4, 64, 4096),
    "cmda": (4096, 4096, 8, 1, 64, 4096),
    "granite": (4096, 768, 18, 10, 256, 1024),
    # `chip_smoke.py`'s small families: 8 rows of 2 of 4 held experts
    "smoke": (256, 128, 4, 2, 16, 1024),
}


@pytest.mark.parametrize("phase", ["decode", "prefill"])
@pytest.mark.parametrize("family", list(GROUPED))
def test_the_grouped_kernel_compiles_for_a_v5e(one_chip, family, phase):
    """A pass's two grouped products (`ops/moe.py`) at each expert
    family's widths and buffers compile for a described v5e through
    Mosaic, the layer a traced scalar into the stack as it lies: no
    temporary but the first product's result and the activation, so no
    slice of a stack."""
    from mxnet_tpu.ops import moe

    M, F, n, L, *rows = GROUPED[family]
    P = rows[phase == "prefill"]
    assert moe._fits(P, M, F, n, jnp.bfloat16)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pass_(x, w13, w2, lo, hi, l):
        walk = moe._walk(lo, hi, l * n, P)
        h = moe._grouped_kernel_call(x, w13.reshape(L * n, M, 2 * F), walk)
        h = (jax.nn.silu(h[:, :F]) * h[:, F:]).astype(w2.dtype)
        return moe._grouped_kernel_call(h, w2.reshape(L * n, F, M), walk)

    compiled = jax.jit(pass_).lower(
        sds((P, M)), sds((L, n, M, 2 * F)), sds((L, n, F, M)),
        sds((n,), jnp.int32), sds((n,), jnp.int32),
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 and "ragged" not in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= P * (2 * F * 4 + F * 2) + (1 << 20)


# (tokens of the stream, rows of the buffer, M) a decode step and a
# prefill call hand a pass's way out in each expert family's cell
WAYS_OUT = {
    "mimo": ((64, 256, 4096), (4096, 1024, 4096)),
    "keye": ((16, 128, 2048), (262144, 65536, 2048)),
    "kimi": ((8, 64, 7168), (16384, 4096, 7168)),
    "cmda": ((8, 64, 4096), (16384, 4096, 4096)),
    "granite": ((128, 256, 4096), (4096, 1024, 4096)),
    "smoke": ((8, 16, 256), (1024, 256, 256)),
}


@pytest.mark.parametrize("phase", ["decode", "prefill"])
@pytest.mark.parametrize("family", list(WAYS_OUT))
def test_the_way_out_kernel_compiles_for_a_v5e(one_chip, family, phase):
    """A pass's way out (`ops/moe.py::_combine_kernel_call`) at each
    expert family's stream and buffer compiles for a described v5e
    through Mosaic with the stream written into its donated argument, no
    scatter left, and no temporary but the rows sorted by token and
    their weights as a column (a lane tile wide in the chip's layout)."""
    from mxnet_tpu.ops import moe

    T, P, M = WAYS_OUT[family][phase == "prefill"]
    assert moe._combine_fits(T, P, M)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(moe._combine_kernel_call, donate_argnums=(0,)).lower(
        sds((T, M)), sds((P, M)), sds((P,)), sds((P,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "scatter" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == T * M * 4
    assert mem.temp_size_in_bytes <= P * (M + 128) * 4 + (4 << 20)


def test_granites_decode_step_compiles_for_a_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """Granite 4.0-H's decode step
    (`gluon/model_zoo/granite_hybrid.py::GraniteHybridProgram.step`, the
    sizes of benchmark/configs/granite-4.0-h-small-ep4.json) lowers and
    compiles for a described v5e with the kernels in it: the 9 Mamba-2
    layers' one-position update (`ops/ssm.py`), the attention layer's
    row write and per-row attention (32 query heads over 8, stacks of
    768 slots); the stacks, the states and the tails are written into
    their donated arguments (5.29 GB), and the program holds no
    temporary near a layer's states (0.54 GB).  (The 128 x 512 prefill
    compiles the same way in 25 s with 8 rows a chunk, 1.76 GB of
    temporaries and `state_updates[512] == {"kernel": 9 * 8}`: the chip
    runs hold it, not this file.)"""
    import json
    import os

    from mxnet_tpu.gluon.model_zoo import granite_hybrid
    from mxnet_tpu.ops import (cache_attention, moe, pallas_attention as pa,
                               ssm)
    from mxnet_tpu.serving.engine import whole_layer_ops

    monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
    monkeypatch.setattr(cache_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-small-ep4.json")) as f:
        kwargs = json.load(f)["program"]["kwargs"]
    net = granite_hybrid.GraniteHybridModel(**kwargs)  # nothing allocated
    z, B = net._sizes, 128
    program = granite_hybrid.GraniteHybridProgram(net, jnp.bfloat16)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stacks, states, counters = program.cache_shapes(B)
    cache = tuple(sds(s, d or jnp.bfloat16)
                  for s, d in stacks + states + counters)
    # the formats donated arrays arrive in, as `init_cache` reads them
    # off allocated ones on the chip
    program._layouts = [jax.jit(lambda x: x).lower(c).compile(
        ).input_formats[0][0] for c in cache[:4]]
    weights = tuple(sds(shape) for _, shape in z.leaves())
    assert sum(int(np.prod(w.shape)) for w in weights) == 2_955_758_208
    traced = jax.jit(program.step, donate_argnums=(1,)).trace(
        weights, cache, sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B, 1), jnp.int32), sds((B,), jnp.bool_))
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(6):
        assert f"{{{i}}}: ({len(weights) + i}, {{}}" in alias, alias
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 5.29e9
    assert mem.temp_size_in_bytes < 128 << 20
    # the held experts (PR 50): every layer's call by the kernel, two
    # products each over the layer's own 18 groups (a buffer of 256 rows
    # in tiles of 128: 2 + 17 visits at the most, of which the grid runs
    # a pass's own count; not 10 x 18 groups), no
    # ragged_dot left, and neither a stack of experts nor a layer's
    # slice of one (113 MB the smaller) copied or sliced out
    assert dict(program.grouped_products[1]) == {"kernel": 10,
                                                 "combine_kernel": 10}
    n = z.experts_held[1]
    calls = _grouped_calls(traced.jaxpr.jaxpr)
    assert calls == [(2, 2 + n - 1, n), (1, 2 + n - 1, n)] * 10, calls
    assert "ragged" not in text
    assert whole_layer_ops(text, n * z.expert_hidden * z.units * 2) == []
    assert dict(program.state_updates[1]) == {
        "kernel": 9 * B, "kernel_live": 9 * B}
    assert dict(program.cache_writes[1]) == {"kernel": 2 * B,
                                             "kernel_live": 2 * B}
    assert dict(program.cache_reads[1]) == {("kernel", 768, 128): 1}
    assert compiled.out_info[1].shape == (B, kwargs["vocab_size"])


def test_mimos_decode_step_compiles_for_a_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """MiMo-V2's decode step (`gluon/model_zoo/mimo_v2.py`, the sizes of
    benchmark/configs/mimo-v2.5-ep16.json, the cell's 64 rows), whose
    expert layers hold their own leaves: the six layers' held experts go
    through the grouped product's kernel with no layer handed (a buffer
    of 256 rows: 2 + 15 visits at the most), the program holds no
    ``ragged_dot`` and neither copies nor slices a layer's experts (268
    MB the smaller leaf), and the four stacks are written into their
    donated arguments."""
    import json
    import os

    from mxnet_tpu.gluon.model_zoo import mimo_v2
    from mxnet_tpu.ops import cache_attention, moe, pallas_attention as pa
    from mxnet_tpu.serving.engine import whole_layer_ops

    monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
    monkeypatch.setattr(cache_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2.5-ep16.json")) as f:
        kwargs = json.load(f)["program"]["kwargs"]
    net = mimo_v2.MiMoV2Model(**kwargs)     # nothing allocated
    z, B = net._sizes, 64
    program = mimo_v2.MiMoV2Program(net, jnp.bfloat16)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stacks, counters = program.cache_shapes(B)
    cache = tuple(sds(s, d or jnp.bfloat16) for s, d in stacks + counters)
    program._layouts = [jax.jit(lambda x: x).lower(c).compile(
        ).input_formats[0][0] for c in cache[:len(stacks)]]
    weights = tuple(sds(getattr(net, name).shape) for name in net._names)
    traced = jax.jit(program.step, donate_argnums=(1,)).trace(
        weights, cache, sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B, 1), jnp.int32), sds((B,), jnp.bool_))
    compiled = traced.lower().compile()
    text = compiled.as_text()
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    for i in range(len(cache)):
        assert f"{{{i}}}: ({len(weights) + i}, {{}}" in alias, alias
    n, F, M = z.experts_held[1], kwargs["expert_hidden"], kwargs["units"]
    assert dict(program.grouped_products[1]) == {"kernel": 6,
                                                 "combine_kernel": 6}
    assert _grouped_calls(traced.jaxpr.jaxpr) \
        == [(4, 2 + n - 1, n), (2, 2 + n - 1, n)] * 6
    assert "ragged" not in text
    assert whole_layer_ops(text, n * F * M * 2) == []

"""The seam between a decoder family and what every family shares
(`gluon/model_zoo/_decoder_program.py`, docs/serving.md "The decoder
program"), on the CPU: (a) a toy family written here on the base alone,
served by `ServingEngine` and held to a plain NumPy walk of its weights;
(b) for each of the six families' tiny models, what the base does on a
family's behalf: the layouts read once, the tallies filled while a step
is traced, and what a row write is told of ``live``; (c) the sampling
rule's one home; (d) a block's real tokens packed and laid back in rows
(`_decoder_ops.packing`, `pack`, `unpack`) and the token-wise tiles over
the packed block (`by_tokens`, `tokens_worked`)."""

import functools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx                                      # noqa: E402
from mxnet_tpu import serving                               # noqa: E402
from mxnet_tpu.gluon.block import HybridBlock               # noqa: E402
from mxnet_tpu.gluon.model_zoo import (                     # noqa: E402
    _decoder_ops, cohere2_moe, gpt, keye_vl2, kimi_k2, mimo_v2, ouro)
from mxnet_tpu.gluon.model_zoo._decoder_program import (    # noqa: E402
    DecoderProgram)
from mxnet_tpu.ops import cache_write                       # noqa: E402
from mxnet_tpu.test_utils import (                          # noqa: E402
    serving_dead_rows_keep_their_cache, serving_unequal_answers)

# -- (a) a family is its sizes, its cache's shapes, a layer body and a head ----

V, C, W = 48, 16, 32


class ToyModel(HybridBlock):
    """One layer: one-head attention whose values are its keys, over
    one stack ``(1, B, 1, C, W)``, then a dense branch; a tied head."""

    _names = ("embed_weight", "q_weight", "k_weight", "ff_weight")
    _max_length, _vocab = W, V

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            for name in self._names:
                setattr(self, name, self.params.get(
                    name, shape=(V if name == "embed_weight" else C, C)))

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        return ToyProgram(self, dtype)


class ToyProgram(DecoderProgram):
    signature = ()

    def cache_shapes(self, B):
        return [((1, B, 1, C, W), None)], []

    def body(self, ctx, w, cache, toks):
        import jax
        import jax.numpy as jnp

        x = jnp.take(w["embed_weight"], toks, axis=0)          # (B, S, C)
        q, k = x @ w["q_weight"] * C ** -0.5, x @ w["k_weight"]
        with jax.named_scope("serve.cache_write"):
            stack, = ctx.write(cache, [k.swapaxes(1, 2)[:, None]], 0,
                               ctx.pos)
        if ctx.decode:
            a = ctx.attend(q[:, :, None], stack, None, 0, leading=C)[:, 0]
        else:   # from an empty cache: inside the block
            at = jnp.arange(ctx.S)
            s = jnp.where(at[None, :] <= at[:, None],
                          jnp.einsum("bsc,btc->bst", q, k), -1e30)
            a = jax.nn.softmax(s, axis=-1) @ k
        x = x + a
        x = x + jnp.tanh(x @ w["ff_weight"])
        h = jnp.take_along_axis(x, ctx.last[:, None, None], axis=1)[:, 0]
        return (stack,), h @ w["embed_weight"].T


def _toy_walk(weights, prompt, steps):
    """The same layer by the full recompute of every token, in NumPy."""
    e, wq, wk, ff = (np.asarray(a, np.float64) for a in weights)
    toks, out = list(prompt), []
    for _ in range(steps):
        x = e[toks]
        q, k = x @ wq * C ** -0.5, x @ wk
        s = np.where(np.tri(len(toks), dtype=bool), q @ k.T, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        x = x + p / p.sum(-1, keepdims=True) @ k
        x = x + np.tanh(x @ ff)
        out.append(int((x[-1] @ e.T).argmax()))
        toks.append(out[-1])
    return out


@pytest.fixture(scope="module")
def toy():
    rng = np.random.default_rng(7)
    net = ToyModel()
    net.initialize()
    for name in net._names:
        getattr(net, name).set_data(mx.nd.array(
            rng.normal(0.0, 0.6, getattr(net, name).shape)
            .astype(np.float32)))
    prompts = [rng.integers(0, V, n).astype(np.int32)
               for n in (5, 11, 3, 8)]
    return net, serving.ServingEngine(net, batch_buckets=(4,)), prompts


def test_a_family_on_the_base_alone_is_served_like_its_numpy_walk(toy):
    net, eng, prompts = toy
    outs, timings = eng.serve_group(prompts, 6)
    weights = [getattr(net, n).data().asnumpy() for n in net._names]
    for p, got in zip(prompts, outs):
        assert list(got) == _toy_walk(weights, p, 6)
    # the engine's contract, from the base: the parameters' own buffers,
    # the tallies of the traced steps, the bucket it padded to
    for name, a in zip(net._names, eng._weights):
        assert a is getattr(net, name).data()._data, name
    assert tuple(timings["bucket"]) == (4, 16)
    assert dict(eng._program.cache_writes[16]) == {"rows": 4}
    assert dict(eng._program.cache_writes[1]) == {"rows": 4}
    assert dict(eng._program.cache_reads[1]) == {("xla", W, W): 1}
    assert timings["decode_cache_write_kernel_share"] == 0.0


@pytest.mark.parametrize("wants", [[2, 6, 4, 6], [4, 1, 5]])
def test_the_base_keeps_a_finished_row_of_the_toy_harmless(toy, wants):
    """``live`` with no line of the family's: a row that wants no token
    attends to nothing (`Step.held`) and writes nothing (`Step.write`),
    so every request gets the tokens it gets alone."""
    _, eng, prompts = toy
    prompts = prompts[:len(wants)]
    serving_unequal_answers(eng, prompts, wants)
    serving_dead_rows_keep_their_cache(eng, prompts, [k > 1 for k in wants])


# -- (b) what the base does for the six families --------------------------------

FAMILIES = {
    "gpt": lambda: gpt.gpt_tiny(scan_layers=True),
    "mimo_v2": mimo_v2.mimo_v2_tiny,
    "keye_vl2": keye_vl2.keye_vl2_tiny,
    "kimi_k2": kimi_k2.kimi_k2_tiny,
    "ouro": ouro.ouro_tiny,
    "cohere2_moe": cohere2_moe.cohere2_moe_tiny,
}
B, S = 2, 16


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def program(request):
    net = FAMILIES[request.param]()
    net.initialize()
    return net.decoder_program()


def _trace(program, S, live=None):
    """The step traced at block length S (nothing runs)."""
    import jax

    zero = np.zeros(B, np.int32)
    args = (program.weights(), program.init_cache(B), zero, zero,
            np.zeros((B, S), np.int32))
    return jax.eval_shape(program.step, *args,
                          *(() if live is None else (live,)))


def test_the_layouts_are_read_once_and_the_tallies_filled(program,
                                                          monkeypatch):
    assert isinstance(program, DecoderProgram)
    read = []
    monkeypatch.setattr(cache_write, "layouts_of", lambda stacks: read.append(
        len(stacks)) or [c.format for c in stacks])
    program._layouts = None
    first, again = program.init_cache(B), program.init_cache(2 * B)
    stacks = [c for c in first if c.ndim == 5]
    assert read == [len(stacks)] and len(program._layouts) == len(stacks)
    assert [c.shape[1] for c in again if c.ndim == 5] == [2 * B] * len(stacks)
    # the stacks first, then what rides in the same carry
    assert [c.ndim == 5 for c in first] == \
        [True] * len(stacks) + [False] * (len(first) - len(stacks))
    cache, logits = _trace(program, S)
    assert logits.shape == (B, program.vocab) and len(cache) == len(first)
    assert program.cache_writes[S]["rows"] and not program.cache_reads[S]
    _trace(program, 1)
    assert program.cache_writes[1]["rows"] and program.cache_reads[1]
    assert not program.block_attends[1]


def test_a_prefill_write_is_told_of_no_row_a_decode_write_what_it_was_handed(
        program, monkeypatch):
    """Read off the tally as tests/test_cache_write.py does: with the
    kernel path open, ``kernel_live`` counts the row writes that were
    handed ``live``."""
    monkeypatch.setattr(cache_write, "_on_tpu", lambda: True)
    monkeypatch.setattr(cache_write, "_write_kernel", functools.partial(
        cache_write._write_kernel, interpret=True))
    _trace(program, 1, live=np.array([True, False]))
    told = dict(program.cache_writes[1])
    assert told["kernel"] > 0 and told["kernel_live"] == told["kernel"], told
    assert "rows" not in told
    # handed none (a host walk, `gpt.CachedDecoder`): told of none
    _trace(program, 1)
    assert dict(program.cache_writes[1]) == {"kernel": told["kernel"]}
    # a prefill takes none, and its write would refuse one
    _trace(program, S)
    assert set(program.cache_writes[S]) == {"rows"}
    with pytest.raises(ValueError, match="live is a decode step's"):
        _trace(program, S, live=np.array([True, False]))


# -- (c) the sampling rule below both its users ----------------------------------

def test_the_sampling_rule_has_one_home():
    from mxnet_tpu.ops import sampling
    from mxnet_tpu.serving import engine

    assert engine._sample is sampling._sample is gpt._sample
    logits = np.array([[0.1, 2.0, 2.0], [3.0, -1.0, 0.0]], np.float32)
    np.testing.assert_array_equal(sampling._sample(logits, None, None),
                                  [1, 0])


# -- (d) a block's real tokens, packed and laid back in rows ---------------------

PACK_S = 160
RAGGED = {"ragged": (1, 2, 127, 128, 129, PACK_S), "full": (PACK_S,) * 4,
          "ones": (1,) * 5}


def _packed(lens, tile, width=3, seed=0):
    import jax.numpy as jnp

    x = np.random.RandomState(seed).randn(len(lens), PACK_S, width).astype(
        np.float32)
    pk = _decoder_ops.packing(jnp.asarray(lens, jnp.int32), PACK_S, tile)
    return x, pk


@pytest.mark.parametrize("lens", RAGGED.values(), ids=RAGGED.keys())
def test_pack_then_unpack_gives_back_every_real_position(lens):
    """Bit for bit; past a row's length its last real token again; ``n``
    and the last tokens' slots are the lengths' own sums; the packed
    block is the rows' real tokens, row after row, in whole tiles."""
    import jax.numpy as jnp

    x, pk = _packed(lens, 64)
    R, n = len(lens), sum(lens)
    ends = np.cumsum(lens)
    assert int(pk.n) == n and pk.tile == 64
    np.testing.assert_array_equal(pk.last, ends - 1)
    assert pk.src.shape == (1, -(-R * PACK_S // 64) * 64) \
        and pk.slot.shape == (R, PACK_S)
    packed = _decoder_ops.pack(jnp.asarray(x), pk.src)
    back = np.asarray(_decoder_ops.unpack(packed, pk.slot))
    packed = np.asarray(packed)
    assert packed.shape == pk.src.shape + (3,)
    np.testing.assert_array_equal(
        packed[0, :n], np.concatenate([x[r, :k] for r, k in enumerate(lens)]))
    assert back.shape == x.shape
    for r, k in enumerate(lens):
        np.testing.assert_array_equal(back[r, :k], x[r, :k])
        np.testing.assert_array_equal(
            back[r, k:], np.broadcast_to(x[r, k - 1], (PACK_S - k, 3)))
    # a block smaller than a tile is one tile, whole
    assert _decoder_ops.packing(pk.n[None], 8, 64).src.shape == (1, 8)


@pytest.mark.parametrize("tile", [32, 64, 4096])
@pytest.mark.parametrize("lens", RAGGED.values(), ids=RAGGED.keys())
def test_the_tiled_product_is_the_whole_product_on_the_tiles_that_hold_a_token(
        lens, tile):
    """`by_tokens` over the packed block with ``live`` the real tokens:
    ``ceil(n / tile)`` tiles are worked (`tokens_worked` counts their
    positions, as the family's counter does), the product on every real
    token is the whole block's, the stream past the worked tiles stays
    what it was and the extras there what ``into`` held."""
    import jax
    import jax.numpy as jnp

    x, pk = _packed(lens, tile, width=8, seed=1)
    w = np.random.RandomState(2).randn(8, 8).astype(np.float32)
    n, P = sum(lens), pk.src.shape[1]
    whole = P <= tile
    want_worked = P if whole else -(-n // tile) * tile
    assert int(_decoder_ops.tokens_worked(pk.tile, pk.n, P)) == want_worked

    def fn(x, mark):
        y = x @ w
        return x + y, (y, mark + 1)

    @jax.jit
    def run(x, lens):
        pk = _decoder_ops.packing(lens, PACK_S, tile)
        xp = _decoder_ops.pack(x, pk.src)
        return xp, _decoder_ops.by_tokens(
            fn, pk.tile, pk.n, xp, jnp.zeros(xp.shape[:2], jnp.int32),
            into=(jnp.full(xp.shape, 7.0), jnp.full(xp.shape[:2], 7)))

    xp, (out, (y, mark)) = jax.tree_util.tree_map(
        np.asarray, run(x, jnp.asarray(lens, jnp.int32)))
    ref = np.asarray(jnp.asarray(xp) @ w)     # the whole block at once
    # the tiles worked, counted by what they left behind
    assert int((mark == 1).sum()) == want_worked
    np.testing.assert_array_equal(y[0, :want_worked], ref[0, :want_worked])
    np.testing.assert_array_equal(out[0, :want_worked],
                                  (xp + ref)[0, :want_worked])
    np.testing.assert_array_equal(out[0, want_worked:], xp[0, want_worked:])
    assert (y[0, want_worked:] == 7.0).all() \
        and (mark[0, want_worked:] == 7).all()
    # and laid back in rows every real position has its product
    back = np.asarray(_decoder_ops.unpack(jnp.asarray(y), pk.slot))
    for r, k in enumerate(lens):
        np.testing.assert_allclose(back[r, :k], x[r, :k] @ w, rtol=1e-5,
                                   atol=1e-5)

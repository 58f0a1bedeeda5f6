"""The seam between a decoder family and what every family shares
(`gluon/model_zoo/_decoder_program.py`, docs/serving.md "The decoder
program"), on the CPU: (a) a toy family written here on the base alone,
served by `ServingEngine` and held to a plain NumPy walk of its weights;
(b) for each of the six families' tiny models, what the base does on a
family's behalf: the layouts read once, the tallies filled while a step
is traced, and what a row write is told of ``live``; (c) the sampling
rule's one home."""

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
    cohere2_moe, gpt, keye_vl2, kimi_k2, mimo_v2, ouro)
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

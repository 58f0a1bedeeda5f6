"""Keye-VL-2.0 (``model_type`` ``KeyeVL2``), the language model: a
decoder whose every layer has

- grouped-query attention with a per-head RMSNorm of queries and keys
  (QK-norm) before the rotation (all ``head_dim`` dimensions, dimension
  j paired with j + head_dim/2);
- a learned **indexer** beside it (`ops/indexed_attention.py`):
  ``index_heads`` small query heads, one key head of ``index_dim`` a
  position (LayerNorm, rotated), per-head weights from the hidden state.
  A query scores every earlier position and attention reads only the
  ``topk`` best (all of them while fewer exist);
- softmax-routed experts (`ops/moe.py::softmax_topk_route`) of which
  this block holds ``experts_held = (lo, n)``: a chip's share of an
  expert-parallel deployment.  The router scores all ``router_experts``;
  what the absent experts would add is left out, and nothing stands in
  for their exchange;
- RMSNorm, no bias, an unscaled embedding, an untied head.

Layers are alike, so the parameters are stacked by layer
(``q_weight`` is ``(L, heads * head_dim, units)``) and both passes scan
them.  ``hybrid_forward`` is the uncached full-sequence forward.
``decoder_program`` hands `serving.ServingEngine` the family's program
(`_decoder_program.DecoderProgram`; docs/serving.md, "The decoder
program"), of which this file states the cache's shapes and the layer
body.  Its cache holds **three kinds of stack**: keys and values ``(L,
B, Hkv, head_dim, W)`` and the indexer's keys ``(L, B, 1, index_dim,
W)``; two small counter arrays ride in the same carry (``counters``).

Prefill (a block of S positions from position 0) works a row chunk at a
time: the selection kernel turns the indexer's scores into an int8 mask
without the scores leaving VMEM, and the block attends inside itself
under that mask through
`ops/pallas_attention.py::flash_attention_forward(keep=)`, the forward
body Kimi-K2's, Ouro's and Command A+'s prefills run, each row to its
own length.  Decode scores the indexer's cache
row, finds the threshold by the same search, and attends over the row's
cache under the mask.

What MiMo-V2's family needs too (the mixed-precision product, RMSNorm,
the rotation, row chunking, the router's frame, the expert counters) is
in `_decoder_ops.py`, which both import; neither imports the other.
"""

from __future__ import annotations

from ...base import MXNetError
from ...ops import indexed_attention, pallas_attention
from ..block import HybridBlock
from . import _decoder_ops as _ops
from ._decoder_program import DecoderProgram

_LAYER_LEAVES = ("ln1_gamma", "q_weight", "k_weight", "v_weight", "o_weight",
                 "q_norm_gamma", "k_norm_gamma", "index_q_weight",
                 "index_k_weight", "index_k_norm_gamma", "index_k_norm_beta",
                 "index_w_weight", "ln2_gamma", "router_weight",
                 "experts_gate_up_weight", "experts_down_weight")
# the experts' stacks are not scanned: `_experts` says why
_SCANNED_LEAVES = _LAYER_LEAVES[:-2]


class _Sizes:
    """The family's sizes, as the constructor got them."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def leaf_names(self):
        return ("embed_weight",) + _LAYER_LEAVES + ("lnf_gamma",
                                                    "head_weight")

    def shape_of(self, name):
        z = self
        L, C, d = z.num_layers, z.units, z.head_dim
        n = z.experts_held[1]
        return {
            "embed_weight": (z.vocab, C), "head_weight": (z.vocab, C),
            "lnf_gamma": (C,), "ln1_gamma": (L, C), "ln2_gamma": (L, C),
            "q_weight": (L, z.num_heads * d, C),
            "k_weight": (L, z.kv_heads * d, C),
            "v_weight": (L, z.kv_heads * d, C),
            "o_weight": (L, C, z.num_heads * d),
            "q_norm_gamma": (L, d), "k_norm_gamma": (L, d),
            "index_q_weight": (L, z.index_heads * z.index_dim, C),
            "index_k_weight": (L, z.index_dim, C),
            "index_k_norm_gamma": (L, z.index_dim),
            "index_k_norm_beta": (L, z.index_dim),
            "index_w_weight": (L, z.index_heads, C),
            "router_weight": (L, z.router_experts, C),
            "experts_gate_up_weight": (L, n, C, 2 * z.expert_hidden),
            "experts_down_weight": (L, n, z.expert_hidden, C)}[name]


# -- a layer's pieces, shared by the forward pass and the cached step ----------

def _qkv(z, p, x, pos):
    """x (B, S, C) float32 → the normed hidden state u (B, S, C),
    q (B, K, G, S, d) normed, rotated and scaled, k (B, K, S, d) normed
    and rotated, v (B, K, S, d); all in the weights' type."""
    import jax

    K, d = z.kv_heads, z.head_dim
    G = z.num_heads // K
    B, S, _ = x.shape
    dt = p["q_weight"].dtype
    with jax.named_scope("serve.attn_qkv"):
        u = _ops.rms_norm(x, p["ln1_gamma"], z.eps).astype(dt)

        def heads(w, n):
            return _ops.mm("bsc,gc->bsg", u, w).reshape(B, S, n, d
                                                    ).transpose(0, 2, 1, 3)

        q = _ops.rope(_ops.rms_norm(heads(p["q_weight"], K * G), p["q_norm_gamma"],
                            z.eps), pos, z.rope_theta, d) * (d ** -0.5)
        k = _ops.rope(_ops.rms_norm(heads(p["k_weight"], K), p["k_norm_gamma"],
                            z.eps), pos, z.rope_theta, d)
        v = heads(p["v_weight"], K)
        return (u, q.astype(dt).reshape(B, K, G, S, d), k.astype(dt),
                v.astype(dt))


def _index(z, p, u, pos):
    """The indexer's projections of the normed hidden state u (B, S, C):
    qi (B, Hi, S, di) and ki (B, S, di) rotated, in the weights' type;
    w (B, S, Hi) float32, scaled."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    Hi, di = z.index_heads, z.index_dim
    B, S, _ = u.shape
    dt = p["index_q_weight"].dtype
    with jax.named_scope("serve.attn_index"):
        qi = _ops.mm("bsc,gc->bsg", u, p["index_q_weight"]).reshape(
            B, S, Hi, di).transpose(0, 2, 1, 3)
        ki = _ops.mm("bsc,gc->bsg", u, p["index_k_weight"])
        mu = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mu), axis=-1, keepdims=True)
        ki = (ki - mu) * lax.rsqrt(var + z.eps) \
            * p["index_k_norm_gamma"].astype(jnp.float32) \
            + p["index_k_norm_beta"].astype(jnp.float32)
        w = _ops.mm("bsc,hc->bsh", u, p["index_w_weight"]) * (Hi * di) ** -0.5
        return (_ops.rope(qi, pos, z.rope_theta, di).astype(dt),
                _ops.rope(ki, pos, z.rope_theta, di).astype(dt), w)


def _softmax_route(z, p, x):
    from ...ops import moe

    return _ops.route(z, p, x, lambda u: moe.softmax_topk_route(
        u, p["router_weight"], z.experts_per_token))


def _experts(z, w, l, x, route, valid, tally=None):
    return _ops.experts_of_layer(z, w["experts_gate_up_weight"],
                                 w["experts_down_weight"], l, x, route,
                                 valid, tally)


def _block_layer(z, p, x, pos, last, tally=None):
    """A layer on a block (B, S, C) that attends inside itself, as far as
    a row needs no other row: the selection, attention over it and the
    router.  Returns (x, (k, v, ki, route, keys selected a row)).
    ``tally``: a Counter of the attention calls, by path."""
    import jax
    import jax.numpy as jnp

    B, S, _ = x.shape
    u, q, k, v = _qkv(z, p, x, pos)
    qi, ki, w = _index(z, p, u, pos)
    with jax.named_scope("serve.attn_select"):
        mask = indexed_attention.select_prefill(
            qi, w, ki.swapaxes(1, 2), last, z.topk)
        real = jnp.arange(S)[None, :] <= last[:, None]
        selected = jnp.sum(
            jnp.where(real, jnp.sum(mask, axis=-1, dtype=jnp.int32), 0),
            axis=-1).astype(jnp.uint32)
    with jax.named_scope("serve.attn_sparse"):
        # the flash forward body under the mask: a key head's query
        # heads lie side by side, the queries arrive scaled
        a = pallas_attention.flash_attention_forward(
            q.reshape(B, z.num_heads, S, z.head_dim), k, v, last + 1,
            scale=1.0, keep=mask).reshape(q.shape)
        if tally is not None:
            tally["kernel"] += 1
    with jax.named_scope("serve.attn_out"):
        x = _ops.attn_out(z, p, x, a)
    return x, (k, v, ki, _softmax_route(z, p, x), selected)


def _scanned(z, w):
    """What the layer loop scans: each layer's weights but the experts',
    and the layer's number."""
    import jax.numpy as jnp

    return ({n: w[n] for n in _SCANNED_LEAVES},
            jnp.arange(z.num_layers, dtype=jnp.int32))


def _forward(z, names, ids, *weights):
    """(B, T) ids → (B, T, vocab) float32 logits, no cache."""
    import jax.numpy as jnp
    from jax import lax

    w = dict(zip(names, weights))
    ids = ids.astype(jnp.int32)
    B, T = ids.shape
    S = indexed_attention.padded_length(T)
    ids = jnp.pad(ids, ((0, 0), (0, S - T)))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    last = jnp.full((B,), T - 1, jnp.int32)
    x = jnp.take(w["embed_weight"], ids, axis=0).astype(jnp.float32)
    rows = _ops.chunk_rows(z, B, S)

    def layer(x, per):
        p, l = per
        x, (_, _, _, route, _) = _ops.by_rows(
            lambda x, pos, last: _block_layer(z, p, x, pos, last),
            rows, x, pos, last)
        x, _ = _experts(z, w, l, x, route, pos < T)
        return x, None

    x, _ = lax.scan(layer, x, _scanned(z, w))
    h = _ops.rms_norm(x[:, :T], w["lnf_gamma"], z.eps)
    return _ops.mm("btc,vc->btv", h, w["head_weight"])


class KeyeVL2Model(HybridBlock):
    """Embedding → ``num_layers`` alike layers → RMSNorm → untied head.
    Input (B, T) token ids, output (B, T, vocab) float32 logits.

    Parameters are stacked by layer and created in ``dtype``;
    ``grad_req="null"`` keeps a serving copy from allocating
    gradients."""

    def __init__(self, vocab_size, units, num_layers, num_heads, kv_heads,
                 head_dim, index_heads, index_dim, topk, expert_hidden,
                 router_experts, experts_per_token, experts_held=None,
                 rope_theta=1e7, eps=1e-6, max_length=2048,
                 dtype="float32", grad_req="write",
                 prefill_chunk_tokens=4096, moe_pass_rows=None, **kwargs):
        super().__init__(**kwargs)
        held = tuple(experts_held or (0, router_experts))
        if held[0] < 0 or held[0] + held[1] > router_experts:
            raise MXNetError(f"KeyeVL2Model: experts_held {held} lies "
                             f"outside the router's {router_experts}")
        if num_heads % kv_heads:
            raise MXNetError("KeyeVL2Model: kv_heads must divide num_heads")
        self._max_length = max_length
        self._vocab = vocab_size
        self._sizes = z = _Sizes(
            vocab=vocab_size, units=units, num_layers=num_layers,
            num_heads=num_heads, kv_heads=kv_heads, head_dim=head_dim,
            index_heads=index_heads, index_dim=index_dim, topk=topk,
            rope_theta=float(rope_theta), expert_hidden=expert_hidden,
            router_experts=router_experts,
            experts_per_token=experts_per_token, experts_held=held,
            eps=float(eps), max_length=max_length,
            prefill_chunk_tokens=prefill_chunk_tokens,
            moe_pass_rows=moe_pass_rows)
        self._names = z.leaf_names()
        with self.name_scope():
            for name in self._names:
                setattr(self, name, self.params.get(
                    name, shape=z.shape_of(name), dtype=dtype,
                    grad_req=grad_req))

    def hybrid_forward(self, F, ids, **params):
        import functools

        from ...ndarray.register import invoke_simple

        fn = functools.partial(_forward, self._sizes, tuple(self._names))
        fn.__name__ = "keye_vl2_forward"
        return invoke_simple(fn, (ids,) + tuple(params[n]
                                                 for n in self._names))

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        """What `serving.ServingEngine` serves this family through."""
        if mesh is not None:
            raise MXNetError(
                "KeyeVL2Model serves from one chip: its experts are a "
                "share of a deployment whose exchange this repo does not "
                "have (mesh= is not supported for this family)")
        return KeyeVL2Program(self, dtype)


class KeyeVL2Program(DecoderProgram):
    """The family's decoder program (docs/serving.md,
    `_decoder_program.py`): its cache's shapes, its layers and its head."""

    def __init__(self, model, dtype=None):
        super().__init__(model, dtype)
        z = self._z
        # what a reloaded model must share beyond its shapes
        self.signature = (z.num_heads, z.kv_heads, z.index_heads, z.topk,
                          z.experts_held, z.experts_per_token, z.rope_theta)

    def cache_shapes(self, B):
        """(keys, values, indexer keys), then the expert and the
        attention counters."""
        import jax.numpy as jnp

        z = self._z
        L, W = z.num_layers, self.window
        return ([((L, B, z.kv_heads, z.head_dim, W), None),
                 ((L, B, z.kv_heads, z.head_dim, W), None),
                 ((L, B, 1, z.index_dim, W), None)],
                [((L, 2, z.experts_held[1] + 3), jnp.int32),
                 # [layer, prefill / decode, live / selected]: a layer's
                 # live keys of a 16 x 16,384 prefill pass 2**31
                 ((L, 2, 2), jnp.uint32)])

    def counters(self, cache):
        """The counters of one served group, read back once
        (docs/observability.md has the table): the expert layers' under
        MiMo's names, and the keys attention could read (live) and read
        (selected), summed over rows, layers and steps."""
        import numpy as np

        out = _ops.moe_counters(cache[3], self._z.experts_held[1])
        c = np.asarray(cache[4]).astype(np.int64).sum(axis=0)
        for i, phase in enumerate(("prefill", "decode")):
            out[f"attn_keys_live_{phase}"] = int(c[i, 0])
            out[f"attn_keys_selected_{phase}"] = int(c[i, 1])
        return out

    # -- the traced step -------------------------------------------------------

    def body(self, ctx, w, cache, toks):
        """S > 1 is a prefill from an empty cache: it attends inside the
        block.  S = 1 attends over the caches, a row that wants no token
        to nothing; it goes to no expert and is counted nowhere."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        z = self._z
        pos, last, live, held = ctx.pos, ctx.last, ctx.live, ctx.held
        decode = ctx.decode
        W, n = self.window, z.experts_held[1]
        x, at, valid = _ops.embed(w["embed_weight"], toks, pos, last)
        rows = _ops.chunk_rows(z, ctx.B, ctx.S)

        def write(stacks, k, v, ki, l):
            """Row b's new keys, values and indexer keys (.., S, D) into
            the three stacks at [l, b, :, :, pos[b]:]."""
            with jax.named_scope("serve.cache_write"):
                return ctx.write(
                    stacks, [k.swapaxes(2, 3), v.swapaxes(2, 3),
                             ki.swapaxes(1, 2)[:, None]], l, pos)

        def of_layer(c, l):
            return lax.dynamic_index_in_dim(c, l, 0, keepdims=False)

        def decode_layer(x, stacks, p, l):
            u, q, k, v = _qkv(z, p, x, at)
            qi, ki, wi = _index(z, p, u, at)
            ck, cv, ci = stacks = write(stacks, k, v, ki, l)
            with jax.named_scope("serve.attn_index"):
                index = indexed_attention.index_scores_decode(
                    qi[:, :, 0], wi[:, 0], of_layer(ci, l)[:, 0])
            with jax.named_scope("serve.attn_select"):
                written = jnp.arange(W)[None, :] <= pos[:, None]
                mask = indexed_attention.select_topk(index, written, z.topk)
            with jax.named_scope("serve.attn_sparse"):
                a = ctx.attend(q[:, :, :, 0], ck, cv, l, mask=mask)
            with jax.named_scope("serve.attn_out"):
                x = _ops.attn_out(z, p, x, a[:, :, :, None])
            seen = jnp.stack([jnp.sum(held),
                              jnp.sum(mask & live[:, None])])
            return x, stacks, _softmax_route(z, p, x), seen

        def prefill_layer(x, stacks, p, l):
            x, (k, v, ki, route, selected) = _ops.by_rows(
                lambda x, at, last: _block_layer(z, p, x, at, last,
                                                  ctx.attends),
                rows, x, at, last)
            n_live = (last + 1).astype(jnp.uint32)
            seen = jnp.stack([jnp.sum(n_live * (n_live + 1) // 2),
                              jnp.sum(selected)])
            return x, write(stacks, k, v, ki, l), route, seen

        def layer(carry, per):
            x, stacks, moe_counts, attn_counts = carry
            p, l = per
            x, stacks, route, seen = (decode_layer if decode
                                      else prefill_layer)(x, stacks, p, l)
            # padding and rows that want no token are routed nowhere:
            # only tokens that are kept cost
            x, stats = _experts(z, w, l, x, route,
                                live[:, None] if decode else valid,
                                ctx.products)
            moe_counts = moe_counts.at[l, int(decode)].add(
                _ops.moe_count_row(stats, n))
            attn_counts = attn_counts.at[l, int(decode)].add(
                seen.astype(jnp.uint32))
            return (x, stacks, moe_counts, attn_counts), None

        # the stacks are carried, not scanned (a scanned output is a new
        # stacked buffer); the weights are scanned
        (x, stacks, moe_counts, attn_counts), _ = lax.scan(
            layer, (x, tuple(cache[:3]), cache[3], cache[4]), _scanned(z, w))
        with jax.named_scope("serve.head"):
            h = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            logits = _ops.mm("bc,vc->bv", _ops.rms_norm(h, w["lnf_gamma"], z.eps),
                         w["head_weight"])
        return stacks + (moe_counts, attn_counts), logits


def keye_vl2_tiny(**kwargs):
    """A test-sized member of the family."""
    cfg = dict(vocab_size=96, units=64, num_layers=3, num_heads=4,
               kv_heads=2, head_dim=16, index_heads=2, index_dim=8, topk=8,
               expert_hidden=32, router_experts=8, experts_per_token=2,
               max_length=64)
    cfg.update(kwargs)
    return KeyeVL2Model(**cfg)

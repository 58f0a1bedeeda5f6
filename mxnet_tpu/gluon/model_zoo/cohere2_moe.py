"""Command A+ (``model_type`` ``cohere2_moe``), the language model: a
decoder of Cohere2's **parallel block** with the feed-forward replaced
by sigmoid-selected experts.

For every layer, from the residual stream ``x``:

- **one norm feeds both branches**: ``h = LN(x)``, a LayerNorm with a
  gain and no bias (`_decoder_ops.layer_norm`);
- **attention** reads ``h``: ``num_heads`` query heads over ``kv_heads``
  key/value heads of ``head_dim`` (query head t reads key head
  ``t // (num_heads / kv_heads)``), no bias, no query/key norm.  Layers
  are of two kinds, listed by ``layer_types``: a **window** layer
  rotates queries and keys at the token's position over the whole head,
  dimension 2j paired with 2j + 1 (``rope_gptj``), and position i
  attends to ``i - window + 1 .. i``; a **full** layer rotates nothing
  (no positional term at all) and attends to ``0 .. i``;
- **the experts read the same ``h``**: sigmoid scores over all
  ``router_experts``, the ``experts_per_token`` largest, their scores
  normalised to sum to one (`ops/moe.py::sigmoid_topk_route` with a
  zero bias), of which this block holds ``experts_held = (lo, n)``, a
  chip's share of an expert-parallel deployment (what the absent
  experts would add is left out and nothing stands in for their
  exchange); and ``shared_experts`` shared SwiGLU experts of the same
  width whose outputs are **averaged**: they run as one SwiGLU of width
  ``shared_experts x expert_hidden`` (gate and up matrices side by
  side, down matrices stacked) whose result is divided by their number;
- **one sum**: ``x + attention + routed + shared``.  Neither branch
  reads the other's result.

An unscaled embedding, a final LayerNorm, and a **tied head**:
``logit_scale x LN(x) Eᵀ``, the embedding read as it lies.

``hybrid_forward`` is the uncached full-sequence forward.
``decoder_program`` hands `serving.ServingEngine` the family's program
(`_decoder_program.DecoderProgram`; docs/serving.md, "The decoder
program"), of which this file states the cache's shapes and the layer
body.  Two kinds of cache, four stacks:

- full layers: keys and values ``(Lf, B, K, D, W)``; row b's block
  lands at ``pos[b] ..``;
- window layers: **rings** of ``window`` slots ``(Lw, B, K, D,
  window)``: position p lives in slot ``p mod window``.  A decode step
  writes one slot through the row-write kernel and attends over
  ``min(pos + 1, window)`` slots through
  `ops/cache_attention.py::attend_rows` (once a ring is full every slot
  is live, and their order does not matter to a softmax); a prefill
  leaves each row's last ``min(length, window)`` positions
  (`cache_write.write_ring`);
- two small counter arrays ride in the same donated carry and are read
  back once a group (``counters``).

Prefill (S > 1, from an empty cache) attends inside the block through
`ops/pallas_attention.py::flash_attention_forward`, each row to its own
length, window layers with ``window=``: no key block behind the band is
copied or multiplied.  The key and value heads go in as they are (8
beside 128 query heads).  **A row chunk goes through all its layers
before the next** (``prefill_chunk_tokens // S`` rows, one at the
published sizes) and inside a layer the token-wise products are cut
along S (`_decoder_ops.by_tokens`, ``token_chunk`` positions): one 16k
row's queries are 537 MB and its shared experts' hidden 1.07 GB.
"""

from __future__ import annotations

from ...base import MXNetError
from ...ops import pallas_attention
from ..block import HybridBlock
from . import _decoder_ops as _ops
from ._decoder_program import DecoderProgram

_LAYER_LEAVES = ("ln_gamma", "q_weight", "k_weight", "v_weight", "o_weight",
                 "router_weight", "shared_gate_weight", "shared_up_weight",
                 "shared_down_weight", "experts_gate_up_weight",
                 "experts_down_weight")
_KINDS = {"full": "full", "full_attention": "full", "window": "window",
          "sliding_attention": "window"}


class _Sizes:
    """The family's sizes, as the constructor got them."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.of_kind = {t: [i for i, x in enumerate(self.layer_types)
                            if x == t] for t in ("full", "window")}
        self.groups = self.num_heads // self.kv_heads

    def leaves(self):
        """[(parameter name, shape)], in the order of the weight tuple."""
        z = self
        C, D, F = z.units, z.head_dim, z.expert_hidden
        Fs, n = z.shared_experts * F, z.experts_held[1]
        layer = {"ln_gamma": (C,),
                 "q_weight": (z.num_heads * D, C),
                 "k_weight": (z.kv_heads * D, C),
                 "v_weight": (z.kv_heads * D, C),
                 "o_weight": (C, z.num_heads * D),
                 "router_weight": (z.router_experts, C),
                 "shared_gate_weight": (Fs, C), "shared_up_weight": (Fs, C),
                 "shared_down_weight": (C, Fs),
                 "experts_gate_up_weight": (n, C, 2 * F),
                 "experts_down_weight": (n, F, C)}
        return ([("embed_weight", (z.vocab, C))]
                + [(f"l{i}_{name}", layer[name])
                   for i in range(len(z.layer_types))
                   for name in _LAYER_LEAVES] + [("lnf_gamma", (C,))])


# -- a layer's pieces, shared by the forward pass and the cached step ----------

def _norm(z, p, x):
    """The block's one norm, in the weights' type: what both branches
    read."""
    import jax

    with jax.named_scope("serve.norm"):
        return _ops.layer_norm(x, p["ln_gamma"], z.eps).astype(
            p["q_weight"].dtype)


def _qkv(z, kind, p, h, pos):
    """h (B, S, C) → q (B, H, S, D), k and v (B, K, S, D), float32,
    unscaled; on a window layer q and k rotated at ``pos`` (B, S) over
    the whole head in interleaved pairs, on a full layer as they are."""
    import jax

    B, S, _ = h.shape
    D = z.head_dim
    with jax.named_scope("serve.attn_qkv"):
        def heads(w, n):
            return _ops.mm("bsc,gc->bsg", h, w).reshape(B, S, n, D
                                                    ).transpose(0, 2, 1, 3)

        q, k = heads(p["q_weight"], z.num_heads), heads(p["k_weight"],
                                                       z.kv_heads)
        if kind == "window":
            q, k = (_ops.rope(a, pos, z.rope_theta, D, pairs="interleaved")
                    for a in (q, k))
        return q, k, heads(p["v_weight"], z.kv_heads)


def _experts_front(z, p, h):
    """What of the expert branch a token needs no other token for: the
    router's choice and the averaged shared experts.  h (B, S, C) in the
    weights' type → (shared (B, S, C) float32, (chosen, weights))."""
    import jax
    import jax.numpy as jnp

    from ...ops import moe

    B, S, C = h.shape
    k = z.experts_per_token
    with jax.named_scope("serve.moe.route"):
        chosen, weights = moe.sigmoid_topk_route(
            h.reshape(B * S, C), p["router_weight"],
            jnp.zeros((z.router_experts,), jnp.float32), k)
    with jax.named_scope("serve.moe.shared"):
        shared = moe.swiglu_ffn(h, p["shared_gate_weight"],
                                p["shared_up_weight"],
                                p["shared_down_weight"])
        if z.shared_experts != 1:
            shared = shared * (1.0 / z.shared_experts)
    return shared, (chosen.reshape(B, S, k), weights.reshape(B, S, k))


def _experts(z, p, h, route, valid, add_to, tally=None):
    """``add_to`` + the held experts' part for the routed tokens; also
    `held_experts_ffn`'s counts.  ``tally``: the program's count of
    such calls by the grouped product's path."""
    import jax

    from ...ops import moe

    B, S, C = h.shape
    chosen, weights = route
    k = z.experts_per_token
    with jax.named_scope("serve.moe.experts"):
        y, stats = moe.held_experts_ffn(
            h.reshape(B * S, C), chosen.reshape(B * S, k),
            weights.reshape(B * S, k), p["experts_gate_up_weight"],
            p["experts_down_weight"], experts_lo=z.experts_held[0],
            valid=None if valid is None else valid.reshape(B * S),
            add_to=add_to.reshape(B * S, C), tally=tally)
        return y.reshape(B, S, C), stats


def _block_layer(z, kind, p, x, pos, lengths, valid, tally=None,
                 products=None):
    """A layer on a block (B, S, C) that attends inside itself.
    ``lengths`` (B,) traced, the rows' real positions, or None for all:
    token-wise products run ``token_chunk`` positions at a time up to
    the longest row's, the kernel to each row's own, and a row's queries
    past its length come out zero.  ``tally``, ``products``: the
    program's counts of the attention calls and of the experts' calls,
    by path.  Returns (x, k, v (B, K, S, D) in the weights' type,
    `held_experts_ffn`'s counts)."""
    import jax
    import jax.numpy as jnp

    S, D = x.shape[1], z.head_dim
    dt = p["q_weight"].dtype
    chunk = min(S, z.token_chunk)
    live = None if lengths is None else jnp.max(lengths)

    def front(x, pos):
        q, k, v = _qkv(z, kind, p, _norm(z, p, x), pos)
        return None, (q.astype(dt), k.astype(dt), v.astype(dt))

    def back(x, a):
        """Both branches' token-wise parts from the same h, and their
        sum with the stream: the routed experts' part is added where
        the whole row is at hand."""
        h = _norm(z, p, x)
        with jax.named_scope("serve.attn_out"):
            # heads first as the kernel left them: contracted where they lie
            a = _ops.mm("bhsd,chd->bsc", a, p["o_weight"].reshape(
                -1, z.num_heads, D))
        shared, route = _experts_front(z, p, h)
        return x + a + shared, (h,) + route

    _, (q, k, v) = _ops.by_tokens(front, chunk, live, x, pos,
                                  out_axes=(2, 2, 2))
    with jax.named_scope(f"serve.attn_{kind}"):
        a = pallas_attention.flash_attention_forward(
            q, k, v, lengths, scale=D ** -0.5,
            window=z.window if kind == "window" else None)
        if tally is not None:
            tally["kernel"] += 1
    x, (h, chosen, weights) = _ops.by_tokens(back, chunk, live, x, a,
                                             axes=(2,))
    x, stats = _experts(z, p, h, (chosen, weights), valid, x, products)
    return x, k, v, stats


def _head(z, w, x):
    """x (B, .., C) → logits over the vocabulary, float32: the final
    norm and the embedding read as it lies."""
    return z.logit_scale * _ops.mm(
        "...c,vc->...v", _ops.layer_norm(x, w["lnf_gamma"], z.eps),
        w["embed_weight"])


def _forward(z, names, ids, *weights):
    """(B, T) ids → (B, T, vocab) float32 logits, no cache."""
    import jax.numpy as jnp

    w = dict(zip(names, weights))
    ids = ids.astype(jnp.int32)
    B, T = ids.shape
    chunk = min(T, z.token_chunk)
    S = T + -T % chunk
    ids = jnp.pad(ids, ((0, 0), (0, S - T)))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = jnp.take(w["embed_weight"], ids, axis=0).astype(jnp.float32)
    for i, kind in enumerate(z.layer_types):
        p = {n: w[f"l{i}_{n}"] for n in _LAYER_LEAVES}
        x, _, _, _ = _block_layer(z, kind, p, x, pos, None, pos < T)
    return _head(z, w, x[:, :T])


class Cohere2MoeModel(HybridBlock):
    """Embedding → ``layer_types`` parallel blocks → LayerNorm → the
    embedding again as the head.  Input (B, T) token ids, output (B, T,
    vocab) float32 logits.

    ``layer_types`` lists ``"window"`` / ``"full"`` (the source's
    ``"sliding_attention"`` / ``"full_attention"`` are taken too).
    Parameters are created in ``dtype``; ``grad_req="null"`` keeps a
    serving copy from allocating gradients."""

    def __init__(self, vocab_size, units, layer_types, num_heads, kv_heads,
                 head_dim, window, expert_hidden, router_experts,
                 experts_per_token, experts_held=None, shared_experts=1,
                 rope_theta=50000.0, eps=1e-5, logit_scale=1.0,
                 max_length=2048, dtype="float32", grad_req="write",
                 prefill_chunk_tokens=16384, token_chunk=2048, **kwargs):
        super().__init__(**kwargs)
        try:
            layer_types = [_KINDS[t] for t in layer_types]
        except KeyError as exc:
            raise MXNetError("Cohere2MoeModel: layer_types lists 'window' "
                             f"/ 'full', got {exc.args[0]!r}") from exc
        held = tuple(experts_held or (0, router_experts))
        if held[0] < 0 or held[0] + held[1] > router_experts:
            raise MXNetError(f"Cohere2MoeModel: experts_held {held} lies "
                             f"outside the router's {router_experts}")
        if num_heads % kv_heads or head_dim % 2 or window < 1:
            raise MXNetError(
                "Cohere2MoeModel: kv_heads divides num_heads, head_dim is "
                "even (whole-head rotation in pairs), a window holds the "
                "token's own position at least")
        self._max_length = max_length
        self._vocab = vocab_size
        self._sizes = z = _Sizes(
            vocab=vocab_size, units=units, layer_types=layer_types,
            num_heads=num_heads, kv_heads=kv_heads, head_dim=head_dim,
            window=int(window), expert_hidden=expert_hidden,
            router_experts=router_experts,
            experts_per_token=experts_per_token, experts_held=held,
            shared_experts=shared_experts, rope_theta=float(rope_theta),
            eps=float(eps), logit_scale=float(logit_scale),
            max_length=max_length,
            prefill_chunk_tokens=prefill_chunk_tokens,
            token_chunk=token_chunk)
        leaves = z.leaves()
        self._names = [name for name, _ in leaves]
        with self.name_scope():
            for name, shape in leaves:
                setattr(self, name, self.params.get(
                    name, shape=shape, dtype=dtype, grad_req=grad_req))

    def hybrid_forward(self, F, ids, **params):
        import functools

        from ...ndarray.register import invoke_simple

        fn = functools.partial(_forward, self._sizes, tuple(self._names))
        fn.__name__ = "cohere2_moe_forward"
        return invoke_simple(fn, (ids,) + tuple(params[n]
                                                 for n in self._names))

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        """What `serving.ServingEngine` serves this family through."""
        if mesh is not None:
            raise MXNetError(
                "Cohere2MoeModel serves from one chip: its experts are a "
                "share of a deployment whose exchange this repo does not "
                "have (mesh= is not supported for this family)")
        return Cohere2MoeProgram(self, dtype)


class Cohere2MoeProgram(DecoderProgram):
    """The family's decoder program (docs/serving.md,
    `_decoder_program.py`): its cache's shapes, its layers and its head."""

    def __init__(self, model, dtype=None):
        super().__init__(model, dtype)
        z = self._z
        # what a reloaded model must share beyond its shapes
        self.signature = (tuple(z.layer_types), z.num_heads, z.kv_heads,
                          z.window, z.rope_theta, z.experts_held,
                          z.experts_per_token, z.shared_experts,
                          z.logit_scale)

    def cache_shapes(self, B):
        """(full keys, full values, window keys, window values), then
        the expert and the attention counters."""
        import jax.numpy as jnp

        z = self._z
        Lf = max(1, len(z.of_kind["full"]))
        Lw = max(1, len(z.of_kind["window"]))
        K, D, L = z.kv_heads, z.head_dim, len(z.layer_types)
        return ([((Lf, B, K, D, self.window), None),
                 ((Lf, B, K, D, self.window), None),
                 ((Lw, B, K, D, z.window), None),
                 ((Lw, B, K, D, z.window), None)],
                [((L, 2, z.experts_held[1] + 3), jnp.int32),
                 # [layer, asked / causal, prefill / decode]: a layer's
                 # pairs of an 8 x 16,384 prefill are 3.7e8, all
                 # layers' pass 2**31
                 ((L, 2, 2), jnp.uint32)])

    def counters(self, cache):
        """The counters of one served group, read back once
        (docs/observability.md has the table): the expert layers' under
        MiMo's names, and the query-key pairs the window and the full
        layers were asked to score, live rows only, summed over rows,
        layers and steps; ``attn_window_pairs_causal_*`` is what the
        window layers would have scored with no window."""
        import numpy as np

        z = self._z
        out = _ops.moe_counters(cache[4], z.experts_held[1])
        c = np.asarray(cache[5]).astype(np.int64)
        for phase, name in enumerate(("prefill", "decode")):
            for kind in ("window", "full"):
                out[f"attn_{kind}_pairs_{name}"] = int(
                    c[z.of_kind[kind], 0, phase].sum())
            out[f"attn_window_pairs_causal_{name}"] = int(
                c[z.of_kind["window"], 1, phase].sum())
        return out

    # -- the traced step -------------------------------------------------------

    def body(self, ctx, w, cache, toks):
        """S > 1 is a prefill from an empty cache: attention inside the
        block, a row chunk through all layers before the next.  S = 1
        attends over the caches, a row that wants no token to nothing;
        it goes to no routed expert and is counted nowhere."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        z = self._z
        B, decode, live = ctx.B, ctx.decode, ctx.live
        R, D, n_held = z.window, z.head_dim, z.experts_held[1]

        def write(stacks, kind, k, v, l, pos, held, row):
            """Layer ``l``'s new keys and values (R, K, S', D) into its
            kind's two stacks: a full layer's at ``pos``, a ring's at
            ``pos mod window`` (decode) or as the ring a prefilled row of
            ``held`` positions leaves."""
            fk, fv, wk, wv = stacks
            new = [a.astype(fk.dtype).swapaxes(2, 3) for a in (k, v)]
            with jax.named_scope("serve.cache_write"):
                if kind == "full":
                    return (*ctx.write((fk, fv), new, l, pos, row=row),
                            wk, wv)
                if decode:
                    return (fk, fv, *ctx.write((wk, wv), new, l, pos % R,
                                               first=2))
                return (fk, fv, *ctx.write_ring((wk, wv), new, l, held,
                                                row=row, first=2))

        def rows(toks, pos, last, row, carry):
            """Rows ``row ..`` of the group through every layer; carry
            (the four stacks, expert counters, attention counters).
            Returns (carry, the rows' logits)."""
            *stacks, moe_counts, pairs = carry
            x, at, valid = _ops.embed(w["embed_weight"], toks, pos, last)
            # a row's positions, itself included: all of them in a
            # prefill, none of a decode row that wants no token
            held = ctx.held if decode else last + 1
            n = held.astype(jnp.uint32)
            for i, kind in enumerate(z.layer_types):
                p = {name: w[f"l{i}_{name}"] for name in _LAYER_LEAVES}
                l = z.of_kind[kind].index(i)
                if decode:
                    # a ring's slot s holds the latest position
                    # congruent to s: its first pos + 1 slots, then all
                    seen = jnp.minimum(held, R) if kind == "window" else held
                    asked, causal = jnp.sum(seen.astype(jnp.uint32)), \
                        jnp.sum(n)
                    h = _norm(z, p, x)
                    q, k, v = _qkv(z, kind, p, h, at)
                    stacks = write(stacks, kind, k, v, l, pos, held, None)
                    with jax.named_scope(f"serve.attn_{kind}"):
                        ck, cv = stacks[:2] if kind == "full" else stacks[2:]
                        a = ctx.attend(
                            (q[:, :, 0] * D ** -0.5).astype(ck.dtype).reshape(
                                B, z.kv_heads, z.groups, D),
                            ck, cv, l, lengths=seen)
                    with jax.named_scope("serve.attn_out"):
                        a = _ops.mm("bg,cg->bc", a.reshape(B, -1),
                                    p["o_weight"])[:, None]
                    shared, route = _experts_front(z, p, h)
                    x, stats = _experts(z, p, h, route, live[:, None],
                                        x + a + shared, ctx.products)
                else:
                    causal = jnp.sum(n * (n + 1) // 2)
                    asked = causal
                    if kind == "window":
                        m = jnp.minimum(n, R)
                        asked = jnp.sum(m * (m + 1) // 2 + (n - m) * R)
                    x, k, v, stats = _block_layer(z, kind, p, x, at, held,
                                                  valid, ctx.attends,
                                                  ctx.products)
                    stacks = write(stacks, kind, k, v, l, pos, held, row)
                moe_counts = moe_counts.at[i, int(decode)].add(
                    _ops.moe_count_row(stats, n_held))
                pairs = pairs.at[i, :, int(decode)].add(
                    jnp.stack([asked, causal]).astype(jnp.uint32))
            with jax.named_scope("serve.head"):
                logits = _head(z, w, jnp.take_along_axis(
                    x, last[:, None, None], axis=1)[:, 0])
            return (*stacks, moe_counts, pairs), logits

        Rows = B if decode else _ops.chunk_rows(z, B, ctx.S)
        if Rows == B:
            return rows(toks, ctx.pos, ctx.last, None, tuple(cache))

        def chunk(c, state):
            carry, logits = state
            cut = lambda a: lax.dynamic_slice_in_dim(a, c * Rows, Rows,
                                                     axis=0)
            carry, part = rows(cut(toks), cut(ctx.pos), cut(ctx.last),
                               c * Rows, carry)
            return carry, lax.dynamic_update_slice_in_dim(
                logits, part, c * Rows, axis=0)

        return lax.fori_loop(
            0, B // Rows, chunk,
            (tuple(cache), jnp.zeros((B, self.vocab), jnp.float32)))


def cohere2_moe_tiny(**kwargs):
    """A test-sized member of the family with every mechanism present:
    three window layers and a full one, four query heads a key head, a
    ring shorter than the contexts, a share of the experts, two shared
    experts."""
    cfg = dict(vocab_size=96, units=64,
               layer_types=["window", "window", "window", "full"],
               num_heads=8, kv_heads=2, head_dim=16, window=8,
               expert_hidden=32, router_experts=8, experts_per_token=2,
               experts_held=(2, 2), shared_experts=2, rope_theta=50000.0,
               max_length=64, token_chunk=16)
    cfg.update(kwargs)
    return Cohere2MoeModel(**cfg)

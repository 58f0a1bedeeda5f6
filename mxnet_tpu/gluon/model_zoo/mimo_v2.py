"""MiMo-V2 (``model_type`` ``mimo_v2``) language-model family.

A decoder whose layers are of two kinds, listed by ``layer_types``:

- **full** layers attend over every earlier position, with few
  key/value heads; **window** layers attend over the last ``window``
  positions, with their own number of key/value heads, their own rotary
  base and one learnable *sink* logit per query head in the softmax's
  denominator;
- query and key heads are ``qk_dim`` wide with rotary positions on the
  first ``rotary_dim`` (dimension d paired with d + rotary_dim/2), value
  heads ``v_dim`` wide and scaled by ``value_scale``; query head h reads
  key/value head ``h // (heads / kv heads)``;
- RMSNorm, no bias, an unscaled embedding, an untied head;
- the feed-forward is a dense SwiGLU, or (``moe_layers``) sigmoid-routed
  experts of which this block holds ``experts_held = (lo, n)``: a chip's
  share of an expert-parallel deployment (`ops/moe.py::moe_share_ffn`).
  The router scores all ``router_experts``; what the absent experts
  would add is left out, and nothing stands in for their exchange.

``hybrid_forward`` is the uncached full-sequence forward.
``decoder_program`` hands `serving.ServingEngine` the family's program
(`_decoder_program.DecoderProgram`; docs/serving.md, "The decoder
program"), of which this file states the cache's shapes and the layer
body: two kinds of cache, four stacks:

- full layers: keys ``(Lf, B, Hkv, qk_dim, W)`` and values
  ``(.., v_dim, W)``, positions on the minor axis (`ops/cache_write.py`
  says why); row b's block lands at ``pos[b] .. pos[b] + S``;
- window layers: a ring of ``window`` slots, ``(Lw, B, Hkv', ..,
  window)``: position p lives in slot ``p mod window``.  Prefill attends
  inside its own block (the engine always prefills from position 0) and
  then writes each row's last ``min(length, window)`` positions; decode
  writes one slot and attends over the ring, masking slots not yet
  written (a slot's position is the latest one congruent to it, so it
  is never older than the window);
- two small arrays of counters ride in the same donated carry and are
  read back once a group (``counters``): the expert layers', and the
  positions the prefill's token-wise tiles worked beside the real ones.

Prefill attention runs in blocks of ``attn_block`` keys with a running
maximum and sum (the sink enters the sum once), window layers visiting
only the blocks their window reaches; no ``(B, H, S, W)`` array exists
for S > 1.  Rows are worked off ``prefill_chunk_tokens`` tokens at a
time through attention and the dense feed-forward, so that a bucket of
64 x 1,024 tokens fits beside the weights.

**What a token needs no other token for works a row chunk's real tokens
only** (`_decoder_ops.packing`; the engine pads a prompt on the right to
its bucket, and of the served cell's 64 x 1,024 positions two in three
are padding): the first norm, the three projections, the rotation (by a
token's own position, which is packed with it), the scales and the
casts, and after attention the way out, the dense SwiGLU or the second
norm and the router, run on the chunk's tokens packed at the front of
one flat row, in tiles of ``_TILE``, only the tiles that hold a token
(`_decoder_ops.by_tokens`).  What needs rows gets rows: q, k and v are
laid back (`unpack`) for `_attend_blocks`, which takes no lengths and
walks every position of every row, and for the cache writes; the stream
and the router's choice are laid back for the experts, which run over
the whole bucket between two layers with ``valid`` as before.  Past its
length a row holds its last real token again, which nobody reads:
causal attention never looks right of a query, and the rings and the
decode steps read no position past a row's last.  The path is taken
where the rows' lengths are given: the cache-less forward pass hands
none and keeps its rows, and a decode step is one token a row, packed
as it lies.
"""

from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock
from . import _decoder_ops as _ops
from ._decoder_ops import _MASKED
from ._decoder_program import DecoderProgram

# packed tokens a tile of the prefill's token-wise products
_TILE = 512


# -- pieces shared by the forward pass and the cached step ---------------------

def _attend_blocks(q, k, v, sink, window, blk):
    """Causal attention of a block of S positions over itself, in key
    blocks of ``blk`` with a running maximum and sum.

    q (B, K, G, S, D) scaled; k (B, K, S, D); v (B, K, S, Dv); ``sink``
    (K, G) float32 or None; ``window`` positions or None for all.
    Offset o pairs query block n with key block n - o, so a window layer
    makes ``1 + ceil((window - 1) / blk)`` passes and a full layer one
    per block.  Returns (B, K, G, S, Dv) float32."""
    import jax.numpy as jnp
    from jax import lax

    B, K, G, S, D = q.shape
    Dv = v.shape[-1]
    nb = S // blk
    n_off = nb if window is None else min(nb, 1 + -(-(window - 1) // blk))
    qb = q.reshape(B, K, G, nb, blk, D)
    front = (n_off - 1) * blk
    kp = jnp.pad(k, ((0, 0), (0, 0), (front, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (front, 0), (0, 0)))
    at = jnp.arange(blk)
    q_at = jnp.arange(nb)[:, None, None] * blk + at[None, :, None]

    def one_offset(o, carry):
        m, l, acc = carry
        start = (n_off - 1 - o) * blk
        ko = lax.dynamic_slice_in_dim(kp, start, S, axis=2
                                      ).reshape(B, K, nb, blk, D)
        vo = lax.dynamic_slice_in_dim(vp, start, S, axis=2
                                      ).reshape(B, K, nb, blk, Dv)
        s = jnp.einsum("bkgnqd,bknsd->bkgnqs", qb, ko,
                       preferred_element_type=jnp.float32)
        k_at = (jnp.arange(nb)[:, None, None] - o) * blk + at[None, None, :]
        seen = (k_at >= 0) & (k_at <= q_at)
        if window is not None:
            seen = seen & (k_at > q_at - window)
        s = jnp.where(seen, s, _MASKED)
        m2 = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m2[..., None])
        scale = jnp.exp(m - m2)
        acc = acc * scale[..., None] + jnp.einsum(
            "bkgnqs,bknsd->bkgnqd", p.astype(vo.dtype), vo,
            preferred_element_type=jnp.float32)
        return m2, l * scale + jnp.sum(p, axis=-1), acc

    stat = (B, K, G, nb, blk)
    if sink is None:
        m0, l0 = jnp.full(stat, _MASKED, jnp.float32), jnp.zeros(stat)
    else:
        # the sink is one more key, of no value: it opens the running sum
        m0 = jnp.broadcast_to(sink[None, :, :, None, None], stat)
        l0 = jnp.ones(stat)
    _, l, acc = lax.fori_loop(
        0, n_off, one_offset,
        (m0.astype(jnp.float32), l0.astype(jnp.float32),
         jnp.zeros(stat + (Dv,), jnp.float32)))
    return (acc / l[..., None]).reshape(B, K, G, S, Dv)


class _Sizes:
    """The family's sizes, as the constructor got them."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.moe_at = [i for i, m in enumerate(self.moe_layers) if m]
        self.of_kind = {t: [i for i, x in enumerate(self.layer_types)
                            if x == t] for t in ("full", "window")}

    def layer_names(self, i):
        names = ["ln1_gamma", "q_weight", "k_weight", "v_weight",
                 "o_weight"]
        if self.layer_types[i] == "window":
            names.append("sink_bias")
        names.append("ln2_gamma")
        names += (["router_weight", "router_bias", "experts_gate_up_weight",
                   "experts_down_weight"] if self.moe_layers[i]
                  else ["gate_weight", "up_weight", "down_weight"])
        return names

    def leaf_names(self):
        return (["embed_weight"]
                + [f"l{i}_{n}" for i in range(len(self.layer_types))
                   for n in self.layer_names(i)]
                + ["lnf_gamma", "head_weight"])

    def shape_of(self, i, name):
        z = self
        hkv = z.kv_heads[z.layer_types[i]]
        n = z.experts_held[1]
        return {"ln1_gamma": (z.units,), "ln2_gamma": (z.units,),
                "q_weight": (z.num_heads * z.qk_dim, z.units),
                "k_weight": (hkv * z.qk_dim, z.units),
                "v_weight": (hkv * z.v_dim, z.units),
                "o_weight": (z.units, z.num_heads * z.v_dim),
                "sink_bias": (z.num_heads,),
                "gate_weight": (z.hidden_size, z.units),
                "up_weight": (z.hidden_size, z.units),
                "down_weight": (z.units, z.hidden_size),
                "router_weight": (z.router_experts, z.units),
                "router_bias": (z.router_experts,),
                "experts_gate_up_weight": (n, z.units, 2 * z.expert_hidden),
                "experts_down_weight": (n, z.expert_hidden, z.units)}[name]


def _qkv(z, kind, p, x, pos):
    """x (B, S, C) float32 → q (B, K, G, S, D) scaled and rotated,
    k (B, K, S, D) rotated, v (B, K, S, Dv) scaled; all in the weights'
    type.  The products are plain ``x Wᵀ``: a weight is read as it lies,
    and only activations are re-laid."""
    import jax

    K = z.kv_heads[kind]
    G, D, Dv = z.num_heads // K, z.qk_dim, z.v_dim
    B, S, _ = x.shape
    dt = p["q_weight"].dtype
    with jax.named_scope("serve.attn_qkv"):
        u = _ops.rms_norm(x, p["ln1_gamma"], z.eps).astype(dt)

        def heads(w, n, d):
            return _ops.mm("bsc,gc->bsg", u, w).reshape(B, S, n, d
                                                    ).transpose(0, 2, 1, 3)

        theta = z.rope_theta[kind]
        q = _ops.rope(heads(p["q_weight"], K * G, D), pos, theta,
                  z.rotary_dim) * (D ** -0.5)
        k = _ops.rope(heads(p["k_weight"], K, D), pos, theta, z.rotary_dim)
        v = heads(p["v_weight"], K, Dv) * z.value_scale
        return (q.astype(dt).reshape(B, K, G, S, D), k.astype(dt),
                v.astype(dt))


def _sink(z, kind, p):
    import jax.numpy as jnp

    if kind != "window":
        return None
    K = z.kv_heads[kind]
    return p["sink_bias"].astype(jnp.float32).reshape(K, z.num_heads // K)


def _dense(z, p, x):
    import jax

    from ...ops import moe

    with jax.named_scope("serve.mlp"):
        u = _ops.rms_norm(x, p["ln2_gamma"], z.eps)
        return x + moe.swiglu_ffn(u, p["gate_weight"], p["up_weight"],
                                  p["down_weight"])


def _experts(z, p, x, route, valid, tally=None):
    """x + the held experts' part for the routed tokens; also
    `held_experts_ffn`'s counts.  ``tally``: the program's count of
    such calls by the grouped product's path."""
    import jax

    from ...ops import moe

    B, S, C = x.shape
    u, chosen, weights = route
    k = z.experts_per_token
    with jax.named_scope("serve.moe.experts"):
        y, stats = moe.held_experts_ffn(
            u.reshape(B * S, C), chosen.reshape(B * S, k),
            weights.reshape(B * S, k), p["experts_gate_up_weight"],
            p["experts_down_weight"], experts_lo=z.experts_held[0],
            valid=None if valid is None else valid.reshape(B * S),
            pass_rows=z.moe_pass_rows, add_to=x.reshape(B * S, C),
            tally=tally)
        return y.reshape(B, S, C), stats


def _feed_forward_front(z, i, p, x):
    """What of layer i's feed-forward a token needs no other token for:
    the whole dense SwiGLU, or the norm and the router (sigmoid scores
    with the correction bias).  Returns (x, route or ())."""
    from ...ops import moe

    if z.moe_layers[i]:
        return x, _ops.route(z, p, x, lambda u: moe.sigmoid_topk_route(
            u, p["router_weight"], p["router_bias"], z.experts_per_token))
    return _dense(z, p, x), ()


def _block_layer(z, i, p, x, pos, held=None):
    """Layer i on a block (B, S, C) that attends inside itself, as far
    as a row needs no other row: attention, then the dense feed-forward
    or the router.  Returns (x, (k, v, route)).

    Given the rows' lengths ``held`` (B,), what acts on one token at a
    time works the block's real tokens packed, a tile at a time, and
    only attention works rows; past its length a row then holds its
    last real token's values again."""
    import jax
    import jax.numpy as jnp

    kind = z.layer_types[i]
    B, S, C = x.shape
    K = z.kv_heads[kind]
    G = z.num_heads // K

    def attend(q, k, v):
        with jax.named_scope(f"serve.attn_{kind}"):
            return _attend_blocks(q, k, v, _sink(z, kind, p),
                                  z.window if kind == "window" else None,
                                  min(S, z.attn_block))

    def way_out(x, a):
        with jax.named_scope(f"serve.attn_{kind}"):
            return _ops.attn_out(z, p, x, a)

    if held is None:
        q, k, v = _qkv(z, kind, p, x, pos)
        x, route = _feed_forward_front(z, i, p, way_out(x, attend(q, k, v)))
        return x, (k, v, route)

    pk = _ops.packing(held, S, _TILE)

    def in_rows(a, *heads):
        """Packed (1, P, ..) → rows; with ``heads``, (B, *heads, S, .)."""
        a = _ops.unpack(a, pk.slot)
        return jnp.moveaxis(a.reshape((B, S) + heads + (-1,)), 1, -2) \
            if heads else a

    # a packed token is a row of one position: `_qkv` and `attn_out`
    # take it as they take a decode step's
    def front(x, pos):
        t = x.shape[1]
        return None, tuple(a.reshape(1, t, -1) for a in _qkv(
            z, kind, p, x.reshape(t, 1, C), pos.reshape(t, 1)))

    x = _ops.pack(x, pk.src)
    _, (q, k, v) = _ops.by_tokens(front, pk.tile, pk.n, x,
                                  _ops.pack(pos, pk.src))
    k, v = in_rows(k, K), in_rows(v, K)
    a = attend(in_rows(q, K, G), k, v)
    # heads side by side in the weights' type, as the way out casts it:
    # packed a tile at a time, only the tiles that are worked
    a = jnp.moveaxis(a, 3, 1).reshape(B, S, -1).astype(p["o_weight"].dtype)

    def back(x, src):
        t = x.shape[1]
        x = way_out(x.reshape(t, 1, C),
                    _ops.pack(a, src).reshape(t, K, G, 1, -1))
        return _feed_forward_front(z, i, p, x.reshape(1, t, C))

    x, route = _ops.by_tokens(back, pk.tile, pk.n, x, pk.src)
    return in_rows(x), (k, v, tuple(in_rows(r) for r in route))


def _forward(z, names, ids, *weights):
    """(B, T) ids → (B, T, vocab) float32 logits, no cache."""
    import jax.numpy as jnp

    w = dict(zip(names, weights))
    ids = ids.astype(jnp.int32)
    B, T = ids.shape
    blk = min(T, z.attn_block)
    pad = -T % blk
    ids = jnp.pad(ids, ((0, 0), (0, pad)))
    S = T + pad
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    valid = pos < T
    x = jnp.take(w["embed_weight"], ids, axis=0).astype(jnp.float32)
    rows = _ops.chunk_rows(z, B, S)
    for i in range(len(z.layer_types)):
        p = {n: w[f"l{i}_{n}"] for n in z.layer_names(i)}
        x, (_, _, route) = _ops.by_rows(
            lambda x, pos, i=i, p=p: _block_layer(z, i, p, x, pos),
            rows, x, pos)
        if route:
            x, _ = _experts(z, p, x, route, valid)
    h = _ops.rms_norm(x[:, :T], w["lnf_gamma"], z.eps)
    return _ops.mm("btc,vc->btv", h, w["head_weight"])


class MiMoV2Model(HybridBlock):
    """Embedding → ``layer_types`` layers → RMSNorm → untied head.
    Input (B, T) token ids, output (B, T, vocab) float32 logits.

    Parameters are created in ``dtype`` (a float32 copy of a large
    share does not fit a chip).  ``grad_req="null"`` keeps a serving
    copy from allocating gradients."""

    def __init__(self, vocab_size, units, layer_types, moe_layers,
                 num_heads, kv_heads, swa_kv_heads, qk_dim, v_dim,
                 rotary_dim, window, rope_theta, swa_rope_theta,
                 hidden_size, expert_hidden, router_experts,
                 experts_per_token, experts_held=None, value_scale=1.0,
                 eps=1e-5, max_length=2048, dtype="float32",
                 grad_req="write", attn_block=128,
                 prefill_chunk_tokens=4096, moe_pass_rows=None, **kwargs):
        super().__init__(**kwargs)
        layer_types, moe_layers = list(layer_types), list(moe_layers)
        if len(layer_types) != len(moe_layers) or \
                set(layer_types) - {"full", "window"}:
            raise MXNetError(
                "MiMoV2Model: layer_types lists 'full' / 'window', and "
                "moe_layers has one entry a layer")
        held = tuple(experts_held or (0, router_experts))
        if held[0] < 0 or held[0] + held[1] > router_experts:
            raise MXNetError(f"MiMoV2Model: experts_held {held} lies "
                             f"outside the router's {router_experts}")
        self._max_length = max_length
        self._vocab = vocab_size
        self._sizes = z = _Sizes(
            vocab=vocab_size, units=units, layer_types=layer_types,
            moe_layers=[bool(m) for m in moe_layers], num_heads=num_heads,
            kv_heads={"full": kv_heads, "window": swa_kv_heads},
            qk_dim=qk_dim, v_dim=v_dim, rotary_dim=rotary_dim,
            window=window,
            rope_theta={"full": float(rope_theta),
                        "window": float(swa_rope_theta)},
            hidden_size=hidden_size, expert_hidden=expert_hidden,
            router_experts=router_experts,
            experts_per_token=experts_per_token, experts_held=held,
            value_scale=float(value_scale), eps=float(eps),
            max_length=max_length, attn_block=attn_block,
            prefill_chunk_tokens=prefill_chunk_tokens,
            moe_pass_rows=moe_pass_rows)
        self._names = z.leaf_names()

        def add(name, shape):
            setattr(self, name, self.params.get(
                name, shape=shape, dtype=dtype, grad_req=grad_req))

        with self.name_scope():
            add("embed_weight", (vocab_size, units))
            for i in range(len(layer_types)):
                for n in z.layer_names(i):
                    add(f"l{i}_{n}", z.shape_of(i, n))
            add("lnf_gamma", (units,))
            add("head_weight", (vocab_size, units))

    def hybrid_forward(self, F, ids, **params):
        import functools

        from ...ndarray.register import invoke_simple

        fn = functools.partial(_forward, self._sizes, tuple(self._names))
        fn.__name__ = "mimo_v2_forward"
        return invoke_simple(fn, (ids,) + tuple(params[n]
                                                 for n in self._names))

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        """What `serving.ServingEngine` serves this family through."""
        if mesh is not None:
            raise MXNetError(
                "MiMoV2Model serves from one chip: its experts are a "
                "share of a deployment whose exchange this repo does not "
                "have (mesh= is not supported for this family)")
        return MiMoV2Program(self, dtype)


class MiMoV2Program(DecoderProgram):
    """The family's decoder program (docs/serving.md,
    `_decoder_program.py`): its cache's shapes, its layers and its head."""

    def __init__(self, model, dtype=None):
        super().__init__(model, dtype)
        z = self._z
        # what a reloaded model must share beyond its shapes
        self.signature = (tuple(z.layer_types), tuple(z.moe_layers),
                          z.experts_held, z.window, z.rotary_dim,
                          tuple(z.rope_theta.items()), z.value_scale)

    def cache_shapes(self, B):
        """(full keys, full values, window keys, window values), then
        the expert layers' counters and the prefill's: the positions
        its token-wise tiles worked and the real ones."""
        import jax.numpy as jnp

        z = self._z
        Lf = max(1, len(z.of_kind["full"]))
        Lw = max(1, len(z.of_kind["window"]))
        Kf, Kw = z.kv_heads["full"], z.kv_heads["window"]
        return ([((Lf, B, Kf, z.qk_dim, self.window), None),
                 ((Lf, B, Kf, z.v_dim, self.window), None),
                 ((Lw, B, Kw, z.qk_dim, z.window), None),
                 ((Lw, B, Kw, z.v_dim, z.window), None)],
                [((max(1, len(z.moe_at)), 2, z.experts_held[1] + 3),
                  jnp.int32), ((2,), jnp.uint32)])

    def counters(self, cache):
        """The counters of one served group, read back once
        (docs/observability.md has the table): the expert layers', and
        the prefill's packed positions under Jamba's names."""
        z = self._z
        out = _ops.moe_counters(cache[4], z.experts_held[1]) \
            if z.moe_at else {}
        out.update(_ops.packed_counters(cache[5]))
        return out

    # -- the traced step -------------------------------------------------------

    def body(self, ctx, w, cache, toks):
        """S > 1 is a prefill from an empty cache: it attends inside the
        block, and works each row chunk's real tokens packed for what
        acts on one token at a time.  S = 1 attends over the caches, a
        row that wants no token to nothing; it goes to no expert and is
        counted nowhere."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        z = self._z
        fk, fv, wk, wv, counts, packed = cache
        pos, last, S, decode = ctx.pos, ctx.last, ctx.S, ctx.decode
        R = z.window
        zero = jnp.int32(0)
        x, at, valid = _ops.embed(w["embed_weight"], toks, pos, last)

        def write(stacks, new, l, starts, first=0):
            """Row b's (K, S', D) blocks into the two stacks at
            [l, b, :, :, starts[b]:]."""
            return ctx.write(stacks, [a.swapaxes(2, 3) for a in new], l,
                             starts, first=first)

        def ring_of(k):
            """The ring a prefilled row leaves: slot s holds the latest
            position congruent to s that is not past the row's last."""
            slot = jnp.arange(R)[None, :]
            p_s = last[:, None] - (last[:, None] - slot) % R      # (B, R)
            got = jnp.take_along_axis(
                k, jnp.clip(p_s, 0, S - 1)[:, None, :, None], axis=2)
            return jnp.where((p_s >= 0)[:, None, :, None], got, 0)

        rows = _ops.chunk_rows(z, ctx.B, S)
        if not decode:
            held = last + 1
            # one layer's worth, every layer packs the same row chunks:
            # a chunk's real tokens, and the whole tiles that hold them
            n = jnp.sum(held.reshape(-1, rows), axis=1)
            tile = min(_TILE, rows * S)
            packed = packed + jnp.stack(
                [jnp.sum(-(-n // tile) * tile), jnp.sum(n)]).astype(
                    packed.dtype)
        for i, kind in enumerate(z.layer_types):
            p = {n: w[f"l{i}_{n}"] for n in z.layer_names(i)}
            l = z.of_kind[kind].index(i)
            if decode:
                q, k, v = _qkv(z, kind, p, x, at)
            else:
                x, (k, v, route) = _ops.by_rows(
                    lambda x, at, held, i=i, p=p: _block_layer(
                        z, i, p, x, at, held), rows, x, at, held)
            with jax.named_scope("serve.cache_write"):
                if kind == "full":
                    fk, fv = write((fk, fv), (k, v), l, pos)
                elif decode:
                    wk, wv = write((wk, wv), (k, v), l, pos % R, first=2)
                else:
                    at_layer = (jnp.int32(l), zero, zero, zero, zero)
                    wk, wv = (lax.dynamic_update_slice(
                        c, ring_of(a).swapaxes(2, 3).astype(c.dtype)[None],
                        at_layer) for c, a in ((wk, k), (wv, v)))
            if decode:
                with jax.named_scope(f"serve.attn_{kind}"):
                    # a ring's slot s holds position pos - (pos - s)
                    # mod R if that position exists: its first pos + 1
                    # slots, then all of them
                    ck, cv, lengths = (fk, fv, None) if kind == "full" \
                        else (wk, wv, jnp.minimum(ctx.held, R))
                    a = ctx.attend(q[:, :, :, 0], ck, cv, l, lengths,
                                   sink=_sink(z, kind, p))
                    x = _ops.attn_out(z, p, x, a[:, :, :, None])
                x, route = _feed_forward_front(z, i, p, x)
            if route:
                # padding and rows that want no token are routed
                # nowhere: only tokens that are kept cost
                x, stats = _experts(z, p, x, route,
                                    ctx.live[:, None] if decode else valid,
                                    ctx.products)
                counts = counts.at[z.moe_at.index(i), int(decode)].add(
                    _ops.moe_count_row(stats, z.experts_held[1]))
        with jax.named_scope("serve.head"):
            h = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            logits = _ops.mm("bc,vc->bv", _ops.rms_norm(h, w["lnf_gamma"], z.eps),
                         w["head_weight"])
        return (fk, fv, wk, wv, counts, packed), logits


def mimo_v2_tiny(**kwargs):
    """A test-sized member of the family: both kinds of layer, one dense
    feed-forward and then experts."""
    cfg = dict(vocab_size=96, units=64,
               layer_types=["full"] + ["window"] * 5 + ["full"],
               moe_layers=[0, 1, 1, 1, 1, 1, 1], num_heads=4, kv_heads=1,
               swa_kv_heads=2, qk_dim=24, v_dim=16, rotary_dim=8, window=4,
               rope_theta=1e7, swa_rope_theta=1e4, hidden_size=96,
               expert_hidden=32, router_experts=8, experts_per_token=2,
               value_scale=0.707, max_length=32, attn_block=4)
    cfg.update(kwargs)
    return MiMoV2Model(**cfg)

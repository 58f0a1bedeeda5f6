"""Granite 4.0-H (``model_type`` ``granitemoehybrid``; IBM's hybrid of
Mamba-2 and attention with experts in every layer): pre-norm residual
blocks under Granite's four multipliers,

    x = e Emb[token]
    x += r Mixer(RMSNorm(x; g1));  u = RMSNorm(x; g2)
    x += r (Routed(u) + Shared(u))
    logits = Emb RMSNorm(x; g_f) / s        (a tied head)

with ``e`` the embedding multiplier, ``r`` the residual multiplier and
``s`` the logits' scaling.  ``layer_types`` names each layer's mixer.

- **Mamba-2 mixer** (Dao & Gu, arXiv:2405.21060): ``[z ; w ; d] = W_in
  u`` (E = H P channels of gate, E + 2 N of the convolution's input, H
  steps); ``[x ; B ; C] = silu(conv4(w) + b)``, a causal depthwise
  convolution; ``dt = softplus(d + dt_bias)``; head h's state ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` with one ``A = -exp(A_log)`` a
  head and ``B_t``, ``C_t`` shared by the heads (one group); ``y_t = S_t
  C_t + D x_t``; ``out = W_out RMSNorm(y * silu(z); g_n)``: the gate
  before the norm, the norm over all E channels.  No bias but the
  convolution's and ``dt``'s.
- **Attention mixer**: ``num_heads`` query heads over ``kv_heads`` key
  and value heads, no bias, **no positions** (order comes from the
  Mamba layers), ``softmax(a q k^T)`` with ``a`` the attention
  multiplier in the place of ``1 / sqrt(d)``.
- **Routed(u)**: the router scores all ``router_experts``, the
  ``experts_per_token`` largest are taken and their softmax weighs them
  (`ops/moe.py::softmax_topk_route`: the softmax over all, the largest
  renormalised, is the same numbers); this block holds ``experts_held =
  (lo, n)`` of them, a chip's share of an expert-parallel deployment
  (`_decoder_ops.experts_of_layer`): what the absent experts would add
  is left out and nothing stands in for their exchange.  **Shared(u)**:
  one SwiGLU every token goes through, counted once when the chips'
  shares are summed.

A row's memory is of two kinds (docs/serving.md, "The second kind of
cache"): the attention layers' keys and values, a position a token, and
for each Mamba layer the heads' states ``(H P, N)`` float32 (the N
states minor: a lane tile at N = 128) and the convolution's last three
inputs, **whatever the row's length**.  The layers' parameters are
stacked by kind (every layer's gains, router, shared expert and held
experts; the Mamba mixers'; the attention mixers'); the layer loop is
unrolled and a layer's kind is static.

``hybrid_forward`` is the uncached full-sequence forward.
``decoder_program`` hands `serving.ServingEngine` the family's program.
Prefill (S > 1, from an empty cache) works ``prefill_chunk_tokens // S``
rows through all layers before the next, **the chunk's real tokens
packed** (`_decoder_ops.packing`): the mixers' ways in and out, the
gated norm, the router and the shared expert run on the packed block in
tiles of ``_TILE`` tokens, only the tiles that hold one
(`_decoder_ops.by_tokens`); the held experts on the packed block whole,
told which slots hold a token; and what needs a row's order is laid
back in rows (`unpack`): the convolution and the chunked scan, each row
to its own length (`ops/ssm.py::mamba2_scan_rows`), the row write and
the attention kernel.  Decode (S = 1) moves each live row's heads on
one position in place (`mamba2_update_rows`).
"""

from __future__ import annotations

from ...base import MXNetError
from ...ops import pallas_attention
from ..block import HybridBlock
from . import _decoder_ops as _ops
from ._decoder_program import DecoderProgram

_LANE = 128
# packed tokens a tile of the prefill's token-wise products
_TILE = 512
_ALL_LEAVES = ("ln1_gamma", "ln2_gamma", "router_weight",
               "shared_gate_weight", "shared_up_weight",
               "shared_down_weight")
_SSM_LEAVES = ("in_weight", "conv_weight", "conv_bias", "dt_bias",
               "a_log_weight", "d_weight", "norm_gamma", "out_weight")
_ATTN_LEAVES = ("q_weight", "k_weight", "v_weight", "o_weight")
_EXPERT_LEAVES = ("experts_gate_up_weight", "experts_down_weight")


class _Sizes:
    """The family's sizes, as the constructor got them."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.groups = self.num_heads // self.kv_heads
        self.inner = self.ssm_heads * self.ssm_head_dim
        # the convolution's channels: [x ; B ; C]
        self.conv_dim = self.inner + 2 * self.d_state
        self.kinds = ["attn" if t == "attention" else "ssm"
                      for t in self.layer_types]
        # a layer's place among the layers of its kind
        self.place = [self.kinds[:i].count(k)
                      for i, k in enumerate(self.kinds)]

    def leaves(self):
        """[(parameter name, shape)], in the order of the weight tuple."""
        z = self
        C, E, N, H = z.units, z.inner, z.d_state, z.ssm_heads
        Hd, Kd = z.num_heads * z.head_dim, z.kv_heads * z.head_dim
        F, Fs, n = z.expert_hidden, z.shared_hidden, z.experts_held[1]
        L = len(z.kinds)
        Lm, La = z.kinds.count("ssm"), z.kinds.count("attn")
        shapes = {
            "ln1_gamma": (L, C), "ln2_gamma": (L, C),
            "router_weight": (L, z.router_experts, C),
            "shared_gate_weight": (L, Fs, C), "shared_up_weight": (L, Fs, C),
            "shared_down_weight": (L, C, Fs),
            # gate beside up, as `ops/moe.py::held_experts_ffn` reads it
            "experts_gate_up_weight": (L, n, C, 2 * F),
            "experts_down_weight": (L, n, F, C),
            # [z ; x B C ; dt]
            "in_weight": (Lm, 2 * E + 2 * N + H, C),
            "conv_weight": (Lm, z.conv_dim, z.d_conv),
            "conv_bias": (Lm, z.conv_dim),
            "dt_bias": (Lm, H), "a_log_weight": (Lm, H), "d_weight": (Lm, H),
            "norm_gamma": (Lm, E), "out_weight": (Lm, C, E),
            "q_weight": (La, Hd, C), "k_weight": (La, Kd, C),
            "v_weight": (La, Kd, C), "o_weight": (La, C, Hd)}
        return ([("embed_weight", (z.vocab, C))]
                + [(n, shapes[n]) for n in _ALL_LEAVES + _EXPERT_LEAVES
                   + _SSM_LEAVES + _ATTN_LEAVES] + [("lnf_gamma", (C,))])


# -- a layer's pieces, shared by the forward pass and the cached step ----------

def _of_layer(w, names, j):
    return {n: w[n][j] for n in names}


def _mixer_leaves(z, w, i):
    return _of_layer(w, _SSM_LEAVES if z.kinds[i] == "ssm" else _ATTN_LEAVES,
                     z.place[i])


def _ssm_in(z, p, g1, x):
    """x (B, S, C) float32 → the gate z (B, S, E), the convolution's
    input (B, S, E + 2 N) and the steps dt (B, S, H) after their
    softplus, float32."""
    import jax

    E, W = z.inner, z.conv_dim
    with jax.named_scope("serve.ssm_in"):
        zwd = _ops.mm("bsc,gc->bsg", _ops.rms_norm(x, g1, z.eps),
                      p["in_weight"])
        return (zwd[..., :E], zwd[..., E:E + W], jax.nn.softplus(
            zwd[..., E + W:] + p["dt_bias"].astype("float32")))


def _ssm_consts(p):
    """A = -exp(A_log) and D, a head each, float32."""
    import jax.numpy as jnp

    return -jnp.exp(p["a_log_weight"].astype(jnp.float32)), \
        p["d_weight"].astype(jnp.float32)


def _conv_cut(z, c):
    """The convolution's output (.., E + 2 N) → x (.., H, P), B and C
    (.., N)."""
    E, N = z.inner, z.d_state
    return (c[..., :E].reshape(c.shape[:-1] + (z.ssm_heads, z.ssm_head_dim)),
            c[..., E:E + N], c[..., E + N:])


def _ssm_out(z, p, x, y, gate):
    """x + r W_out RMSNorm(y * silu(gate); g_n) for y, gate (B, S, E)."""
    import jax

    with jax.named_scope("serve.ssm_out"):
        return x + z.residual_multiplier * _ops.mm(
            "bse,ce->bsc", _ops.rms_norm(y * jax.nn.silu(gate),
                                         p["norm_gamma"], z.eps),
            p["out_weight"])


def _attn_in(z, p, g1, x):
    """x (B, S, C) → q (B, S, H d), k and v (B, S, K d) float32, heads
    side by side, unscaled, unrotated."""
    import jax

    with jax.named_scope("serve.attn_qkv"):
        u = _ops.rms_norm(x, g1, z.eps)
        return tuple(_ops.mm("bsc,gc->bsg", u, p[n])
                     for n in ("q_weight", "k_weight", "v_weight"))


def _heads(z, a):
    """(B, S, n d), heads side by side → (B, n, S, d), heads first."""
    B, S, G = a.shape
    return a.reshape(B, S, G // z.head_dim, z.head_dim).transpose(0, 2, 1, 3)


def _attn_out(z, p, x, a):
    """x + r a Wo for a (B, S, H d), heads side by side."""
    import jax

    with jax.named_scope("serve.attn_out"):
        return x + z.residual_multiplier * _ops.mm("bsg,cg->bsc", a,
                                                   p["o_weight"])


def _block_attention(z, q, k, v, lengths, tally=None):
    """A block's attention inside itself, each row to ``lengths`` (B,)
    or whole (None): (B, H, S, d) in the keys' type, heads first; the
    key heads go in as they are; the scale is the family's multiplier."""
    import jax

    with jax.named_scope("serve.attn_full"):
        a = pallas_attention.flash_attention_forward(
            q.astype(k.dtype), k, v, lengths, scale=z.attention_multiplier)
        if tally is not None:
            tally["kernel"] += 1
        return a


def _mixer_out(z, w, i, x, *outs):
    """Layer i's mixer's way out: what it left (y and the gate, or the
    heads' outputs) onto the stream."""
    return (_ssm_out if z.kinds[i] == "ssm" else _attn_out)(
        z, _mixer_leaves(z, w, i), x, *outs)


def _mixer_in(z, w, i, x):
    return (_ssm_in if z.kinds[i] == "ssm" else _attn_in)(
        z, _mixer_leaves(z, w, i), w["ln1_gamma"][i], x)


def _feed_forward_front(z, w, i, x):
    """What of layer i's feed-forward a token needs no other token for:
    the second norm, the router and the shared expert.  Returns (x with
    the shared expert's part, the route with the residual multiplier in
    its weights: `experts_of_layer` adds the held experts' sum as it
    is)."""
    import jax

    from ...ops import moe

    p = _of_layer(w, _ALL_LEAVES, i)
    u, chosen, weights = _ops.route(
        z, p, x, lambda u: moe.softmax_topk_route(
            u, p["router_weight"], z.experts_per_token))
    with jax.named_scope("serve.moe.shared"):
        x = x + z.residual_multiplier * moe.swiglu_ffn(
            u, p["shared_gate_weight"], p["shared_up_weight"],
            p["shared_down_weight"])
    return x, (u, chosen, weights * z.residual_multiplier)


def _experts(z, w, i, x, route, valid, tally=None):
    """x + r times layer i's held experts' part; also
    `held_experts_ffn`'s counts."""
    return _ops.experts_of_layer(
        z, w["experts_gate_up_weight"], w["experts_down_weight"], i, x,
        route, valid, tally)


def _head(z, w, x):
    """x (B, .., C) → logits over the vocabulary, float32: the final
    norm, the embedding read as it lies, the logits' scaling."""
    return _ops.mm("...c,vc->...v", _ops.rms_norm(x, w["lnf_gamma"], z.eps),
                   w["embed_weight"]) / z.logits_scaling


def _forward(z, names, ids, *weights):
    """(B, T) ids → (B, T, vocab) float32 logits, no cache."""
    import jax.numpy as jnp

    from ...ops import ssm

    w = dict(zip(names, weights))
    ids = ids.astype(jnp.int32)
    B, T = ids.shape
    whole = jnp.full((B,), T, jnp.int32)
    dt = w["q_weight"].dtype
    x = z.embedding_multiplier * jnp.take(w["embed_weight"], ids,
                                          axis=0).astype(jnp.float32)
    for i, kind in enumerate(z.kinds):
        p = _mixer_leaves(z, w, i)
        ins = _mixer_in(z, w, i, x)
        if kind == "ssm":
            gate, a, step = ins
            c, _ = ssm.causal_conv_rows(a, p["conv_weight"].T,
                                        p["conv_bias"], whole)
            xs, Bm, Cm = _conv_cut(z, c)
            A, D = _ssm_consts(p)
            y, _ = ssm.mamba2_scan_rows(xs, step, A, Bm, Cm, D, whole,
                                        operands=dt)
            x = _ssm_out(z, p, x, y.reshape(B, T, -1), gate)
        else:
            q, k, v = (_heads(z, t) for t in ins)
            a = _block_attention(z, q, k.astype(dt), v.astype(dt), None)
            x = _attn_out(z, p, x, a.transpose(0, 2, 1, 3).reshape(B, T, -1))
        x, route = _feed_forward_front(z, w, i, x)
        x, _ = _experts(z, w, i, x, route, None)
    return _head(z, w, x)


class GraniteHybridModel(HybridBlock):
    """Embedding → ``layer_types`` blocks (a Mamba-2 or an attention
    mixer, then routed experts beside a shared one) → RMSNorm → the
    embedding again as the head.  Input (B, T) token ids, output (B, T,
    vocab) float32 logits.

    Parameters are stacked by kind and created in ``dtype``;
    ``grad_req="null"`` keeps a serving copy from allocating
    gradients."""

    def __init__(self, vocab_size, units, layer_types, num_heads, kv_heads,
                 ssm_heads, ssm_head_dim, d_state, expert_hidden,
                 shared_hidden, router_experts, experts_per_token,
                 experts_held=None, d_conv=4, embedding_multiplier=1.0,
                 residual_multiplier=1.0, attention_multiplier=None,
                 logits_scaling=1.0, eps=1e-5, max_length=2048,
                 dtype="float32", grad_req="write",
                 prefill_chunk_tokens=4096, **kwargs):
        super().__init__(**kwargs)
        layer_types = list(layer_types)
        held = tuple(experts_held or (0, router_experts))
        if set(layer_types) - {"mamba", "attention"} or units % num_heads \
                or num_heads % kv_heads or d_conv < 2:
            raise MXNetError(
                "GraniteHybridModel: layer_types lists 'mamba' / "
                "'attention', num_heads divides units, kv_heads divides "
                "num_heads, and a convolution has two taps at least")
        if held[0] < 0 or held[0] + held[1] > router_experts:
            raise MXNetError(f"GraniteHybridModel: experts_held {held} lies "
                             f"outside the router's {router_experts}")
        self._max_length = max_length
        self._vocab = vocab_size
        head_dim = units // num_heads
        self._sizes = z = _Sizes(
            vocab=vocab_size, units=units, layer_types=layer_types,
            num_heads=num_heads, kv_heads=kv_heads, head_dim=head_dim,
            ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim, d_state=d_state,
            d_conv=d_conv, expert_hidden=expert_hidden,
            shared_hidden=shared_hidden, router_experts=router_experts,
            experts_per_token=experts_per_token, experts_held=held,
            embedding_multiplier=float(embedding_multiplier),
            residual_multiplier=float(residual_multiplier),
            attention_multiplier=float(
                head_dim ** -0.5 if attention_multiplier is None
                else attention_multiplier),
            logits_scaling=float(logits_scaling), eps=float(eps),
            prefill_chunk_tokens=prefill_chunk_tokens,
            # `experts_of_layer`: the buffer `ops/moe.py` sizes
            moe_pass_rows=None)
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
        fn.__name__ = "granite_hybrid_forward"
        return invoke_simple(fn, (ids,) + tuple(params[n]
                                                 for n in self._names))

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        """What `serving.ServingEngine` serves this family through."""
        if mesh is not None:
            raise MXNetError(
                "GraniteHybridModel serves from one chip: its experts are "
                "a share of a deployment whose exchange this repo does "
                "not have (mesh= is not supported for this family)")
        return GraniteHybridProgram(self, dtype)


class GraniteHybridProgram(DecoderProgram):
    """The family's decoder program (docs/serving.md,
    `_decoder_program.py`): its cache's shapes, its layers and its head."""

    def __init__(self, model, dtype=None):
        super().__init__(model, dtype)
        z = self._z
        # what a reloaded model must share beyond its shapes
        self.signature = (
            tuple(z.kinds), z.num_heads, z.kv_heads, z.ssm_heads, z.d_state,
            z.d_conv, z.experts_held, z.experts_per_token,
            z.embedding_multiplier, z.residual_multiplier,
            z.attention_multiplier, z.logits_scaling, z.eps)

    def cache_shapes(self, B):
        """(keys, values) of the attention layers; the Mamba layers'
        heads' states ``(Lm, B, H P, N)``, float32, and tails; the
        expert layers' counters and the states' and the prefill's.  The
        stacks' window is whole lane blocks (the row-write and attention
        kernels walk blocks of 128 positions): the positions past
        ``window`` are never written or read."""
        import jax.numpy as jnp

        z = self._z
        La, Lm = max(1, z.kinds.count("attn")), max(1, z.kinds.count("ssm"))
        kv = (La, B, z.kv_heads, z.head_dim,
              -(-self.window // _LANE) * _LANE)
        return ([(kv, None), (kv, None)],
                [((Lm, B, z.inner, z.d_state), jnp.float32),
                 ((Lm, B, (z.d_conv - 1) * z.conv_dim), None)],
                [((len(z.kinds), 2, z.experts_held[1] + 3), jnp.int32),
                 # as Jamba's seven: the scan's positions walked and
                 # real, the decode steps' row updates, the attention
                 # layers' pairs and positions, the positions the
                 # prefill's token-wise tiles worked and the real ones
                 ((7,), jnp.uint32)])

    def counters(self, cache):
        """The counters of one served group, read back once
        (docs/observability.md has the table): the expert layers' under
        MiMo's names, the states' and the packed prefill's under
        Jamba's."""
        import numpy as np

        out = _ops.moe_counters(cache[4], self._z.experts_held[1])
        c = np.asarray(cache[5]).astype(np.int64)
        out.update(_ops.state_counters(c[:5]))
        out.update(_ops.packed_counters(c[5:]))
        return out

    # -- the traced step -------------------------------------------------------

    def body(self, ctx, w, cache, toks):
        """S > 1 is a prefill from an empty cache: a row chunk through
        all layers before the next, its real tokens packed for what
        acts on one token at a time and for the experts, and laid back
        in rows for the convolution, the scan to each row's length and
        attention inside the block.  S = 1 attends over the caches and
        moves the heads' states on one position: a row is its one
        token, so the block is packed as it lies; a row that wants no
        token attends to nothing, goes to no expert, keeps its state and
        tail and is counted nowhere."""
        import functools

        import jax
        import jax.numpy as jnp

        from ...ops import ssm

        z = self._z
        B, S, decode = ctx.B, ctx.S, ctx.decode
        d, E, n_held = z.head_dim, z.inner, z.experts_held[1]
        Lm, La = z.kinds.count("ssm"), z.kinds.count("attn")
        dtype = w["in_weight" if Lm else "q_weight"].dtype
        scan_rows = functools.partial(ssm.mamba2_scan_rows, operands=dtype)

        def rows(toks, pos, last, row, carry):
            """Rows ``row ..`` of the group through every layer; carry
            (keys, values, states, tails, the experts' counters, the
            others).  Returns (carry, the rows' logits)."""
            ck, cv, states, tails, moe_counts, counts = carry
            R = toks.shape[0]
            # a row's positions, itself included: all of them in a
            # prefill, none of a decode row that wants no token
            held = ctx.held if decode else last + 1
            if decode:
                # a row is its one token: the block is packed as it lies
                tile, real, spare, at = 1, None, None, toks
                to_rows, pack = (lambda a: a), (lambda a, at: a)
                x, _, _ = _ops.embed(w["embed_weight"], toks, pos, last)
                valid = ctx.live[:, None]
            else:
                pk = _ops.packing(held, S, _TILE)
                tile, real, at, pack = pk.tile, pk.n, pk.src, _ops.pack
                to_rows = lambda a: _ops.unpack(a, pk.slot)
                x, _, _ = _ops.embed(w["embed_weight"], pack(toks, at),
                                     jnp.zeros((1,), jnp.int32),
                                     real[None] - 1)
                valid = jnp.arange(x.shape[1])[None, :] < real
                # what a Mamba layer's way in fills: the gates of every
                # other layer by turns (the last layer's is being
                # read), and the convolution's input and the steps, dead
                # once they lie in rows
                P = x.shape[1]
                spare = [jnp.zeros((1, P, E), jnp.float32)] * 2 + [
                    jnp.zeros((1, P, z.conv_dim), jnp.float32),
                    jnp.zeros((1, P, z.ssm_heads), jnp.float32)]
            x = z.embedding_multiplier * x
            # what the last mixer left: in rows, to be packed a tile at
            # a time, and what never left the packed block
            left, kept = (), ()
            for i, kind in enumerate(z.kinds + [None]):
                if i:
                    # one token at a time, the packed block's live tiles
                    # only: the mixer's way out of the layer before, its
                    # router and its shared expert; then its held
                    # experts, on the block whole
                    def way_out(x, at, *kept, i=i, left=left):
                        outs = tuple(pack(a, at) for a in left) + kept
                        return _feed_forward_front(
                            z, w, i - 1, _mixer_out(z, w, i - 1, x, *outs))

                    x, route = _ops.by_tokens(way_out, tile, real, x, at,
                                              *kept)
                    # padding and rows that want no token are routed
                    # nowhere: only tokens that are kept cost
                    x, stats = _experts(z, w, i - 1, x, route, valid,
                                        ctx.products)
                    moe_counts = moe_counts.at[i - 1, int(decode)].add(
                        _ops.moe_count_row(stats, n_held))
                if kind is None:
                    break
                j = z.place[i]
                p = _mixer_leaves(z, w, i)
                into = (spare[j % 2], spare[2], spare[3]) \
                    if spare and kind == "ssm" else None
                _, ins = _ops.by_tokens(
                    lambda x, i=i: (None, _mixer_in(z, w, i, x)), tile, real,
                    x, into=into)
                if kind == "ssm":
                    gate, a, dt = ins
                    if spare:
                        spare[j % 2], spare[2], spare[3] = gate, a, dt
                    with jax.named_scope("serve.ssm_conv"):
                        c, tails = ctx.conv(
                            tails, j, to_rows(a), p["conv_weight"].T,
                            p["conv_bias"], lengths=held, row=row, first=3)
                        xs, Bm, Cm = _conv_cut(z, c)
                    A, D = _ssm_consts(p)
                    if decode:
                        with jax.named_scope("serve.ssd_update"):
                            y, states = ctx.update(
                                states, j, xs[:, 0], dt[:, 0], A, Bm[:, 0],
                                Cm[:, 0], D, first=2,
                                rows=ssm.mamba2_update_rows)
                    else:
                        with jax.named_scope("serve.ssd_scan"):
                            y, states = ctx.scan(
                                states, j, xs, to_rows(dt), A, Bm, Cm, D,
                                held, row=row, first=2, rows=scan_rows)
                    left, kept = (y.reshape(R, S, E),), (gate,)
                else:
                    q, k, v = (to_rows(t) for t in ins)
                    with jax.named_scope("serve.cache_write"):
                        # row b's block at [j, row + b, :, :, pos[b]:]
                        k = _heads(z, k.astype(ck.dtype))
                        v = _heads(z, v.astype(ck.dtype))
                        ck, cv = ctx.write(
                            (ck, cv), (k.swapaxes(2, 3), v.swapaxes(2, 3)),
                            j, pos, row=row)
                    if decode:
                        with jax.named_scope("serve.attn"):
                            a = ctx.attend(
                                (q[:, 0] * z.attention_multiplier).astype(
                                    ck.dtype).reshape(R, z.kv_heads,
                                                      z.groups, d),
                                ck, cv, j).reshape(R, 1, -1)
                    else:
                        # heads side by side again for the way out
                        a = _block_attention(
                            z, _heads(z, q), k, v, held,
                            ctx.attends).transpose(0, 2, 1, 3).reshape(
                                R, S, -1)
                    left, kept = (a,), ()
            with jax.named_scope("serve.head"):
                logits = _head(z, w, x[:, 0] if decode else x[0, pk.last])
            n = held.astype(jnp.uint32)
            if decode:
                alive = jnp.sum(ctx.live, dtype=jnp.uint32)
                add = [0, 0, Lm * alive, 0, La * jnp.sum(n), 0, 0]
            else:
                Tc = ssm.mamba2_chunk()
                walked = jnp.sum((n + Tc - 1) // Tc * Tc) \
                    if ctx.updates["kernel"] else jnp.uint32(R * S)
                add = [Lm * walked, Lm * jnp.sum(n), 0,
                       La * jnp.sum(n * (n + 1) // 2), 0,
                       _ops.tokens_worked(tile, real, x.shape[1]), real]
            counts = counts + jnp.stack([jnp.uint32(a) for a in add])
            return (ck, cv, states, tails, moe_counts, counts), logits

        return _ops.rows_in_chunks(
            rows, B if decode else _ops.chunk_rows(z, B, S), tuple(cache),
            self.vocab, toks, ctx.pos, ctx.last)


def granite_hybrid_tiny(**kwargs):
    """A test-sized member of the family with every mechanism present
    and every ratio kept: five layers with the attention layer inside,
    four Mamba heads of 8 channels over 16 states (one group), four
    query heads over two, eight experts of which two are held, three a
    token, a shared expert twice an expert's width, the four
    multipliers off one."""
    cfg = dict(vocab_size=96, units=64,
               layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
               num_heads=4, kv_heads=2, ssm_heads=4, ssm_head_dim=8,
               d_state=16, expert_hidden=24, shared_hidden=48,
               router_experts=8, experts_per_token=3, experts_held=(0, 2),
               embedding_multiplier=12.0, residual_multiplier=0.22,
               attention_multiplier=0.0625, logits_scaling=4.0,
               max_length=64)
    cfg.update(kwargs)
    return GraniteHybridModel(**cfg)

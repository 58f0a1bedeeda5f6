"""Jamba (``model_type`` ``jamba``; AI21's hybrid of Mamba-1 and
attention, arXiv:2403.19887), the dense members (``num_experts`` 1:
Jamba2-3B): pre-norm residual blocks, ``x += Mixer(RMSNorm(x))``,
``x += SwiGLU(RMSNorm(x))``, an unscaled embedding, a final RMSNorm and
a **tied head**.  Layer ``i``'s mixer is attention where ``i mod
attn_period == attn_offset`` and a Mamba mixer elsewhere.

- **Mamba mixer** (Gu & Dao, arXiv:2312.00752, with Jamba's three inner
  norms): ``[a ; z] = W_in u``; ``c = silu(conv4(a) + b)``, a causal
  depthwise convolution; ``[dt' ; B' ; C'] = W_x c``; ``dt =
  softplus(W_dt RMSNorm(dt') + b_dt)``, ``B = RMSNorm(B')``, ``C =
  RMSNorm(C')``; ``h_t = exp(dt_t A) h_{t-1} + (dt_t c_t) B_t`` with
  ``A = -exp(A_log)``; ``y_t = h_t C_t + D c_t``; ``out = W_out (y *
  silu(z))``.  No bias but the convolution's and ``dt``'s.
- **Attention mixer**: ``num_heads`` query heads over ``kv_heads`` key
  and value heads, no bias and **no positional signal at all**: order
  comes from the Mamba layers.

A row's memory is of two kinds (docs/serving.md, "The decoder
program"): the attention layers' keys and values, a position a token,
and for each Mamba layer a state ``(N, E)`` float32 and the
convolution's last three inputs, **whatever the row's length**.  The
layers' parameters are stacked by kind (all layers' norms and SwiGLU,
the Mamba mixers', the attention mixers'); the layer loop is unrolled
and a layer's kind is static.

``hybrid_forward`` is the uncached full-sequence forward.
``decoder_program`` hands `serving.ServingEngine` the family's program
(`_decoder_program.DecoderProgram`): its cache's shapes and its layer
body.  Prefill (S > 1, from an empty cache) works ``prefill_chunk_tokens
// S`` rows through all layers before the next; the engine pads a prompt
on the right to its bucket, so the scan and the convolution are told
each row's length (`ops/ssm.py`): past it the state does not change, and
the tail is of the row's last real inputs.  Decode (S = 1) moves each
live row's state on one position in place.

**A row chunk's residual stream lives packed** (`_decoder_ops.packing`):
its real tokens, row after row, at the front of one flat row of
positions.  What acts on one token at a time (a mixer's way in with the
first norm, its way out with the gate, the SwiGLU with its norm) works
that row in tiles of ``_TILE`` packed tokens, only the tiles that hold a
real token (`_decoder_ops.by_tokens`), one loop between two layers'
rows: of the served cell's 128 x 512 positions three in five are
padding, and these products are four fifths of the prefill.  What needs
a row's order is laid back in rows (`unpack`: the scan's input before
the convolution, ``W_x`` and the scan, which run on (R, S, E) to each
row's length; q, k and v before the row write and the attention kernel)
and what it puts out is packed again (`pack`) to meet the gate, which
never left the packed layout.  A padded position's values were never
read by anyone, so no request's numbers change.  `packing`, `pack` and
`unpack` name no family: MiMo's prefill calls them too, Keye's next.
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
_ALL_LEAVES = ("ln1_gamma", "ln2_gamma", "gate_weight", "up_weight",
               "down_weight")
_SSM_LEAVES = ("in_weight", "conv_weight", "conv_bias", "x_weight",
               "dt_gamma", "b_gamma", "c_gamma", "dt_weight", "dt_bias",
               "a_log_weight", "d_weight", "out_weight")
_ATTN_LEAVES = ("q_weight", "k_weight", "v_weight", "o_weight")


class _Sizes:
    """The family's sizes, as the constructor got them."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.groups = self.num_heads // self.kv_heads
        self.inner = self.expand * self.units
        self.kinds = ["attn" if i % self.attn_period == self.attn_offset
                      else "ssm" for i in range(self.num_layers)]
        # a layer's place among the layers of its kind
        self.place = [self.kinds[:i].count(k)
                      for i, k in enumerate(self.kinds)]

    def leaves(self):
        """[(parameter name, shape)], in the order of the weight tuple."""
        z = self
        C, E, N, R, F = z.units, z.inner, z.d_state, z.dt_rank, z.hidden_size
        Hd, Kd = z.num_heads * z.head_dim, z.kv_heads * z.head_dim
        L = z.num_layers
        Lm, La = z.kinds.count("ssm"), z.kinds.count("attn")
        shapes = {
            "ln1_gamma": (L, C), "ln2_gamma": (L, C),
            "gate_weight": (L, F, C), "up_weight": (L, F, C),
            "down_weight": (L, C, F),
            # [a ; z]: the scan's input first, then the gate
            "in_weight": (Lm, 2 * E, C),
            "conv_weight": (Lm, E, z.d_conv), "conv_bias": (Lm, E),
            # [dt' ; B' ; C']
            "x_weight": (Lm, R + 2 * N, E),
            "dt_gamma": (Lm, R), "b_gamma": (Lm, N), "c_gamma": (Lm, N),
            "dt_weight": (Lm, E, R), "dt_bias": (Lm, E),
            "a_log_weight": (Lm, E, N), "d_weight": (Lm, E),
            "out_weight": (Lm, C, E),
            "q_weight": (La, Hd, C), "k_weight": (La, Kd, C),
            "v_weight": (La, Kd, C), "o_weight": (La, C, Hd)}
        return ([("embed_weight", (z.vocab, C))]
                + [(n, shapes[n]) for n in _ALL_LEAVES + _SSM_LEAVES
                   + _ATTN_LEAVES] + [("lnf_gamma", (C,))])


# -- a layer's pieces, shared by the forward pass and the cached step ----------

def _of_layer(w, names, j):
    return {n: w[n][j] for n in names}


def _ssm_in(z, p, g1, x):
    """x (B, S, C) float32 → the scan's input a and the gate (B, S, E)
    float32."""
    import jax

    with jax.named_scope("serve.ssm_in"):
        az = _ops.mm("bsc,gc->bsg", _ops.rms_norm(x, g1, z.eps),
                     p["in_weight"])
        return az[..., :z.inner], az[..., z.inner:]


def _ssm_x(z, p, c):
    """c (B, S, E) → dt (B, S, E) after its softplus, B and C (B, S, N),
    float32, each under its own norm."""
    import jax

    R, N = z.dt_rank, z.d_state
    with jax.named_scope("serve.ssm_x"):
        dbc = _ops.mm("bse,ge->bsg", c, p["x_weight"])
        dt = _ops.mm("bsr,er->bse",
                     _ops.rms_norm(dbc[..., :R], p["dt_gamma"], z.eps),
                     p["dt_weight"]) + p["dt_bias"].astype("float32")
        return (jax.nn.softplus(dt),
                _ops.rms_norm(dbc[..., R:R + N], p["b_gamma"], z.eps),
                _ops.rms_norm(dbc[..., R + N:], p["c_gamma"], z.eps))


def _ssm_consts(p):
    """A = -exp(A_log) with the channels minor (N, E), float32; D."""
    import jax.numpy as jnp

    return -jnp.exp(p["a_log_weight"].astype(jnp.float32)).T, \
        p["d_weight"].astype(jnp.float32)


def _ssm_out(z, p, x, y, gate):
    import jax

    with jax.named_scope("serve.ssm_out"):
        return x + _ops.mm("bse,ce->bsc", y * jax.nn.silu(gate),
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
    """x + a Wo for a (B, S, H d), heads side by side."""
    import jax

    with jax.named_scope("serve.attn_out"):
        return x + _ops.mm("bsg,cg->bsc", a, p["o_weight"])


def _block_attention(z, q, k, v, lengths, tally=None):
    """A block's attention inside itself, each row to ``lengths`` (B,)
    or whole (None): (B, H, S, d) in the keys' type, heads first; the
    key heads go in as they are."""
    import jax

    with jax.named_scope("serve.attn_full"):
        a = pallas_attention.flash_attention_forward(
            q.astype(k.dtype), k, v, lengths, scale=z.head_dim ** -0.5)
        if tally is not None:
            tally["kernel"] += 1
        return a


def _mlp(z, w, i, x):
    import jax

    from ...ops import moe

    with jax.named_scope("serve.mlp"):
        return x + moe.swiglu_ffn(
            _ops.rms_norm(x, w["ln2_gamma"][i], z.eps),
            w["gate_weight"][i], w["up_weight"][i], w["down_weight"][i])


def _head(z, w, x):
    """x (B, .., C) → logits over the vocabulary, float32: the final
    norm and the embedding read as it lies."""
    return _ops.mm("...c,vc->...v", _ops.rms_norm(x, w["lnf_gamma"], z.eps),
                   w["embed_weight"])


def _forward(z, names, ids, *weights):
    """(B, T) ids → (B, T, vocab) float32 logits, no cache."""
    import jax.numpy as jnp

    from ...ops import ssm

    w = dict(zip(names, weights))
    ids = ids.astype(jnp.int32)
    B, T = ids.shape
    whole = jnp.full((B,), T, jnp.int32)
    dt = w["q_weight"].dtype
    x = jnp.take(w["embed_weight"], ids, axis=0).astype(jnp.float32)
    for i, kind in enumerate(z.kinds):
        g1, j = w["ln1_gamma"][i], z.place[i]
        if kind == "ssm":
            p = _of_layer(w, _SSM_LEAVES, j)
            a, gate = _ssm_in(z, p, g1, x)
            c, _ = ssm.causal_conv_rows(a, p["conv_weight"].T,
                                        p["conv_bias"], whole)
            step, Bm, Cm = _ssm_x(z, p, c)
            A, D = _ssm_consts(p)
            y, _ = ssm.selective_scan_rows(c, step, A, Bm, Cm, D, whole)
            x = _ssm_out(z, p, x, y, gate)
        else:
            p = _of_layer(w, _ATTN_LEAVES, j)
            q, k, v = (_heads(z, t) for t in _attn_in(z, p, g1, x))
            a = _block_attention(z, q, k.astype(dt), v.astype(dt), None)
            x = x + _ops.mm("bhsd,chd->bsc", a, p["o_weight"].reshape(
                -1, z.num_heads, z.head_dim))
        x = _mlp(z, w, i, x)
    return _head(z, w, x)


class JambaModel(HybridBlock):
    """Embedding → ``num_layers`` blocks (a Mamba or an attention mixer,
    then a SwiGLU) → RMSNorm → the embedding again as the head.  Input
    (B, T) token ids, output (B, T, vocab) float32 logits.

    Parameters are stacked by kind and created in ``dtype``;
    ``grad_req="null"`` keeps a serving copy from allocating
    gradients."""

    def __init__(self, vocab_size, units, num_layers, num_heads, kv_heads,
                 hidden_size, attn_period, attn_offset, d_state=16,
                 d_conv=4, dt_rank=None, expand=2, eps=1e-6,
                 max_length=2048, dtype="float32", grad_req="write",
                 prefill_chunk_tokens=8192, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads or num_heads % kv_heads or d_conv < 2 \
                or not 0 <= attn_offset < attn_period:
            raise MXNetError(
                "JambaModel: num_heads divides units, kv_heads divides "
                "num_heads, a convolution of two taps at least, and "
                "attn_offset lies inside attn_period")
        self._max_length = max_length
        self._vocab = vocab_size
        self._sizes = z = _Sizes(
            vocab=vocab_size, units=units, num_layers=num_layers,
            num_heads=num_heads, kv_heads=kv_heads,
            head_dim=units // num_heads, hidden_size=hidden_size,
            attn_period=attn_period, attn_offset=attn_offset,
            d_state=d_state, d_conv=d_conv,
            dt_rank=dt_rank or -(-units // 16), expand=expand,
            eps=float(eps), prefill_chunk_tokens=prefill_chunk_tokens)
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
        fn.__name__ = "jamba_forward"
        return invoke_simple(fn, (ids,) + tuple(params[n]
                                                 for n in self._names))

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        """What `serving.ServingEngine` serves this family through."""
        if mesh is not None:
            raise MXNetError(
                "JambaModel serves from one chip, which holds it whole: "
                "more chips are more replicas (mesh= is not supported "
                "for this family)")
        return JambaProgram(self, dtype)


class JambaProgram(DecoderProgram):
    """The family's decoder program (docs/serving.md,
    `_decoder_program.py`): its cache's shapes, its layers and its head."""

    def __init__(self, model, dtype=None):
        super().__init__(model, dtype)
        z = self._z
        # what a reloaded model must share beyond its shapes
        self.signature = (tuple(z.kinds), z.num_heads, z.kv_heads,
                          z.d_state, z.d_conv, z.dt_rank, z.eps)

    def cache_shapes(self, B):
        """(keys, values) of the attention layers; the Mamba layers'
        states, float32, and tails; the counters.  The stacks' window is
        whole lane blocks (the row-write and attention kernels walk
        blocks of 128 positions): the positions past ``window`` are
        never written or read."""
        import jax.numpy as jnp

        z = self._z
        La, Lm = max(1, z.kinds.count("attn")), max(1, z.kinds.count("ssm"))
        kv = (La, B, z.kv_heads, z.head_dim,
              -(-self.window // _LANE) * _LANE)
        return ([(kv, None), (kv, None)],
                [((Lm, B, z.d_state, z.inner), jnp.float32),
                 ((Lm, B, (z.d_conv - 1) * z.inner), None)],
                # the scan's positions walked and real, the decode
                # steps' row updates, the attention layers' pairs in a
                # prefill and positions in a decode step, the positions
                # the prefill's token-wise tiles worked and the real ones
                [((7,), jnp.uint32)])

    def counters(self, cache):
        """The counters of one served group, read back once
        (docs/observability.md has the table); live rows only, summed
        over layers and steps."""
        import numpy as np

        c = np.asarray(cache[4]).astype(np.int64)
        out = _ops.state_counters(c[:5])
        out.update(_ops.packed_counters(c[5:]))
        return out

    # -- the traced step -------------------------------------------------------

    def body(self, ctx, w, cache, toks):
        """S > 1 is a prefill from an empty cache: a row chunk through
        all layers before the next, its real tokens packed for what
        acts on one token at a time and laid back in rows for the
        convolution, the scan to each row's length and attention inside
        the block.  S = 1 attends over the caches and moves a state on
        one position: a row is its one token, so the block is packed as
        it lies; a row that wants no token attends to nothing, keeps its
        state and tail and is counted nowhere."""
        import jax
        import jax.numpy as jnp

        from ...ops import ssm

        z = self._z
        B, S, decode = ctx.B, ctx.S, ctx.decode
        d, E = z.head_dim, z.inner
        Lm, La = z.kinds.count("ssm"), z.kinds.count("attn")

        def rows(toks, pos, last, row, carry):
            """Rows ``row ..`` of the group through every layer; carry
            (keys, values, states, tails, counters).  Returns (carry,
            the rows' logits)."""
            ck, cv, states, tails, counts = carry
            R = toks.shape[0]
            # a row's positions, itself included: all of them in a
            # prefill, none of a decode row that wants no token
            held = ctx.held if decode else last + 1
            if decode:
                # a row is its one token: the block is packed as it lies
                tile, real, spare, at = 1, None, None, toks
                to_rows, pack = (lambda a: a), (lambda a, at: a)
                x, _, _ = _ops.embed(w["embed_weight"], toks, pos, last)
            else:
                pk = _ops.packing(held, S, _TILE)
                tile, real, at, pack = pk.tile, pk.n, pk.src, _ops.pack
                to_rows = lambda a: _ops.unpack(a, pk.slot)
                x, _, _ = _ops.embed(w["embed_weight"], pack(toks, at),
                                     jnp.zeros((1,), jnp.int32),
                                     real[None] - 1)
                # what a Mamba layer's way in fills: the scan's input,
                # dead once it lies in rows, and the gates of every
                # other layer by turns (the last layer's is being read)
                spare = [jnp.zeros((1, x.shape[1], E), jnp.float32)] * 3
            # what the last mixer left: in rows, to be packed a tile at
            # a time, and what never left the packed block
            left, kept = (), ()
            for i, kind in enumerate(z.kinds + [None]):
                # one token at a time, the packed block's live tiles
                # only: the mixer's way out and the SwiGLU of the layer
                # before, this layer's norm and its mixer's way in
                def tokenwise(x, at, *kept, i=i, kind=kind, left=left):
                    if i:
                        outs = tuple(pack(a, at) for a in left) + kept
                        before, q = z.kinds[i - 1], z.place[i - 1]
                        x = _ssm_out(z, _of_layer(w, _SSM_LEAVES, q), x,
                                     *outs) if before == "ssm" else \
                            _attn_out(z, _of_layer(w, _ATTN_LEAVES, q), x,
                                      *outs)
                        x = _mlp(z, w, i - 1, x)
                    if kind is None:
                        return x, ()
                    enter = _ssm_in if kind == "ssm" else _attn_in
                    return (x if i else None), enter(
                        z, _of_layer(w, _SSM_LEAVES if kind == "ssm"
                                     else _ATTN_LEAVES, z.place[i]),
                        w["ln1_gamma"][i], x)

                j = z.place[i] if kind else None
                into = (spare[0], spare[1 + j % 2]) \
                    if spare and kind == "ssm" else None
                x, ins = _ops.by_tokens(tokenwise, tile, real, x, at, *kept,
                                        into=into)
                if kind == "ssm":
                    p = _of_layer(w, _SSM_LEAVES, j)
                    a, gate = ins
                    if spare:
                        spare[0], spare[1 + j % 2] = a, gate
                    a = to_rows(a)
                    with jax.named_scope("serve.ssm_conv"):
                        c, tails = ctx.conv(
                            tails, j, a, p["conv_weight"].T, p["conv_bias"],
                            lengths=held, row=row, first=3)
                    dt, Bm, Cm = _ssm_x(z, p, c)
                    with jax.named_scope("serve.ssm_update" if decode
                                         else "serve.ssm_scan"):
                        A, D = _ssm_consts(p)
                        if decode:
                            y, states = ctx.update(
                                states, j, c[:, 0], dt[:, 0], A, Bm[:, 0],
                                Cm[:, 0], D, first=2)
                            y = y[:, None]
                        else:
                            y, states = ctx.scan(states, j, c, dt, A, Bm,
                                                 Cm, D, held, row=row,
                                                 first=2)
                    left, kept = (y,), (gate,)
                elif kind == "attn":
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
                                (q[:, 0] * d ** -0.5).astype(
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
                live = jnp.sum(ctx.live, dtype=jnp.uint32)
                add = [0, 0, Lm * live, 0, La * jnp.sum(n), 0, 0]
            else:
                Tc = ssm.scan_chunk(S)
                walked = jnp.sum((n + Tc - 1) // Tc * Tc) \
                    if ctx.updates["kernel"] else jnp.uint32(R * S)
                add = [Lm * walked, Lm * jnp.sum(n), 0,
                       La * jnp.sum(n * (n + 1) // 2), 0,
                       _ops.tokens_worked(tile, real, x.shape[1]), real]
            counts = counts + jnp.stack([jnp.uint32(a) for a in add])
            return (ck, cv, states, tails, counts), logits

        return _ops.rows_in_chunks(
            rows, B if decode else _ops.chunk_rows(z, B, S), tuple(cache),
            self.vocab, toks, ctx.pos, ctx.last)


def jamba_tiny(**kwargs):
    """A test-sized member of the family with every mechanism present:
    one period of 14 layers with its attention layer at 7, four query
    heads over one key head, a state of 16, channels of one lane
    block."""
    cfg = dict(vocab_size=96, units=64, num_layers=14, num_heads=4,
               kv_heads=1, hidden_size=96, attn_period=14, attn_offset=7,
               d_state=16, d_conv=4, dt_rank=4, expand=2, max_length=64)
    cfg.update(kwargs)
    return JambaModel(**cfg)

"""Ouro (``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped
Language Models"), the language model: a plain modern dense decoder
(RMSNorm, rotary positions on whole heads, SwiGLU, no bias, an unscaled
embedding, an untied head) whose stack of ``num_layers`` layers is **run
``loop_steps`` times a token over one set of weights**.

- A layer has four norms, one before and one after each branch
  ("sandwich"): ``x += RMSNorm(Attn(RMSNorm(x)) Wo)``, ``x +=
  RMSNorm(SwiGLU(RMSNorm(x)))``.
- After the last layer of loop step ``t`` the stream is normed,
  ``h_t = RMSNorm(x)``, and ``h_t`` is what loop step ``t + 1`` starts
  from.  An **exit gate** reads it: ``lambda_t = sigmoid(w_e h_t + b_e)``.
- The exit rule: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for
  ``t < T - 1``; a position leaves at the first ``t`` whose cumulative
  ``p`` reaches ``exit_threshold``, else at ``T - 1``; the head is
  applied to that step's ``h``.  (At the published threshold of 1.0 every
  position whose gates are below 1 leaves at ``T - 1``.)

**The cache is deeper than the weights.**  Loop step ``t`` of layer ``l``
has keys and values of its own: a position attends, in that pass, to the
earlier positions' keys of the same pass.  So the weights are
``num_layers`` stacked layers and the cache ``loop_steps x num_layers``
slots, ``(T L, B, K, head_dim, W)`` keys beside as many values; slot
``t L + l`` is a traced index into the write and the read.  A step is one
traced layer body, scanned over the layers' weights, inside one traced
loop over ``t`` that carries the stream and the two stacks: the weights
are held once and read ``T`` times.

**Every loop step always runs**, for every row, at any threshold: later
tokens read every pass's slot, and a lock-step batch saves nothing by a
row's early exit.  What the rule decides is which ``h_t`` the head reads.

``hybrid_forward`` is the uncached full-sequence forward (the rule at
every position).  ``decoder_program`` hands `serving.ServingEngine` the
family's program (`_decoder_program.DecoderProgram`; docs/serving.md,
"The decoder program"; this file states the cache's shapes and the layer
body): decode (S = 1) attends over the caches through
`ops/cache_attention.py::attend_rows`, prefill (S > 1, from an empty
cache) inside the block through
`ops/pallas_attention.py::flash_attention_forward`, each row to its own
length; neither takes a gradient.  One counter array rides in the
donated carry (``counters``).
"""

from __future__ import annotations

from ...base import MXNetError
from ...ops import pallas_attention
from ..block import HybridBlock
from . import _decoder_ops as _ops
from ._decoder_program import DecoderProgram

_LAYER_LEAVES = ("ln1_gamma", "qkv_weight", "o_weight", "ln2_gamma",
                 "ln3_gamma", "gate_weight", "up_weight", "down_weight",
                 "ln4_gamma")


class _Sizes:
    """The family's sizes, as the constructor got them."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.groups = self.num_heads // self.kv_heads

    def leaf_names(self):
        return (("embed_weight",) + _LAYER_LEAVES
                + ("lnf_gamma", "exit_weight", "exit_bias", "head_weight"))

    def shape_of(self, name):
        z = self
        C, d = z.units, z.head_dim
        if name in ("embed_weight", "head_weight"):
            return (z.vocab, C)
        shape = {
            "lnf_gamma": (C,), "exit_weight": (1, C), "exit_bias": (1,),
            # a layer's queries, keys and values side by side
            "qkv_weight": ((z.num_heads + 2 * z.kv_heads) * d, C),
            "o_weight": (C, z.num_heads * d),
            "gate_weight": (z.hidden_size, C), "up_weight": (z.hidden_size, C),
            "down_weight": (C, z.hidden_size),
        }.get(name, (C,))
        return (z.num_layers,) + shape if name in _LAYER_LEAVES else shape


# -- a layer's pieces, shared by the forward pass and the cached step ----------

def _qkv(z, p, x, pos):
    """x (B, S, C) float32 at positions ``pos`` (B, S) → q (B, H, S, d)
    rotated float32, unscaled; k, v (B, K, S, d), k rotated, in the
    weights' type."""
    import jax

    H, K, d = z.num_heads, z.kv_heads, z.head_dim
    B, S, _ = x.shape
    dt = p["qkv_weight"].dtype
    with jax.named_scope("serve.attn_qkv"):
        u = _ops.rms_norm(x, p["ln1_gamma"], z.eps)
        qkv = _ops.mm("bsc,gc->bsg", u, p["qkv_weight"]).reshape(
            B, S, H + 2 * K, d).transpose(0, 2, 1, 3)
        qk = _ops.rope(qkv[:, :H + K], pos, z.rope_theta, d)
        return qk[:, :H], qk[:, H:].astype(dt), qkv[:, H + K:].astype(dt)


def _branches(z, p, x, a):
    """The stream after a layer's attention output ``a`` (B, S, H d) and
    its feed-forward, each branch normed before it is added."""
    import jax

    from ...ops import moe

    with jax.named_scope("serve.attn_out"):
        x = x + _ops.rms_norm(_ops.mm("bsg,cg->bsc", a, p["o_weight"]),
                              p["ln2_gamma"], z.eps)
    with jax.named_scope("serve.mlp"):
        u = _ops.rms_norm(x, p["ln3_gamma"], z.eps)
        return x + _ops.rms_norm(
            moe.swiglu_ffn(u, p["gate_weight"], p["up_weight"],
                           p["down_weight"]), p["ln4_gamma"], z.eps)


def _block_attention(z, q, k, v, lengths, tally=None):
    """A block's attention inside itself: q (B, H, S, d) float32, k, v
    (B, K, S, d), each row to ``lengths`` (B,) or whole (None) → (B, S,
    H d) in the weights' type.  A key head's ``groups`` query heads each
    take a copy of it: the kernel has one key head a query head."""
    import jax
    import jax.numpy as jnp

    B, H, S, d = q.shape
    with jax.named_scope("serve.attn_full"):
        if z.groups > 1:
            k, v = (jnp.repeat(c, z.groups, axis=1) for c in (k, v))
        a = pallas_attention.flash_attention_forward(
            q.astype(k.dtype), k, v, lengths, scale=d ** -0.5)
        if tally is not None:
            tally["kernel"] += 1
        return a.transpose(0, 2, 1, 3).reshape(B, S, H * d)


def _exit_step(z, gates):
    """The exit rule on the gates (T, ...) float32 → the step each
    position leaves at, int32 (...)."""
    import jax.numpy as jnp

    T = z.loop_steps
    if T == 1:
        return jnp.zeros(gates.shape[1:], jnp.int32)
    lam = gates[:T - 1]
    stay = jnp.cumprod(1.0 - lam, axis=0)
    stay = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    reached = jnp.cumsum(lam * stay, axis=0) >= z.exit_threshold
    return jnp.where(jnp.any(reached, axis=0),
                     jnp.argmax(reached, axis=0), T - 1).astype(jnp.int32)


def _loops(z, w, x, carry, layer, keep):
    """x through the ``loop_steps`` passes of the scanned stack.
    ``layer(x, carry, p, slot) -> (x, carry)`` is a layer (the cached
    step writes and reads slot ``slot`` of its stacks there, in
    ``carry``); ``keep(h) -> kept`` says what of a pass's normed stream
    the exit reads (the whole block, or one position a row).  Returns
    (carry, kept states (T, ...), their gates (T, ...))."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, L = z.loop_steps, z.num_layers
    scanned = {n: w[n] for n in _LAYER_LEAVES}

    def one_layer(state, per):
        x, carry = state
        p, slot = per
        return layer(x, carry, p, slot), None

    def one_pass(t, state):
        x, carry, kept, gates = state
        (x, carry), _ = lax.scan(
            one_layer, (x, carry),
            (scanned, t * L + jnp.arange(L, dtype=jnp.int32)))
        with jax.named_scope("serve.loop_norm"):
            x = _ops.rms_norm(x, w["lnf_gamma"], z.eps)
        with jax.named_scope("serve.exit"):
            h = keep(x)
            gate = jax.nn.sigmoid(
                _ops.mm("...c,oc->...o", h, w["exit_weight"])[..., 0]
                + w["exit_bias"].astype(jnp.float32)[0])
            return (x, carry, kept.at[t].set(h), gates.at[t].set(gate))

    shape = jax.eval_shape(keep, x).shape
    _, carry, kept, gates = lax.fori_loop(
        0, T, one_pass, (x, carry, jnp.zeros((T,) + shape, jnp.float32),
                         jnp.zeros((T,) + shape[:-1], jnp.float32)))
    return carry, kept, gates


def _head(z, w, kept, gates):
    """The kept states (T, ..., C) and their gates (T, ...) → (the exit
    steps (...), logits (..., vocab) of each position's exit step)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("serve.exit"):
        t = _exit_step(z, gates)
        h = jnp.take_along_axis(kept, t[None, ..., None], axis=0)[0]
    with jax.named_scope("serve.head"):
        return t, _ops.mm("...c,vc->...v", h, w["head_weight"])


def _forward(z, names, ids, *weights):
    """(B, T) ids → (B, T, vocab) float32 logits, no cache."""
    import jax.numpy as jnp

    w = dict(zip(names, weights))
    ids = ids.astype(jnp.int32)
    B, S = ids.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def layer(x, carry, p, slot):
        q, k, v = _qkv(z, p, x, pos)
        return _branches(z, p, x, _block_attention(z, q, k, v, None)), carry

    x = jnp.take(w["embed_weight"], ids, axis=0).astype(jnp.float32)
    _, kept, gates = _loops(z, w, x, (), layer, lambda h: h)
    return _head(z, w, kept, gates)[1]


class OuroModel(HybridBlock):
    """Embedding → (``num_layers`` layers → RMSNorm → exit gate) x
    ``loop_steps`` over one set of weights → untied head at each
    position's exit step.  Input (B, T) token ids, output (B, T, vocab)
    float32 logits.

    The layers' parameters are stacked by layer; all are created in
    ``dtype``; ``grad_req="null"`` keeps a serving copy from allocating
    gradients."""

    def __init__(self, vocab_size, units, num_layers, num_heads, head_dim,
                 hidden_size, loop_steps, kv_heads=None, exit_threshold=1.0,
                 rope_theta=1000000.0, eps=1e-6, max_length=2048,
                 dtype="float32", grad_req="write", **kwargs):
        super().__init__(**kwargs)
        kv_heads = kv_heads or num_heads
        if num_heads % kv_heads or head_dim % 2 or loop_steps < 1:
            raise MXNetError(
                "OuroModel: kv_heads divides num_heads, an even head_dim "
                "and at least one loop step")
        self._max_length = max_length
        self._vocab = vocab_size
        self._sizes = z = _Sizes(
            vocab=vocab_size, units=units, num_layers=num_layers,
            num_heads=num_heads, kv_heads=kv_heads, head_dim=head_dim,
            hidden_size=hidden_size, loop_steps=loop_steps,
            exit_threshold=float(exit_threshold),
            rope_theta=float(rope_theta), eps=float(eps))
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
        fn.__name__ = "ouro_forward"
        return invoke_simple(fn, (ids,) + tuple(params[n]
                                                 for n in self._names))

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        """What `serving.ServingEngine` serves this family through."""
        if mesh is not None:
            raise MXNetError(
                "OuroModel serves from one chip, which holds it whole: "
                "more chips are more replicas (mesh= is not supported "
                "for this family)")
        return OuroProgram(self, dtype)


class OuroProgram(DecoderProgram):
    """The family's decoder program (docs/serving.md,
    `_decoder_program.py`): its cache's shapes, its layers and its head.
    The one layer body is traced once: a call its tallies count stands
    for its T L runs."""

    def __init__(self, model, dtype=None):
        super().__init__(model, dtype)
        z = self._z
        # what a reloaded model must share beyond its shapes
        self.signature = (z.num_heads, z.kv_heads, z.head_dim, z.loop_steps,
                          z.exit_threshold, z.rope_theta, z.eps)

    def cache_shapes(self, B):
        """(keys, values): a slot for every (loop step, layer); then the
        counters."""
        import jax.numpy as jnp

        z = self._z
        shape = (z.loop_steps * z.num_layers, B, z.kv_heads, z.head_dim,
                 self.window)
        return ([(shape, None), (shape, None)],
                # [prefill / decode, (passes, positions a slot, rows
                # that left at each step)]
                [((2, 2 + z.loop_steps), jnp.uint32)])

    def counters(self, cache):
        """The counters of one served group, read back once
        (docs/observability.md has the table).  A decode step counts
        the rows that still want a token (``live``); its passes run for
        the whole bucket and count once a step."""
        import numpy as np

        z = self._z
        c = np.asarray(cache[2]).astype(np.int64)
        out = {}
        for i, phase in enumerate(("prefill", "decode")):
            out[f"loop_passes_{phase}"] = int(c[i, 0])
            # a position is read in every slot
            out[f"attn_positions_{phase}"] = int(c[i, 1]) \
                * z.loop_steps * z.num_layers
            out[f"loop_exit_step_{phase}"] = [int(n) for n in c[i, 2:]]
        return out

    # -- the traced step -------------------------------------------------------

    def body(self, ctx, w, cache, toks):
        """The logits are of each row's exit step.  S > 1 is a prefill
        from an empty cache: it attends inside the block.  S = 1 attends
        over the caches, a row that wants no token to nothing; it is
        counted nowhere."""
        import jax
        import jax.numpy as jnp

        z = self._z
        B, decode, pos, last = ctx.B, ctx.decode, ctx.pos, ctx.last
        x, at, _ = _ops.embed(w["embed_weight"], toks, pos, last)

        def layer(x, stacks, p, slot):
            q, k, v = _qkv(z, p, x, at)
            with jax.named_scope("serve.cache_write"):
                # row b's block at [slot, b, :, :, pos[b]:]
                stacks = ctx.write(
                    stacks, (k.swapaxes(2, 3), v.swapaxes(2, 3)), slot, pos)
            if decode:
                with jax.named_scope("serve.attn"):
                    q = (q[:, :, 0] * z.head_dim ** -0.5).astype(k.dtype)
                    a = ctx.attend(
                        q.reshape(B, z.kv_heads, z.groups, z.head_dim),
                        *stacks, slot)
                    a = a.reshape(B, 1, -1)
            else:
                a = _block_attention(z, q, k, v, last + 1, ctx.attends)
            return _branches(z, p, x, a), stacks

        stacks, kept, gates = _loops(
            z, w, x, tuple(cache[:2]), layer,
            lambda h: jnp.take_along_axis(h, last[:, None, None],
                                          axis=1)[:, 0])
        left, logits = _head(z, w, kept, gates)
        n = (last + 1).astype(jnp.uint32)
        seen = jnp.sum(ctx.held.astype(jnp.uint32)) if decode \
            else jnp.sum(n * (n + 1) // 2)
        left_at = left[:, None] == jnp.arange(z.loop_steps)[None, :]
        if decode:
            left_at &= ctx.live[:, None]
        counts = cache[2].at[int(decode)].add(jnp.concatenate([
            jnp.stack([jnp.uint32(z.loop_steps), seen]),
            jnp.sum(left_at, axis=0, dtype=jnp.uint32)]))
        return stacks + (counts,), logits


def ouro_tiny(**kwargs):
    """A test-sized member of the family: more loop steps than one and a
    cache deeper than its weights."""
    cfg = dict(vocab_size=96, units=64, num_layers=2, num_heads=4,
               head_dim=16, hidden_size=96, loop_steps=3, max_length=64)
    cfg.update(kwargs)
    return OuroModel(**cfg)

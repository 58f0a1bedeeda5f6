"""Kimi-K2 (``model_type`` ``kimi_k2``; DeepSeek-V3's block), the
language model: a decoder whose every layer has

- **latent attention** (MLA).  A position's keys and values are
  up-projections of one normed latent ``c`` (``kv_rank`` wide) and one
  rotated key ``k_r`` (``rope_dim`` wide) that all heads share; queries
  come from a normed latent of their own (``q_rank``).  A head's query
  and key are ``nope_dim`` unrotated dimensions beside ``rope_dim``
  rotated ones (dimension j paired with j + rope_dim/2, YaRN's table of
  frequencies: `_decoder_ops.yarn_frequencies`), its value ``v_dim``;
  the softmax scale carries YaRN's magnitude factor squared;
- layer 0 a dense SwiGLU; every later layer sigmoid-routed experts
  (`ops/moe.py::sigmoid_topk_route` with the routed scaling factor) of
  which this block holds ``experts_held = (lo, n)``, a chip's share of
  an expert-parallel deployment, **and a shared expert** that every
  token goes through (every chip computes it alike: it is counted once
  when shares are summed).  The router scores all ``router_experts``;
  what the absent experts would add is left out, and nothing stands in
  for their exchange;
- RMSNorm, no bias, an unscaled embedding, an untied head.

Layer 0 has parameters of its own (``l0_*``); the expert layers are
alike, stacked by layer and scanned.  ``hybrid_forward`` is the uncached
full-sequence forward by the expanded equations.  ``decoder_program``
hands `serving.ServingEngine` the family's program
(`_decoder_program.DecoderProgram`; docs/serving.md, "The decoder
program"), of which this file states the cache's shapes and the layer
body.  Its cache is **one stack with no heads**, ``(L, B, 1, kv_rank +
rope_dim, W)``: a position's ``[c ; k_r]``, 1,152 B in bfloat16 at the
published sizes where 64 heads of keys and values would take 40,960 B.
Two counter arrays ride in the same carry (``counters``).

**Two attention paths in one program.**  Decode (S = 1) is *absorbed*:
it never expands the cache.  ``W_uk`` is folded into the query
(``q~ = q_n W_uk``, kv_rank wide) and ``W_uv`` into the output, and
`ops/cache_attention.py::attend_rows` reads the latent stack as one
shared key head of ``kv_rank + rope_dim`` whose first ``kv_rank`` rows
are also the values, one copy a block.  Prefill (S > 1, from an empty
cache) is *expanded*: the block's latents become ``num_heads`` keys and
values, heads first, and the block attends inside itself through
`ops/pallas_attention.py::flash_attention_forward`, each row to its own
length (any block length: the entry pads a bucket of 8-64 positions to
the kernel's 128).  That call is a forward alone: neither path takes a
gradient, as the loops with traced bounds before it took none.

**Prefill works a row through all its layers before the next**
(``prefill_chunk_tokens // S`` rows at a time, one at the published
sizes), and inside a layer cuts the token-wise products along S
(`_decoder_ops.by_tokens`, ``token_chunk`` positions), stopping at the
row's length.  At a width of 7,168 a group's residual stream (8 x 16,384
x 7,168 float32) is 3.76 GB and one row's dense feed-forward
temporaries 2.4 GB, beside 7.0 GB of weights: rows inside each layer, as
`mimo_v2.py` and `keye_vl2.py` work, do not fit.  The price is that the
weights are read once a row chunk instead of once a group: 7 GB x 8 rows
at 819 GB/s is 68 ms of a prefill of seconds.
"""

from __future__ import annotations

from ...base import MXNetError
from ...ops import pallas_attention
from ..block import HybridBlock
from . import _decoder_ops as _ops
from ._decoder_program import DecoderProgram

_ATTN_LEAVES = ("ln1_gamma", "q_down_weight", "q_norm_gamma", "q_up_weight",
                "kv_down_weight", "kv_norm_gamma", "kv_up_weight",
                "o_weight", "ln2_gamma")
_DENSE_LEAVES = ("gate_weight", "up_weight", "down_weight")
_MOE_LEAVES = ("router_weight", "router_bias", "shared_gate_weight",
               "shared_up_weight", "shared_down_weight",
               "experts_gate_up_weight", "experts_down_weight")
# the experts' stacks are not scanned: `_decoder_ops.experts_of_layer`
# says why
_SCANNED_LEAVES = _ATTN_LEAVES + _MOE_LEAVES[:-2]


class _Sizes:
    """The family's sizes, as the constructor got them."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.latent = self.kv_rank + self.rope_dim
        self.inv_freq = _ops.yarn_frequencies(
            self.rope_dim, self.rope_theta, self.rope_factor,
            self.rope_original_length, self.beta_fast, self.beta_slow)
        # YaRN: the scores carry mscale_all_dim's factor squared, and
        # the rotation the ratio of the two factors (1 as published)
        m = _ops.yarn_mscale(self.rope_factor, self.mscale_all_dim)
        self.softmax_scale = (self.nope_dim + self.rope_dim) ** -0.5 * m * m
        self.rope_mscale = _ops.yarn_mscale(self.rope_factor,
                                            self.mscale) / m

    def leaf_names(self):
        return (("embed_weight",)
                + tuple("l0_" + n for n in _ATTN_LEAVES + _DENSE_LEAVES)
                + _ATTN_LEAVES + _MOE_LEAVES + ("lnf_gamma", "head_weight"))

    def shape_of(self, name):
        z = self
        C, H, n = z.units, z.num_heads, z.experts_held[1]
        Fs = z.shared_experts * z.expert_hidden
        if name in ("embed_weight", "head_weight"):
            return (z.vocab, C)
        if name == "lnf_gamma":
            return (C,)
        shape = {
            "ln1_gamma": (C,), "ln2_gamma": (C,),
            "q_down_weight": (z.q_rank, C), "q_norm_gamma": (z.q_rank,),
            "q_up_weight": (H * (z.nope_dim + z.rope_dim), z.q_rank),
            "kv_down_weight": (z.latent, C), "kv_norm_gamma": (z.kv_rank,),
            "kv_up_weight": (H * (z.nope_dim + z.v_dim), z.kv_rank),
            "o_weight": (C, H * z.v_dim),
            "gate_weight": (z.hidden_size, C), "up_weight": (z.hidden_size, C),
            "down_weight": (C, z.hidden_size),
            "router_weight": (z.router_experts, C),
            "router_bias": (z.router_experts,),
            "shared_gate_weight": (Fs, C), "shared_up_weight": (Fs, C),
            "shared_down_weight": (C, Fs),
            "experts_gate_up_weight": (n, C, 2 * z.expert_hidden),
            "experts_down_weight": (n, z.expert_hidden, C),
        }[name[3:] if name.startswith("l0_") else name]
        return shape if name.startswith("l0_") else \
            (z.num_layers - 1,) + shape


# -- a layer's pieces, shared by the forward pass and the cached step ----------

def _rotate(z, x, pos):
    """x (B, .., S, rope_dim) float32 at positions ``pos`` (B, S)."""
    x = _ops.rope(x, pos, None, z.rope_dim, freq=z.inv_freq)
    return x if z.rope_mscale == 1.0 else x * z.rope_mscale


def _down(z, p, x, pos):
    """x (B, S, C) float32 → the query latent c_q (B, S, q_rank), normed,
    and what the cache holds of a position, (B, S, kv_rank + rope_dim):
    the normed latent c beside the rotated shared key k_r; both in the
    weights' type."""
    import jax
    import jax.numpy as jnp

    dt = p["q_down_weight"].dtype
    with jax.named_scope("serve.attn_down"):
        u = _ops.rms_norm(x, p["ln1_gamma"], z.eps).astype(dt)
        cq = _ops.rms_norm(_ops.mm("bsc,rc->bsr", u, p["q_down_weight"]),
                           p["q_norm_gamma"], z.eps)
        ck = _ops.mm("bsc,rc->bsr", u, p["kv_down_weight"])
        c = _ops.rms_norm(ck[..., :z.kv_rank], p["kv_norm_gamma"], z.eps)
        return cq.astype(dt), jnp.concatenate(
            [c, _rotate(z, ck[..., z.kv_rank:], pos)], axis=-1).astype(dt)


def _queries(z, p, cq, pos):
    """c_q (B, S, q_rank) → q_n (B, H, S, nope_dim) and q_r (B, H, S,
    rope_dim) rotated, float32."""
    B, S, _ = cq.shape
    q = _ops.mm("bsr,gr->bsg", cq, p["q_up_weight"]).reshape(
        B, S, z.num_heads, z.nope_dim + z.rope_dim).transpose(0, 2, 1, 3)
    return q[..., :z.nope_dim], _rotate(z, q[..., z.nope_dim:], pos)


def _kv_up(z, p):
    """W_ukv by head: (H, nope_dim + v_dim, kv_rank), a head's W_uk
    above its W_uv."""
    return p["kv_up_weight"].reshape(z.num_heads, z.nope_dim + z.v_dim,
                                     z.kv_rank)


def _expanded(z, p, cq, latent, pos):
    """The block's queries, keys and values heads first, (B, H, S, .) in
    the weights' type, as the kernel takes them: q scaled, k = [c W_uk ;
    k_r], v = c W_uv.  q and k end in zeros up to a whole number of lane
    tiles (192 -> 256), which is how HBM holds their rows anyway: the
    kernel's copies move whole tiles, and the scores are the same."""
    import jax
    import jax.numpy as jnp

    B, S, _ = cq.shape
    H, dt = z.num_heads, cq.dtype
    D = z.nope_dim + z.rope_dim
    zeros = jnp.zeros((B, H, S, pallas_attention.lane_tiles(D) - D), dt)
    with jax.named_scope("serve.attn_q_up"):
        q = jnp.concatenate(
            [(x * z.softmax_scale).astype(dt)
             for x in _queries(z, p, cq, pos)] + [zeros], axis=-1)
    with jax.named_scope("serve.attn_kv_up"):
        kv = _ops.mm("bsr,hdr->bhsd", latent[..., :z.kv_rank],
                     _kv_up(z, p)).astype(dt)
        kr = jnp.broadcast_to(latent[:, None, :, z.kv_rank:],
                              (B, H, S, z.rope_dim))
        return (q, jnp.concatenate([kv[..., :z.nope_dim], kr, zeros],
                                   axis=-1), kv[..., z.nope_dim:])


def _absorbed_query(z, p, cq, pos):
    """One position a row, c_q (B, 1, q_rank) → (B, 1, H, kv_rank +
    rope_dim): [q_n W_uk ; q_r] scaled, the query of the latent stack
    read as one key head."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("serve.attn_q_up"):
        qn, qr = _queries(z, p, cq, pos)
        # heads first: the one batched form every backend has in bfloat16
        qa = _ops.mm("hbd,hdr->bhr", qn[:, :, 0].swapaxes(0, 1),
                     _kv_up(z, p)[:, :z.nope_dim])
        q = jnp.concatenate([qa, qr[:, :, 0]], axis=-1) * z.softmax_scale
        return q[:, None].astype(cq.dtype)


def _absorbed_out(z, p, x, a):
    """x + [a^h W_uv^h] W_o for the latent-wide a (B, 1, H, kv_rank)."""
    import jax

    with jax.named_scope("serve.attn_out"):
        o = _ops.mm("hbr,hdr->bhd", a[:, 0].swapaxes(0, 1),
                    _kv_up(z, p)[:, z.nope_dim:])
        return x + _ops.mm("bg,cg->bc", o.reshape(o.shape[0], -1),
                           p["o_weight"])[:, None]


def _sigmoid_route(z, p, x):
    from ...ops import moe

    return _ops.route(z, p, x, lambda u: moe.sigmoid_topk_route(
        u, p["router_weight"], p["router_bias"], z.experts_per_token,
        scale=z.route_scale))


def _feed_forward_front(z, p, x):
    """What of a layer's feed-forward a token needs no other token for:
    layer 0's whole SwiGLU (``p`` holds ``gate_weight``), or the router
    and the shared expert.  Returns (x, route or ())."""
    import jax

    from ...ops import moe

    if "gate_weight" in p:
        with jax.named_scope("serve.mlp"):
            u = _ops.rms_norm(x, p["ln2_gamma"], z.eps)
            return x + moe.swiglu_ffn(u, p["gate_weight"], p["up_weight"],
                                      p["down_weight"]), ()
    route = _sigmoid_route(z, p, x)
    with jax.named_scope("serve.moe.shared"):
        return x + moe.swiglu_ffn(route[0], p["shared_gate_weight"],
                                  p["shared_up_weight"],
                                  p["shared_down_weight"]), route


def _block_layer(z, p, x, pos, lengths, tally=None):
    """A layer on a block (B, S, C) that attends inside itself, as far
    as a row needs no other row: expanded attention, then the dense
    feed-forward or the router and the shared expert.  ``lengths`` (B,)
    traced, the rows' real positions, or None for all: token-wise
    products run ``token_chunk`` positions at a time up to the longest
    row's, the kernel (in blocks of ``attn_block`` where S is a
    multiple of it, its own choice for a shorter block) to each row's
    own, and a row's queries past its length come out zero.  ``tally`` (a
    Counter or None) is told the attention call's path.  Returns
    (x, latent (B, S, kv_rank + rope_dim), route or ())."""
    import jax
    import jax.numpy as jnp

    S = x.shape[1]
    chunk = min(S, z.token_chunk)
    live = None if lengths is None else jnp.max(lengths)

    def front(x, pos):
        cq, latent = _down(z, p, x, pos)
        return None, (latent,) + _expanded(z, p, cq, latent, pos)

    def back(x, a):
        # heads first as the kernel left them: contracted where they lie
        with jax.named_scope("serve.attn_out"):
            x = x + _ops.mm("bhsd,chd->bsc", a, p["o_weight"].reshape(
                -1, z.num_heads, z.v_dim))
        return _feed_forward_front(z, p, x)

    x, (latent, q, k, v) = _ops.by_tokens(front, chunk, live, x, pos,
                                          out_axes=(1, 2, 2, 2))
    with jax.named_scope("serve.attn_full"):
        # the queries arrive scaled (YaRN's factor inside)
        block = None if S % z.attn_block else z.attn_block
        a = pallas_attention.flash_attention_forward(
            q, k, v, lengths, scale=1.0, block_q=block, block_k=block)
        if tally is not None:
            tally["kernel"] += 1
    x, route = _ops.by_tokens(back, chunk, live, x, a, axes=(2,))
    return x, latent, route


def _layers(z, w, x, valid, carry, moe_counts, attend, tally=None):
    """x through layer 0 and the scanned expert layers.  ``attend(x,
    carry, p, l) -> (x, carry, route or ())`` is a layer's attention and
    what of its feed-forward needs no other token (the cached step
    writes and reads its cache there, in ``carry``); ``valid`` (B, S)
    marks the real tokens (None: all), ``moe_counts`` (L - 1, n + 3) are
    the expert layers' counters, ``tally`` the program's count of the
    experts' calls by path.  Returns (x, carry, moe_counts)."""
    import jax.numpy as jnp
    from jax import lax

    n = z.experts_held[1]
    p0 = {name: w["l0_" + name] for name in _ATTN_LEAVES + _DENSE_LEAVES}
    x, carry, _ = attend(x, carry, p0, 0)

    def layer(state, per):
        x, carry, moe_counts = state
        p, l = per
        x, carry, route = attend(x, carry, p, l)
        # padding is routed nowhere: only real tokens cost
        x, stats = _ops.experts_of_layer(
            z, w["experts_gate_up_weight"], w["experts_down_weight"], l - 1,
            x, route, valid, tally)
        return (x, carry, moe_counts.at[l - 1].add(
            _ops.moe_count_row(stats, n))), None

    state, _ = lax.scan(
        layer, (x, carry, moe_counts),
        ({name: w[name] for name in _SCANNED_LEAVES},
         jnp.arange(1, z.num_layers, dtype=jnp.int32)))
    return state


def _forward(z, names, ids, *weights):
    """(B, T) ids → (B, T, vocab) float32 logits, no cache."""
    import jax.numpy as jnp

    w = dict(zip(names, weights))
    ids = ids.astype(jnp.int32)
    B, T = ids.shape
    blk = min(T, z.attn_block)
    S = T + -T % blk
    ids = jnp.pad(ids, ((0, 0), (0, S - T)))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def attend(x, carry, p, l):
        x, _, route = _block_layer(z, p, x, pos, None)
        return x, carry, route

    x = jnp.take(w["embed_weight"], ids, axis=0).astype(jnp.float32)
    x, _, _ = _layers(
        z, w, x, pos < T, (), jnp.zeros(
            (z.num_layers - 1, z.experts_held[1] + 3), jnp.int32), attend)
    h = _ops.rms_norm(x[:, :T], w["lnf_gamma"], z.eps)
    return _ops.mm("btc,vc->btv", h, w["head_weight"])


class KimiK2Model(HybridBlock):
    """Embedding → a dense layer → ``num_layers - 1`` expert layers →
    RMSNorm → untied head.  Input (B, T) token ids, output (B, T, vocab)
    float32 logits.

    The expert layers' parameters are stacked by layer; all are created
    in ``dtype``; ``grad_req="null"`` keeps a serving copy from
    allocating gradients."""

    def __init__(self, vocab_size, units, num_layers, num_heads, q_rank,
                 kv_rank, nope_dim, rope_dim, v_dim, hidden_size,
                 expert_hidden, router_experts, experts_per_token,
                 experts_held=None, shared_experts=1, route_scale=1.0,
                 rope_theta=50000.0, rope_factor=1.0,
                 rope_original_length=4096, beta_fast=32, beta_slow=1,
                 mscale=1.0, mscale_all_dim=0.0, eps=1e-5, max_length=2048,
                 dtype="float32", grad_req="write", attn_block=1024,
                 prefill_chunk_tokens=16384, token_chunk=2048,
                 moe_pass_rows=None, **kwargs):
        super().__init__(**kwargs)
        held = tuple(experts_held or (0, router_experts))
        if held[0] < 0 or held[0] + held[1] > router_experts:
            raise MXNetError(f"KimiK2Model: experts_held {held} lies "
                             f"outside the router's {router_experts}")
        if num_layers < 2 or rope_dim % 2:
            raise MXNetError("KimiK2Model: a dense layer and at least one "
                             "expert layer, an even rope_dim")
        self._max_length = max_length
        self._vocab = vocab_size
        self._sizes = z = _Sizes(
            vocab=vocab_size, units=units, num_layers=num_layers,
            num_heads=num_heads, q_rank=q_rank, kv_rank=kv_rank,
            nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
            hidden_size=hidden_size, expert_hidden=expert_hidden,
            shared_experts=shared_experts, router_experts=router_experts,
            experts_per_token=experts_per_token, experts_held=held,
            route_scale=float(route_scale), rope_theta=float(rope_theta),
            rope_factor=float(rope_factor),
            rope_original_length=rope_original_length,
            beta_fast=beta_fast, beta_slow=beta_slow, mscale=float(mscale),
            mscale_all_dim=float(mscale_all_dim), eps=float(eps),
            max_length=max_length, attn_block=attn_block,
            prefill_chunk_tokens=prefill_chunk_tokens,
            token_chunk=token_chunk, moe_pass_rows=moe_pass_rows)
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
        fn.__name__ = "kimi_k2_forward"
        return invoke_simple(fn, (ids,) + tuple(params[n]
                                                 for n in self._names))

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        """What `serving.ServingEngine` serves this family through."""
        if mesh is not None:
            raise MXNetError(
                "KimiK2Model serves from one chip: its experts are a "
                "share of a deployment whose exchange this repo does not "
                "have (mesh= is not supported for this family)")
        return KimiK2Program(self, dtype)


class KimiK2Program(DecoderProgram):
    """The family's decoder program (docs/serving.md,
    `_decoder_program.py`): its cache's shapes, its layers and its head."""

    def __init__(self, model, dtype=None):
        super().__init__(model, dtype)
        z = self._z
        # what a reloaded model must share beyond its shapes
        self.signature = (
            z.num_heads, z.nope_dim, z.rope_dim, z.experts_held,
            z.experts_per_token, z.route_scale, z.rope_theta, z.rope_factor,
            z.rope_original_length, z.beta_fast, z.beta_slow, z.mscale,
            z.mscale_all_dim)

    def cache_shapes(self, B):
        """The latent stack, ``L x B x (kv_rank + rope_dim) x W``
        elements for attention and no more; then the expert and the
        attention counters."""
        import jax.numpy as jnp

        z = self._z
        L = z.num_layers
        return ([((L, B, 1, z.latent, self.window), None)],
                [((L - 1, 2, z.experts_held[1] + 3), jnp.int32),
                 # [layer, prefill / decode]: a layer's positions of an
                 # 8 x 16,384 prefill are 3.7e8, all layers' pass 2**31
                 ((L, 2), jnp.uint32)])

    def counters(self, cache):
        """The counters of one served group, read back once
        (docs/observability.md has the table): the expert layers' under
        MiMo's names, and the positions a row's queries attended to,
        summed over rows, layers and steps."""
        import numpy as np

        out = _ops.moe_counters(cache[1], self._z.experts_held[1])
        c = np.asarray(cache[2]).astype(np.int64).sum(axis=0)
        out["attn_latent_positions_prefill"] = int(c[0])
        out["attn_latent_positions_decode"] = int(c[1])
        return out

    # -- the traced step -------------------------------------------------------

    def body(self, ctx, w, cache, toks):
        """S > 1 is a prefill from an empty cache: expanded attention
        inside the block, a row chunk through all layers before the
        next.  S = 1 is absorbed attention over the latent stack, a row
        that wants no token to nothing; it goes to no routed expert and
        is counted nowhere."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        z = self._z
        B, decode, live, held = ctx.B, ctx.decode, ctx.live, ctx.held

        def write(stack, latent, l, at, row):
            """The rows' latents (R, S, .) into the stack at [l, row + r,
            0, :, at[r]:]."""
            with jax.named_scope("serve.cache_write"):
                stack, = ctx.write(
                    [stack], [latent.swapaxes(1, 2)[:, None]], l, at,
                    row=row)
                return stack

        def rows(toks, pos, last, row, carry):
            """Rows ``row ..`` of the group through every layer; carry
            (stack, attention counters, expert counters).  Returns
            (carry, the rows' logits)."""
            x, at, valid = _ops.embed(w["embed_weight"], toks, pos, last)

            def attend(x, carry, p, l):
                stack, seen = carry
                if decode:
                    cq, latent = _down(z, p, x, at)
                    stack = write(stack, latent, l, pos, None)
                    q = _absorbed_query(z, p, cq, at)
                    with jax.named_scope("serve.attn_latent"):
                        a = ctx.attend(q, stack, None, l,
                                       leading=z.kv_rank)
                    x, route = _feed_forward_front(
                        z, p, _absorbed_out(z, p, x, a))
                    n_seen = jnp.sum(held)
                else:
                    x, latent, route = _block_layer(z, p, x, at, last + 1,
                                                    ctx.attends)
                    stack = write(stack, latent, l, pos, row)
                    n_live = (last + 1).astype(jnp.uint32)
                    n_seen = jnp.sum(n_live * (n_live + 1) // 2)
                seen = seen.at[l, int(decode)].add(n_seen.astype(jnp.uint32))
                return x, (stack, seen), route

            # the experts' counters: this phase's column of the carry
            stack, seen, moe_counts = carry
            x, (stack, seen), phase = _layers(
                z, w, x, live[:, None] if decode else valid, (stack, seen),
                moe_counts[:, int(decode)], attend, ctx.products)
            moe_counts = moe_counts.at[:, int(decode)].set(phase)
            with jax.named_scope("serve.head"):
                h = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
                logits = _ops.mm("bc,vc->bv",
                                 _ops.rms_norm(h, w["lnf_gamma"], z.eps),
                                 w["head_weight"])
            return (stack, seen, moe_counts), logits

        stack, moe_counts, seen = cache
        R = B if decode else _ops.chunk_rows(z, B, ctx.S)
        if R == B:
            (stack, seen, moe_counts), logits = rows(
                toks, ctx.pos, ctx.last, None, (stack, seen, moe_counts))
            return (stack, moe_counts, seen), logits

        def chunk(c, state):
            carry, logits = state
            cut = lambda a: lax.dynamic_slice_in_dim(a, c * R, R, axis=0)
            carry, part = rows(cut(toks), cut(ctx.pos), cut(ctx.last),
                               c * R, carry)
            return carry, lax.dynamic_update_slice_in_dim(
                logits, part, c * R, axis=0)

        (stack, seen, moe_counts), logits = lax.fori_loop(
            0, B // R, chunk, ((stack, seen, moe_counts),
                               jnp.zeros((B, self.vocab), jnp.float32)))
        return (stack, moe_counts, seen), logits


def kimi_k2_tiny(**kwargs):
    """A test-sized member of the family with every mechanism present: a
    dense layer 0, a shared expert, rotated dimensions fewer than
    unrotated ones, a YaRN factor with contexts past the original
    length."""
    cfg = dict(vocab_size=96, units=64, num_layers=3, num_heads=4, q_rank=24,
               kv_rank=16, nope_dim=16, rope_dim=8, v_dim=12, hidden_size=96,
               expert_hidden=32, router_experts=8, experts_per_token=2,
               route_scale=2.5, rope_theta=50000.0, rope_factor=8.0,
               rope_original_length=8, beta_fast=32, beta_slow=1,
               mscale=1.0, mscale_all_dim=1.0, max_length=64, attn_block=16,
               token_chunk=16)
    cfg.update(kwargs)
    return KimiK2Model(**cfg)

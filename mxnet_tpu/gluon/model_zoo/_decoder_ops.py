"""What the served decoder families share (`mimo_v2.py`, `keye_vl2.py`,
`kimi_k2.py`, `ouro.py`, `cohere2_moe.py`, `jamba.py`, `granite_hybrid.py`): the pieces of a layer that do
not depend on a family's attention or routing rule.  No family imports
another; a change here is a change to all, and their cells measure it.

- `mm`, `rms_norm`, `layer_norm`, `rope`: the mixed-precision product,
  RMSNorm, the bias-free LayerNorm and the rotation (over the first
  ``rot`` dimensions, by ``theta`` or by a table of frequencies:
  `yarn_frequencies`; rotate-half, or Cohere's interleaved pairs);
- `attn_out`: heads side by side, then ``x + a Wo`` (attention over
  the caches is `ops/cache_attention.py`, GPT's too; a block's
  attention inside itself is the family's own: Kimi-K2's goes through
  `ops/pallas_attention.py::flash_attention_forward`);
- `route`: the second norm and the router, the routing rule passed in
  (the dense feed-forward and the shared expert are
  `ops/moe.py::swiglu_ffn`); `experts_of_layer`: a scanned layer's held
  experts; `moe_count_row`, `moe_counters`: an expert layer's counters in the
  donated carry and their read-back (docs/observability.md);
- `by_rows`, `chunk_rows`: a prefill block worked off a few rows at a
  time; `rows_in_chunks`: a few rows through every layer before the
  next (the families whose carry holds states); `by_tokens`: a few positions at a time; `embed`: a block's way
  in;
- `packing`, `pack`, `unpack`: a block's real tokens, row after row, at
  the front of one flat row of positions, so that what acts on one
  token at a time (`by_tokens` over that row) works no padding, and the
  way back into rows for what needs a row's order (`jamba.py`'s and
  `mimo_v2.py`'s prefills; Keye's is the next caller);
  `packed_counters`: the two counters such a prefill keeps, read back;
  `state_counters`: a state-space family's five (`jamba.py`,
  `granite_hybrid.py`).

What a decoder program is apart from its layers (its weights, its cache,
the step's prologue, the cache write and the attention over the cache)
is `_decoder_program.py`.
"""

from __future__ import annotations

import collections

_MASKED = -1e30
# `packing`'s answer
Packing = collections.namedtuple("Packing", "src slot n last tile")


def mm(spec, a, w):
    """The one mixed-precision product: the activation in the weight's
    type, the result float32."""
    import jax.numpy as jnp

    return jnp.einsum(spec, a.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    import jax.numpy as jnp
    from jax import lax

    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g.astype(jnp.float32)


def layer_norm(x, g, eps):
    """LayerNorm with a gain and no bias, the statistics in x's float32."""
    import jax.numpy as jnp
    from jax import lax

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g.astype(jnp.float32)


def rope(x, pos, theta, rot, freq=None, pairs="halves"):
    """x (B, .., S, D) float32 rotated on its first ``rot`` dimensions at
    positions ``pos`` (B, S) by ``theta ** (-2 j / rot)`` or by the
    table ``freq`` (rot / 2,).  ``pairs``: ``"halves"`` pairs dimension
    j with j + rot/2 (rotate-half); ``"interleaved"`` pairs 2j with
    2j + 1 (``rope_gptj``: Cohere's).  The two are a relabelling of the
    projection's columns and not interchangeable under given weights."""
    import jax.numpy as jnp

    half = rot // 2
    if freq is None:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    else:
        freq = jnp.asarray(freq, jnp.float32)
    ang = pos.astype(jnp.float32)[..., None] * freq          # (B, S, half)
    shape = (pos.shape[0],) + (1,) * (x.ndim - 3) + (pos.shape[1], half)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if pairs == "interleaved":
        a, b = x[..., 0:rot:2], x[..., 1:rot:2]
        turned = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
        return jnp.concatenate(
            [turned.reshape(x.shape[:-1] + (rot,)), x[..., rot:]], axis=-1)
    if pairs != "halves":
        raise ValueError(f"rope: pairs is 'halves' or 'interleaved', "
                         f"got {pairs!r}")
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def yarn_mscale(factor, m):
    """YaRN's magnitude factor ``0.1 m ln(factor) + 1`` (1 without
    stretching)."""
    import math

    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_frequencies(rot, base, factor, original, beta_fast, beta_slow):
    """YaRN's table of ``rot / 2`` rotary frequencies (float32): pair i
    keeps ``base ** (-2 i / rot)`` where it turns more than ``beta_fast``
    times in the ``original`` positions, takes a ``factor``-th of it
    where it turns less than ``beta_slow`` times, and a linear mix in
    between."""
    import math

    import numpy as np

    def turns_at(n):
        return rot * math.log(original / (2 * math.pi * n)) \
            / (2 * math.log(base))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), rot - 1)
    i = np.arange(rot // 2, dtype=np.float64)
    f = base ** (-2.0 * i / rot)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * (1.0 - ramp) + f / factor * ramp).astype(np.float32)


def embed(table, toks, pos, last):
    """A block's way in: ``toks`` (B, S) → the stream x (B, S, C)
    float32 (an unscaled embedding), each token's position ``at`` (B, S)
    from the rows' first positions ``pos`` (B,), and ``valid`` (B, S):
    the tokens up to each row's ``last``, the others padding."""
    import jax
    import jax.numpy as jnp

    S = toks.shape[1]
    with jax.named_scope("serve.embed"):
        x = jnp.take(table, toks, axis=0).astype(jnp.float32)
        at = pos[:, None] + jnp.arange(S)[None, :]
        valid = jnp.arange(S)[None, :] <= last[:, None]
    return x, at, valid


def attn_out(z, p, x, a):
    """x + a Wo for a (B, K, G, S, Dv): heads side by side, then the
    plain product."""
    B, K, G, S, Dv = a.shape
    a = a.transpose(0, 3, 1, 2, 4).reshape(B, S, K * G * Dv)
    return x + mm("bsg,cg->bsc", a, p["o_weight"])


def route(z, p, x, choose):
    """Layer's second norm and its router on x (B, S, C): (u in the
    weights' type, chosen (B, S, k), weights (B, S, k)).  ``choose``
    (u (T, C) float32 → chosen (T, k), weights (T, k)) is the family's
    routing rule (`ops/moe.py` has them)."""
    import jax

    B, S, C = x.shape
    k = z.experts_per_token
    with jax.named_scope("serve.moe.route"):
        u = rms_norm(x, p["ln2_gamma"], z.eps)
        chosen, weights = choose(u.reshape(B * S, C))
        return (u.astype(p["router_weight"].dtype),
                chosen.reshape(B, S, k), weights.reshape(B, S, k))


def experts_of_layer(z, w13, w2, l, x, route, valid, tally=None):
    """x + layer ``l``'s held experts' part for the routed tokens, the
    experts' weights stacked by layer (``w13`` (L, n, C, 2F), ``w2``
    (L, n, F, C)); also `held_experts_ffn`'s counts for that layer.

    The stacks are handed whole with the layer beside them, and read
    where they lie: on a TPU the grouped product's kernel takes ``l`` as
    a scalar operand and finds expert e's block at ``l * n + e`` of the
    stack seen as ``(L n, ...)``, so the pairs are sorted into the
    layer's own ``n`` groups and no slice of a stack is made.  A slice
    would be copied for the product: 151 MB a layer and a decode step at
    Keye-VL-2.0's sizes, 2.8 ms of a 9.4 ms step on the v5e (PR 30,
    which for that reason gave ``lax.ragged_dot`` the whole stack as
    ``L n`` groups, all but ``n`` of them empty; the plain path, which
    the chip takes only for widths the kernel has no tiles for, slices).
    ``tally``: the program's count of such calls by path."""
    import jax

    from ...ops import moe

    B, S, C = x.shape
    u, chosen, weights = route
    k = z.experts_per_token
    with jax.named_scope("serve.moe.experts"):
        y, stats = moe.held_experts_ffn(
            u.reshape(B * S, C), chosen.reshape(B * S, k),
            weights.reshape(B * S, k), w13, w2, experts_lo=z.experts_held[0],
            valid=None if valid is None else valid.reshape(B * S),
            pass_rows=z.moe_pass_rows, add_to=x.reshape(B * S, C), layer=l,
            tally=tally)
        return y.reshape(B, S, C), stats


def moe_count_row(stats, n):
    """One call's row of an expert layer's counters, from
    `held_experts_ffn`'s counts: assignments per held expert, the rows
    the grouped product was given, the held experts hit, one call."""
    import jax.numpy as jnp

    return jnp.concatenate([
        stats, jnp.sum(stats[:n] > 0, dtype=jnp.int32)[None],
        jnp.ones((1,), jnp.int32)])


def moe_counters(c, n):
    """The expert layers' counters ``c`` (layers, prefill/decode,
    n + 3), read back: docs/observability.md has the table."""
    import numpy as np

    c = np.asarray(c).astype(np.int64)
    load = c[:, :, :n]
    total = load.sum(axis=1)                        # (Lm, n)
    decode_calls = int(c[0, 1, n + 2])
    return {
        "moe_pairs_prefill": int(load[:, 0].sum()),
        "moe_pairs_decode": int(load[:, 1].sum()),
        "moe_rows_computed_prefill": int(c[:, 0, n].sum()),
        "moe_rows_computed_decode": int(c[:, 1, n].sum()),
        "moe_experts_hit_per_step":
            float(c[:, 1, n + 1].sum()) / (decode_calls * len(c))
            if decode_calls else 0.0,
        "moe_load_max_over_mean": float(np.mean(
            total.max(axis=1) / np.maximum(total.mean(axis=1), 1e-9))),
    }


def by_rows(fn, rows, x, *per_row):
    """``fn(x, *per_row) -> (x, extras)`` over the rows of a block,
    ``rows`` at a time and one chunk after another, so that only one
    chunk's temporaries are alive: the residual stream is updated where
    it lies, and the extras (keys, values, the router's choice) fill
    buffers of their own.  ``per_row`` arrays (positions, lengths) are
    cut by rows as ``x`` is.  Whole when one chunk holds every row."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    B = x.shape[0]
    if rows >= B:
        return fn(x, *per_row)

    def chunk(a, c):
        return lax.dynamic_slice_in_dim(a, c * rows, rows, axis=0)

    _, shapes = jax.eval_shape(fn, chunk(x, 0),
                               *(chunk(a, 0) for a in per_row))
    extras = jax.tree_util.tree_map(
        lambda s: jnp.zeros((B,) + s.shape[1:], s.dtype), shapes)

    def one(c, carry):
        x, extras = carry
        xc, ex = fn(chunk(x, c), *(chunk(a, c) for a in per_row))
        put = lambda whole, part: lax.dynamic_update_slice_in_dim(
            whole, part, c * rows, axis=0)
        return put(x, xc), jax.tree_util.tree_map(put, extras, ex)

    return lax.fori_loop(0, B // rows, one, (x, extras))


def rows_in_chunks(fn, rows, carry, vocab, *per_row):
    """``fn(*per_row's rows, first row, carry) -> (carry, logits)`` over
    the rows of a group, ``rows`` at a time and one chunk after another
    through **all** that ``fn`` does (a family's every layer), the
    donated ``carry`` threaded through: (carry, logits (B, vocab)
    float32).  Whole, with ``None`` for the first row, when one chunk
    holds every row."""
    import jax.numpy as jnp
    from jax import lax

    B = per_row[0].shape[0]
    if rows >= B:
        return fn(*per_row, None, carry)

    def chunk(c, state):
        carry, logits = state
        carry, part = fn(*(lax.dynamic_slice_in_dim(a, c * rows, rows, axis=0)
                           for a in per_row), c * rows, carry)
        return carry, lax.dynamic_update_slice_in_dim(logits, part, c * rows,
                                                      axis=0)

    return lax.fori_loop(0, B // rows, chunk,
                         (carry, jnp.zeros((B, vocab), jnp.float32)))


def _chunks(tokens, live, S):
    """The chunks of ``tokens`` positions that begin before position
    ``live`` (a traced scalar, or None for all) of ``S``."""
    import jax.numpy as jnp

    return S // tokens if live is None else \
        jnp.clip((live + tokens - 1) // tokens, 0, S // tokens)


def tokens_worked(tokens, live, S):
    """The positions of ``S`` that `by_tokens` hands its ``fn`` at
    ``tokens`` a chunk and ``live``: all where one chunk holds them,
    else whole chunks up to the one that holds position ``live - 1``."""
    return S if tokens >= S else _chunks(tokens, live, S) * tokens


def by_tokens(fn, tokens, live, x, *per_token, axes=None, out_axes=1,
              into=None):
    """``fn(x, *per_token) -> (x or None, extras)`` over the positions
    of a block, ``tokens`` at a time and one chunk after another, so
    that only one chunk's temporaries are alive.  The positions are
    axis 1 of x, axis ``axes[i]`` of ``per_token[i]`` (1 where ``axes``
    is None) and axis ``out_axes`` of the extras (an int for all, or a
    tuple, one for each of a tuple of extras): heads-first arrays (B, H,
    S, .) are cut and filled along 2.  Only the chunks that begin
    before position ``live`` (a traced scalar, or None for all) are
    worked: past it the residual stream stays what it was and the
    extras stay zero, or what ``into`` held: buffers of the extras'
    shapes to fill in place of fresh zeros (a caller that comes back a
    layer later with extras of hundreds of megabytes hands the last
    layer's dead ones in and fills nothing).  ``fn`` may return None
    for x where it leaves the stream alone.  Whole when one chunk holds
    every position.

    `chunk_rows` never cuts below one row, whose token-wise temporaries
    at a width of 7,168 and 16,384 positions are gigabytes; this is the
    cut along the other axis."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = x.shape[1]
    if tokens >= S:
        out, extras = fn(x, *per_token)
        return (x if out is None else out), extras
    axes = (1,) * len(per_token) if axes is None else axes

    def chunk(a, c, axis=1):
        return lax.dynamic_slice_in_dim(a, c * tokens, tokens, axis=axis)

    def cut(c):
        return [chunk(a, c, axis) for a, axis in zip(per_token, axes)]

    shapes = into
    if into is None:
        _, shapes = jax.eval_shape(fn, chunk(x, 0), *cut(0))
    if isinstance(out_axes, int):
        out_axes = jax.tree_util.tree_map(lambda _: out_axes, shapes)
    extras = into if into is not None else jax.tree_util.tree_map(
        lambda s, axis: jnp.zeros(s.shape[:axis] + (S,) + s.shape[axis + 1:],
                                  s.dtype), shapes, out_axes)

    def one(c, carry):
        x, extras = carry
        xc, ex = fn(chunk(x, c), *cut(c))
        put = lambda whole, part, axis=1: lax.dynamic_update_slice_in_dim(
            whole, part, c * tokens, axis=axis)
        return (x if xc is None else put(x, xc),
                jax.tree_util.tree_map(put, extras, ex, out_axes))

    return lax.fori_loop(0, _chunks(tokens, live, S), one, (x, extras))


def chunk_rows(z, B, S):
    """Rows a chunk: about ``prefill_chunk_tokens`` tokens, a divisor
    of B."""
    rows = max(1, min(B, z.prefill_chunk_tokens // S))
    while B % rows:
        rows -= 1
    return rows


# -- a block's real tokens, packed ---------------------------------------------

def packing(lengths, S, tile):
    """Where a block's real tokens lie when they are packed, row after
    row, at the front of one flat row of positions: from the rows'
    ``lengths`` (R,) of ``S`` padded positions each, a `Packing` of

    - ``src`` (1, P) int32: the flat source ``row * S + position`` of
      every packed slot; a slot past the ``n`` real tokens reads some
      position of the last row, which nobody reads.  P is ``R * S`` in
      whole tiles;
    - ``slot`` (R, S) int32: the packed slot of every (row, position),
      past a row's length its last real token's, so that the way back
      into rows is a gather too, and no scatter;
    - ``n`` (): the real tokens, ``sum(lengths)``;
    - ``last`` (R,) int32: each row's last real token's slot;
    - ``tile``: the slots a tile, ``tile`` or the whole of a smaller
      block: what `by_tokens` is handed with ``live = n``.
    """
    import jax.numpy as jnp

    R = lengths.shape[0]
    tile = min(tile, R * S)
    P = -(-R * S // tile) * tile
    held = lengths.astype(jnp.int32)
    ends = jnp.cumsum(held)
    starts = ends - held
    i = jnp.arange(P, dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(ends, i, side="right"), R - 1)
    src = row * S + jnp.clip(i - starts[row], 0, S - 1)
    last = jnp.maximum(ends - 1, 0)
    slot = jnp.minimum(starts[:, None] + jnp.arange(S, dtype=jnp.int32),
                       last[:, None])
    return Packing(src[None], slot, ends[-1], last, tile)


def pack(x, src):
    """Rows ``x`` (R, S, ..) → the packed block (1, P, ..): slot i is
    ``x`` at `packing`'s ``src[0, i]``."""
    import jax

    with jax.named_scope("serve.pack"):
        flat = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
        return flat.at[src].get(mode="promise_in_bounds",
                                indices_are_sorted=True)


def unpack(x, slot):
    """The packed block ``x`` (1, P, ..) → rows (R, S, ..): position s
    of row r is slot `packing`'s ``slot[r, s]``, past the row's length
    its last real token again."""
    import jax

    with jax.named_scope("serve.pack"):
        return x[0].at[slot].get(mode="promise_in_bounds",
                                 indices_are_sorted=True)


def state_counters(c):
    """A served group's state-space and attention counters ``c`` (5,),
    read back (docs/observability.md has the table): the positions the
    prefill's scans walked and the real ones, the decode steps' row
    updates, the attention layers' pairs in a prefill and positions in
    a decode step, each summed over its layers; and of the positions
    walked those past their row's length."""
    import numpy as np

    out = dict(zip(("ssm_positions_scanned_prefill", "ssm_positions_prefill",
                    "ssm_row_updates_decode", "attn_pairs_prefill",
                    "attn_positions_decode"),
                   (int(n) for n in np.asarray(c))))
    scanned = out["ssm_positions_scanned_prefill"]
    out["ssm_positions_padded_prefill"] = \
        scanned - out["ssm_positions_prefill"]
    if scanned:
        out["ssm_scan_padded_pct"] = \
            100.0 * out["ssm_positions_padded_prefill"] / scanned
    return out


def packed_counters(c):
    """A served group's packed-prefill counters ``c`` (2,), read back
    (docs/observability.md has the table): the positions the token-wise
    tiles worked (one layer's worth: every layer works the same tiles,
    summed over row chunks), the real ones, and of the worked those that
    hold no token."""
    import numpy as np

    worked, real = (int(n) for n in np.asarray(c))
    out = {"prefill_positions_worked": worked, "prefill_positions": real,
           "prefill_positions_padded": worked - real}
    if worked:
        out["prefill_tokens_padded_pct"] = 100.0 * (worked - real) / worked
    return out

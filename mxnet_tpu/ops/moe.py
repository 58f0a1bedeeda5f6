"""Mixture-of-Experts ops (Switch/GShard-style sparse FFN).

NEW, TPU-first (SURVEY.md §2.5 scoped expert parallelism out of v1; this
closes it): the reference has no MoE — the design here follows the
public GShard/Switch recipe that TPU systems use, because it is the
shape XLA compiles well: capacity-based DENSE dispatch (einsum with a
(tokens, experts, capacity) one-hot) instead of data-dependent gather —
static shapes, MXU-friendly, and under a mesh the expert dimension of
the weights shards over the ``ep`` axis so GSPMD inserts the
token↔expert all-to-alls from annotations alone.

Capacity semantics match Switch Transformers: each expert processes at
most ``ceil(tokens/experts · capacity_factor)`` tokens; overflow tokens
pass through the residual (combine weight 0).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .registry import register


def _top1_dispatch(probs, capacity, base_counts):
    """probs: (N, E) → dispatch (N, E, C) one-hot, combine (N, E, C).

    ``base_counts`` (E,) is the number of slots each expert already has
    occupied by earlier top-1 rounds; this round's queue positions start
    after them (GShard: second-choice positions begin after all kept
    first-choice tokens), so rounds never collide on a capacity slot.
    Also returns the updated per-expert occupied-slot counts and this
    round's (N, E) selection one-hot (the caller masks with it).
    """
    n, e = probs.shape
    gate = jnp.max(probs, axis=1)                      # (N,)
    idx = jnp.argmax(probs, axis=1)                    # (N,)
    sel = jax.nn.one_hot(idx, e, dtype=probs.dtype)    # (N, E)
    # position of each token within its expert's queue, offset by the
    # slots earlier rounds already filled
    pos = (jnp.cumsum(sel, axis=0) - 1.0 + base_counts[None, :]) * sel
    pos_tok = jnp.sum(pos, axis=1)                     # (N,)
    keep = pos_tok < capacity
    gate = gate * keep.astype(probs.dtype)
    dispatch = sel[:, :, None] * jax.nn.one_hot(
        pos_tok, capacity, dtype=probs.dtype)[:, None, :]
    dispatch = dispatch * keep[:, None, None].astype(probs.dtype)
    combine = dispatch * gate[:, None, None]
    new_counts = base_counts + jnp.sum(
        sel * keep[:, None].astype(probs.dtype), axis=0)
    return dispatch, combine, new_counts, sel


@register("moe_ffn", aliases=("MoEFFN_op",))
def moe_ffn(data, gate_weight, w1, b1, w2, b2, num_experts=None, k=1,
            capacity_factor=1.25, activation="relu",
            output_aux_loss=False):
    """Sparse MoE FFN: route → dispatch → per-expert FFN → combine.

    data: (..., M); gate_weight: (E, M) (FullyConnected layout);
    w1: (E, M, F); b1: (E, F); w2: (E, F, M); b2: (E, M).
    Returns y (same shape as data); with output_aux_loss also returns
    the Switch load-balancing loss  E · Σ_e f_e · p̄_e  (scalar).
    """
    orig_shape = data.shape
    m = orig_shape[-1]
    x = data.reshape(-1, m)
    n = x.shape[0]
    e = gate_weight.shape[0]
    capacity = max(1, int(math.ceil(n / e * capacity_factor)))

    logits = jnp.einsum("nm,em->ne", x.astype(jnp.float32),
                        gate_weight.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)

    dispatch = jnp.zeros((n, e, capacity), probs.dtype)
    combine = jnp.zeros((n, e, capacity), probs.dtype)
    masked = probs
    counts = jnp.zeros((e,), probs.dtype)
    for _ in range(int(k)):
        d_i, c_i, counts, sel_i = _top1_dispatch(masked, capacity, counts)
        dispatch = jnp.maximum(dispatch, d_i)
        combine = combine + c_i
        # mask out the chosen expert for the next pick (by argmax
        # selection, not by kept slot — a dropped token must not re-pick
        # the same, full expert)
        masked = masked * (1.0 - sel_i)
    if k > 1:
        # renormalize combine weights over the k picks (GShard top-2)
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)

    dispatch = dispatch.astype(data.dtype)
    combine = combine.astype(data.dtype)

    expert_in = jnp.einsum("nec,nm->ecm", dispatch, x)
    h = jnp.einsum("ecm,emf->ecf", expert_in, w1,
                   preferred_element_type=jnp.float32).astype(data.dtype)
    h = h + b1[:, None, :]
    if activation == "relu":
        h = jnp.maximum(h, 0)
    elif activation == "gelu":
        h = jax.nn.gelu(h)
    out_e = jnp.einsum("ecf,efm->ecm", h, w2,
                       preferred_element_type=jnp.float32) \
        .astype(data.dtype)
    out_e = out_e + b2[:, None, :]
    y = jnp.einsum("nec,ecm->nm", combine, out_e).reshape(orig_shape)

    if not output_aux_loss:
        return y
    # Switch aux loss: fraction of tokens per expert × mean router prob
    sel1 = jax.nn.one_hot(jnp.argmax(probs, axis=1), e,
                          dtype=jnp.float32)
    f = jnp.mean(sel1, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p)
    return y, aux.astype(data.dtype)


# -- a chip's share of a routed expert layer, dropless -------------------------
#
# Expert parallelism gives each chip some of a layer's experts.  The
# router still scores all of them; the chip computes what its own
# experts add for the tokens routed to them, and nothing stands in for
# the rest (their chips add their parts; on one chip they are left out).
# Unlike `moe_ffn` above there is no capacity: every assignment that
# falls to a held expert is computed.  Shapes stay static by sorting the
# (token, expert) pairs by expert and running a grouped product over a
# buffer of ``pass_rows`` rows, as many passes as the pairs need: the
# cost follows the assignments, not tokens x experts held.

def share_pass_rows(tokens, k, held):
    """Default rows of the grouped product's buffer: a quarter row a
    token (uniform routing over many experts sends a token to far fewer
    than ``k`` held ones; what does not fit takes a further pass), at
    least 256, and never more than the ``tokens * min(k, held)`` pairs
    the routing can make."""
    return min(tokens * min(k, held), max(256, tokens // 4))


def sigmoid_topk_route(x, router_weight, router_bias, k, scale=1.0):
    """x (T, M) → (chosen (T, k) int32, weights (T, k) float32).

    Scores are ``sigmoid(x Wrᵀ)`` in float32; the chosen experts are the
    top ``k`` of score + ``router_bias`` (the aux-loss-free correction
    bias: it moves the choice, not the weight); weights are the chosen
    scores, normalised to sum to ``scale`` (the routed scaling
    factor)."""
    logits = jnp.einsum("tm,em->te", x.astype(jnp.float32),
                        router_weight.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    score = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(score + router_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), w if scale == 1.0 else w * scale


def softmax_topk_route(x, router_weight, k):
    """x (T, M) → (chosen (T, k) int32, weights (T, k) float32).

    Scores are ``softmax(x Wrᵀ)`` over all the router's experts, in
    float32; the chosen experts are the ``k`` largest and their weights
    the chosen scores, normalised to sum to one (``norm_topk_prob``)."""
    logits = jnp.einsum("tm,em->te", x.astype(jnp.float32),
                        router_weight.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    w, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return chosen.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


def held_experts_ffn(x, chosen, weights, w13, w2, experts_lo=0, valid=None,
                     pass_rows=None, add_to=None, layer=None, tally=None):
    """Σ over the held experts e of weight · W2ᵉ(silu(W1ᵉ x) ⊙ W3ᵉ x).

    x (T, M); chosen/weights (T, k) from the router, expert ids global;
    w13 (n, M, 2F), gate beside up; w2 (n, F, M): experts
    ``experts_lo .. experts_lo + n``.  With ``layer`` (an int or a
    traced scalar) the two are stacks by layer, ``(L, n, M, 2F)`` and
    ``(L, n, F, M)``, of which that layer's experts are meant.
    ``valid`` (T,) bool masks tokens that are padding; ``add_to`` (T, M)
    float32 is what the sum is added to (the residual stream; zeros if
    None).  Returns (y (T, M) float32, stats (n + 1,) int32:
    assignments per held expert, then the rows the grouped product was
    given, padding included).  No assignment is dropped: the pairs are
    worked off in passes of ``pass_rows`` rows until none is left.

    The two grouped products of a pass go by one of two paths, chosen on
    what the call can see (`_fits`), and ``tally`` (a
    ``collections.Counter`` or None) is told at trace time which, once a
    call: ``"kernel"`` (a TPU: `_grouped_kernel_call`, which reads a hit
    expert's weights once, where they lie in the stack, and works only
    the row tiles that hold a pair) or ``"plain"`` (``lax.ragged_dot``
    over the layer's experts: the oracle the kernel is tested against,
    tests/test_moe_grouped.py).

    A pass's weighted rows reach the stream by one of two paths as well
    (`_combine_fits`), told beside the first: ``"combine_kernel"`` (a
    TPU: the rows sorted by token and `_combine_kernel_call`, which
    walks the stream's token tiles that a row of the pass falls in,
    reads each once, adds its rows and writes it once) or
    ``"combine_plain"`` (XLA's scatter-add of the buffer's rows, one at
    a time: what every CPU test runs and the oracle of the other).  The
    sum is the same float32 sum either way; a token's parts are added in
    another order."""
    T, M = x.shape
    k = chosen.shape[1]
    n, _, F2 = w13.shape[-3:]
    F = F2 // 2
    P = int(pass_rows or share_pass_rows(T, k, n))
    kernel = _on_tpu() and _fits(P, M, F, n, w13.dtype)
    walked = _on_tpu() and _combine_fits(T, P, M)
    combine = _combine_kernel_call if walked else _combine_plain
    if tally is not None:
        tally["kernel" if kernel else "plain"] += 1
        tally["combine_kernel" if walked else "combine_plain"] += 1
    if kernel:
        # the stacks as they lie, a layer's experts from ``layer * n``
        # (a reshape of leading axes moves nothing)
        dot = _grouped_kernel_call
        w13, w2 = (w.reshape((-1,) + w.shape[-2:]) for w in (w13, w2))
        first = 0 if layer is None else layer * n
    else:
        dot = _grouped_plain
        if layer is not None:
            w13, w2 = (jax.lax.dynamic_index_in_dim(w, layer, 0,
                                                    keepdims=False)
                       for w in (w13, w2))
    local = chosen - experts_lo
    here = (local >= 0) & (local < n)
    if valid is not None:
        here = here & valid[:, None]
    key = jnp.where(here, local, n).reshape(-1)              # (T k,)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # held first
    counts = jnp.sum(key[:, None] == jnp.arange(n)[None, :], axis=0,
                     dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    starts, total = ends - counts, ends[-1]
    order = jnp.pad(order, (0, -(T * k) % P))
    flat_w = weights.reshape(-1)
    xs_all = x.astype(w13.dtype)

    def one_pass(p, y):
        base = p * P
        idx = jax.lax.dynamic_slice(order, (base,), (P,))
        live = base + jnp.arange(P, dtype=jnp.int32) < total
        tok = idx // k
        lo, hi = jnp.clip(starts - base, 0, P), jnp.clip(ends - base, 0, P)
        groups = _walk(lo, hi, first, P) if kernel else hi - lo
        # ``tok`` is under T whatever the row (the padding of ``order``
        # is pair 0): the gather is told so, and fills nothing
        h = dot(xs_all.at[tok].get(mode="promise_in_bounds"), w13, groups)
        h = (jax.nn.silu(h[:, :F]) * h[:, F:]).astype(w2.dtype)
        o = dot(h, w2, groups)
        return combine(y, o, flat_w[idx], jnp.where(live, tok, T))

    passes = (total + P - 1) // P
    y = jax.lax.fori_loop(
        0, passes, one_pass,
        jnp.zeros(x.shape, jnp.float32) if add_to is None else add_to)
    return y, jnp.concatenate([counts, (passes * P)[None]])


# -- the grouped product as one kernel -----------------------------------------
#
# ``x`` (P, K) holds a pass's rows sorted by expert: group g's rows are
# ``lo[g] .. hi[g]``, and what lies past the last group is padding.
# ``w`` (G, K, N) is the experts' stack as it lies, a layer after
# another; group g's matrix is ``w[first + g]``.  The kernel walks the
# pairs (row tile, group) in which the group has rows, in order, the
# tiles of N outermost: a grid step takes the tile's rows (``tm`` of
# them, all K wide), the group's block of ``tn`` columns (all K deep),
# and stores the product's rows that are the group's into the output
# tile, which stays where it is while the groups that share it pass.
# Block indices follow the walk, so
#
# - a weight block is copied in when the group changes and not
#   otherwise: each hit expert's weights are read once a pass, an
#   expert no pair chose is not read, and no slice of a stack is made;
# - a row tile at or past the last pair is neither copied in nor
#   multiplied, and the output's rows there are never written: they
#   hold what the buffer held;
# - the grid ends with the walk (its second bound is the pass's own
#   count of visits, a scalar operand; the walk's arrays are sized for
#   the most a pass can need, the row tiles + n - 1): a step that would
#   do nothing would also keep the next block's copy from starting
#   behind the step before it, which is where a call with few visits a
#   column tile spends its time (PERF.md, PR 50).
#
# K is not cut: the sums over it are one product's, float32 inside the
# MXU's accumulation, and a group that spans row tiles finds its block
# where the tile before left it.
#
# A pass has a second walk, on its way out (`_combine_kernel_call`,
# further down): the second product's rows sorted by token, and the
# stream's token tiles walked as the groups are here, a tile that no
# row falls in left as it is.

_LANE = 128
# rows a tile: what one pass of the MXU's 128 x 128 takes; a larger
# tile multiplies more rows of other groups for each pair it visits
_ROWS = 128
# the most a weight block (all K deep, ``tn`` columns) may take.  Two are
# in flight; a call's first has nothing to hide behind, which a decode
# step pays twice a layer, and the rows are read again for every column
# tile, which a prefill pass pays: at 16 MB the cells' decode passes
# take 2-4 % longer and their prefill passes 1-2 % less (PERF.md, PR 50)
_WEIGHT_BLOCK = 8 << 20
_VMEM_DEFAULT = 14 << 20


def _on_tpu():
    return jax.default_backend() == "tpu"


def _row_tile(P, n):
    """The rows of a grid step: `_ROWS` (a shorter buffer whole), twice
    as many where the buffer holds that many for each of the ``n``
    experts (a prefill pass of few large groups: the MXU keeps a weight
    tile for 256 rows, and a visit's rows of other groups are few beside
    the group's own; measured on the chip, PERF.md, PR 50)."""
    return min(P, 2 * _ROWS if P >= 2 * _ROWS * n else _ROWS)


def _tiles(P, K, N, n, itemsize):
    """``(tm, tn)``: the rows and the columns of a grid step, from the
    shapes: `_row_tile`, and the widest lane-aligned divisor of N whose
    block (all K deep) fits `_WEIGHT_BLOCK`; N whole where it has no
    lane-aligned divisor (interpret-mode shapes)."""
    wide = [d for d in range(N // _LANE * _LANE, 0, -_LANE)
            if N % d == 0 and K * d * itemsize <= _WEIGHT_BLOCK]
    return _row_tile(P, n), (wide or [N])[0]


def _fits(P, M, F, n, dtype):
    """Whether both products of a pass (``(P, M) x (M, 2F)`` and ``(P, F)
    x (F, M)``) are whole tiles for the kernel: lane-aligned widths, row
    tiles of whole packed sublanes that divide the buffer, and a weight
    block of 128 columns inside `_WEIGHT_BLOCK`."""
    item = jnp.dtype(dtype).itemsize
    tm = _row_tile(P, n)
    return item in (2, 4) and M % _LANE == 0 and F % _LANE == 0 \
        and P % tm == 0 and tm % (32 // item) == 0 \
        and max(M, F) * _LANE * item <= _WEIGHT_BLOCK


def _walk(lo, hi, first, P):
    """The kernel's scalar operands for a pass whose group g holds rows
    ``lo[g] .. hi[g]`` (n,) int32 of a buffer of ``P`` rows: ``(first (1,),
    visits (1,), group (V,), tile (V,), lo, hi)``, V = the row tiles +
    n - 1 at the most.  Visit v < ``visits`` works ``tile[v]`` for
    ``group[v]``: each group's tiles in order, a group after another, a
    group with no row left out."""
    n = lo.shape[0]
    tm = _row_tile(P, n)
    V = P // tm + n - 1
    tile0 = lo // tm
    tiles = jnp.where(hi > lo, (hi - 1) // tm - tile0 + 1, 0)
    upto = jnp.cumsum(tiles)
    v = jnp.arange(V, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(v[:, None] >= upto[None, :], axis=1, dtype=jnp.int32), n - 1)
    tile = jnp.take(tile0, group) + v - jnp.take(upto - tiles, group)
    return (jnp.asarray(first, jnp.int32).reshape(1), upto[-1:], group,
            tile, lo, hi)


def _grouped_plain(x, w, sizes):
    """``x`` (P, K) times ``w[g]`` (K, N) for the ``sizes[g]`` rows of
    each group g in turn, float32: what the kernel is held to."""
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32)


def _grouped_kernel(first_ref, visits_ref, group_ref, tile_ref, lo_ref,
                    hi_ref, x_ref, w_ref, o_ref):
    """One (column tile, visit): the tile's rows times the group's block,
    the group's rows of it stored."""
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    g, tm = group_ref[v], o_ref.shape[0]
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= lo_ref[g]) & (row < hi_ref[g])
    o_ref[...] = jnp.where(
        mine, jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32), o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_kernel_call(x, w, walk, interpret=False):
    """``x`` (P, K) times ``w[first + g]`` (K, N) for the rows of each
    group g of ``walk`` (`_walk`), float32 (P, N); rows of no group are
    not written.  Jitted, so that a program whose layers are unrolled
    traces and lowers the call once for all of them (1.6 s of a warm
    start at Granite's 40 calls otherwise: PERF.md, PR 50)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, K = x.shape
    N = w.shape[-1]
    item = jnp.dtype(w.dtype).itemsize
    tm, tn = _tiles(P, K, N, walk[4].shape[0], item)
    # every block twice (the pipeline's double buffer) and the product
    # before its rows are chosen
    need = 2 * (tm * K * item + K * tn * item + tm * tn * 4) + 2 * tm * tn * 4
    kw = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        **({} if need <= _VMEM_DEFAULT
           else {"vmem_limit_bytes": need + need // 4}))}
    def spec(block, index):
        """A block at ``index(column tile, the visit's row tile, the
        visit's place in the stack)``."""
        return pl.BlockSpec(
            block, lambda j, v, first, visits, group, tile, lo, hi: index(
                j, tile[v], first[0] + group[v]))

    return pl.pallas_call(
        _grouped_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(N // tn, walk[1][0]),
            in_specs=[spec((tm, K), lambda j, t, g: (t, 0)),
                      spec((None, K, tn), lambda j, t, g: (g, 0, j))],
            out_specs=spec((tm, tn), lambda j, t, g: (t, j))),
        out_shape=jax.ShapeDtypeStruct((P, N), jnp.float32),
        name="moe_grouped", interpret=interpret, **kw,
    )(*walk, x, w)


# -- a pass's way out, by token tile -------------------------------------------
#
# The second product leaves a pass's rows sorted by expert; the stream
# ``y`` (T, M) float32 wants them by token.  XLA's scatter-add takes the
# buffer's rows one at a time, the dead ones too (0.3-3.3 us a row on
# the v5e: PERF.md, PR 52).  The second walk: the rows are sorted by
# token (P int32 keys, the dead rows last; stable, so a token's rows
# stay in expert order) and gathered into that order, and the stream is
# cut in tiles of ``tt`` tokens as the buffer is in tiles of ``tr``
# rows.  A visit is a (token tile, row tile) in which a row of the tile
# falls to a token of the tile; sorted, both only grow along the rows,
# so the visits of a token tile follow one another and a pass has at
# most the row tiles + the token tiles - 1 of them.  A grid step takes
# the stream's tile (it stays while the visits that share it pass), the
# row tile with its tokens and weights, and adds ``onehot (tt, tr) x
# weighted rows`` to the tile: the one-hot is exact in bfloat16 and the
# float32 rows go as three bfloat16 parts that sum to them exactly, so
# the products are exact and the sums float32, in three passes of the
# MXU where ``Precision.HIGHEST`` takes six.  The stream is aliased in
# and out:
#
# - a tile no row of the pass falls in is not visited and keeps what it
#   held, to the bit; row tiles past the pairs are not visited;
# - each touched tile is read once and written once a pass and column
#   tile, whatever the rows that fall in it;
# - the walk is made from the sorted tokens by differences, a
#   cumulative sum and a binary search (a stream has thousands of
#   tiles: `_walk`'s comparison of every visit with every group would
#   be millions), and the grid ends with its count of visits;
# - a stream of one tile (a decode step's rows) needs no order among
#   its rows: they are taken as they lie, and nothing is sorted or
#   gathered.

# tokens a tile of the stream
_TOKENS = 128
# the most a tile of the stream (``tt`` tokens, ``tn`` columns, float32)
# may take: it is in flight four times (in and out, double-buffered)
# beside two row tiles and the parts they are split into
_STREAM_BLOCK = 1 << 20


def _combine_tiles(T, P, M):
    """``(tt, tr, tn)``: the tokens, the rows and the columns of a grid
    step, from the shapes: `_TOKENS` and `_ROWS` (a shorter stream or
    buffer whole) and the widest lane-aligned divisor of M whose tile of
    the stream fits `_STREAM_BLOCK`; M whole where it has none."""
    tt, tr = min(T, _TOKENS), min(P, _ROWS)
    wide = [d for d in range(M // _LANE * _LANE, 0, -_LANE)
            if M % d == 0 and tt * d * 4 <= _STREAM_BLOCK]
    return tt, tr, (wide or [M])[0]


def _combine_fits(T, P, M):
    """Whether a pass's way out is whole tiles for the kernel: a
    lane-aligned width, and tiles of whole sublanes that divide the
    stream's tokens and the buffer's rows."""
    tt, tr, _ = _combine_tiles(T, P, M)
    return M % _LANE == 0 and T % tt == 0 and P % tr == 0 \
        and tt % 8 == 0 and tr % 8 == 0


def _combine_plain(y, o, w, tok):
    """``y`` (T, M) float32 plus row r of ``o`` (P, M) times ``w[r]``
    at token ``tok[r]``, for the rows whose token is under T: XLA's
    scatter-add, what the kernel is held to."""
    # rows past the pairs hold whatever the product left there, or (the
    # kernel, which visits no tile past them) whatever the buffer held:
    # a select keeps them out, a product would not
    o = jnp.where((tok < y.shape[0])[:, None], o * w[:, None], 0.0)
    return y.at[tok].add(o, mode="drop")


def _token_walk(tok, T, tt, tr):
    """The kernel's scalar operands for a pass whose rows fall to the
    tokens ``tok`` (P,) int32, sorted, T for a row past the pairs:
    ``(visits (1,), token tile (V,), row tile (V,))``, V = the row tiles
    + the token tiles (or the rows, if fewer) - 1.  Visit v < ``visits``
    adds the rows of ``row tile[v]`` that fall in ``token tile[v]``: a
    visit starts at each live row that is the first of its row tile or
    of its token tile.  A stream of one tile is visited once a row tile
    up to the last that holds a live row, whatever the rows' order."""
    P = tok.shape[0]
    V = P // tr + min(P, T // tt) - 1
    row = jnp.arange(P, dtype=jnp.int32)
    if T == tt:
        visits = jnp.max(jnp.where(tok < T, row // tr + 1, 0))
        return visits[None], jnp.zeros((V,), jnp.int32), row[:V]
    tile = tok // tt
    starts = (tok < T) & ((row % tr == 0) | (tile != jnp.roll(tile, 1)))
    upto = jnp.cumsum(starts, dtype=jnp.int32)
    at = jnp.minimum(jnp.searchsorted(
        upto, jnp.arange(1, V + 1, dtype=jnp.int32)), P - 1).astype(jnp.int32)
    return (upto[-1:], jnp.minimum(jnp.take(tile, at), T // tt - 1),
            at // tr)


def _combine_kernel(visits_ref, ttile_ref, rtile_ref, tok_ref, w_ref, rows_ref,
                    y_ref, o_ref):
    """One (column tile, visit): the row tile's rows that fall in the
    token tile, weighted, added to their tokens' rows of it."""
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    t, tt = ttile_ref[v], o_ref.shape[0]
    token = t * tt + jax.lax.broadcasted_iota(jnp.int32, (tt, 1), 0)
    onehot = (token == tok_ref[...]).astype(jnp.bfloat16)      # (tt, tr)
    # a row past the pairs (`_combine_plain`) comes with the weight 0:
    # the select keeps what it holds out, a product would not
    w = w_ref[...]                                             # (tr, 1)
    rest, add = jnp.where(w != 0.0, rows_ref[...] * w, 0.0), None
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(jnp.float32)
        part = jnp.dot(onehot, part, preferred_element_type=jnp.float32)
        add = part if add is None else add + part
    first = (v == 0) | (ttile_ref[jnp.maximum(v - 1, 0)] != t)

    @pl.when(first)
    def _():
        o_ref[...] = y_ref[...] + add

    @pl.when(jnp.logical_not(first))
    def _():
        o_ref[...] += add


@functools.partial(jax.jit, static_argnames=("interpret",))
def _combine_kernel_call(y, o, w, tok, interpret=False):
    """`_combine_plain`'s sum by the walk of the stream's token tiles:
    ``y`` is written in place (aliased; jitted for the reason
    `_grouped_kernel_call` is)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (T, M), P = y.shape, o.shape[0]
    tt, tr, tn = _combine_tiles(T, P, M)
    w = jnp.where(tok < T, w, 0.0)
    if T > tt:
        # a stream of several tiles: the rows by token, so that a
        # tile's rows follow one another (a permutation: every index is
        # in bounds, and the gather is told so)
        tok, w, order = jax.lax.sort(
            (tok, w, jnp.arange(P, dtype=jnp.int32)), num_keys=1,
            is_stable=True)
        o = o.at[order].get(mode="promise_in_bounds")
    # a stream of one tile (a decode step's) takes the rows as they lie
    walk = _token_walk(tok, T, tt, tr)
    # the stream's tile in and out and the row tile, each twice (the
    # pipeline's double buffer), the row tile weighted, its three parts
    # and the sum
    need = 4 * tt * tn * 4 + 2 * tr * tn * 4 + tr * tn * 14 + 2 * tt * tn * 4
    kw = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        **({} if need <= _VMEM_DEFAULT
           else {"vmem_limit_bytes": need + need // 4}))}

    def spec(block, index):
        """A block at ``index(column tile, the visit's token tile, the
        visit's row tile)``."""
        return pl.BlockSpec(block, lambda j, v, visits, ttile, rtile: index(
            j, ttile[v], rtile[v]))

    stream = spec((tt, tn), lambda j, t, r: (t, j))
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(M // tn, walk[0][0]),
            in_specs=[spec((1, tr), lambda j, t, r: (0, r)),
                      spec((tr, 1), lambda j, t, r: (r, 0)),
                      spec((tr, tn), lambda j, t, r: (r, j)), stream],
            out_specs=stream),
        out_shape=jax.ShapeDtypeStruct((T, M), jnp.float32),
        input_output_aliases={6: 0},
        name="moe_combine", interpret=interpret, **kw,
    )(*walk, tok.reshape(1, P), w.reshape(P, 1), o, y)


def swiglu_ffn(x, gate, up, down):
    """``W2 (silu(W1 x) ⊙ W3 x)`` for x (.., M) with gate, up (F, M) and
    down (M, F), float32, the activations in the weights' type: a dense
    feed-forward, and the **shared expert** that every token goes
    through whatever the router says.  Every chip of an expert-parallel
    deployment computes that one alike for its own tokens, so it is
    counted **once** when the chips' shares of a layer are summed."""
    def mm(spec, a, w):
        return jnp.einsum(spec, a.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    h = jax.nn.silu(mm("...m,fm->...f", x, gate)) \
        * mm("...m,fm->...f", x, up)
    return mm("...f,mf->...m", h, down)


@register("moe_share_ffn")
def moe_share_ffn(data, router_weight, router_bias, w13, w2, k=8,
                  experts_lo=0, pass_rows=None, output_stats=False,
                  scale=1.0, shared=None):
    """A chip's share of a sigmoid-routed, dropless expert layer.

    data (..., M); router_weight (E, M) over ALL E experts;
    router_bias (E,); w13 (n, M, 2F) and w2 (n, F, M): the n experts
    from ``experts_lo`` that this chip holds; ``scale`` the routed
    scaling factor; ``shared`` the shared expert's (gate, up, down) or
    None.  Returns what those experts add, the shared one included
    (float32, data's shape); with ``output_stats`` also
    `held_experts_ffn`'s counts."""
    x = data.reshape(-1, data.shape[-1])
    chosen, weights = sigmoid_topk_route(x, router_weight, router_bias, k,
                                         scale)
    y, stats = held_experts_ffn(x, chosen, weights, w13, w2,
                                experts_lo=experts_lo, pass_rows=pass_rows)
    if shared is not None:
        y = y + swiglu_ffn(x, *shared)
    y = y.reshape(data.shape)
    return (y, stats) if output_stats else y

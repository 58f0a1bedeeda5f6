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
                     pass_rows=None, add_to=None):
    """Σ over the held experts e of weight · W2ᵉ(silu(W1ᵉ x) ⊙ W3ᵉ x).

    x (T, M); chosen/weights (T, k) from the router, expert ids global;
    w13 (n, M, 2F), gate beside up; w2 (n, F, M): experts
    ``experts_lo .. experts_lo + n``.  ``valid`` (T,) bool masks tokens
    that are padding; ``add_to`` (T, M) float32 is what the sum is added
    to (the residual stream; zeros if None).  Returns (y (T, M) float32,
    stats (n + 1,) int32:
    assignments per held expert, then the rows the grouped product was
    given, padding included).  No assignment is dropped: the pairs are
    worked off in passes of ``pass_rows`` rows until none is left."""
    T, _ = x.shape
    k = chosen.shape[1]
    n, _, F2 = w13.shape
    F = F2 // 2
    P = int(pass_rows or share_pass_rows(T, k, n))
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
        sizes = jnp.clip(ends - base, 0, P) - jnp.clip(starts - base, 0, P)
        h = jax.lax.ragged_dot(jnp.take(xs_all, tok, axis=0), w13, sizes,
                               preferred_element_type=jnp.float32)
        h = (jax.nn.silu(h[:, :F]) * h[:, F:]).astype(w2.dtype)
        o = jax.lax.ragged_dot(h, w2, sizes,
                               preferred_element_type=jnp.float32)
        # rows past the pairs hold whatever the product left there
        o = jnp.where(live[:, None], o * flat_w[idx][:, None], 0.0)
        return y.at[jnp.where(live, tok, T)].add(o, mode="drop")

    passes = (total + P - 1) // P
    y = jax.lax.fori_loop(
        0, passes, one_pass,
        jnp.zeros(x.shape, jnp.float32) if add_to is None else add_to)
    return y, jnp.concatenate([counts, (passes * P)[None]])


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

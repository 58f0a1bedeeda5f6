"""What the served decoder families share (`mimo_v2.py`, `keye_vl2.py`):
the pieces of a layer and of a decoder program that do not depend on a
family's attention or routing rule.  Neither family imports the other;
a change here is a change to both, and both cells measure it.

- `mm`, `rms_norm`, `rope`: the mixed-precision product, RMSNorm and
  the rotation (rotate-half over the first ``rot`` dimensions);
- `attn_out`: heads side by side, then ``x + a Wo`` (attention over
  the caches is `ops/cache_attention.py`, GPT's too);
- `route`: the second norm and the router, the routing rule passed in;
  `moe_count_row`, `moe_counters`: an expert layer's counters in the
  donated carry and their read-back (docs/observability.md);
- `by_rows`, `chunk_rows`: a prefill block worked off a few rows at a
  time; `own_weights`: a program's weight tuple.
"""

from __future__ import annotations

_MASKED = -1e30


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


def rope(x, pos, theta, rot):
    """x (B, .., S, D) float32 rotated on its first ``rot`` dimensions at
    positions ``pos`` (B, S)."""
    import jax.numpy as jnp

    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = pos.astype(jnp.float32)[..., None] * freq          # (B, S, half)
    shape = (pos.shape[0],) + (1,) * (x.ndim - 3) + (pos.shape[1], half)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


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


def chunk_rows(z, B, S):
    """Rows a chunk: about ``prefill_chunk_tokens`` tokens, a divisor
    of B."""
    rows = max(1, min(B, z.prefill_chunk_tokens // S))
    while B % rows:
        rows -= 1
    return rows


def own_weights(model, dtype):
    """A program's weight tuple: the parameters' own buffers, in the
    order of their names: no second copy, unless ``dtype`` asks for
    another type than a parameter has."""
    out = []
    for n in model._names:
        a = getattr(model, n).data()._data
        if dtype is not None and a.dtype != dtype:
            a = a.astype(dtype)
        out.append(a)
    return tuple(out)

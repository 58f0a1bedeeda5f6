"""Command A+ (`model_type` ``cohere2_moe``: CohereLabs
command-a-plus-05-2026), the plain reference of its language model.

The forward pass in straightforward ``jax.numpy``: float32, every product
at ``Precision.HIGHEST``, no cache, no kernel, no batching of requests.
It follows the model's public ``config.json`` with the sizes under the
source's keys, and Cohere2's published block for what the keys name:

- ``x = E[id]``, unscaled.  For every layer **one** bias-free LayerNorm
  (``layer_norm_eps``; ``rms_norm_eps`` null) feeds both branches
  (``use_parallel_block``): ``h = LN(x)``, and ``x + attention(h) +
  experts(h)`` is the layer's one sum;
- attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``, no bias
  (``attention_bias`` false), no query/key norm (``use_qk_norm``
  false); query head t reads key head ``t // (heads / kv heads)``;
  softmax of ``q kᵀ / sqrt(head_dim)``.  ``layer_types`` lists the
  kinds: a ``sliding_attention`` layer rotates q and k over the whole
  head (``rotary_pct`` 1) in interleaved pairs (2j, 2j + 1)
  (``position_embedding_type`` ``rope_gptj``), angle ``pos x
  rope_theta^(-2j / head_dim)``, and position i attends to the last
  ``sliding_window`` positions; a ``full_attention`` layer rotates
  nothing and attends to every earlier position;
- experts, from the same ``h``: sigmoid scores (``expert_selection_fn``)
  over all ``router_experts``, the ``num_experts_per_tok`` largest,
  their scores normalised to sum to one (``norm_topk_prob``); every
  expert a SwiGLU (``use_gated_activation``, ``hidden_act`` silu)
  ``intermediate_size`` wide; ``num_shared_experts`` shared experts that
  every token goes through, their outputs averaged
  (``shared_expert_combination_strategy`` ``average``) and added to the
  routed sum;
- after the last layer ``logit_scale x LN(x) Eᵀ``
  (``tie_word_embeddings``): the head is the embedding.

Readings the config leaves open, each also under ``assumed`` in the
configuration's file:

- ``nope``: full layers take no rotation (the catalog's "global NoPE",
  Cohere2's published block; the config has no key for it);
- ``average``: the mean of the shared experts' outputs, added to the
  routed sum (not half of routed plus shared);
- ``shared_width``: a shared expert is ``intermediate_size`` wide, as a
  routed one (the one width the config gives);
- ``router``: no correction bias and no scaling factor (no key for
  either);
- ``window``: ``sliding_window`` positions, the token's own included;
- ``left_out``: the vision tower; ``first_k_dense_replace`` 0, so the
  ``prefix_dense_*`` keys describe no layer of this model;
- projection weights are ``(out, in)``; the routed experts' are stacked
  ``(experts, in, out)`` with gate and up side by side and the shared
  experts' gate, up and down matrices lie one after the other in one
  leaf each, as the program's parameters do: the same maps;
- **a share of the experts.**  ``experts_held = [lo, n]`` says which
  routed experts' weights exist here.  The router still scores all
  ``router_experts``; what an absent expert would add is left out (the
  chip's share of an expert-parallel deployment, with no exchange).
  Attention, the shared experts, the norms and the head are whole.

**Worked in pieces, because it runs beside the program.**  The harness
makes this module's weights (6.2 GB at the published cut) while the
program's 6.2 GB are still on the chip.  The residual stream is a list
of chunks of ``TOKEN_CHUNK`` positions; attention is worked one
key/value head (its 16 query heads) and ``QUERY_BLOCK`` queries at a
time; a SwiGLU ``FFN_TILE`` of its width at a time, each weight widened
as it is used; the logits go to the host chunk by chunk (`logits`
returns a NumPy array).  None of the cuts changes a result beyond the
order of float32 sums (tests/test_cohere2_moe.py sets them small).

``product`` is the one place a matrix product is made, so that the
control (``low_precision``) can put the same model through float8
operands.  It imports nothing of the program and makes its own weights
from the seed (``param_spec``).
"""

import functools
import math

TOKEN_CHUNK = 2048
QUERY_BLOCK = 128
FFN_TILE = 2048

_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def sizes(config):
    """The configuration's sizes under short names."""
    if not config["use_parallel_block"] or config["use_qk_norm"] \
            or config["attention_bias"] or config["first_k_dense_replace"] \
            or config["expert_selection_fn"] != "sigmoid" \
            or not config["norm_topk_prob"] \
            or config["position_embedding_type"] != "rope_gptj" \
            or config["rotary_pct"] != 1 \
            or config["shared_expert_combination_strategy"] != "average" \
            or not config["tie_word_embeddings"] \
            or not config["use_gated_activation"] \
            or config["hidden_act"] != "silu":
        raise ValueError(
            "cohere2_moe: a parallel block with no bias and no query/key "
            "norm, no leading dense layer, sigmoid scores normalised over "
            "the chosen, whole-head interleaved rotation, averaged shared "
            "SwiGLU experts and a tied head is what this reference computes")
    types = [_KINDS[t] for t in config["layer_types"]]
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("cohere2_moe: layer_types needs one entry a layer")
    lo, n = config.get("experts_held", (0, config["num_experts"]))
    return {
        "C": config["hidden_size"], "L": len(types), "types": tuple(types),
        "H": config["num_attention_heads"],
        "K": config["num_key_value_heads"], "D": config["head_dim"],
        "window": config["sliding_window"],
        "theta": float(config["rope_theta"]),
        "F": config["intermediate_size"],
        "shared": config["num_shared_experts"],
        "E": config.get("router_experts", config["num_experts"]),
        "held": (int(lo), int(n)), "k": config["num_experts_per_tok"],
        "V": config["vocab_size"], "eps": config["layer_norm_eps"],
        "logit_scale": float(config["logit_scale"])}


def param_spec(config):
    """(name, shape, init) of every leaf; names are the suffixes of the
    program's parameter names.  Matrices normal(``initializer_range``,
    0.02 where the config gives none), unit gains; ``seeded`` of the
    configuration ({leaf: init}; a name without its ``l<i>_`` stands for
    every layer's) overrides a leaf's draw (its ``assumed`` says why)."""
    z = sizes(config)
    C, D, F = z["C"], z["D"], z["F"]
    Fs, n = z["shared"] * F, z["held"][1]
    w = f"normal:{config.get('initializer_range', 0.02)}"
    layer = [("ln_gamma", (C,), "ones"),
             ("q_weight", (z["H"] * D, C), w),
             ("k_weight", (z["K"] * D, C), w),
             ("v_weight", (z["K"] * D, C), w),
             ("o_weight", (C, z["H"] * D), w),
             ("router_weight", (z["E"], C), w),
             ("shared_gate_weight", (Fs, C), w),
             ("shared_up_weight", (Fs, C), w),
             ("shared_down_weight", (C, Fs), w),
             ("experts_gate_up_weight", (n, C, 2 * F), w),
             ("experts_down_weight", (n, F, C), w)]
    seeded = dict(config.get("seeded", {}))
    spec = [("embed_weight", (z["V"], C), seeded.pop("embed_weight", w))]
    for i in range(z["L"]):
        spec += [(f"l{i}_{name}", shape,
                  seeded.get(f"l{i}_{name}", seeded.get(name, init)))
                 for name, shape, init in layer]
    spec.append(("lnf_gamma", (C,), seeded.pop("lnf_gamma", "ones")))
    unknown = set(seeded) - {name for name, _, _ in spec} \
        - {name for name, _, _ in layer}
    if unknown:
        raise ValueError(
            f"cohere2_moe: seeded names no leaf: {sorted(unknown)}")
    return spec


# -- the one product -----------------------------------------------------------

def product(spec, a, b):
    import jax
    import jax.numpy as jnp

    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _to_f8(x):
    """Round to float8 e4m3 under one scale per tensor, and widen."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def low_precision(spec, a, b):
    """The control's product: both operands through float8 e4m3."""
    return product(spec, _to_f8(a), _to_f8(b))


# -- the model -----------------------------------------------------------------

def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def layer_norm(x, g, eps):
    """LayerNorm with a gain and no bias."""
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(g)


def rotate(x, t0, z):
    """Rotate the whole last axis of (B, T, .., D) at positions t0 ..
    t0 + T, dimension 2j paired with 2j + 1 (``rope_gptj``)."""
    import jax.numpy as jnp

    D = z["D"]
    freq = z["theta"] ** (-jnp.arange(D // 2, dtype=jnp.float32) * 2.0 / D)
    t = (t0 + jnp.arange(x.shape[1])).astype(jnp.float32)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (D // 2,)
    cos = jnp.cos(t[:, None] * freq).reshape(shape)
    sin = jnp.sin(t[:, None] * freq).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def heads(h, t0, w_q, w_k, w_v, kind, z, prod):
    """One key/value head and its query heads on a chunk h (B, T, C) at
    positions t0 ..: w_q (g D, C), w_k, w_v (D, C) → q (B, T, g, D),
    k, v (B, T, D); a window layer's q and k rotated (a full layer's
    take no position: ``assumed.nope``)."""
    B, T, _ = h.shape
    q = prod("btc,gc->btg", h, _f32(w_q)).reshape(B, T, -1, z["D"])
    k = prod("btc,gc->btg", h, _f32(w_k))
    if kind == "window":
        q, k = rotate(q, t0, z), rotate(k, t0, z)
    return q, k, prod("btc,gc->btg", h, _f32(w_v))


def attend(q, k, v, t0, kind, z, prod):
    """Causal softmax attention of a block of queries q (B, Q, g, D) at
    positions t0 .. over one key head k, v (B, T, D); on a window layer
    position i sees ``i - window + 1 .. i`` (``assumed.window``)."""
    import jax.numpy as jnp

    s = prod("bqgd,bsd->bgqs", q, k) / math.sqrt(z["D"])
    i = t0 + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if kind == "window":
        seen = seen & (j > i - z["window"])
    s = jnp.where(seen, s, -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return prod("bgqs,bsd->bqgd", e / jnp.sum(e, axis=-1, keepdims=True), v)


def swiglu_tile(h, gate, up, down, prod):
    """A tile of a SwiGLU's hidden width: gate, up (f, C), down (C, f)."""
    import jax

    a = jax.nn.silu(prod("btc,fc->btf", h, _f32(gate))) \
        * prod("btc,fc->btf", h, _f32(up))
    return prod("btf,cf->btc", a, _f32(down))


def route(h, router_weight, z, prod):
    """(B, T, E) combine weights: the sigmoid score of each chosen
    expert over the chosen ones' sum, zero elsewhere (no correction bias
    and no scaling factor: ``assumed.router``)."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.sigmoid(prod("btc,ec->bte", h, _f32(router_weight)))
    _, chosen = jax.lax.top_k(score, z["k"])
    picked = jnp.sum(jax.nn.one_hot(chosen, z["E"], dtype=score.dtype),
                     axis=-2)
    w = score * picked
    return w / jnp.sum(w, axis=-1, keepdims=True)


def expert_part(h, w_e, w13, w2, F, prod):
    """One routed expert's weighted part of the layer's output: ``w_e``
    (B, T) is its combine weight per token (0 where not chosen)."""
    import jax

    a = prod("btc,cf->btf", h, _f32(w13))
    a = jax.nn.silu(a[..., :F]) * a[..., F:]
    return w_e[..., None] * prod("btf,fc->btc", a, _f32(w2))


# -- in pieces -----------------------------------------------------------------
#
# The residual stream is a list of chunks (B, <= TOKEN_CHUNK, C) from
# the embedding to the head: an update then makes one chunk anew, not
# the stream.

@functools.lru_cache(maxsize=None)
def _jitted(key, prod):
    import jax

    z = dict(key)
    kinds = ("full", "window")
    return {
        "norm": jax.jit(lambda x, g: layer_norm(x, g, z["eps"])),
        "heads": {t: jax.jit(lambda h, t0, a, b, c, t=t: heads(
            h, t0, a, b, c, t, z, prod)) for t in kinds},
        "attend": {t: jax.jit(lambda q, k, v, t0, t=t: attend(
            q, k, v, t0, t, z, prod)) for t in kinds},
        "out": jax.jit(lambda acc, a, w: acc + prod("btg,cg->btc", a,
                                                    _f32(w))),
        "swiglu": jax.jit(lambda acc, h, a, b, c, scale: acc
                          + scale * swiglu_tile(h, a, b, c, prod)),
        "route": jax.jit(lambda h, w: route(h, w, z, prod)),
        "expert": jax.jit(lambda acc, h, w, w13, w2: acc + expert_part(
            h, w, w13, w2, z["F"], prod)),
        "head": jax.jit(lambda h, w: z["logit_scale"] * prod(
            "btc,vc->btv", h, _f32(w)))}


def _key(z):
    return tuple(sorted(z.items()))


def _chunks(T, n):
    return [slice(t, min(t + n, T)) for t in range(0, T, n)]


def _cat(parts, axis=1):
    import jax.numpy as jnp

    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def attention_branch(hs, params, i, z, parts):
    """[a^1 .. a^H] W_o for layer ``i`` on the normed chunks ``hs``: a
    list of chunks (B, ., C).  One key/value head and its query heads
    at a time."""
    import jax.numpy as jnp

    kind = z["types"][i]
    g, D = z["H"] // z["K"], z["D"]
    starts = [sum(h.shape[1] for h in hs[:j]) for j in range(len(hs))]
    w_q, w_k, w_v, w_o = (params[f"l{i}_{n}_weight"] for n in "qkvo")
    out = [jnp.zeros_like(h) for h in hs]
    for j in range(z["K"]):
        cut = [parts["heads"][kind](
            h, t0, w_q[j * g * D:(j + 1) * g * D], w_k[j * D:(j + 1) * D],
            w_v[j * D:(j + 1) * D]) for h, t0 in zip(hs, starts)]
        q, k, v = (_cat([c[m] for c in cut]) for m in range(3))
        del cut
        a = _cat([parts["attend"][kind](q[:, at], k, v, at.start)
                  for at in _chunks(q.shape[1], QUERY_BLOCK)])
        a = a.reshape(a.shape[:2] + (-1,))
        out = [parts["out"](acc, a[:, t0:t0 + acc.shape[1]],
                            w_o[:, j * g * D:(j + 1) * g * D])
               for acc, t0 in zip(out, starts)]
    return out


def shared_experts(h, params, i, z, parts):
    """The mean of layer ``i``'s shared experts on a chunk h: each a
    SwiGLU of ``intermediate_size`` (``assumed.shared_width``), its
    rows ``j F .. (j + 1) F`` of the shared leaves, worked ``FFN_TILE``
    of its width at a time (``assumed.average``)."""
    import jax.numpy as jnp

    gate, up, down = (params[f"l{i}_shared_{m}_weight"]
                      for m in ("gate", "up", "down"))
    out = jnp.zeros_like(h)
    for j in range(z["shared"]):
        for f in _chunks(z["F"], FFN_TILE):
            at = slice(j * z["F"] + f.start, j * z["F"] + f.stop)
            out = parts["swiglu"](out, h, gate[at], up[at], down[:, at],
                                  1.0 / z["shared"])
    return out


def routed_experts(h, params, i, z, parts, held=None):
    """The held routed experts' weighted parts for layer ``i`` on a
    chunk h; ``held`` overrides the share (its experts are then read
    from the stacks at their global index)."""
    import jax.numpy as jnp

    lo, n = z["held"] if held is None else held
    at = 0 if held is None else lo
    w = parts["route"](h, params[f"l{i}_router_weight"])
    out = jnp.zeros_like(h)
    for e in range(n):
        out = parts["expert"](
            out, h, w[..., lo + e],
            params[f"l{i}_experts_gate_up_weight"][at + e],
            params[f"l{i}_experts_down_weight"][at + e])
    return out


def layer(xs, params, i, z, parts):
    """The parallel block on the stream's chunks: both branches read the
    one norm's output, and the stream takes their sum once."""
    hs = [parts["norm"](x, params[f"l{i}_ln_gamma"]) for x in xs]
    attn = attention_branch(hs, params, i, z, parts)
    return [x + a + shared_experts(h, params, i, z, parts)
            + routed_experts(h, params, i, z, parts)
            for x, h, a in zip(xs, hs, attn)]


def hidden(params, ids, config, prod=product):
    """The residual stream after the last layer, float32, as chunks
    (B, <= TOKEN_CHUNK, C)."""
    z = sizes(config)
    parts = _jitted(_key(z), prod)
    xs = [_f32(params["embed_weight"][ids[:, at]])
          for at in _chunks(ids.shape[1], TOKEN_CHUNK)]
    for i in range(z["L"]):
        xs = layer(xs, params, i, z, parts)
    return xs


def logits(params, ids, config, prod=product):
    """(B, T, vocab) float32 logits of (B, T) int ids, a NumPy array
    (made a chunk of positions and ``FFN_TILE`` rows of the tied head at
    a time)."""
    import numpy as np

    z = sizes(config)
    parts = _jitted(_key(z), prod)
    out = []
    for x in hidden(params, ids, config, prod):
        h = parts["norm"](x, params["lnf_gamma"])
        out.append(np.concatenate(
            [np.asarray(parts["head"](h, params["embed_weight"][v]))
             for v in _chunks(z["V"], FFN_TILE)], axis=-1))
    return np.concatenate(out, axis=1)

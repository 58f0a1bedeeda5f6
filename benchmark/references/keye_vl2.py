"""Keye-VL-2.0 (`model_type` ``KeyeVL2``), the plain reference of its
language model.

The forward pass in straightforward ``jax.numpy``: float32, every product
at ``Precision.HIGHEST``, no cache, no kernel, no scan; one layer (and
one expert) widened at a time and attention walked ``QUERY_BLOCK``
queries at a time, so that 16,384 positions fit (a block's scores of 32
heads are 1 GB; blocks change no result).  Per token ``x`` at position
``t`` of a layer (sizes under the source's keys):

- ``u = RMSNorm(x; ln1)``; ``q = u Wq`` (32 heads of 128), ``k = u Wk``,
  ``v = u Wv`` (4 heads); ``q`` and ``k`` RMS-normed per head
  (``q_norm``, ``k_norm``), then rotated at ``t`` (``rope_theta``, all
  128 dimensions, dimension j paired with j + 64);
- the **indexer** (``sa_config``): ``qi = u Wqi`` (16 heads of 64),
  ``ki = LayerNorm(u Wki)`` (one head), both rotated at ``t`` on all 64
  dimensions, ``w = (u Ww) / sqrt(16 * 64)``;
  ``I[t, s] = sum_h w[t, h] relu(qi[t, h] . ki[s])`` for ``s <= t``;
- **selection**: ``tau[t]`` the ``topk``-th largest of ``I[t, :t+1]``
  (by ``jnp.sort``), ``S[t] = {s <= t : I[t, s] >= tau[t]}``, every
  ``s <= t`` where ``t < topk``;
- attention of query head h over key/value head ``h // 8`` with the
  softmax taken over ``S[t]`` only; ``x += a Wo``;
- ``u = RMSNorm(x; ln2)``, ``pi = softmax(u Wr)`` over ``router_experts``,
  the ``num_experts_per_tok`` largest renormalised (``norm_topk_prob``),
  ``x +=`` the held experts' weighted SwiGLU parts (``experts_held =
  [lo, n]``; what the absent experts would add is left out);
- an unscaled embedding, a final RMSNorm, an untied head.

The configuration's ``assumed`` lists what the source does not say (the
indexer's form, QK-norm, plain RoPE for text-only positions).  Weights
are stacked by layer (``q_weight`` is ``(L, 4096, 2048)``), as the
program's parameters are.  ``product`` is the one place a matrix product
is made, so that the control (``low_precision``) can put the same model
through float8 operands.  It imports nothing of the program and makes
its own weights from the seed (``param_spec``).
"""

import functools
import math

QUERY_BLOCK = 512


def sizes(config):
    """The configuration's sizes under short names."""
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or config["mlp_only_layers"] \
            or config["decoder_sparse_step"] != 1:
        raise ValueError("keye_vl2: one indexer key head and experts in "
                         "every layer is what this reference computes")
    lo, n = config.get("experts_held", (0, config["num_experts"]))
    return {
        "C": config["hidden_size"], "L": config["num_hidden_layers"],
        "Hq": config["num_attention_heads"],
        "Hkv": config["num_key_value_heads"], "d": config["head_dim"],
        "theta": float(config["rope_theta"]),
        "Hi": sa["indexer_num_heads"], "di": sa["indexer_head_dim"],
        "topk": sa["topk"], "Fe": config["moe_intermediate_size"],
        "E": config.get("router_experts", config["num_experts"]),
        "held": (int(lo), int(n)), "k": config["num_experts_per_tok"],
        "V": config["vocab_size"], "eps": config["rms_norm_eps"]}


def param_spec(config):
    """(name, shape, init) of every leaf, stacked by layer; names are the
    suffixes of the program's parameter names.  Matrices
    normal(``initializer_range``, 0.02 where the config gives none),
    unit gains, a zero LayerNorm shift; ``seeded`` of the configuration
    ({leaf: init}) overrides a leaf's draw (its ``assumed`` says why)."""
    z = sizes(config)
    L, C, d, n = z["L"], z["C"], z["d"], z["held"][1]
    w = f"normal:{config.get('initializer_range', 0.02)}"
    spec = [
        ("embed_weight", (z["V"], C), w),
        ("ln1_gamma", (L, C), "ones"),
        ("q_weight", (L, z["Hq"] * d, C), w),
        ("k_weight", (L, z["Hkv"] * d, C), w),
        ("v_weight", (L, z["Hkv"] * d, C), w),
        ("o_weight", (L, C, z["Hq"] * d), w),
        ("q_norm_gamma", (L, d), "ones"),
        ("k_norm_gamma", (L, d), "ones"),
        ("index_q_weight", (L, z["Hi"] * z["di"], C), w),
        ("index_k_weight", (L, z["di"], C), w),
        ("index_k_norm_gamma", (L, z["di"]), "ones"),
        ("index_k_norm_beta", (L, z["di"]), "zeros"),
        ("index_w_weight", (L, z["Hi"], C), w),
        ("ln2_gamma", (L, C), "ones"),
        ("router_weight", (L, z["E"], C), w),
        ("experts_gate_up_weight", (L, n, C, 2 * z["Fe"]), w),
        ("experts_down_weight", (L, n, z["Fe"], C), w),
        ("lnf_gamma", (C,), "ones"),
        ("head_weight", (z["V"], C), w)]
    seeded = config.get("seeded", {})
    unknown = set(seeded) - {name for name, _, _ in spec}
    if unknown:
        raise ValueError(f"keye_vl2: seeded names no leaf: {sorted(unknown)}")
    return [(name, shape, seeded.get(name, init))
            for name, shape, init in spec]


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

def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * g


def _layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _rope(x, theta):
    """Rotate the whole last axis of (B, T, .., D) at positions 0..T-1,
    pairing dimension j with j + D/2."""
    import jax.numpy as jnp

    D = x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / D)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def projections(x, p, z, prod):
    """(B, T, C) → q (B, T, Hq, d), k, v (B, T, Hkv, d), and the
    indexer's qi (B, T, Hi, di), ki (B, T, di), w (B, T, Hi)."""
    B, T, _ = x.shape
    d, Hi, di = z["d"], z["Hi"], z["di"]
    u = _rms_norm(x, p["ln1_gamma"], z["eps"])
    q = prod("btc,gc->btg", u, p["q_weight"]).reshape(B, T, z["Hq"], d)
    k = prod("btc,gc->btg", u, p["k_weight"]).reshape(B, T, z["Hkv"], d)
    v = prod("btc,gc->btg", u, p["v_weight"]).reshape(B, T, z["Hkv"], d)
    q = _rope(_rms_norm(q, p["q_norm_gamma"], z["eps"]), z["theta"])
    k = _rope(_rms_norm(k, p["k_norm_gamma"], z["eps"]), z["theta"])
    qi = prod("btc,gc->btg", u, p["index_q_weight"]).reshape(B, T, Hi, di)
    ki = _layer_norm(prod("btc,gc->btg", u, p["index_k_weight"]),
                     p["index_k_norm_gamma"], p["index_k_norm_beta"],
                     z["eps"])
    w = prod("btc,hc->bth", u, p["index_w_weight"]) / math.sqrt(Hi * di)
    return q, k, v, _rope(qi, z["theta"]), _rope(ki, z["theta"]), w


def select(qi, w, ki, t0, z, prod):
    """The selection of a block of queries at positions ``t0 ..``:
    qi (B, Q, Hi, di), w (B, Q, Hi), ki (B, T, di) → bool (B, Q, T)."""
    import jax.numpy as jnp

    Q, T = qi.shape[1], ki.shape[1]
    s = prod("bqhd,bsd->bqhs", qi, ki)
    index = jnp.sum(w[..., None] * jnp.maximum(s, 0.0), axis=2)  # (B, Q, T)
    t = t0 + jnp.arange(Q)[:, None]
    seen = jnp.arange(T)[None, :] <= t                           # (Q, T)
    index = jnp.where(seen[None], index, -jnp.inf)
    kth = jnp.sort(index, axis=-1)[..., max(T - z["topk"], 0)]
    return (index >= kth[..., None]) & seen[None]


def attend(q, k, v, chosen, z, prod):
    """Softmax attention of a block of queries q (B, Q, Hq, d) over
    k, v (B, T, Hkv, d), each query over its ``chosen`` (B, Q, T)
    positions only."""
    import jax.numpy as jnp

    group = z["Hq"] // z["Hkv"]
    # query head h reads key/value head h // (Hq / Hkv)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = prod("bqhd,bshd->bhqs", q, k) / math.sqrt(z["d"])
    s = jnp.where(chosen[:, None], s, -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return prod("bhqs,bshd->bqhd", e / jnp.sum(e, axis=-1, keepdims=True),
                v)


def route(u, router_weight, z, prod):
    """(B, T, E) combine weights: the softmax score of each chosen
    expert over the chosen ones' sum, zero elsewhere."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.softmax(prod("btc,ec->bte", u, router_weight), axis=-1)
    _, chosen = jax.lax.top_k(score, z["k"])
    picked = jnp.sum(jax.nn.one_hot(chosen, z["E"], dtype=score.dtype),
                     axis=-2)
    w = score * picked
    return w / jnp.sum(w, axis=-1, keepdims=True)


def expert_part(u, w_e, w13, w2, Fe, prod):
    """One expert's weighted part of the layer's output: ``w_e`` (B, T)
    is its combine weight per token (0 where not chosen)."""
    import jax

    h = prod("btc,cf->btf", u, w13)
    h = jax.nn.silu(h[..., :Fe]) * h[..., Fe:]
    return w_e[..., None] * prod("btf,fc->btc", h, w2)


# -- widening one layer (one expert) at a time ---------------------------------

def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


ATTENTION_LEAVES = ("ln1_gamma", "q_weight", "k_weight", "v_weight",
                    "q_norm_gamma", "k_norm_gamma", "index_q_weight",
                    "index_k_weight", "index_k_norm_gamma",
                    "index_k_norm_beta", "index_w_weight")


@functools.lru_cache(maxsize=None)
def _jitted(key, prod):
    import jax

    z = dict(key)

    def block(q, qi, w, k, v, ki, t0):
        chosen = select(qi, w, ki, t0, z, prod)
        return attend(q, k, v, chosen, z, prod), chosen

    return {
        "proj": jax.jit(lambda x, p: projections(x, p, z, prod)),
        "block": jax.jit(block),
        "out": jax.jit(lambda x, a, w: x + prod("btg,cg->btc", a, w)),
        "norm": jax.jit(lambda x, g: _rms_norm(x, g, z["eps"])),
        "route": jax.jit(lambda u, w: route(u, w, z, prod)),
        "expert": jax.jit(lambda u, w, w13, w2: expert_part(
            u, w, w13, w2, z["Fe"], prod)),
        "head": jax.jit(lambda x, g, w: prod(
            "btc,vc->btv", _rms_norm(x, g, z["eps"]), w))}


def _key(z):
    return tuple(sorted(z.items()))


def attention_layer(x, params, i, z, parts, chosen_out=None):
    """x + a Wo for layer ``i``, QUERY_BLOCK queries at a time;
    ``chosen_out`` (a list) receives the layer's input and its selection
    (B, T, T)."""
    import jax.numpy as jnp

    p = {n: _f32(params[n][i]) for n in ATTENTION_LEAVES}
    q, k, v, qi, ki, w = parts["proj"](x, p)
    T = x.shape[1]
    blocks, chosen = [], []
    for t0 in range(0, T, QUERY_BLOCK):
        at = slice(t0, t0 + QUERY_BLOCK)
        a, c = parts["block"](q[:, at], qi[:, at], w[:, at], k, v, ki,
                              jnp.int32(t0))
        blocks.append(a)
        if chosen_out is not None:
            chosen.append(c)
    if chosen_out is not None:
        chosen_out.append((x, jnp.concatenate(chosen, axis=1)))
    a = jnp.concatenate(blocks, axis=1).reshape(x.shape[:2] + (-1,))
    return parts["out"](x, a, _f32(params["o_weight"][i]))


def moe_layer(u, params, i, z, parts, held=None):
    """Σ over the held experts of their weighted parts, for layer ``i``
    on normalised input ``u``; ``held`` overrides the share (its experts
    are then read from the stacks at their global index)."""
    lo, n = z["held"] if held is None else held
    at = 0 if held is None else lo
    w = parts["route"](u, _f32(params["router_weight"][i]))
    out = 0.0
    for e in range(n):
        out = out + parts["expert"](
            u, w[..., lo + e],
            _f32(params["experts_gate_up_weight"][i, at + e]),
            _f32(params["experts_down_weight"][i, at + e]))
    return out


def hidden(params, ids, config, prod=product, chosen_out=None):
    """(B, T, C) float32: the residual stream after the last layer."""
    z = sizes(config)
    parts = _jitted(_key(z), prod)
    x = _f32(params["embed_weight"][ids])
    for i in range(z["L"]):
        x = attention_layer(x, params, i, z, parts, chosen_out)
        u = parts["norm"](x, _f32(params["ln2_gamma"][i]))
        x = x + moe_layer(u, params, i, z, parts)
    return x


def logits(params, ids, config, prod=product):
    """(B, T, vocab) float32 logits of (B, T) int ids."""
    z = sizes(config)
    return _jitted(_key(z), prod)["head"](
        hidden(params, ids, config, prod), _f32(params["lnf_gamma"]),
        _f32(params["head_weight"]))


def selections(params, ids, config, prod=product):
    """[(layer input (B, T, C), S[t] as rows (B, T, T) bool) per layer],
    for the tests."""
    out = []
    hidden(params, ids, config, prod, chosen_out=out)
    return out

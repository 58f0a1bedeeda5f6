"""Kimi-K2 (`model_type` ``kimi_k2``: Kimi-K2 .. K2.6; DeepSeek-V3's
block), the plain reference of its language model.

The forward pass in straightforward ``jax.numpy`` by the **expanded**
(non-absorbed) equations: float32, every product at
``Precision.HIGHEST``, no cache, no kernel, no scan.  Per token ``x`` at
position ``t`` of a layer, ``u = RMSNorm(x)`` (sizes under the source's
keys, 64 heads):

- ``c_q = RMSNorm(u W_dq)`` (``q_lora_rank``); head h's query
  ``c_q W_uq^h = [q_n (qk_nope_head_dim) ; q_r (qk_rope_head_dim)]``;
- ``[c' ; k'] = u W_dkv`` (``kv_lora_rank + qk_rope_head_dim``),
  ``c = RMSNorm(c')``, ``k_r = rot_t(k')``: one for all heads;
- ``[k_n^h ; v^h] = c W_ukv^h`` (``qk_nope_head_dim + v_head_dim``);
- ``s^h[t, s] = (q_n^h[t] . k_n^h[s] + rot_t(q_r^h[t]) . k_r[s]) *
  scale`` for ``s <= t``, softmax over s, ``o^h = sum_s p v^h[s]``,
  ``x += [o^1 .. o^H] W_o``;
- **YaRN** (``rope_scaling``): the rotation's frequencies are
  `yarn_frequencies`' table, its cos and sin are multiplied by
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` and
  ``scale = (qk_nope_head_dim + qk_rope_head_dim)^-1/2 * mscale(factor,
  mscale_all_dim)^2`` with ``mscale(f, m) = 0.1 m ln f + 1``;
- layer 0 (``first_k_dense_replace`` 1): ``x += W_2 (silu(W_1 u) * W_3
  u)``; every later layer: ``g = sigmoid(u W_r^T)``, the chosen the
  ``num_experts_per_tok`` largest of ``g + b`` (``noaux_tc`` with
  ``n_group = topk_group = 1``: no group limit), ``w_e =
  routed_scaling_factor * g_e / (sum over the chosen of g + 1e-20)``,
  ``x += sum_chosen w_e FFN_e(u) + FFN_shared(u)``, every FFN a SwiGLU;
- an unscaled embedding, a final RMSNorm, an untied head.

Inferences and departures, each also under ``assumed`` in the
configuration's file:

- **the rotation's pairing**: dimension j of the rotated part is paired
  with j + ``qk_rope_head_dim``/2.  The source's weights hold the pairs
  interleaved and its code reorders them to halves before it rotates;
  with seeded weights the order is a relabelling of W_uq's and W_dkv's
  columns, and the halves are taken (the program takes the same);
- ``e_score_correction_bias`` (``router_bias``) is seeded zeros;
- projection weights are ``(out, in)``; the experts' are stacked
  ``(experts, in, out)`` with gate and up side by side, as the program's
  parameters are; layer 0's leaves are ``l0_*`` and the expert layers'
  are stacked by layer;
- **a share of the experts.**  ``experts_held = [lo, n]`` says which
  routed experts' weights exist here.  The router still scores all
  ``router_experts``; what an absent expert would add is left out (the
  chip's share of an expert-parallel deployment, with no exchange).  The
  shared expert is whole;
- the vision tower and multi-token-prediction layers are left out.

**Worked in pieces, because it runs beside the program.**  The harness
makes this module's weights (7 GB at the published cut) while the
program's 7 GB are still on the chip, so a 16,384-position pass has
some 1.5 GB to live in.  Token-wise products are made ``TOKEN_CHUNK``
positions at a time against one weight, or ``FFN_TILE`` rows of one,
widened at a time; attention ``HEAD_GROUP`` heads and ``QUERY_BLOCK``
queries at a time; the logits go to the host chunk by chunk (`logits`
returns a NumPy array).  None of the cuts changes a result beyond the
order of float32 sums (tests/test_kimi_k2.py sets them small).

``product`` is the one place a matrix product is made, so that the
control (``low_precision``) can put the same model through float8
operands.  It imports nothing of the program and makes its own weights
from the seed (``param_spec``).
"""

import functools
import math

TOKEN_CHUNK = 2048
QUERY_BLOCK = 256
HEAD_GROUP = 4
FFN_TILE = 2048


def sizes(config):
    """The configuration's sizes under short names."""
    if config["first_k_dense_replace"] != 1 or config["moe_layer_freq"] != 1 \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["scoring_func"] != "sigmoid" \
            or not config["norm_topk_prob"]:
        raise ValueError(
            "kimi_k2: one leading dense layer, experts in every later "
            "layer, sigmoid scores normalised over the chosen and no group "
            "limit is what this reference computes")
    lo, n = config.get("experts_held", (0, config["n_routed_experts"]))
    rs = config["rope_scaling"]
    return {
        "C": config["hidden_size"], "L": config["num_hidden_layers"],
        "H": config["num_attention_heads"],
        "rq": config["q_lora_rank"], "rkv": config["kv_lora_rank"],
        "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"], "F": config["intermediate_size"],
        "Fe": config["moe_intermediate_size"],
        "shared": config["n_shared_experts"],
        "E": config.get("router_experts", config["n_routed_experts"]),
        "held": (int(lo), int(n)), "k": config["num_experts_per_tok"],
        "route_scale": float(config["routed_scaling_factor"]),
        "V": config["vocab_size"], "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]), "factor": float(rs["factor"]),
        "original": rs["original_max_position_embeddings"],
        "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
        "mscale": float(rs["mscale"]),
        "mscale_all_dim": float(rs["mscale_all_dim"])}


ATTENTION_LEAVES = (
    ("ln1_gamma", lambda z: (z["C"],), "ones"),
    ("q_down_weight", lambda z: (z["rq"], z["C"]), None),
    ("q_norm_gamma", lambda z: (z["rq"],), "ones"),
    ("q_up_weight", lambda z: (z["H"] * (z["dn"] + z["dr"]), z["rq"]), None),
    ("kv_down_weight", lambda z: (z["rkv"] + z["dr"], z["C"]), None),
    ("kv_norm_gamma", lambda z: (z["rkv"],), "ones"),
    ("kv_up_weight", lambda z: (z["H"] * (z["dn"] + z["dv"]), z["rkv"]),
     None),
    ("o_weight", lambda z: (z["C"], z["H"] * z["dv"]), None),
    ("ln2_gamma", lambda z: (z["C"],), "ones"))
DENSE_LEAVES = (
    ("gate_weight", lambda z: (z["F"], z["C"]), None),
    ("up_weight", lambda z: (z["F"], z["C"]), None),
    ("down_weight", lambda z: (z["C"], z["F"]), None))
MOE_LEAVES = (
    ("router_weight", lambda z: (z["E"], z["C"]), None),
    ("router_bias", lambda z: (z["E"],), "zeros"),
    ("shared_gate_weight", lambda z: (z["shared"] * z["Fe"], z["C"]), None),
    ("shared_up_weight", lambda z: (z["shared"] * z["Fe"], z["C"]), None),
    ("shared_down_weight", lambda z: (z["C"], z["shared"] * z["Fe"]), None),
    ("experts_gate_up_weight",
     lambda z: (z["held"][1], z["C"], 2 * z["Fe"]), None),
    ("experts_down_weight",
     lambda z: (z["held"][1], z["Fe"], z["C"]), None))


def param_spec(config):
    """(name, shape, init) of every leaf; names are the suffixes of the
    program's parameter names: layer 0's ``l0_*``, the expert layers'
    stacked by layer.  Matrices normal(``initializer_range``, 0.02 where
    the config gives none), unit gains, a zero correction bias;
    ``seeded`` of the configuration ({leaf: init}) overrides a leaf's
    draw (its ``assumed`` says why)."""
    z = sizes(config)
    w = f"normal:{config.get('initializer_range', 0.02)}"
    spec = [("embed_weight", (z["V"], z["C"]), w)]
    spec += [("l0_" + name, shape(z), init or w)
             for name, shape, init in ATTENTION_LEAVES + DENSE_LEAVES]
    spec += [(name, (z["L"] - 1,) + shape(z), init or w)
             for name, shape, init in ATTENTION_LEAVES + MOE_LEAVES]
    spec += [("lnf_gamma", (z["C"],), "ones"),
             ("head_weight", (z["V"], z["C"]), w)]
    seeded = config.get("seeded", {})
    unknown = set(seeded) - {name for name, _, _ in spec}
    if unknown:
        raise ValueError(f"kimi_k2: seeded names no leaf: {sorted(unknown)}")
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


# -- YaRN ----------------------------------------------------------------------

def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_range(z):
    """(low, high): the pairs below ``low`` keep their frequency, those
    from ``high`` on take a ``factor``-th of it."""
    def turns_at(n):
        return z["dr"] * math.log(z["original"] / (2 * math.pi * n)) \
            / (2 * math.log(z["theta"]))

    return (max(math.floor(turns_at(z["beta_fast"])), 0),
            min(math.ceil(turns_at(z["beta_slow"])), z["dr"] - 1))


def yarn_frequencies(z):
    """The ``qk_rope_head_dim / 2`` rotary frequencies, NumPy float64."""
    import numpy as np

    low, high = yarn_range(z)
    i = np.arange(z["dr"] // 2, dtype=np.float64)
    f = z["theta"] ** (-2.0 * i / z["dr"])
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / z["factor"] * ramp


def softmax_scale(z):
    return (z["dn"] + z["dr"]) ** -0.5 \
        * yarn_mscale(z["factor"], z["mscale_all_dim"]) ** 2


# -- the model -----------------------------------------------------------------

def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(g)


def _rot(x, t0, z):
    """Rotate the last axis of (B, T, .., dr) at positions t0 .. t0 + T,
    dimension j paired with j + dr/2."""
    import jax.numpy as jnp

    half = z["dr"] // 2
    freq = jnp.asarray(yarn_frequencies(z), jnp.float32)
    t = (t0 + jnp.arange(x.shape[1])).astype(jnp.float32)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    m = yarn_mscale(z["factor"], z["mscale"]) \
        / yarn_mscale(z["factor"], z["mscale_all_dim"])
    cos = (jnp.cos(t[:, None] * freq) * m).reshape(shape)
    sin = (jnp.sin(t[:, None] * freq) * m).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def latents(x, t0, ln1, w_dq, q_norm, w_dkv, kv_norm, z, prod):
    """A chunk x (B, T, C) at positions t0 ..: the query latent c_q
    (B, T, rq), the latent c (B, T, rkv) and the shared rotated key k_r
    (B, T, dr)."""
    u = _rms_norm(x, ln1, z["eps"])
    cq = _rms_norm(prod("btc,rc->btr", u, _f32(w_dq)), q_norm, z["eps"])
    ck = prod("btc,rc->btr", u, _f32(w_dkv))
    return (cq, _rms_norm(ck[..., :z["rkv"]], kv_norm, z["eps"]),
            _rot(ck[..., z["rkv"]:], t0, z))


def heads(cq, c, kr, w_uq, w_ukv, z, prod):
    """Some heads' queries, keys and values over all positions: w_uq
    (g (dn + dr), rq), w_ukv (g (dn + dv), rkv) → q, k (B, T, g, dn +
    dr), v (B, T, g, dv)."""
    import jax.numpy as jnp

    B, T, _ = cq.shape
    q = prod("btr,gr->btg", cq, _f32(w_uq)).reshape(B, T, -1,
                                                     z["dn"] + z["dr"])
    kv = prod("btr,gr->btg", c, _f32(w_ukv)).reshape(B, T, -1,
                                                      z["dn"] + z["dv"])
    g = q.shape[2]
    q = jnp.concatenate([q[..., :z["dn"]], _rot(q[..., z["dn"]:], 0, z)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :z["dn"]], jnp.broadcast_to(
        kr[:, :, None], (B, T, g, z["dr"]))], axis=-1)
    return q, k, kv[..., z["dn"]:]


def attend(q, k, v, t0, z, prod):
    """Causal softmax attention of a block of queries q (B, Q, g, d) at
    positions t0 .. over k (B, T, g, d), v (B, T, g, dv)."""
    import jax.numpy as jnp

    s = prod("bqhd,bshd->bhqs", q, k) * softmax_scale(z)
    t = t0 + jnp.arange(q.shape[1])[:, None]
    s = jnp.where(jnp.arange(k.shape[1])[None, :] <= t, s, -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return prod("bhqs,bshd->bqhd", e / jnp.sum(e, axis=-1, keepdims=True),
                v)


def swiglu_tile(u, gate, up, down, prod):
    """A tile of a SwiGLU's hidden width: gate, up (f, C), down (C, f)."""
    import jax

    h = jax.nn.silu(prod("btc,fc->btf", u, _f32(gate))) \
        * prod("btc,fc->btf", u, _f32(up))
    return prod("btf,cf->btc", h, _f32(down))


def route(u, router_weight, router_bias, z, prod):
    """(B, T, E) combine weights: ``routed_scaling_factor`` times the
    sigmoid score of each chosen expert over the chosen ones' sum, zero
    elsewhere."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.sigmoid(prod("btc,ec->bte", u, _f32(router_weight)))
    _, chosen = jax.lax.top_k(score + _f32(router_bias), z["k"])
    picked = jnp.sum(jax.nn.one_hot(chosen, z["E"], dtype=score.dtype),
                     axis=-2)
    w = score * picked
    return z["route_scale"] * w / (jnp.sum(w, axis=-1, keepdims=True)
                                   + 1e-20)


def expert_part(u, w_e, w13, w2, Fe, prod):
    """One routed expert's weighted part of the layer's output: ``w_e``
    (B, T) is its combine weight per token (0 where not chosen)."""
    import jax

    h = prod("btc,cf->btf", u, _f32(w13))
    h = jax.nn.silu(h[..., :Fe]) * h[..., Fe:]
    return w_e[..., None] * prod("btf,fc->btc", h, _f32(w2))


# -- in pieces -----------------------------------------------------------------
#
# The residual stream is a list of chunks (B, <= TOKEN_CHUNK, C) from
# the embedding to the head: an update then makes one chunk anew, not
# the stream.

@functools.lru_cache(maxsize=None)
def _jitted(key, prod):
    import jax

    z = dict(key)
    return {
        "latents": jax.jit(lambda x, t0, *w: latents(x, t0, *w, z, prod)),
        "heads": jax.jit(lambda cq, c, kr, a, b: heads(cq, c, kr, a, b, z,
                                                       prod)),
        "attend": jax.jit(lambda q, k, v, t0: attend(q, k, v, t0, z, prod)),
        "out": jax.jit(lambda x, a, w: x + prod("btg,cg->btc", a, _f32(w))),
        "norm": jax.jit(lambda x, g: _rms_norm(x, g, z["eps"])),
        "swiglu": jax.jit(lambda acc, u, a, b, c: acc + swiglu_tile(
            u, a, b, c, prod)),
        "route": jax.jit(lambda u, w, b: route(u, w, b, z, prod)),
        "expert": jax.jit(lambda acc, u, w, w13, w2: acc + expert_part(
            u, w, w13, w2, z["Fe"], prod)),
        "head": jax.jit(lambda h, w: prod("btc,vc->btv", h, _f32(w)))}


def _key(z):
    return tuple(sorted(z.items()))


def _chunks(T, n):
    return [slice(t, min(t + n, T)) for t in range(0, T, n)]


def _cat(parts, axis=1):
    import jax.numpy as jnp

    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def leaf(params, name, i, *index):
    """Layer ``i``'s leaf ``name`` (``index``: a part of it, cut out in
    the same step)."""
    if i == 0:
        return params["l0_" + name][index] if index else \
            params["l0_" + name]
    return params[name][(i - 1,) + index]


def attention_layer(xs, params, i, z, parts):
    """x + [o^1 .. o^H] W_o for layer ``i``, on the stream's chunks."""
    w = [leaf(params, n, i) for n in ("ln1_gamma", "q_down_weight",
                                      "q_norm_gamma", "kv_down_weight",
                                      "kv_norm_gamma")]
    starts = [sum(x.shape[1] for x in xs[:j]) for j in range(len(xs))]
    lat = [parts["latents"](x, t0, *w) for x, t0 in zip(xs, starts)]
    cq, c, kr = (_cat([part[j] for part in lat]) for j in range(3))
    del lat
    w_uq = leaf(params, "q_up_weight", i).reshape(z["H"], -1, z["rq"])
    w_ukv = leaf(params, "kv_up_weight", i).reshape(z["H"], -1, z["rkv"])
    w_o = leaf(params, "o_weight", i).reshape(z["C"], z["H"], z["dv"])
    for h in _chunks(z["H"], HEAD_GROUP):
        q, k, v = parts["heads"](cq, c, kr,
                                 w_uq[h].reshape(-1, z["rq"]),
                                 w_ukv[h].reshape(-1, z["rkv"]))
        a = _cat([parts["attend"](q[:, at], k, v, at.start)
                  for at in _chunks(q.shape[1], QUERY_BLOCK)])
        a = a.reshape(a.shape[:2] + (-1,))
        xs = [parts["out"](x, a[:, t0:t0 + x.shape[1]],
                           w_o[:, h].reshape(z["C"], -1))
              for x, t0 in zip(xs, starts)]
    return xs


def swiglu(u, gate, up, down, parts):
    """A whole SwiGLU on a chunk u, ``FFN_TILE`` of its width at a time."""
    import jax.numpy as jnp

    out = jnp.zeros_like(u)
    for f in _chunks(gate.shape[0], FFN_TILE):
        out = parts["swiglu"](out, u, gate[f], up[f], down[:, f])
    return out


def moe_layer(u, params, i, z, parts, held=None, shared=True):
    """The held routed experts' weighted parts and (``shared``) the
    shared expert's, for layer ``i`` on a chunk's normalised input
    ``u``; ``held`` overrides the share (its experts are then read from
    the stacks at their global index)."""
    import jax.numpy as jnp

    lo, n = z["held"] if held is None else held
    at = 0 if held is None else lo
    w = parts["route"](u, leaf(params, "router_weight", i),
                       leaf(params, "router_bias", i))
    out = jnp.zeros_like(u)
    for e in range(n):
        out = parts["expert"](
            out, u, w[..., lo + e],
            leaf(params, "experts_gate_up_weight", i, at + e),
            leaf(params, "experts_down_weight", i, at + e))
    if shared:
        out = out + swiglu(u, *(leaf(params, f"shared_{m}_weight", i)
                                for m in ("gate", "up", "down")), parts)
    return out


def feed_forward_layer(xs, params, i, z, parts):
    """x + layer ``i``'s feed-forward, on the stream's chunks."""
    out = []
    for x in xs:
        u = parts["norm"](x, leaf(params, "ln2_gamma", i))
        out.append(x + (
            swiglu(u, *(leaf(params, f"{m}_weight", i)
                        for m in ("gate", "up", "down")), parts)
            if i == 0 else moe_layer(u, params, i, z, parts)))
    return out


def hidden(params, ids, config, prod=product):
    """The residual stream after the last layer, float32, as chunks
    (B, <= TOKEN_CHUNK, C)."""
    z = sizes(config)
    parts = _jitted(_key(z), prod)
    xs = [_f32(params["embed_weight"][ids[:, at]])
          for at in _chunks(ids.shape[1], TOKEN_CHUNK)]
    for i in range(z["L"]):
        xs = attention_layer(xs, params, i, z, parts)
        xs = feed_forward_layer(xs, params, i, z, parts)
    return xs


def logits(params, ids, config, prod=product):
    """(B, T, vocab) float32 logits of (B, T) int ids, a NumPy array
    (made a chunk of positions and ``FFN_TILE`` rows of the head at a
    time)."""
    import numpy as np

    z = sizes(config)
    parts = _jitted(_key(z), prod)
    out = []
    for x in hidden(params, ids, config, prod):
        h = parts["norm"](x, params["lnf_gamma"])
        out.append(np.concatenate(
            [np.asarray(parts["head"](h, params["head_weight"][v]))
             for v in _chunks(z["V"], FFN_TILE)], axis=-1))
    return np.concatenate(out, axis=1)

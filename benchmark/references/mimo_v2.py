"""MiMo-V2 (`model_type` ``mimo_v2``: MiMo-V2-Flash, MiMo-V2.5), the plain
reference of its language model.

The forward pass in straightforward ``jax.numpy``: float32, every product
at ``Precision.HIGHEST``, no cache, no kernel, no scan, one layer (and
one expert) widened at a time.  It follows the model's public
``config.json`` with the sizes under the source's keys:

- RMSNorm (``layernorm_epsilon``), no bias anywhere, an unscaled
  embedding look-up and an untied head;
- **full** and **window** attention layers (``hybrid_layer_pattern``: 0
  full, 1 window of ``sliding_window`` positions), each with its own
  number of key/value heads (``num_key_value_heads`` /
  ``swa_num_key_value_heads``) and rotary base (``rope_theta`` /
  ``swa_rope_theta``); query and key heads ``head_dim`` wide, value heads
  ``v_head_dim``; rotary positions on the first
  ``int(partial_rotary_factor * head_dim)`` dimensions, dimension ``d``
  paired with ``d + r/2`` ("rotate-half"); query head ``h`` reads
  key/value head ``h // (heads / kv heads)``;
- a learnable sink logit per query head in the window layers' softmax
  (``add_swa_attention_sink_bias``): it enters the denominator and has
  no value;
- a dense SwiGLU feed-forward where ``moe_layer_freq`` is 0 and routed
  experts where it is 1: sigmoid scores over ``router_experts``, the top
  ``num_experts_per_tok`` of score + correction bias (``noaux_tc`` with
  ``n_group = topk_group = 1``: no group limit), weights the chosen
  scores normalised (``norm_topk_prob``), ``routed_scaling_factor``
  null = 1, no shared expert.

Inferences and departures, each also under ``assumed`` in the
configuration's file:

- ``attention_value_scale`` multiplies the value projection's output
  (the config gives the number, not its place);
- ``attention_chunk_size`` has no equation in the source row and is not
  used; the three multi-token-prediction layers and the vision and
  audio towers are left out (not in the language model's config);
- projection weights are ``(out, in)``; the experts' are stacked
  ``(experts, in, out)`` with gate and up side by side
  (``experts_gate_up_weight``), as the program's parameters are: the same maps;
- **a share of the experts.**  ``experts_held = [lo, n]`` says which
  experts' weights exist here.  The router still scores all
  ``router_experts``; what an absent expert would add is left out (the
  chip's share of an expert-parallel deployment, with no exchange).

``product`` is the one place a matrix product is made, so that the
control (``low_precision``) can put the same model through float8
operands.  It imports nothing of the program and makes its own weights
from the seed (``param_spec``).
"""

import functools
import math


def sizes(config):
    """The configuration's sizes under short names."""
    types = ["window" if t else "full"
             for t in config["hybrid_layer_pattern"]]
    moe = [bool(m) for m in config["moe_layer_freq"]]
    L = config["num_hidden_layers"]
    if len(types) != L or len(moe) != L:
        raise ValueError("mimo_v2: hybrid_layer_pattern and moe_layer_freq "
                         "need one entry per layer")
    lo, n = config.get("experts_held", (0, config["n_routed_experts"]))
    return {
        "C": config["hidden_size"], "L": L, "types": types, "moe": moe,
        "Hq": config["num_attention_heads"],
        "Hkv": {"full": config["num_key_value_heads"],
                "window": config["swa_num_key_value_heads"]},
        "theta": {"full": float(config["rope_theta"]),
                  "window": float(config["swa_rope_theta"])},
        "dqk": config["head_dim"], "dv": config["v_head_dim"],
        "rot": int(config["partial_rotary_factor"] * config["head_dim"]),
        "window": config["sliding_window"],
        "vscale": float(config["attention_value_scale"]),
        "F": config["intermediate_size"],
        "Fe": config["moe_intermediate_size"],
        "E": config.get("router_experts", config["n_routed_experts"]),
        "held": (int(lo), int(n)), "k": config["num_experts_per_tok"],
        "V": config["vocab_size"], "eps": config["layernorm_epsilon"]}


def param_spec(config):
    """(name, shape, init) of every leaf; names are the suffixes of the
    program's parameter names.  ``assumed.weights`` of the configuration:
    matrices normal(``initializer_range``, 0.02 where the config gives
    none), unit gains, sink logits normal(1) and correction biases
    normal(0.1): wide enough that ignoring either changes the result."""
    z = sizes(config)
    C, Hq, dqk, dv = z["C"], z["Hq"], z["dqk"], z["dv"]
    w = f"normal:{config.get('initializer_range', 0.02)}"
    spec = [("embed_weight", (z["V"], C), w)]
    for i, (t, moe) in enumerate(zip(z["types"], z["moe"])):
        hkv = z["Hkv"][t]
        spec += [(f"l{i}_ln1_gamma", (C,), "ones"),
                 (f"l{i}_q_weight", (Hq * dqk, C), w),
                 (f"l{i}_k_weight", (hkv * dqk, C), w),
                 (f"l{i}_v_weight", (hkv * dv, C), w),
                 (f"l{i}_o_weight", (C, Hq * dv), w)]
        if t == "window":
            spec.append((f"l{i}_sink_bias", (Hq,), "normal:1.0"))
        spec.append((f"l{i}_ln2_gamma", (C,), "ones"))
        if moe:
            n = z["held"][1]
            spec += [(f"l{i}_router_weight", (z["E"], C), w),
                     (f"l{i}_router_bias", (z["E"],), "normal:0.1"),
                     (f"l{i}_experts_gate_up_weight", (n, C, 2 * z["Fe"]), w),
                     (f"l{i}_experts_down_weight", (n, z["Fe"], C), w)]
        else:
            spec += [(f"l{i}_gate_weight", (z["F"], C), w),
                     (f"l{i}_up_weight", (z["F"], C), w),
                     (f"l{i}_down_weight", (C, z["F"]), w)]
    spec += [("lnf_gamma", (C,), "ones"),
             ("head_weight", (z["V"], C), w)]
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

def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * g


def _rope(x, theta, rot):
    """Rotate the first ``rot`` of the last axis of (B, T, H, D) at
    positions 0..T-1, pairing dimension d with d + rot/2."""
    import jax.numpy as jnp

    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


# Attention is worked a block of query heads at a time, and the dense
# feed-forward a block of its width at a time, each block a call of its
# own: at 2,048 positions the scores of 64 heads are 1 GB in float32,
# which does not fit beside two copies of the weights.
HEAD_BLOCK = 8
WIDTH_BLOCK = 4096


def qkv(x, p, z, kind, prod):
    """(B, T, C) → q (B, T, Hq, dqk), k (B, T, Hkv, dqk) rotated, and
    v (B, T, Hkv, dv) scaled."""
    B, T, _ = x.shape
    Hq, hkv, dqk, dv = z["Hq"], z["Hkv"][kind], z["dqk"], z["dv"]
    u = _rms_norm(x, p["ln1_gamma"], z["eps"])
    q = prod("btc,gc->btg", u, p["q_weight"]).reshape(B, T, Hq, dqk)
    k = prod("btc,gc->btg", u, p["k_weight"]).reshape(B, T, hkv, dqk)
    v = z["vscale"] * prod("btc,gc->btg", u, p["v_weight"]
                           ).reshape(B, T, hkv, dv)
    return (_rope(q, z["theta"][kind], z["rot"]),
            _rope(k, z["theta"][kind], z["rot"]), v)


def attend(q, k, v, sink, z, kind, prod):
    """Softmax attention of some query heads q (B, T, h, dqk) over their
    own key/value heads k, v (B, T, h, ..), one each; ``sink`` (h,) is
    the window layers' extra term of the denominator, with no value."""
    import jax.numpy as jnp

    T = q.shape[1]
    s = prod("bqhd,bkhd->bhqk", q, k) / math.sqrt(z["dqk"])
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if kind == "window":
        seen = seen & (j > i - z["window"])
    s = jnp.where(seen[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    if kind == "window":
        denom = denom + jnp.exp(sink[None, :, None, None] - m)
    return prod("bhqk,bkhd->bqhd", e / denom, v)


def _swiglu_part(u, gate, up, down, prod):
    """down(silu(gate u) * up u) for a block of the width, with
    (out, in) weights."""
    import jax

    h = jax.nn.silu(prod("btc,fc->btf", u, gate)) \
        * prod("btc,fc->btf", u, up)
    return prod("btf,cf->btc", h, down)


def route(u, p, z, prod):
    """(T.., E) combine weights: the normalised sigmoid score of each
    chosen expert, zero elsewhere."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.sigmoid(prod("btc,ec->bte", u, p["router_weight"]))
    _, chosen = jax.lax.top_k(score + p["router_bias"], z["k"])
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


def _layer_params(params, i, names):
    return {n: _f32(params[f"l{i}_{n}"]) for n in names
            if f"l{i}_{n}" in params}


@functools.lru_cache(maxsize=None)
def _jitted(key, prod):
    import jax

    z = dict(key)
    z["Hkv"], z["theta"] = dict(z["Hkv"]), dict(z["theta"])
    kinds = ("full", "window")
    proj = {t: jax.jit(lambda x, p, t=t: qkv(x, p, z, t, prod))
            for t in kinds}
    att = {t: jax.jit(lambda q, k, v, s, t=t: attend(q, k, v, s, z, t, prod))
           for t in kinds}
    out = jax.jit(lambda x, a, w: x + prod("btg,cg->btc", a, w))
    norm = jax.jit(lambda x, g: _rms_norm(x, g, z["eps"]))
    dense = jax.jit(lambda u, g, up, d: _swiglu_part(u, g, up, d, prod))
    rt = jax.jit(lambda u, p: route(u, p, z, prod))
    ex = jax.jit(lambda u, w, w13, w2: expert_part(u, w, w13, w2, z["Fe"],
                                                   prod))
    head = jax.jit(lambda x, g, w: prod(
        "btc,vc->btv", _rms_norm(x, g, z["eps"]), w))
    return {"qkv": proj, "attend": att, "out": out, "norm": norm,
            "dense": dense, "route": rt, "expert": ex, "head": head}


def _key(z):
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else
         tuple(v) if isinstance(v, list) else v) for k, v in z.items()))


def attention_layer(x, params, i, z, parts):
    """x + a Wo for layer ``i``, HEAD_BLOCK query heads at a time."""
    import jax.numpy as jnp

    kind = z["types"][i]
    p = _layer_params(params, i, ("ln1_gamma", "q_weight", "k_weight",
                                  "v_weight", "sink_bias"))
    q, k, v = parts["qkv"][kind](x, p)
    Hq, group = z["Hq"], z["Hq"] // z["Hkv"][kind]
    # query head h reads key/value head h // (Hq / Hkv)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    sink = p.get("sink_bias", jnp.zeros((Hq,), jnp.float32))
    blocks = []
    for h in range(0, Hq, HEAD_BLOCK):
        at = slice(h, h + HEAD_BLOCK)
        blocks.append(parts["attend"][kind](q[:, :, at], k[:, :, at],
                                            v[:, :, at], sink[at]))
    a = jnp.concatenate(blocks, axis=2).reshape(x.shape[:2] + (-1,))
    return parts["out"](x, a, _f32(params[f"l{i}_o_weight"]))


def dense_layer(u, params, i, z, parts):
    """The dense SwiGLU of layer ``i``, WIDTH_BLOCK of its width at a
    time."""
    out = 0.0
    for f in range(0, z["F"], WIDTH_BLOCK):
        at = slice(f, f + WIDTH_BLOCK)
        out = out + parts["dense"](
            u, _f32(params[f"l{i}_gate_weight"][at]),
            _f32(params[f"l{i}_up_weight"][at]),
            _f32(params[f"l{i}_down_weight"][:, at]))
    return out


def moe_layer(u, params, i, z, parts, held=None):
    """Σ over the held experts of their weighted parts, for layer ``i``
    on normalised input ``u``; ``held`` overrides the share."""
    lo, n = z["held"] if held is None else held
    w = parts["route"](u, _layer_params(params, i, ("router_weight",
                                                    "router_bias")))
    out = 0.0
    for e in range(n):
        out = out + parts["expert"](
            u, w[..., lo + e], _f32(params[f"l{i}_experts_gate_up_weight"][e]),
            _f32(params[f"l{i}_experts_down_weight"][e]))
    return out


def hidden(params, ids, config, prod=product):
    """(B, T, C) float32: the residual stream after the last layer."""
    z = sizes(config)
    parts = _jitted(_key(z), prod)
    x = _f32(params["embed_weight"][ids])
    for i, moe in enumerate(z["moe"]):
        x = attention_layer(x, params, i, z, parts)
        u = parts["norm"](x, _f32(params[f"l{i}_ln2_gamma"]))
        x = x + (moe_layer(u, params, i, z, parts) if moe
                 else dense_layer(u, params, i, z, parts))
    return x


def logits(params, ids, config, prod=product):
    """(B, T, vocab) float32 logits of (B, T) int ids."""
    z = sizes(config)
    return _jitted(_key(z), prod)["head"](
        hidden(params, ids, config, prod), _f32(params["lnf_gamma"]),
        _f32(params["head_weight"]))

"""Ouro (`model_type` ``ouro``: Ouro-1.4B / 2.6B, the looped language
model of "Scaling Latent Reasoning via Looped Language Models"), the
plain reference.

The forward pass in straightforward ``jax.numpy``: float32, every
product at ``Precision.HIGHEST``, no cache, no kernel, no scan.  Sizes
under the source's keys.  For token ids at positions ``0 .. n-1``:

- ``x = E[id]`` (unscaled);
- for loop step ``t = 0 .. total_ut_steps - 1`` and inside it for layer
  ``l = 0 .. num_hidden_layers - 1``, **layer l's one set of weights in
  every loop step**:
  ``a = RMSNorm(x; g1)``; ``[q ; k ; v] = a W_qkv`` as heads of
  ``head_dim``; q and k rotated at the position (``rope_theta``, all of a
  head, dimension j paired with j + head_dim/2); position i attends
  causally over this pass's keys of positions ``<= i``, ``o = softmax(q
  k^T / sqrt(head_dim)) v``; ``x += RMSNorm(o W_o; g2)``;
  ``m = RMSNorm(x; g3)``; ``x += RMSNorm(W_d (silu(W_g m) * W_u m); g4)``;
- after the last layer ``h_t = RMSNorm(x; g_f)``, which is what loop
  step ``t + 1`` starts from, and the gate ``lambda_t = sigmoid(w_e . h_t
  + b_e)``;
- the exit rule: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t <
  T - 1``, ``c_t = sum_{j<=t} p_j``; a position leaves at the first such
  ``t`` with ``c_t >= early_exit_threshold``, else at ``T - 1``;
  ``logits = h_{t*} W_head``.

Inferences and departures, each also under ``assumed`` in the
configuration's file: no bias anywhere; the four norms a layer (the
paper's "sandwich"); ``h_t`` as the next pass's input; the exit rule
(the paper's description of inference); the rotation's pairing (halves);
projection weights are ``(out, in)``, a layer's query, key and value
projections one matrix with the queries' rows first, then the keys',
then the values' (a relabelling of three); the layers' leaves stacked by
layer.

**Worked a layer at a time, because it runs beside the program**: the
harness makes this module's weights (5.3 GB in bfloat16 at the published
sizes) while the program's 5.3 GB are on the chip, so a layer's weights
are widened to float32 as it is worked (0.2 GB), never the model's.

``product`` is the one place a matrix product is made, so that the
control (``low_precision``) can put the same model through float8
operands.  It imports nothing of the program and makes its own weights
from the seed (``param_spec``).
"""

import functools

LAYER_LEAVES = ("ln1_gamma", "qkv_weight", "o_weight", "ln2_gamma",
                "ln3_gamma", "gate_weight", "up_weight", "down_weight",
                "ln4_gamma")


def sizes(config):
    """The configuration's sizes under short names."""
    if config.get("use_sliding_window") or config.get("rope_scaling") \
            or config["hidden_act"] != "silu" \
            or config["tie_word_embeddings"]:
        raise ValueError(
            "ouro: full attention with plain rotary positions, a SiLU "
            "gate and an untied head is what this reference computes")
    H = config["num_attention_heads"]
    return {
        "C": config["hidden_size"], "L": config["num_hidden_layers"],
        "T": config["total_ut_steps"], "H": H,
        "K": config.get("num_key_value_heads", H),
        "d": config["head_dim"], "F": config["intermediate_size"],
        "V": config["vocab_size"], "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "threshold": float(config["early_exit_threshold"])}


def param_spec(config):
    """(name, shape, init) of every leaf; names are the suffixes of the
    program's parameter names, the layers' stacked by layer.  Matrices
    normal(``initializer_range``, 0.02 where the config gives none), unit
    gains, a zero gate bias; ``seeded`` of the configuration ({leaf:
    init}) overrides a leaf's draw (its ``assumed`` says why)."""
    z = sizes(config)
    C, L, d, F = z["C"], z["L"], z["d"], z["F"]
    w = f"normal:{config.get('initializer_range', 0.02)}"
    spec = [("embed_weight", (z["V"], C), w),
            ("ln1_gamma", (L, C), "ones"),
            ("qkv_weight", (L, (z["H"] + 2 * z["K"]) * d, C), w),
            ("o_weight", (L, C, z["H"] * d), w),
            ("ln2_gamma", (L, C), "ones"),
            ("ln3_gamma", (L, C), "ones"),
            ("gate_weight", (L, F, C), w), ("up_weight", (L, F, C), w),
            ("down_weight", (L, C, F), w),
            ("ln4_gamma", (L, C), "ones"),
            ("lnf_gamma", (C,), "ones"),
            ("exit_weight", (1, C), w), ("exit_bias", (1,), "zeros"),
            ("head_weight", (z["V"], C), w)]
    seeded = config.get("seeded", {})
    unknown = set(seeded) - {name for name, _, _ in spec}
    if unknown:
        raise ValueError(f"ouro: seeded names no leaf: {sorted(unknown)}")
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

def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(g)


def _rot(x, z):
    """Rotate the last axis of (B, T, heads, d) at positions 0 .. T - 1,
    dimension j paired with j + d/2."""
    import jax.numpy as jnp

    half = z["d"] // 2
    freq = z["theta"] ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                          / z["d"])
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer(x, p, z, prod):
    """One layer on x (B, T, C) with its leaves ``p`` (by name, unstacked)
    → (x, this pass's rotated keys (B, T, K, d), its values)."""
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    H, K, d = z["H"], z["K"], z["d"]
    a = _rms_norm(x, p["ln1_gamma"], z["eps"])
    qkv = prod("btc,gc->btg", a, _f32(p["qkv_weight"])).reshape(
        B, T, H + 2 * K, d)
    q, k, v = _rot(qkv[:, :, :H], z), _rot(qkv[:, :, H:H + K], z), \
        qkv[:, :, H + K:]
    # query head h reads key head h // (H / K)
    kh, vh = (jnp.repeat(c, H // K, axis=2) for c in (k, v))
    s = prod("bqhd,bshd->bhqs", q, kh) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(jnp.arange(T)[None, :] <= jnp.arange(T)[:, None], s,
                  -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    o = prod("bhqs,bshd->bqhd", e / jnp.sum(e, axis=-1, keepdims=True), vh)
    x = x + _rms_norm(prod("btg,cg->btc", o.reshape(B, T, H * d),
                           _f32(p["o_weight"])), p["ln2_gamma"], z["eps"])
    m = _rms_norm(x, p["ln3_gamma"], z["eps"])
    f = jax.nn.silu(prod("btc,fc->btf", m, _f32(p["gate_weight"]))) \
        * prod("btc,fc->btf", m, _f32(p["up_weight"]))
    return x + _rms_norm(prod("btf,cf->btc", f, _f32(p["down_weight"])),
                         p["ln4_gamma"], z["eps"]), k, v


def exit_step(gates, threshold):
    """gates (T, ...) → the loop step each position leaves at."""
    import jax.numpy as jnp

    T = gates.shape[0]
    left = jnp.full(gates.shape[1:], T - 1, jnp.int32)
    stay = jnp.ones(gates.shape[1:], jnp.float32)
    total = jnp.zeros(gates.shape[1:], jnp.float32)
    for t in range(T - 1):
        total = total + gates[t] * stay
        stay = stay * (1.0 - gates[t])
        left = jnp.where((left == T - 1) & (total >= threshold), t, left)
    return left


def close(x, g, w, b, z, prod):
    """The end of a pass: (h = RMSNorm(x; g), the gate sigmoid(w . h +
    b))."""
    import jax

    h = _rms_norm(x, g, z["eps"])
    return h, jax.nn.sigmoid(prod("btc,oc->bto", h, _f32(w))[..., 0]
                             + _f32(b)[0])


@functools.lru_cache(maxsize=None)
def _jitted(key, prod):
    import jax

    z = dict(key)
    return {
        "layer": jax.jit(lambda x, p: layer(x, p, z, prod)),
        "close": jax.jit(lambda x, g, w, b: close(x, g, w, b, z, prod)),
        "head": jax.jit(lambda h, w: prod("btc,vc->btv", h, _f32(w)))}


def _key(z):
    return tuple(sorted(z.items()))


def passes(params, ids, config, prod=product, keep_keys=False):
    """Every loop step's normed stream ``h`` (T, B, n, C) and gate
    (T, B, n); with ``keep_keys`` also each pass's rotated keys and its
    values by layer, ``[t][l]`` → (k, v), each (B, n, K, d)."""
    import jax.numpy as jnp

    z = sizes(config)
    parts = _jitted(_key(z), prod)
    x = _f32(params["embed_weight"][ids])
    hs, gates, kept = [], [], []
    for _ in range(z["T"]):
        kept.append([])
        for l in range(z["L"]):
            x, k, v = parts["layer"](x, {n: params[n][l]
                                         for n in LAYER_LEAVES})
            if keep_keys:
                kept[-1].append((k, v))
        x, gate = parts["close"](x, params["lnf_gamma"],
                                 params["exit_weight"], params["exit_bias"])
        hs.append(x)
        gates.append(gate)
    out = jnp.stack(hs), jnp.stack(gates)
    return out + (kept,) if keep_keys else out


def logits(params, ids, config, prod=product):
    """(B, n, vocab) float32 logits of (B, n) int ids, each position's
    at its own exit step; a NumPy array."""
    import jax.numpy as jnp
    import numpy as np

    z = sizes(config)
    hs, gates = passes(params, ids, config, prod)
    left = exit_step(gates, z["threshold"])
    h = jnp.take_along_axis(hs, left[None, ..., None], axis=0)[0]
    return np.asarray(_jitted(_key(z), prod)["head"](
        h, params["head_weight"]))

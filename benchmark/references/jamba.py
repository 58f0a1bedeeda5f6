"""Jamba (`model_type` ``jamba``: AI21's hybrid of Mamba-1 layers and
attention, arXiv:2403.19887; the dense members, ``num_experts`` 1, such
as Jamba2-3B), the plain reference.

The forward pass in straightforward ``jax.numpy``: float32, every
product at ``Precision.HIGHEST``, no cache, no kernel, the recurrence a
plain ``lax.scan`` over the positions.  Sizes under the source's keys.
For token ids at positions ``0 .. n-1``:

- ``x = E[id]`` (unscaled);
- for layer ``l = 0 .. num_hidden_layers - 1``: ``x += Mixer_l(RMSNorm(x;
  g1_l))``, then ``x += W_down (silu(W_gate m) * (W_up m))`` with ``m =
  RMSNorm(x; g2_l)``;
- layer ``l``'s mixer is **attention** where ``l mod attn_layer_period ==
  attn_layer_offset``: ``q = W_q u`` (``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``), ``k = W_k u``, ``v = W_v u``
  (``num_key_value_heads`` heads; query head h reads key head ``h //
  (H / K)``), **no rotation, no positional term**, causal softmax of ``q
  k / sqrt(d)``, ``W_o`` over the heads' outputs;
- and a **Mamba mixer** elsewhere, with E = ``mamba_expand x
  hidden_size`` channels, N = ``mamba_d_state``, R = ``mamba_dt_rank``:
  ``[a ; z] = W_in u``; ``c_t = silu(b_conv + sum_{j < k} w_conv[:, j]
  a_{t-k+1+j})`` (``a`` zero before position 0, k = ``mamba_d_conv``);
  ``[dt' ; B' ; C'] = W_x c_t``; ``dt = softplus(W_dt RMSNorm(dt'; g_dt)
  + b_dt)``, ``B = RMSNorm(B'; g_B)``, ``C = RMSNorm(C'; g_C)``; ``A =
  -exp(A_log)``; ``h_t = exp(dt_t A) * h_{t-1} + (dt_t c_t) B_t`` from
  ``h_{-1} = 0``; ``y_t = h_t C_t + D c_t``; ``out = W_out (y * silu(z))``;
- ``logits = RMSNorm(x; g_f) E^T`` (``tie_word_embeddings``).

Inferences and departures, each also under ``assumed`` in the
configuration's file: which layers are attention (the family's rule on
the two keys above); the order of ``W_in``'s and ``W_x``'s outputs; no
bias but the convolution's (``mamba_conv_bias``) and ``dt``'s; the state
in float32; projection weights are ``(out, in)``; the leaves stacked by
kind (every layer's two gains and SwiGLU; the Mamba mixers'; the
attention mixers'), ``A_log`` and the convolution's taps with the
channels first, as the source holds them.

**Worked a layer at a time, because it runs beside the program**: the
harness makes this module's weights (6.06 GB in bfloat16 at the
published sizes) while the program's are on the chip, so a layer's
weights are widened to float32 as it is worked (0.4 GB), never a stacked
leaf's 26 layers.

``product`` is the one place a matrix product is made, so that the
control (``low_precision``) can put the same model through float8
operands.  It imports nothing of the program and makes its own weights
from the seed (``param_spec``).
"""

import functools

ALL_LEAVES = ("ln1_gamma", "ln2_gamma", "gate_weight", "up_weight",
              "down_weight")
SSM_LEAVES = ("in_weight", "conv_weight", "conv_bias", "x_weight",
              "dt_gamma", "b_gamma", "c_gamma", "dt_weight", "dt_bias",
              "a_log_weight", "d_weight", "out_weight")
ATTN_LEAVES = ("q_weight", "k_weight", "v_weight", "o_weight")


def sizes(config):
    """The configuration's sizes under short names."""
    if config.get("num_experts", 1) != 1 or config["hidden_act"] != "silu" \
            or not config["tie_word_embeddings"] \
            or config.get("sliding_window") \
            or config.get("mamba_proj_bias") \
            or not config.get("mamba_conv_bias", True):
        raise ValueError(
            "jamba: dense feed-forwards, a SiLU gate, a tied head, full "
            "attention, a biased convolution and bias-free projections "
            "are what this reference computes")
    C, H = config["hidden_size"], config["num_attention_heads"]
    return {
        "C": C, "L": config["num_hidden_layers"], "H": H,
        "K": config.get("num_key_value_heads", H), "d": C // H,
        "F": config["intermediate_size"], "V": config["vocab_size"],
        "E": config["mamba_expand"] * C, "N": config["mamba_d_state"],
        "R": config["mamba_dt_rank"], "k": config["mamba_d_conv"],
        "period": config["attn_layer_period"],
        "offset": config["attn_layer_offset"],
        "eps": config["rms_norm_eps"]}


def kinds(config):
    """Each layer's mixer, ``"attn"`` or ``"ssm"``."""
    z = sizes(config)
    return ["attn" if i % z["period"] == z["offset"] else "ssm"
            for i in range(z["L"])]


def param_spec(config):
    """(name, shape, init) of every leaf; names are the suffixes of the
    program's parameter names, stacked by kind.  Matrices
    normal(``initializer_range``, 0.02 where the config gives none), unit
    gains and ``D``, zero biases; ``seeded`` of the configuration ({leaf:
    init}) overrides a leaf's draw (its ``assumed`` says why)."""
    z = sizes(config)
    C, E, N, R, F, d = z["C"], z["E"], z["N"], z["R"], z["F"], z["d"]
    L = z["L"]
    La = kinds(config).count("attn")
    Lm = L - La
    w = f"normal:{config.get('initializer_range', 0.02)}"
    spec = [("embed_weight", (z["V"], C), w),
            ("ln1_gamma", (L, C), "ones"), ("ln2_gamma", (L, C), "ones"),
            ("gate_weight", (L, F, C), w), ("up_weight", (L, F, C), w),
            ("down_weight", (L, C, F), w),
            ("in_weight", (Lm, 2 * E, C), w),
            ("conv_weight", (Lm, E, z["k"]), w),
            ("conv_bias", (Lm, E), "zeros"),
            ("x_weight", (Lm, R + 2 * N, E), w),
            ("dt_gamma", (Lm, R), "ones"), ("b_gamma", (Lm, N), "ones"),
            ("c_gamma", (Lm, N), "ones"),
            ("dt_weight", (Lm, E, R), w), ("dt_bias", (Lm, E), "zeros"),
            ("a_log_weight", (Lm, E, N), "zeros"),
            ("d_weight", (Lm, E), "ones"),
            ("out_weight", (Lm, C, E), w),
            ("q_weight", (La, z["H"] * d, C), w),
            ("k_weight", (La, z["K"] * d, C), w),
            ("v_weight", (La, z["K"] * d, C), w),
            ("o_weight", (La, C, z["H"] * d), w),
            ("lnf_gamma", (C,), "ones")]
    seeded = config.get("seeded", {})
    unknown = set(seeded) - {name for name, _, _ in spec}
    if unknown:
        raise ValueError(f"jamba: seeded names no leaf: {sorted(unknown)}")
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


def conv(a, p, z):
    """c (B, T, E) of the mixer's input a: the causal depthwise
    convolution from a zero history, its bias and the SiLU."""
    import jax
    import jax.numpy as jnp

    k, T = z["k"], a.shape[1]
    w = _f32(p["conv_weight"])                              # (E, k)
    ap = jnp.pad(a, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(_f32(p["conv_bias"]) + sum(
        w[:, j] * ap[:, j:j + T] for j in range(k)))


def scan(c, dt, B, C, p, state=None):
    """The recurrence over the positions of c (B, T, E), one after the
    other: (y (B, T, E), the state (B, E, N) after the last), from
    ``state`` or zero."""
    import jax
    import jax.numpy as jnp

    A = -jnp.exp(_f32(p["a_log_weight"]))                   # (E, N)
    h0 = jnp.zeros((c.shape[0],) + A.shape, jnp.float32) \
        if state is None else state

    def step(h, at):
        c_t, dt_t, B_t, C_t = at
        h = jnp.exp(dt_t[:, :, None] * A) * h \
            + (dt_t * c_t)[:, :, None] * B_t[:, None, :]
        return h, jnp.sum(h * C_t[:, None, :], axis=-1)

    h, y = jax.lax.scan(step, h0, tuple(
        x.swapaxes(0, 1) for x in (c, dt, B, C)))
    return y.swapaxes(0, 1) + _f32(p["d_weight"]) * c, h


def mamba(u, p, z, prod, state=None):
    """The Mamba mixer on the normed stream u (B, T, C): (its output,
    the state after the last position, the convolution's input a)."""
    import jax

    E, N, R, eps = z["E"], z["N"], z["R"], z["eps"]
    az = prod("btc,gc->btg", u, _f32(p["in_weight"]))
    a, gate = az[..., :E], az[..., E:]
    c = conv(a, p, z)
    dbc = prod("bte,ge->btg", c, _f32(p["x_weight"]))
    dt = jax.nn.softplus(prod(
        "btr,er->bte", _rms_norm(dbc[..., :R], p["dt_gamma"], eps),
        _f32(p["dt_weight"])) + _f32(p["dt_bias"]))
    y, h = scan(c, dt, _rms_norm(dbc[..., R:R + N], p["b_gamma"], eps),
                _rms_norm(dbc[..., R + N:], p["c_gamma"], eps), p, state)
    return prod("bte,ce->btc", y * jax.nn.silu(gate),
                _f32(p["out_weight"])), h, a


def attention(u, p, z, prod):
    """The attention mixer on the normed stream u (B, T, C)."""
    import jax.numpy as jnp

    B, T, _ = u.shape
    H, K, d = z["H"], z["K"], z["d"]
    q = prod("btc,gc->btg", u, _f32(p["q_weight"])).reshape(B, T, H, d)
    k, v = (prod("btc,gc->btg", u, _f32(p[n])).reshape(B, T, K, d)
            for n in ("k_weight", "v_weight"))
    # query head h reads key head h // (H / K)
    kh, vh = (jnp.repeat(x, H // K, axis=2) for x in (k, v))
    s = prod("bqhd,bshd->bhqs", q, kh) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(jnp.arange(T)[None, :] <= jnp.arange(T)[:, None], s,
                  -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    o = prod("bhqs,bshd->bqhd", e / jnp.sum(e, axis=-1, keepdims=True), vh)
    return prod("btg,cg->btc", o.reshape(B, T, H * d), _f32(p["o_weight"]))


def layer(x, p, kind, z, prod):
    """One block on x (B, T, C) with its leaves ``p`` (by name,
    unstacked) → (x, the Mamba state after the last position or
    None)."""
    import jax

    u = _rms_norm(x, p["ln1_gamma"], z["eps"])
    if kind == "ssm":
        out, h, _ = mamba(u, p, z, prod)
    else:
        out, h = attention(u, p, z, prod), None
    x = x + out
    m = _rms_norm(x, p["ln2_gamma"], z["eps"])
    f = jax.nn.silu(prod("btc,fc->btf", m, _f32(p["gate_weight"]))) \
        * prod("btc,fc->btf", m, _f32(p["up_weight"]))
    return x + prod("btf,cf->btc", f, _f32(p["down_weight"])), h


@functools.lru_cache(maxsize=None)
def _jitted(key, prod):
    import jax

    z = dict(key)
    return {
        "ssm": jax.jit(lambda x, p: layer(x, p, "ssm", z, prod)[0]),
        "attn": jax.jit(lambda x, p: layer(x, p, "attn", z, prod)[0]),
        "head": jax.jit(lambda x, g, w: prod(
            "btc,vc->btv", _rms_norm(x, g, z["eps"]), _f32(w)))}


def layer_leaves(params, config, i):
    """Layer ``i``'s leaves by name, unstacked."""
    ks = kinds(config)
    j = ks[:i].count(ks[i])
    p = {n: params[n][i] for n in ALL_LEAVES}
    p.update((n, params[n][j])
             for n in (SSM_LEAVES if ks[i] == "ssm" else ATTN_LEAVES))
    return p


def logits(params, ids, config, prod=product):
    """(B, n, vocab) float32 logits of (B, n) int ids; a NumPy array."""
    import numpy as np

    z = sizes(config)
    parts = _jitted(tuple(sorted(z.items())), prod)
    x = _f32(params["embed_weight"][ids])
    for i, kind in enumerate(kinds(config)):
        x = parts[kind](x, layer_leaves(params, config, i))
    return np.asarray(parts["head"](x, params["lnf_gamma"],
                                    params["embed_weight"]))

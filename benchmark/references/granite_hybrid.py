"""Granite 4.0-H (`model_type` ``granitemoehybrid``: IBM's hybrid of
Mamba-2 layers and attention with routed experts and a shared expert in
every layer; `transformers`' ``modeling_granitemoehybrid.py``), the plain
reference.

The forward pass in straightforward ``jax.numpy``: float32, every
product at ``Precision.HIGHEST``, no cache, no kernel, the recurrence a
plain ``lax.scan`` over the positions, the experts a loop over the held
ones.  Sizes under the source's keys: M = ``hidden_size``, H =
``mamba_n_heads`` heads of P = ``mamba_d_head`` channels (E = H P =
``mamba_expand`` M), N = ``mamba_d_state``, one group
(``mamba_n_groups``), k = ``mamba_d_conv``; ``e`` =
``embedding_multiplier``, ``r`` = ``residual_multiplier``, ``a`` =
``attention_multiplier``, ``s`` = ``logits_scaling``.  For token ids at
positions ``0 .. n-1``:

- ``x = e Emb[id]``;
- for layer ``l``: ``x += r Mixer_l(RMSNorm(x; g1_l))``; ``u = RMSNorm(x;
  g2_l)``; ``x += r (Routed_l(u) + Shared_l(u))``;
- layer ``l``'s mixer is **attention** where ``layer_types[l] ==
  "attention"``: ``q = W_q u`` (``num_attention_heads`` heads of M /
  heads), ``k = W_k u``, ``v = W_v u`` (``num_key_value_heads`` heads;
  query head h reads key head ``h // (H_q / H_kv)``), **no rotation, no
  positional term** (``position_embedding_type`` ``nope``), causal
  softmax of ``a q k``, ``W_o`` over the heads' outputs;
- and a **Mamba-2 mixer** elsewhere: ``[z ; w ; d] = W_in u`` (E, E + 2 N
  and H outputs); ``[x ; B ; C]_t = silu(b_conv + sum_{j < k} w_conv[:,
  j] w_{t-k+1+j})`` (``w`` zero before position 0; cut E, N, N); ``dt =
  softplus(d + dt_bias)``; ``A = -exp(A_log)`` (a head each); head h:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (P x N, from ``S_{-1} =
  0``), ``y_t = S_t C_t + D x_t``; ``out = W_out RMSNorm(y * silu(z);
  g_n)``: the gate before the norm, the norm over all E channels;
- **Routed(u)**: ``l = W_r u`` over ``router_experts``; the
  ``num_experts_per_tok`` largest; ``g`` their softmax; expert i is
  ``W2_i (silu(p) * q)`` with ``[p ; q] = W13_i u``; the sum of ``g_i
  expert_i(u)`` over the chosen experts that are **held**
  (``experts_held = [lo, n]``: a chip's share; what the absent experts
  would add is left out).  **Shared(u)**: the same form at
  ``shared_intermediate_size``, for every token;
- ``logits = Emb RMSNorm(x; g_f) / s`` (``tie_word_embeddings``).

Inferences, each also under ``assumed`` in the configuration's file:
``intermediate_size`` is one routed expert's width; the order of
``W_in``'s outputs and of the convolution's; the gate before the norm;
no bias but the convolution's and ``dt``'s; the state in float32;
projection weights are ``(out, in)``, the experts' ``(in, out)`` with
the gate beside the up-projection; the leaves stacked by kind (every
layer's gains, router, shared expert and held experts; the Mamba
mixers'; the attention mixers').

**Worked a layer at a time, because it runs beside the program**: the
harness makes this module's weights (5.9 GB in bfloat16 at the served
sizes) while the program's are on the chip, so a layer's weights are
widened to float32 as it is worked (1.2 GB), never a stacked leaf's ten
layers.

``product`` is the one place a matrix product is made, so that the
control (``low_precision``) can put the same model through float8
operands.  It imports nothing of the program and makes its own weights
from the seed (``param_spec``).
"""

import functools

ALL_LEAVES = ("ln1_gamma", "ln2_gamma", "router_weight",
              "shared_gate_weight", "shared_up_weight", "shared_down_weight",
              "experts_gate_up_weight", "experts_down_weight")
SSM_LEAVES = ("in_weight", "conv_weight", "conv_bias", "dt_bias",
              "a_log_weight", "d_weight", "norm_gamma", "out_weight")
ATTN_LEAVES = ("q_weight", "k_weight", "v_weight", "o_weight")


def sizes(config):
    """The configuration's sizes under short names (hashable)."""
    C = config["hidden_size"]
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    if config["hidden_act"] != "silu" or not config["tie_word_embeddings"] \
            or config.get("mamba_proj_bias") or config.get("attention_bias") \
            or not config.get("mamba_conv_bias", True) \
            or config.get("mamba_n_groups", 1) != 1 \
            or config.get("position_embedding_type", "nope") != "nope" \
            or H * P != config["mamba_expand"] * C:
        raise ValueError(
            "granite_hybrid: a SiLU gate, a tied head, one group of B and "
            "C, heads that fill mamba_expand x hidden_size, a biased "
            "convolution, bias-free projections and attention without "
            "positions are what this reference computes")
    Hq = config["num_attention_heads"]
    E = config.get("router_experts", config["num_local_experts"])
    lo, n = config.get("experts_held", (0, config["num_local_experts"]))
    return {
        "C": C, "H": Hq, "K": config.get("num_key_value_heads", Hq),
        "d": C // Hq, "Hm": H, "P": P, "N": config["mamba_d_state"],
        "k": config["mamba_d_conv"], "F": config["intermediate_size"],
        "Fs": config["shared_intermediate_size"], "E": E,
        "held": (int(lo), int(n)), "top": config["num_experts_per_tok"],
        "V": config["vocab_size"], "eps": config["rms_norm_eps"],
        "e": float(config["embedding_multiplier"]),
        "r": float(config["residual_multiplier"]),
        "a": float(config["attention_multiplier"]),
        "s": float(config["logits_scaling"])}


def kinds(config):
    """Each layer's mixer, ``"attn"`` or ``"ssm"``."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] \
            or set(types) - {"mamba", "attention"}:
        raise ValueError("granite_hybrid: layer_types names 'mamba' or "
                         "'attention' for each of num_hidden_layers")
    return ["attn" if t == "attention" else "ssm" for t in types]


def param_spec(config):
    """(name, shape, init) of every leaf; names are the suffixes of the
    program's parameter names, stacked by kind.  Matrices
    normal(``initializer_range``, 0.02 where the config gives none), unit
    gains and ``D``, zero biases; ``seeded`` of the configuration ({leaf:
    init}) overrides a leaf's draw (its ``assumed`` says why)."""
    z = sizes(config)
    C, d, N, F, Fs = z["C"], z["d"], z["N"], z["F"], z["Fs"]
    E, W = z["Hm"] * z["P"], z["Hm"] * z["P"] + 2 * z["N"]
    n = z["held"][1]
    ks = kinds(config)
    L, La = len(ks), ks.count("attn")
    Lm = L - La
    w = f"normal:{config.get('initializer_range', 0.02)}"
    spec = [("embed_weight", (z["V"], C), w),
            ("ln1_gamma", (L, C), "ones"), ("ln2_gamma", (L, C), "ones"),
            ("router_weight", (L, z["E"], C), w),
            ("shared_gate_weight", (L, Fs, C), w),
            ("shared_up_weight", (L, Fs, C), w),
            ("shared_down_weight", (L, C, Fs), w),
            ("experts_gate_up_weight", (L, n, C, 2 * F), w),
            ("experts_down_weight", (L, n, F, C), w),
            ("in_weight", (Lm, E + W + z["Hm"], C), w),
            ("conv_weight", (Lm, W, z["k"]), w),
            ("conv_bias", (Lm, W), "zeros"),
            ("dt_bias", (Lm, z["Hm"]), "zeros"),
            ("a_log_weight", (Lm, z["Hm"]), "zeros"),
            ("d_weight", (Lm, z["Hm"]), "ones"),
            ("norm_gamma", (Lm, E), "ones"),
            ("out_weight", (Lm, C, E), w),
            ("q_weight", (La, z["H"] * d, C), w),
            ("k_weight", (La, z["K"] * d, C), w),
            ("v_weight", (La, z["K"] * d, C), w),
            ("o_weight", (La, C, z["H"] * d), w),
            ("lnf_gamma", (C,), "ones")]
    seeded = config.get("seeded", {})
    unknown = set(seeded) - {name for name, _, _ in spec}
    if unknown:
        raise ValueError(f"granite_hybrid: seeded names no leaf: "
                         f"{sorted(unknown)}")
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


def conv(w_in, p, z):
    """The convolution's output (B, T, E + 2 N) of its input: causal,
    depthwise, from a zero history, its bias and the SiLU."""
    import jax
    import jax.numpy as jnp

    k, T = z["k"], w_in.shape[1]
    w = _f32(p["conv_weight"])                              # (E + 2 N, k)
    ap = jnp.pad(w_in, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(_f32(p["conv_bias"]) + sum(
        w[:, j] * ap[:, j:j + T] for j in range(k)))


def scan(x, dt, B, C, p, state=None):
    """The recurrence over the positions of x (B, T, H, P), one after
    the other: (y (B, T, H, P), the state (B, H, P, N) after the last),
    from ``state`` or zero."""
    import jax
    import jax.numpy as jnp

    A = -jnp.exp(_f32(p["a_log_weight"]))                   # (H,)
    h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], jnp.float32) \
        if state is None else state

    def step(h, at):
        x_t, dt_t, B_t, C_t = at
        h = jnp.exp(dt_t * A)[:, :, None, None] * h \
            + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, None, None, :]
        return h, jnp.sum(h * C_t[:, None, None, :], axis=-1)

    h, y = jax.lax.scan(step, h0, tuple(
        a.swapaxes(0, 1) for a in (x, dt, B, C)))
    return y.swapaxes(0, 1) + _f32(p["d_weight"])[:, None] * x, h


def mamba(u, p, z, prod, state=None):
    """The Mamba-2 mixer on the normed stream u (B, T, C): (its output,
    the state after the last position, the convolution's input)."""
    import jax

    Bt, T, _ = u.shape
    E, N = z["Hm"] * z["P"], z["N"]
    zwd = prod("btc,gc->btg", u, _f32(p["in_weight"]))
    gate, w_in, d = zwd[..., :E], zwd[..., E:2 * E + 2 * N], \
        zwd[..., 2 * E + 2 * N:]
    c = conv(w_in, p, z)
    dt = jax.nn.softplus(d + _f32(p["dt_bias"]))
    y, h = scan(c[..., :E].reshape(Bt, T, z["Hm"], z["P"]), dt,
                c[..., E:E + N], c[..., E + N:], p, state)
    y = _rms_norm(y.reshape(Bt, T, E) * jax.nn.silu(gate), p["norm_gamma"],
                  z["eps"])
    return prod("bte,ce->btc", y, _f32(p["out_weight"])), h, w_in


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
    s = prod("bqhd,bshd->bhqs", q, kh) * z["a"]
    s = jnp.where(jnp.arange(T)[None, :] <= jnp.arange(T)[:, None], s,
                  -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    o = prod("bhqs,bshd->bqhd", e / jnp.sum(e, axis=-1, keepdims=True), vh)
    return prod("btg,cg->btc", o.reshape(B, T, H * d), _f32(p["o_weight"]))


def swiglu(u, w13, w2, prod):
    """``W2 (silu(p) * q)`` with ``[p ; q] = W13 u``; w13 (C, 2 F), w2
    (F, C)."""
    import jax

    F = w2.shape[0]
    pq = prod("btc,cf->btf", u, w13)
    return prod("btf,fc->btc", jax.nn.silu(pq[..., :F]) * pq[..., F:], w2)


def routed(u, p, z, prod, held=None):
    """The held experts' weighted parts for u (B, T, C); ``p``'s expert
    leaves hold experts ``held[0] .. held[0] + held[1]`` (the
    configuration's share where None)."""
    import jax
    import jax.numpy as jnp

    lo, n = held or z["held"]
    scores = prod("btc,ec->bte", u, _f32(p["router_weight"]))
    top, chosen = jax.lax.top_k(scores, z["top"])
    g = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(u)
    for i in range(n):
        weight = jnp.sum(jnp.where(chosen == lo + i, g, 0.0), axis=-1)
        out = out + weight[..., None] * swiglu(
            u, _f32(p["experts_gate_up_weight"][i]),
            _f32(p["experts_down_weight"][i]), prod)
    return out


def shared(u, p, z, prod):
    """The shared expert's part for u (B, T, C)."""
    import jax

    h = jax.nn.silu(prod("btc,fc->btf", u, _f32(p["shared_gate_weight"]))) \
        * prod("btc,fc->btf", u, _f32(p["shared_up_weight"]))
    return prod("btf,cf->btc", h, _f32(p["shared_down_weight"]))


def mixer(x, p, kind, z, prod):
    """x + r Mixer(RMSNorm(x; g1)) → (x, the Mamba state after the last
    position or None)."""
    u = _rms_norm(x, p["ln1_gamma"], z["eps"])
    if kind == "ssm":
        out, h, _ = mamba(u, p, z, prod)
    else:
        out, h = attention(u, p, z, prod), None
    return x + z["r"] * out, h


def layer(x, p, kind, z, prod):
    """One block on x (B, T, C) with its leaves ``p`` (by name,
    unstacked) → x."""
    x, _ = mixer(x, p, kind, z, prod)
    u = _rms_norm(x, p["ln2_gamma"], z["eps"])
    return x + z["r"] * (routed(u, p, z, prod) + shared(u, p, z, prod))


@functools.lru_cache(maxsize=None)
def _jitted(key, prod):
    import jax

    z = dict(key)
    return {
        "ssm": jax.jit(lambda x, p: layer(x, p, "ssm", z, prod)),
        "attn": jax.jit(lambda x, p: layer(x, p, "attn", z, prod)),
        "head": jax.jit(lambda x, g, w: prod(
            "btc,vc->btv", _rms_norm(x, g, z["eps"]), _f32(w)) / z["s"])}


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
    x = z["e"] * _f32(params["embed_weight"][ids])
    for i, kind in enumerate(kinds(config)):
        x = parts[kind](x, layer_leaves(params, config, i))
    return np.asarray(parts["head"](x, params["lnf_gamma"],
                                    params["embed_weight"]))

"""GPT-2 (Radford et al. 2019), the plain reference.

The forward pass, the next-token loss and its gradients in
straightforward ``jax.numpy``: float32, every product at
``Precision.HIGHEST``, no kernels, no cache, no scan.  It follows the
published model (pre-LN blocks, learned positions, tanh GELU
``gelu_new``, a head tied to the token embedding) with the sizes of the
configuration's ``config.json`` keys.  It imports nothing of the program
and makes its own weights from the seed.

Departures, each noted where it is made: the packed ``c_attn`` weight is
laid out ``(3*n_embd, n_embd)`` (out, in) as the program's
``qkv_stack_weight`` is, where the published checkpoint stores
``(in, out)``: the same map, written transposed.

``product`` is the one place a matrix product is made, so that the
control (``low_precision``) can put the same model through float8
operands: the precision below bfloat16 that the contract names.

Training is worked layer by layer and in blocks of rows, so that the
gradients of a whole batch fit beside nothing else: the forward pass
keeps each layer's input, and the backward pass takes one layer's vjp at
a time.
"""

import functools
import math

LEAVES = ("tok_embed_weight", "pos_embed_weight",
          "qkv_stack_weight", "qkv_stack_bias",
          "proj_stack_weight", "proj_stack_bias",
          "ffn1_stack_weight", "ffn1_stack_bias",
          "ffn2_stack_weight", "ffn2_stack_bias",
          "ln1_stack_gamma", "ln1_stack_beta",
          "ln2_stack_gamma", "ln2_stack_beta",
          "lnf_gamma", "lnf_beta")
PER_LAYER = LEAVES[2:14]


def sizes(config):
    C, L, H = config["n_embd"], config["n_layer"], config["n_head"]
    if C % H:
        raise ValueError(f"gpt2: n_embd {C} is no multiple of n_head {H}")
    return (C, L, H, config["n_positions"], config["vocab_size"],
            config.get("n_inner") or 4 * C)


def param_spec(config):
    """(name, shape, init) of every leaf.  Names are the suffixes of the
    program's parameter names; inits are GPT-2's own: normal(0.02), the
    two residual projections scaled by 1/sqrt(2 n_layer), zero biases,
    unit LayerNorm gains."""
    C, L, H, P, V, F = sizes(config)
    std = config.get("initializer_range", 0.02)
    w, r = f"normal:{std}", f"normal:{std / math.sqrt(2 * L)}"
    return [("tok_embed_weight", (V, C), w),
            ("pos_embed_weight", (P, C), w),
            ("qkv_stack_weight", (L, 3 * C, C), w),
            ("qkv_stack_bias", (L, 3 * C), "zeros"),
            ("proj_stack_weight", (L, C, C), r),
            ("proj_stack_bias", (L, C), "zeros"),
            ("ffn1_stack_weight", (L, F, C), w),
            ("ffn1_stack_bias", (L, F), "zeros"),
            ("ffn2_stack_weight", (L, C, F), r),
            ("ffn2_stack_bias", (L, C), "zeros"),
            ("ln1_stack_gamma", (L, C), "ones"),
            ("ln1_stack_beta", (L, C), "zeros"),
            ("ln2_stack_gamma", (L, C), "ones"),
            ("ln2_stack_beta", (L, C), "zeros"),
            ("lnf_gamma", (C,), "ones"),
            ("lnf_beta", (C,), "zeros")]


# -- the one product -----------------------------------------------------------

def product(spec, a, b):
    import jax
    import jax.numpy as jnp

    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _to_f8(x):
    """Round to float8 e4m3 under one scale per tensor, and widen.  The
    rounding is straight-through for gradients: cotangents stay float32
    (an unscaled cotangent would underflow float8 to zero)."""
    import jax
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def low_precision(spec, a, b):
    """The control's product: both operands through float8 e4m3."""
    return product(spec, _to_f8(a), _to_f8(b))


# -- the model -----------------------------------------------------------------

def _layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def embed(tok, pos, ids):
    return tok[ids] + pos[None, :ids.shape[1]]


def block(x, p, n_head, eps, prod):
    """One pre-LN block on (B, T, C); ``p`` maps PER_LAYER names to this
    layer's slices."""
    import jax
    import jax.numpy as jnp

    B, T, C = x.shape
    D = C // n_head
    h = _layer_norm(x, p["ln1_stack_gamma"], p["ln1_stack_beta"], eps)
    qkv = prod("btc,gc->btg", h, p["qkv_stack_weight"]) \
        + p["qkv_stack_bias"]
    q, k, v = (t.reshape(B, T, n_head, D).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    s = prod("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = prod("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    a = a.transpose(0, 2, 1, 3).reshape(B, T, C)
    x = x + prod("btg,cg->btc", a, p["proj_stack_weight"]) \
        + p["proj_stack_bias"]
    h = _layer_norm(x, p["ln2_stack_gamma"], p["ln2_stack_beta"], eps)
    h = _gelu_new(prod("btc,fc->btf", h, p["ffn1_stack_weight"])
                  + p["ffn1_stack_bias"])
    return x + prod("btf,cf->btc", h, p["ffn2_stack_weight"]) \
        + p["ffn2_stack_bias"]


def head(x, lnf_g, lnf_b, tok, eps, prod):
    return prod("btc,vc->btv", _layer_norm(x, lnf_g, lnf_b, eps), tok)


def _widen(params):
    import jax.numpy as jnp

    return {k: v.astype(jnp.float32) for k, v in params.items()}


def _layer_of(params, l):
    return {k: params[k][l] for k in PER_LAYER}


@functools.lru_cache(maxsize=None)
def _jitted(n_head, eps, prod):
    import jax

    blk = jax.jit(lambda x, p: block(x, p, n_head, eps, prod))
    hd = jax.jit(lambda x, g, b, tok: head(x, g, b, tok, eps, prod))
    return blk, hd, jax.jit(embed)


def logits(params, ids, config, prod=product):
    """(B, T, vocab) float32 logits of (B, T) int ids."""
    C, L, H, P, V, F = sizes(config)
    blk, hd, emb = _jitted(H, config.get("layer_norm_epsilon", 1e-5), prod)
    params = _widen(params)
    x = emb(params["tok_embed_weight"], params["pos_embed_weight"], ids)
    for l in range(L):
        x = blk(x, _layer_of(params, l))
    return hd(x, params["lnf_gamma"], params["lnf_beta"],
              params["tok_embed_weight"])


def _token_loss_sum(lg, ids):
    """Summed next-token cross-entropy of (B, T, V) logits."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(lg[:, :-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


@functools.lru_cache(maxsize=None)
def _grad_parts(n_head, eps, prod):
    import jax

    def head_loss(x, g, b, tok, ids):
        return _token_loss_sum(head(x, g, b, tok, eps, prod), ids)

    def block_vjp(x, p, ct):
        _, vjp = jax.vjp(lambda x, p: block(x, p, n_head, eps, prod), x, p)
        return vjp(ct)

    def embed_vjp(tok, pos, ids, ct):
        _, vjp = jax.vjp(lambda t, p: embed(t, p, ids), tok, pos)
        return vjp(ct)

    return (jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1, 2, 3))),
            jax.jit(block_vjp), jax.jit(embed_vjp))


def loss_and_grads(params, ids, config, prod=product, rows=2):
    """Mean next-token loss of (B, T) ids and its gradients by leaf, in
    float32, worked in blocks of ``rows`` rows and layer by layer."""
    import jax.numpy as jnp

    C, L, H, P, V, F = sizes(config)
    eps = config.get("layer_norm_epsilon", 1e-5)
    blk, _, emb = _jitted(H, eps, prod)
    head_vg, block_vjp, embed_vjp = _grad_parts(H, eps, prod)
    params = _widen(params)
    n_tokens = ids.shape[0] * (ids.shape[1] - 1)
    total = 0.0
    grads = {k: jnp.zeros_like(params[k]) for k in LEAVES
             if k not in PER_LAYER}
    by_layer = [None] * L
    for r0 in range(0, ids.shape[0], rows):
        part = ids[r0:r0 + rows]
        xs = [emb(params["tok_embed_weight"], params["pos_embed_weight"],
                  part)]
        for l in range(L):
            xs.append(blk(xs[-1], _layer_of(params, l)))
        loss, (ct, g_g, g_b, g_tok) = head_vg(
            xs[-1], params["lnf_gamma"], params["lnf_beta"],
            params["tok_embed_weight"], part)
        total = total + loss
        grads["lnf_gamma"] += g_g
        grads["lnf_beta"] += g_b
        grads["tok_embed_weight"] += g_tok
        for l in reversed(range(L)):
            ct, g_p = block_vjp(xs[l], _layer_of(params, l), ct)
            by_layer[l] = g_p if by_layer[l] is None else {
                k: by_layer[l][k] + g_p[k] for k in PER_LAYER}
        g_tok, g_pos = embed_vjp(params["tok_embed_weight"],
                                 params["pos_embed_weight"], part, ct)
        grads["tok_embed_weight"] += g_tok
        grads["pos_embed_weight"] += g_pos
    for k in PER_LAYER:
        grads[k] = jnp.stack([g[k] for g in by_layer])
    return total / n_tokens, {k: g / n_tokens for k, g in grads.items()}

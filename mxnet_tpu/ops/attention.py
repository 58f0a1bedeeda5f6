"""Attention ops.

Reference parity: src/operator/contrib/transformer.cu (≥1.5 interleaved
self-attention GEMM ops: interleaved_matmul_selfatt_qk / valatt, plus
multi-head attention support ops).  TPU-first: attention is expressed as
einsums XLA maps straight onto the MXU; the sequence-parallel variants
(ring / ulysses, parallel/ring.py) plug in via ``impl=``; the Pallas
flash-attention kernel (ops/pallas_attention.py) takes over for long
sequences on real TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

_NEG = -1e30


@register("scaled_dot_product_attention", random=True,
          mode_dependent=True)
def scaled_dot_product_attention(query, key, value, mask=None,
                                 causal=False, scale=None, impl="dense",
                                 dropout_p=0.0, _key=None,
                                 _is_training=True):
    """q,k,v: (B, H, T, D).  mask: broadcastable to (B, H, Tq, Tk), 1=keep.

    impl: 'dense' | 'ring' | 'ulysses' | 'flash' (the Pallas kernel:
    compiled on TPU, interpreted on the CPU).  mask/dropout are
    dense-path features; the sharded/fused impls reject them loudly
    instead of silently ignoring them.
    """
    if scale is None:
        scale = query.shape[-1] ** -0.5
    if impl != "dense" and (mask is not None or dropout_p > 0.0):
        raise NotImplementedError(
            f"attention impl={impl!r} supports only causal masking; "
            "explicit masks / attention dropout require impl='dense'")
    if impl == "ring":
        from ..parallel.ring import ring_attention

        return ring_attention(query, key, value, causal=causal,
                              scale=scale)
    if impl == "ulysses":
        from ..parallel.ring import ulysses_attention

        return ulysses_attention(query, key, value, causal=causal,
                                 scale=scale)
    if impl == "flash":
        return _flash_on_mesh(query, key, value, causal, scale)
    s = jnp.einsum("bhqd,bhkd->bhqk", query.astype(jnp.float32),
                   key.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        cmask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(cmask[None, None], s, _NEG)
    if mask is not None:
        s = jnp.where(mask.astype(bool), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0 and _is_training:
        keep = jax.random.bernoulli(_key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      value.astype(jnp.float32)).astype(query.dtype)


def _flash_on_mesh(q, k, v, causal, scale):
    """The Pallas kernel on each device's own shard.

    A compiled Pallas call is opaque to the SPMD partitioner, and JAX
    refuses one under a sharded jit on TPU ("Mosaic kernels cannot be
    automatically partitioned").  Attention is independent per (batch,
    head), so under
    the process default mesh (`parallel.shard_model` sets it) the call
    runs in a `shard_map` with batch over ``dp`` and heads over ``tp``
    — the layout the Megatron rules give q/k/v anyway — and needs no
    collective.  An axis that does not divide its dim stays out of the
    spec (replicated), like `parallel.sharding.constrain`.
    """
    from jax.sharding import PartitionSpec

    from ..parallel.mesh import DP, TP, default_mesh
    from .pallas_attention import _use_interpret, flash_attention

    mesh = default_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal=causal, scale=scale)

    def axis_for(name, dim):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and dim % n == 0 else None

    spec = PartitionSpec(axis_for(DP, q.shape[0]),
                         axis_for(TP, q.shape[1]), None, None)
    # interpret-mode pallas_call trips the vma check inside a manual
    # region (parallel/ring.py has the same switch); on TPU the kernel's
    # out_shapes carry the vma and the check stays on
    check = not _use_interpret()

    def local(q, k, v):
        vma = tuple(jax.typeof(q).vma) if check else ()
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               vma=vma)

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=check)(q, k, v)


def _split_heads(x, num_heads):
    B, T, C = x.shape
    return x.reshape(B, T, num_heads, C // num_heads).transpose(0, 2, 1, 3)


def _merge_heads(x):
    B, H, T, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)


@register("multi_head_attention")
def multi_head_attention(query, key, value, qkv_weight=None, qkv_bias=None,
                         proj_weight=None, proj_bias=None, num_heads=1,
                         mask=None, causal=False, impl="dense"):
    """Full fused MHA on (B, T, C) inputs with packed qkv projection
    (reference: the contrib/transformer interleaved kernels fused exactly
    this to avoid three GEMMs — one packed MXU matmul here)."""
    # the scopes name the block's parts in the compiled program and in
    # every device trace (metadata only)
    with jax.named_scope("attn_qkv"):
        if qkv_weight is not None:
            if query is key and key is value:
                qkv = jnp.einsum("btc,gc->btg", query, qkv_weight)
                if qkv_bias is not None:
                    qkv = qkv + qkv_bias
                q, k, v = jnp.split(qkv, 3, axis=-1)
            else:
                wq, wk, wv = jnp.split(qkv_weight, 3, axis=0)
                bq = bk = bv = None
                if qkv_bias is not None:
                    bq, bk, bv = jnp.split(qkv_bias, 3, axis=0)
                q = jnp.einsum("btc,gc->btg", query, wq)
                k = jnp.einsum("btc,gc->btg", key, wk)
                v = jnp.einsum("btc,gc->btg", value, wv)
                if bq is not None:
                    q, k, v = q + bq, k + bk, v + bv
        else:
            q, k, v = query, key, value
        qh = _split_heads(q, num_heads)
        kh = _split_heads(k, num_heads)
        vh = _split_heads(v, num_heads)
    with jax.named_scope("flash" if impl == "flash" else "attn"):
        out = scaled_dot_product_attention(qh, kh, vh, mask=mask,
                                           causal=causal, impl=impl)
    with jax.named_scope("attn_out"):
        out = _merge_heads(out)
        if proj_weight is not None:
            out = jnp.einsum("btg,cg->btc", out, proj_weight)
            if proj_bias is not None:
                out = out + proj_bias
    return out


# reference contrib op names (src/operator/contrib/transformer.cu): the
# interleaved projections as explicit ops for API parity
@register("_contrib_interleaved_matmul_selfatt_qk",
          aliases=("interleaved_matmul_selfatt_qk",))
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1):
    """Input (T, B, 3C) interleaved qkv → scores (B*heads, T, T)."""
    T, B, C3 = queries_keys_values.shape
    C = C3 // 3
    x = queries_keys_values.reshape(T, B, heads, 3 * (C // heads))
    q, k, _ = jnp.split(x, 3, axis=-1)
    q = q.transpose(1, 2, 0, 3).reshape(B * heads, T, C // heads)
    k = k.transpose(1, 2, 0, 3).reshape(B * heads, T, C // heads)
    scale = (C // heads) ** -0.5
    return jnp.einsum("nqd,nkd->nqk", q, k) * scale


@register("_contrib_interleaved_matmul_selfatt_valatt",
          aliases=("interleaved_matmul_selfatt_valatt",))
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention,
                                      heads=1):
    """attention (B*heads, T, T) × interleaved values → (T, B, C)."""
    T, B, C3 = queries_keys_values.shape
    C = C3 // 3
    x = queries_keys_values.reshape(T, B, heads, 3 * (C // heads))
    _, _, v = jnp.split(x, 3, axis=-1)
    v = v.transpose(1, 2, 0, 3).reshape(B * heads, T, C // heads)
    out = jnp.einsum("nqk,nkd->nqd", attention, v)
    out = out.reshape(B, heads, T, C // heads).transpose(2, 0, 1, 3)
    return out.reshape(T, B, C)


@register("scan_transformer_encoder", mode_dependent=True, random=True)
def scan_transformer_encoder(data, qkv_w, qkv_b, proj_w, proj_b,
                             ffn1_w, ffn1_b, ffn2_w, ffn2_b,
                             ln1_g, ln1_b, ln2_g, ln2_b, lnf_g, lnf_b,
                             qkv_lora_a=None, qkv_lora_b=None,
                             num_heads=1, dropout=0.0,
                             activation="gelu", impl="dense",
                             causal=False, remat=False, lora_scale=1.0,
                             _is_training=True, _key=None):
    """Pre-LN transformer trunk as ONE lax.scan over stacked (L, ...)
    per-layer parameters.

    TPU-first compile-time scalability: N separate layer blocks emit an
    HLO that grows linearly with depth (a BERT-base whole-step compile
    through the AOT helper takes tens of minutes); scanning one layer
    body over parameter stacks compiles the layer once.  Same math as
    gluon's TransformerEncoder (packed-qkv MHA + pre-LN FFN),
    equivalence-tested in tests/test_model_zoo.py.

    LoRA fine-tuning (Hu et al. 2021, beyond reference): optional
    ``qkv_lora_a`` (L, r, U) / ``qkv_lora_b`` (L, 3U, r) stacks add a
    rank-r update to each layer's packed qkv weight — the effective
    weight ``qkv + lora_scale·(B@A)`` is formed per scan step (one
    (3U,r)x(r,U) matmul, transient), so the trunk stays ONE scanned
    layer and the adapters train through the product while the base
    stacks stay frozen (grad_req='null').
    """
    from .nn import layer_norm

    use_drop = bool(dropout) and _is_training
    use_lora = qkv_lora_a is not None and qkv_lora_b is not None
    L = qkv_w.shape[0]

    def body(carry, per_layer):
        (qw, qb, pw, pb, f1w, f1b, f2w, f2b, g1, b1, g2, b2) = \
            per_layer[:12]
        rest = list(per_layer[12:])
        if use_lora:
            la, lb = rest[0], rest[1]
            rest = rest[2:]
            qw = (qw + lora_scale * jnp.matmul(
                lb, la, preferred_element_type=jnp.float32)
                .astype(qw.dtype))
        key = rest[0] if use_drop else None
        x = carry
        h = layer_norm(x, g1, b1)
        attn = multi_head_attention(
            h, h, h, qkv_weight=qw, qkv_bias=qb, proj_weight=pw,
            proj_bias=pb, num_heads=num_heads, impl=impl,
            causal=causal)
        if use_drop:
            k1, k2 = jax.random.split(key)
            keep = 1.0 - dropout
            attn = jnp.where(
                jax.random.bernoulli(k1, keep, attn.shape),
                attn / keep, 0.0).astype(attn.dtype)
        x = x + attn
        with jax.named_scope("mlp"):
            h = layer_norm(x, g2, b2)
            h = jnp.einsum("btc,hc->bth", h, f1w,
                           preferred_element_type=jnp.float32) \
                .astype(x.dtype) + f1b
            h = jax.nn.gelu(h) if activation == "gelu" \
                else jnp.maximum(h, 0)
            h = (jnp.einsum("bth,ch->btc", h, f2w,
                            preferred_element_type=jnp.float32)
                 .astype(x.dtype) + f2b)
        if use_drop:
            h = jnp.where(jax.random.bernoulli(k2, keep, h.shape),
                          h / keep, 0.0).astype(h.dtype)
        return x + h, None

    xs = (qkv_w, qkv_b, proj_w, proj_b, ffn1_w, ffn1_b, ffn2_w,
          ffn2_b, ln1_g, ln1_b, ln2_g, ln2_b)
    if use_lora:
        xs = xs + (qkv_lora_a, qkv_lora_b)
    if use_drop:
        xs = xs + (jax.random.split(_key, L),)
    from .. import remat as _remat

    pol = _remat.trunk_policy(remat)
    every = pol[1] if pol is not None and pol[0] == "every" else None
    if every is not None and (L % every != 0 or every == 1):
        # non-divisible chunking would need a ragged tail scan;
        # degrade to per-layer (every=1 IS per-layer)
        pol = ("layer", None)
        every = None
    if every is not None:
        # chunked rematerialization (remat.py 'save_every_k:N'): scan
        # L/N checkpointed chunks of N layers each — the backward keeps
        # only chunk-boundary carries resident and recomputes inside a
        # chunk.  The inner scan runs the SAME body on the same values
        # as the flat scan, so the math is bitwise-unchanged.
        def chunk(carry, per_chunk):
            out, _ = jax.lax.scan(body, carry, per_chunk)
            return out, None

        chunk = jax.checkpoint(chunk)
        xs = tuple(x.reshape((L // every, every) + x.shape[1:])
                   for x in xs)
        out, _ = jax.lax.scan(chunk, data, xs)
        return layer_norm(out, lnf_g, lnf_b)
    if pol is not None:
        # per-layer rematerialization: the backward recomputes each
        # layer's activations from its carry — O(1) layers of
        # activations resident instead of O(L) (the long-context knob;
        # composes with the reference's MXNET_BACKWARD_DO_MIRROR story)
        body = jax.checkpoint(body, policy=pol[1])
    out, _ = jax.lax.scan(body, data, xs)
    return layer_norm(out, lnf_g, lnf_b)

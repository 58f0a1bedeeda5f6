"""Decoder-only (GPT-style) language model family.

Beyond-reference breadth: the 2018-era reference zoo has no decoder-only
LM (its nearest is example/rnn word_lm and the NMT Transformer decoder);
this family completes the transformer spread — encoder (BERT),
encoder-decoder (transformer.py NMT), decoder-only (here) — on the same
TPU-first trunk primitives:

- causal attention via the SAME packed-qkv MHA op (flash/ring/ulysses
  ``attention_impl`` all apply — the long-context causal config);
- ``scan_layers=True`` compiles the trunk as one scanned layer
  (compile-time scalability, same as BERT's bench config);
- the LM head is WEIGHT-TIED to the token embedding (standard GPT-2
  parameterization): one (vocab, units) matrix serves both.
"""

from __future__ import annotations

from ...ops.sampling import _sample
from ..block import HybridBlock
from .. import nn
from ._decoder_program import DecoderProgram
from .bert import ScanTransformerEncoder, TransformerEncoder


class GPTModel(HybridBlock):
    """Token+position embedding → causal pre-LN trunk → tied-head
    logits.  Input: (B, T) int token ids; output: (B, T, vocab)."""

    def __init__(self, vocab_size=50257, units=768, num_layers=12,
                 num_heads=12, max_length=1024, hidden_size=None,
                 dropout=0.1, attention_impl="dense", scan_layers=False,
                 remat=False, lora_rank=0, lora_alpha=None, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._vocab = vocab_size
        self._max_length = max_length
        self._dropout = dropout
        if lora_rank and not scan_layers:
            raise ValueError("GPTModel: lora_rank requires "
                             "scan_layers=True (adapters live in the "
                             "scanned trunk)")
        with self.name_scope():
            self.tok_embed_weight = self.params.get(
                "tok_embed_weight", shape=(vocab_size, units))
            self.pos_embed_weight = self.params.get(
                "pos_embed_weight", shape=(max_length, units))
            if scan_layers:
                self.encoder = ScanTransformerEncoder(
                    num_layers, units, num_heads, hidden_size, dropout,
                    attention_impl, causal=True, remat=remat,
                    lora_rank=lora_rank, lora_alpha=lora_alpha,
                    prefix="trunk_")
            else:
                self.encoder = TransformerEncoder(
                    num_layers, units, num_heads, hidden_size, dropout,
                    attention_impl, causal=True, prefix="trunk_")
            if dropout:
                self.drop = nn.Dropout(dropout)

    def hybrid_forward(self, F, ids, tok_embed_weight,
                       pos_embed_weight):
        # stored sizes keep the op attrs static ints under the trace
        x = F.Embedding(ids, tok_embed_weight, input_dim=self._vocab,
                        output_dim=self._units)
        T = ids.shape[1]
        x = x + F.slice_axis(pos_embed_weight, axis=0, begin=0, end=T)
        if self._dropout:
            x = self.drop(x)
        h = self.encoder(x)                       # (B, T, C)
        # tied head: logits = h @ embedᵀ — one big MXU matmul
        # (kwarg shape= so the symbolic trace maps it as an attribute)
        flat = F.reshape(h, shape=(-1, self._units))
        logits = F.dot(flat, tok_embed_weight, transpose_b=True)
        return F.reshape(logits, shape=(-1, T, self._vocab))

    def decoder_program(self, dtype=None, mesh=None, tp_axis="tp"):
        """What `serving.ServingEngine` serves this family through."""
        return GPTDecoderProgram(self, dtype=dtype, mesh=mesh,
                                 tp_axis=tp_axis)


def _lm_loss_pure(logits, labels):
    """Shifted next-token cross-entropy; labels < 0 are ignored —
    the shift plus the zoo's shared masked-CE."""
    from .bert import masked_token_ce

    return masked_token_ce(logits[:, :-1], labels[:, 1:])


class GPTLMLoss(HybridBlock):
    """Causal LM loss: mean next-token NLL over valid (>= 0) labels.
    Call with (logits, token_ids) — the shift happens inside."""

    def hybrid_forward(self, F, logits, labels):
        from ...ndarray.register import invoke_simple

        return invoke_simple(_lm_loss_pure, (logits, labels))


def _windowed_last_logits(model, flat, nd_mod, np_mod):
    """Last-position logits for (N, T) token rows through the model's
    fixed max_length window: right-pad to W (one compiled shape — causal
    masking hides the pad) and read position cur-1.  Shared by
    generate() and beam_generate()."""
    W = model._max_length
    ctx = flat[:, -W:]
    cur = ctx.shape[1]
    if cur < W:
        ctx = np_mod.concatenate(
            [ctx, np_mod.zeros((ctx.shape[0], W - cur), np_mod.int32)],
            axis=1)
    logits = model(nd_mod.array(ctx.astype(np_mod.float32))).asnumpy()
    return logits[:, cur - 1]


def generate(model, ids, max_new_tokens=16, temperature=None, rng=None):
    """Greedy (or sampled) decode by full-recompute per step — the
    simple deploy path; ids: (B, T0) NDArray of seed tokens.

    The context is RIGHT-padded to max_length so every step runs at ONE
    shape (one compile); causal masking makes positions > cur-1
    invisible to the read position, so the pad content never matters."""
    import numpy as np

    from ... import ndarray as nd

    out = ids.asnumpy().astype(np.int32)
    for _ in range(max_new_tokens):
        last = _windowed_last_logits(model, out, nd, np)
        nxt = _sample(last, temperature, rng)
        out = np.concatenate([out, nxt[:, None]], axis=1)
    return nd.array(out.astype(np.float32))


def gpt2_small(**kwargs):
    """GPT-2 124M config."""
    kwargs.setdefault("vocab_size", 50257)
    kwargs.setdefault("units", 768)
    kwargs.setdefault("num_layers", 12)
    kwargs.setdefault("num_heads", 12)
    kwargs.setdefault("max_length", 1024)
    return GPTModel(**kwargs)


def gpt2_medium(**kwargs):
    kwargs.setdefault("vocab_size", 50257)
    kwargs.setdefault("units", 1024)
    kwargs.setdefault("num_layers", 24)
    kwargs.setdefault("num_heads", 16)
    kwargs.setdefault("max_length", 1024)
    return GPTModel(**kwargs)


def gpt_tiny(**kwargs):
    """Test-sized config."""
    kwargs.setdefault("vocab_size", 128)
    kwargs.setdefault("units", 32)
    kwargs.setdefault("num_layers", 2)
    kwargs.setdefault("num_heads", 2)
    kwargs.setdefault("max_length", 64)
    kwargs.setdefault("dropout", 0.0)
    return GPTModel(**kwargs)


# -- KV-cache incremental decoding ---------------------------------------------
#
# TPU-native inference engine for the decoder-only family: a STATIC
# (L, B, H, Dh, W) key/value cache written in place at a traced position
# (ops/cache_write.py), so the per-token step is ONE compiled program doing
# O(W) attention instead of recomputing the O(W²) trunk (the role the
# reference's inference-time BucketingModule/exec cache plays for RNNs).


#: the scanned-trunk parameter stacks every cached/serving decoder runs on
STACK_NAMES = ("qkv_stack_weight", "qkv_stack_bias",
               "proj_stack_weight", "proj_stack_bias",
               "ffn1_stack_weight", "ffn1_stack_bias",
               "ffn2_stack_weight", "ffn2_stack_bias",
               "ln1_stack_gamma", "ln1_stack_beta",
               "ln2_stack_gamma", "ln2_stack_beta")


def extract_decoder_stacks(model):
    """Pull a GPTModel's trunk parameters into (L, ...) stacks, for scan
    and unstacked trunks alike.  Returns
    ``(stacks, (lnf_gamma, lnf_beta), tok_embed, pos_embed, num_heads,
    activation)`` — the single weight-extraction home: what
    GPTDecoderProgram, and through it CachedDecoder and the serving
    tier (mxnet_tpu/serving/engine.py), build their layout from."""
    params = dict(model.collect_params())

    def get1(suffix):
        ks = [k for k in params if k.endswith(suffix)]
        assert len(ks) == 1, (suffix, ks)
        return params[ks[0]].data()._data

    if any(k.endswith("qkv_stack_weight") for k in params):
        stacks = {nm: get1(nm) for nm in STACK_NAMES}
        lnf_g, lnf_b = get1("lnf_gamma"), get1("lnf_beta")
        num_heads = model.encoder._num_heads
        act = model.encoder._activation
    else:
        enc = model.encoder
        layers = list(enc.layers._children.values())
        num_heads = layers[0]._num_heads
        act = layers[0]._activation

        def stacked(name):
            import jax.numpy as jnp

            return jnp.stack([
                getattr(l, name).data()._data for l in layers])

        stacks = {
            "qkv_stack_weight": stacked("qkv_weight"),
            "qkv_stack_bias": stacked("qkv_bias"),
            "proj_stack_weight": stacked("proj_weight"),
            "proj_stack_bias": stacked("proj_bias"),
            "ffn1_stack_weight": stacked("ffn1_weight"),
            "ffn1_stack_bias": stacked("ffn1_bias"),
            "ffn2_stack_weight": stacked("ffn2_weight"),
            "ffn2_stack_bias": stacked("ffn2_bias"),
        }
        import jax.numpy as jnp

        stacks["ln1_stack_gamma"] = jnp.stack(
            [l.ln1.gamma.data()._data for l in layers])
        stacks["ln1_stack_beta"] = jnp.stack(
            [l.ln1.beta.data()._data for l in layers])
        stacks["ln2_stack_gamma"] = jnp.stack(
            [l.ln2.gamma.data()._data for l in layers])
        stacks["ln2_stack_beta"] = jnp.stack(
            [l.ln2.beta.data()._data for l in layers])
        lnf_g = enc.ln_f.gamma.data()._data
        lnf_b = enc.ln_f.beta.data()._data

    return (stacks, (lnf_g, lnf_b), get1("tok_embed_weight"),
            get1("pos_embed_weight"), num_heads, act)


def stacks_from_state(state):
    """Rebuild (stacks, lnf, tok, pos) from a flat name→array state dict
    (scanned-trunk convention: scan_layers=True param names)."""
    import jax.numpy as jnp

    from ...base import MXNetError

    def get1(suffix):
        ks = [k for k in state if k.endswith(suffix)]
        if len(ks) != 1:
            raise MXNetError(
                f"serving reload: expected exactly one param ending "
                f"{suffix!r} in the checkpoint state, found {ks}")
        return jnp.asarray(state[ks[0]])

    if not any(k.endswith("qkv_stack_weight") for k in state):
        raise MXNetError(
            "serving reload: checkpoint state lacks the scanned-trunk "
            "(*_stack_*) parameter convention; save the model with "
            "scan_layers=True (serving.state_for_serving) or reload "
            "from a live model via reload_from_model")
    stacks = {nm: get1(nm) for nm in STACK_NAMES}
    return (stacks, (get1("lnf_gamma"), get1("lnf_beta")),
            get1("tok_embed_weight"), get1("pos_embed_weight"))


class GPTDecoderProgram(DecoderProgram):
    """GPT's decoder program for `serving.ServingEngine`
    (docs/serving.md, `_decoder_program.py`).

    The cache is one ``(L, B, H, Dh, W)`` pair, stage-major like the
    ``*_stack_*`` weights and position-minor, which is how a v5e stores
    a head under 128 wide whatever the logical order (so a kernel sees
    the buffer as it lies); the layer loop *carries* it whole: a layer
    writes its new ``(B, H, Dh, S)`` rows into the stack
    (`ops/cache_write.py`) and attends over its own slice of it: a
    prefill block over the whole window, a decode step through
    `ops/cache_attention.py`, each row to its own length.  Under a
    ``mesh`` the weight stacks follow the Megatron column/row split of
    TRANSFORMER_TP_RULES and the cache shards on its head axis
    (parallel/sharding.serving_cache_sharding).  The weights are a
    packed tuple of this family's own (`_prepare`), not the parameters'
    buffers.
    """

    def __init__(self, model, dtype=None, mesh=None, tp_axis="tp"):
        from ...base import MXNetError

        super().__init__(model, dtype, mesh, tp_axis)
        (stacks, lnf, tok, pos, self._H,
         self._act) = extract_decoder_stacks(model)
        self._C = int(tok.shape[1])
        self._L = int(stacks["qkv_stack_weight"].shape[0])
        # what a reloaded model must share beyond its shapes
        self.signature = (self._H, self._act)
        if mesh is not None:
            n_tp = mesh.shape[tp_axis]
            F = int(stacks["ffn1_stack_weight"].shape[1])
            if self._H % n_tp or F % n_tp:
                raise MXNetError(
                    f"ServingEngine: tp axis size {n_tp} must divide "
                    f"num_heads={self._H} and ffn hidden={F}")
        self._w = self._prepare(stacks, lnf, tok, pos)

    # -- weights ---------------------------------------------------------------

    def _shard(self, arr, spec):
        if self._mesh is None:
            return arr
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(arr,
                              NamedSharding(self._mesh, P(*spec)))

    def _prepare(self, stacks, lnf, tok, pos):
        """Head-/hidden-major restructure + serving dtype + tp placement
        (Megatron column-parallel qkv/ffn1, row-parallel proj/ffn2),
        produced as a flat argument tuple so the compiled programs take
        weights as inputs — the hot-reload contract."""
        s = dict(stacks)
        if self._dtype is not None:
            for nm in ("qkv_stack_weight", "proj_stack_weight",
                       "ffn1_stack_weight", "ffn2_stack_weight"):
                s[nm] = s[nm].astype(self._dtype)
            tok = tok.astype(self._dtype)
            pos = pos.astype(self._dtype)
        L, H, C = self._L, self._H, self._C
        Dh = C // H
        tp = self._tp_axis
        qkvw = self._shard(s["qkv_stack_weight"].reshape(L, 3, H, Dh, C),
                           (None, None, tp))
        qkvb = self._shard(s["qkv_stack_bias"].reshape(L, 3, H, Dh),
                           (None, None, tp))
        pwh = self._shard(s["proj_stack_weight"].reshape(L, C, H, Dh),
                          (None, None, tp))
        f1w = self._shard(s["ffn1_stack_weight"], (None, tp))
        f1b = self._shard(s["ffn1_stack_bias"], (None, tp))
        f2w = self._shard(s["ffn2_stack_weight"], (None, None, tp))
        rep = ()
        return (self._shard(tok, rep), self._shard(pos, rep),
                qkvw, qkvb, pwh, self._shard(s["proj_stack_bias"], rep),
                f1w, f1b, f2w, self._shard(s["ffn2_stack_bias"], rep),
                self._shard(s["ln1_stack_gamma"], rep),
                self._shard(s["ln1_stack_beta"], rep),
                self._shard(s["ln2_stack_gamma"], rep),
                self._shard(s["ln2_stack_beta"], rep),
                self._shard(lnf[0], rep), self._shard(lnf[1], rep))

    def weights(self):
        return self._w

    def _named(self, w):
        return w        # the packed tuple, as `body` unpacks it

    def _embedding(self):
        return self._w[0]

    def weights_from_state(self, state):
        """The weight tuple of an AsyncCheckpointer state dict
        (``serving.state_for_serving`` convention)."""
        from ...base import MXNetError

        stacks, lnf, tok, pos = stacks_from_state(state)
        got = tuple(stacks["qkv_stack_weight"].shape)
        want = (self._L, 3 * self._C, self._C)
        if got != want:
            raise MXNetError(
                f"serving reload: weight mismatch — qkv stack {got} vs "
                f"compiled {want}; a mismatched swap would force a "
                f"retrace on the request path")
        return self._prepare(stacks, lnf, tok, pos)

    # -- cache -----------------------------------------------------------------

    def cache_shapes(self, B):
        """(ck, cv): stage-major and position-minor (L, B, H, Dh, W)."""
        shape = (self._L, B, self._H, self._C // self._H, self.window)
        return [(shape, None), (shape, None)], []

    # -- the traced block step -------------------------------------------------

    def body(self, ctx, w, cache, toks):
        """cache = (ck, cv), each (L, B, H, Dh, W), donated.  Returns
        ((ck', cv'), logits (B, vocab)) at ``ctx.last``.  S = seq bucket
        for prefill, 1 for decode, where a row that wants no token
        attends to nothing."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ...ops.nn import layer_norm

        H, W = self._H, self.window
        Dh = self._C // H
        act = self._act
        pos, last, S = ctx.pos, ctx.last, ctx.S

        ck, cv = cache
        (tok_e, pos_e, qkvw, qkvb, pwh, pb, f1w, f1b, f2w, f2b,
         g1s, b1s, g2s, b2s, lnf_g, lnf_b) = w
        with jax.named_scope("serve.embed"):
            positions = pos[:, None] + jnp.arange(S)[None, :]  # (B, S)
            x = (jnp.take(tok_e, toks, axis=0) +
                 jnp.take(pos_e, positions, axis=0)
                 ).astype(jnp.float32)                     # (B, S, C)

        def layer(carry, per):
            x, ck, cv = carry
            (qw, qb, pw, pb_l, f1w_l, f1b_l, f2w_l, f2b_l,
             g1, b1, g2, b2, l) = per
            with jax.named_scope("serve.attn_qkv"):
                h = layer_norm(x, g1, b1)
                qkv = jnp.einsum("bsc,thdc->bsthd", h, qw) + qb
                qh = qkv[:, :, 0].swapaxes(1, 2)     # (B, H, S, Dh)
                kh = qkv[:, :, 1].transpose(0, 2, 3, 1)  # (B, H, Dh, S)
                vh = qkv[:, :, 2].transpose(0, 2, 3, 1)
            with jax.named_scope("serve.cache_write"):
                # row b's block at [l, b, :, :, pos[b]:pos[b] + S] and
                # nothing else (a start that would run past W is
                # clamped)
                ck, cv = ctx.write((ck, cv), (kh, vh), l, pos)
            with jax.named_scope("serve.attn"):
                # row b at block offset s may see cache slots
                # <= pos[b] + s (stale pad garbage beyond is invisible
                # — the overwrite-before-attend invariant)
                if ctx.decode:
                    # one position a row: (B, H, 1, Dh) is one query a
                    # key head, over the row's pos[b] + 1 positions (a
                    # row that wants no token: none, and zeros)
                    attn = ctx.attend(qh * (Dh ** -0.5), ck, cv, l)
                else:
                    ck_l = lax.dynamic_index_in_dim(ck, l, 0,
                                                    keepdims=False)
                    cv_l = lax.dynamic_index_in_dim(cv, l, 0,
                                                    keepdims=False)
                    scores = jnp.einsum("bhsd,bhdw->bhsw", qh, ck_l) \
                        * (Dh ** -0.5)
                    mask = jnp.arange(W)[None, None, :] <= \
                        (pos[:, None, None] +
                         jnp.arange(S)[None, :, None])     # (B, S, W)
                    scores = jnp.where(mask[:, None], scores, -1e30)
                    p = jax.nn.softmax(scores, axis=-1)
                    attn = jnp.einsum("bhsw,bhdw->bhsd", p, cv_l)
                attn = jnp.einsum("bhsd,chd->bsc", attn, pw) + pb_l
                x = x + attn
            with jax.named_scope("serve.mlp"):
                h = layer_norm(x, g2, b2)
                h = h @ f1w_l.T + f1b_l
                h = jax.nn.gelu(h) if act == "gelu" \
                    else jnp.maximum(h, 0)
                x = x + (h @ f2w_l.T + f2b_l)
            return (x, ck, cv), None

        # the cache is carried, not scanned: a scanned input is
        # sliced a layer at a time and a scanned output is a new
        # stacked buffer, which cost a copy of every layer's keys
        # and values each way
        per_layer = (qkvw, qkvb, pwh, pb, f1w, f1b, f2w, f2b,
                     g1s, b1s, g2s, b2s,
                     jnp.arange(ck.shape[0], dtype=jnp.int32))
        (x, ck2, cv2), _ = lax.scan(layer, (x, ck, cv), per_layer)
        with jax.named_scope("serve.head"):
            # the head reads one position a row: the last real token's
            h = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            logits = layer_norm(h, lnf_g, lnf_b) @ tok_e.T
        if self._mesh is not None:
            # pin the donated buffers' output layout to the input
            # layout, so the next AOT call sees identical shardings
            cache_ns = self._cache_sharding()
            ck2 = lax.with_sharding_constraint(ck2, cache_ns)
            cv2 = lax.with_sharding_constraint(cv2, cache_ns)
        return (ck2, cv2), logits


class CachedDecoder:
    """The model zoo's NDArray-in, NDArray-out cached decode: one
    uniform-length batch walked through the family's own serving step
    (``GPTDecoderProgram.step``, jitted once with the cache donated),
    the host picking every token with ``_sample``.  ``decode`` mirrors
    ``generate``'s sampling surface and gives its tokens.

    Scan and unstacked trunks alike; ``dtype=`` and ``mesh=`` (with a
    ``tp_axis`` mesh axis) are the program's: bf16 weight stacks, embed
    tables and cache with f32 accumulation, and the Megatron head/FFN
    split with the cache sharded on its head axis.

    No bucket table, batching, counters or spans: a server wants
    `serving.ServingEngine` (docs/serving.md), which compiles the same
    step ahead of time and feeds it on the device.
    """

    def __init__(self, model, mesh=None, tp_axis="tp", dtype=None):
        import jax

        self._program = GPTDecoderProgram(model, dtype=dtype, mesh=mesh,
                                          tp_axis=tp_axis)
        self._step_fn = jax.jit(self._program.step, donate_argnums=(1,))

    def decode(self, ids, max_new_tokens=16, temperature=None,
               rng=None, return_logits=False):
        """ids: (B, T0) NDArray seed; returns (B, T0+N) NDArray like
        generate(), at O(W) per new token.  The cache window is fixed:
        T0 + max_new_tokens must fit max_length (generate()'s sliding
        window has no cache to shift, so it has no such bound).

        With ``return_logits=True`` also returns the (N, B, vocab)
        pre-sampling logits stack (scoring / equivalence checks)."""
        import numpy as np

        from ... import ndarray as nd

        program = self._program
        out = ids.asnumpy().astype(np.int32)
        B, T0 = out.shape
        if T0 + max_new_tokens > program.window:
            raise ValueError(
                f"decode: {T0} seed + {max_new_tokens} new tokens "
                f"exceed the cache window max_length={program.window}; "
                "use generate() for sliding-window decoding")
        # The whole seed in one block step, right-padded to a
        # power-of-two bucket (log2(W) prefill programs, not one per
        # T0).  Pad garbage written at cache positions >= T0 is
        # harmless: position q only becomes attendable at the step
        # whose pos == q, and that same step overwrites q first.
        T0p = 8
        while T0p < T0:
            T0p *= 2
        toks = np.zeros((B, min(T0p, program.window)), np.int32)
        toks[:, :T0] = out
        w, cache = program.weights(), program.init_cache(B)
        pos = np.zeros(B, np.int32)
        last = np.full(B, T0 - 1, np.int32)
        lg = []
        for n in range(max_new_tokens):
            cache, logits = self._step_fn(w, cache, pos, last, toks)
            lg.append(np.asarray(logits))
            nxt = _sample(lg[-1], temperature, rng)
            out = np.concatenate([out, nxt[:, None]], axis=1)
            pos = np.full(B, T0 + n, np.int32)
            last, toks = np.zeros(B, np.int32), nxt[:, None]
        result = nd.array(out.astype(np.float32))
        if return_logits:
            stacked = np.stack(lg) if lg else \
                np.zeros((0, B, program.vocab), np.float32)
            return result, stacked
        return result


# -- pipeline-parallel parts ---------------------------------------------------

class GPTEmbedding(HybridBlock):
    """Token + position embedding front (pipeline prologue)."""

    def __init__(self, vocab_size, units, max_length, dropout=0.0,
                 **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._vocab = vocab_size
        self._dropout = dropout
        with self.name_scope():
            self.tok_embed_weight = self.params.get(
                "tok_embed_weight", shape=(vocab_size, units))
            self.pos_embed_weight = self.params.get(
                "pos_embed_weight", shape=(max_length, units))
            if dropout:
                self.drop = nn.Dropout(dropout)

    def hybrid_forward(self, F, ids, tok_embed_weight,
                       pos_embed_weight):
        x = F.Embedding(ids, tok_embed_weight, input_dim=self._vocab,
                        output_dim=self._units)
        x = x + F.slice_axis(pos_embed_weight, axis=0, begin=0,
                             end=ids.shape[1])
        if self._dropout:
            x = self.drop(x)
        return x


class GPTHead(HybridBlock):
    """Final LN + LM projection (pipeline epilogue).  UNTIED: the
    pipeline partitions prologue and epilogue parameters separately, so
    the single-model weight tying cannot span them."""

    def __init__(self, vocab_size, units, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln_f = nn.LayerNorm(in_channels=units)
            self.proj = nn.Dense(vocab_size, in_units=units,
                                 flatten=False)

    def hybrid_forward(self, F, x):
        return self.proj(self.ln_f(x))


def gpt_pipeline_parts(vocab_size=50257, units=768, num_layers=12,
                       num_heads=12, hidden_size=None, max_length=1024,
                       dropout=0.0, attention_impl="dense"):
    """(prologue, trunk stages, epilogue) for parallel.PipelineTrainer:
    a full causal LM as embedding + homogeneous causal layers + head
    (mirrors bert_pipeline_parts for the decoder-only family)."""
    from .bert import TransformerEncoderLayer

    embed = GPTEmbedding(vocab_size, units, max_length, dropout,
                         prefix="ppgptembed_")
    layers = [TransformerEncoderLayer(
        units, num_heads, hidden_size or 4 * units, dropout,
        attention_impl, causal=True, prefix=f"ppgptlayer{i}_")
        for i in range(num_layers)]
    head = GPTHead(vocab_size, units, prefix="ppgpthead_")
    return embed, layers, head


def beam_generate(model, ids, max_new_tokens=16, beam_size=4,
                  eos_id=None, alpha=0.6):
    """Beam-search continuation of a shared prompt (decoder-only analog
    of transformer.beam_search, same ``beam_loop`` core and GNMT length
    penalty).  ids: (B, T0) NDArray seed; returns
    (tokens (B, T0+N), scores (B,))."""
    import numpy as np

    from ... import autograd
    from ... import ndarray as nd
    from .transformer import beam_loop

    seed = ids.asnumpy().astype(np.int32)
    B = seed.shape[0]

    def score_last(flat):
        with autograd.predict_mode():
            return _windowed_last_logits(model, flat, nd, np)

    out, scores = beam_loop(score_last, B, beam_size, None, eos_id,
                            max_new_tokens, alpha, seed_beams=seed)
    return nd.array(out.astype(np.float32)), scores

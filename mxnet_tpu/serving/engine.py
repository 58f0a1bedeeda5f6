"""AOT bucketed serving engine for the decoder-only model zoo.

The training side captures the whole step as ONE donated jit program
(gluon/captured.py); this module applies the same discipline to the
request path.  Three properties, all pinned by tests/test_serving.py:

- **Zero retraces after warmup.**  Every (batch bucket × seq bucket)
  pair gets ONE ahead-of-time program via the same
  ``jit(...).lower(*avals).compile()`` path ``CapturedStep`` uses for
  the train step; requests are padded to the nearest bucket and run
  through the pre-compiled executable directly — the jit tracing
  machinery is never re-entered on the request path.  A module-level
  trace counter (incremented as a Python side effect inside the traced
  function, so it ticks exactly once per compile) makes the pin
  checkable: ``trace_count()`` must not move after ``warmup()``.
- **KV-cache decode.**  The per-layer key/value cache is laid out
  stage-major — ``(L, B, H, W, Dh)`` with L the scanned-trunk layer
  axis, matching the ``*_stack_*`` weight stacks
  (parallel/sharding.py TRANSFORMER_TP_RULES).  The pair is donated
  and the layer loop *carries* it whole: a layer writes its new
  ``(B, H, S, Dh)`` rows into the stack and attends over its own slice
  of it, so a step's outputs are its inputs' buffers with ``B x S``
  slots a layer changed, and only the two attention contractions read
  a whole layer (tests pin the aliasing and, with ``whole_layer_ops``,
  that the compiled decode program moves no layer-sized buffer).
  Before PR 25 the cache was a scanned input and output of the loop:
  donation handed the buffers back, but each layer of each step was
  sliced out, re-laid, written and copied into a new stack.  Prefill
  (S = seq bucket) and decode (S = 1) are separate bucketed programs
  of the SAME traced function.
- **Hot reload without recompile.**  Weights are *arguments* to the
  compiled programs, not closed-over constants: swapping in new
  weights (from a live model or an AsyncCheckpointer state dict) is an
  array replacement under a lock — no retrace, no dropped requests
  (serving/replica.py swaps between batches).

Unlike ``gpt.CachedDecoder`` (one uniform-length batch, scalar write
position), the step here takes a **per-row position vector**, so a
coalesced batch can mix prompt lengths: each row's cache writes land at
its own offset (one dynamic_update_slice a row) and its own causal mask.
Every op is row-independent (per-row LN / softmax / einsum rows), which
is what makes a coalesced batch bitwise equal to the same requests
served one-by-one through the same batch bucket — pad rows can never
leak into real rows.

Tensor-parallel serving (``mesh=``): weight stacks are head-/hidden-
reshaped and placed with NamedShardings following the Megatron
column/row split of TRANSFORMER_TP_RULES; the cache shards on its head
axis (parallel/sharding.serving_cache_sharding).
"""

from __future__ import annotations

import os
import re
import threading
import time

from ..base import MXNetError
from ..obs.spans import wall
from ..profiler import scope
from ..gluon.model_zoo.gpt import (STACK_NAMES, _sample,
                                   extract_decoder_stacks)

# -- counters (the retrace-free pin) -------------------------------------------

_LOCK = threading.Lock()
_TRACE_COUNT = 0      # ticks inside the traced fn: once per (re)trace
_COMPILE_COUNT = 0    # lower().compile() calls
_DISPATCH_COUNT = 0   # compiled-program invocations


def _mark_trace():
    global _TRACE_COUNT
    with _LOCK:
        _TRACE_COUNT += 1


def trace_count():
    return _TRACE_COUNT


def compile_count():
    return _COMPILE_COUNT


def dispatch_count():
    return _DISPATCH_COUNT


def reset_counters():
    global _TRACE_COUNT, _COMPILE_COUNT, _DISPATCH_COUNT
    with _LOCK:
        _TRACE_COUNT = _COMPILE_COUNT = _DISPATCH_COUNT = 0


# -- bucket policy -------------------------------------------------------------

def batch_buckets_from_env(default=(1, 2, 4, 8)):
    """MXTPU_SERVE_BUCKETS: comma-separated ascending batch buckets."""
    raw = os.environ.get("MXTPU_SERVE_BUCKETS")
    if not raw:
        return tuple(default)
    try:
        buckets = tuple(sorted({int(x) for x in raw.split(",") if x}))
    except ValueError:
        return tuple(default)
    return buckets or tuple(default)


def prefill_buckets_for(window, floor=8):
    """Power-of-two prefill sequence buckets up to the cache window —
    log2(W) programs cover every prompt length (the same policy
    CachedDecoder.decode uses for its chunked prefill)."""
    buckets, s = [], max(1, floor)
    while s < window:
        buckets.append(s)
        s *= 2
    buckets.append(window)
    return tuple(buckets)


def state_for_serving(model):
    """Flat host state dict ``{param_name: np.ndarray}`` — the serving
    checkpoint convention AsyncCheckpointer saves and
    ``ServingEngine.reload_from_state`` consumes."""
    import numpy as np

    return {name: np.asarray(p.data()._data)
            for name, p in model.collect_params().items()}


def _stacks_from_state(state):
    """Rebuild (stacks, lnf, tok, pos) from a flat name→array state dict
    (scanned-trunk convention: scan_layers=True param names)."""
    import jax.numpy as jnp

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


# -- reading a compiled program: what moves a layer of the cache ---------------

_HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                 "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
                 "u64": 8, "f64": 8}
_HLO_INSTR = re.compile(
    r"^\s+(ROOT\s+)?(%?[\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)$")


def whole_layer_ops(hlo_text, layer_bytes):
    """Names of the instructions of a compiled program's text
    (``compiled.as_text()``) that materialise ``layer_bytes`` or more by
    moving data: a ``copy`` or ``dynamic-slice`` with so large a result,
    a ``dynamic-update-slice`` with so large an *update* (its result is
    the whole buffer by definition, also when it writes in place), or a
    fusion whose root is one of those.  A slice read inside the fusion
    that consumes it materialises nothing and does not count."""
    # computation -> {instruction: (bytes, op, operands, called fusion)}
    comps, roots, fused, comp = {}, {}, set(), None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            # "%name (params) -> shape {" opens a computation
            comp = line.split("(")[0].replace("ENTRY", "").strip(" %") \
                if line.rstrip().endswith("{") else None
            if comp is not None:
                comps[comp] = {}
            continue
        m = _HLO_INSTR.match(line)
        if m is None or comp is None:
            continue
        root, instr, dtype, dims, op, rest = m.groups()
        instr = instr.lstrip("%")
        size = _HLO_ITEMSIZE.get(dtype, 4)
        for d in dims.split(","):
            size *= int(d) if d else 1
        called = re.search(r"calls=%?([\w.\-]+)", rest) \
            if op == "fusion" else None
        if called:
            fused.add(called.group(1))
        comps[comp][instr] = (
            size, op, re.findall(r"%([\w.\-]+)", rest.split("), ")[0]),
            called and called.group(1))
        if root:
            roots[comp] = instr

    def moved(comp, instr):
        size, op, operands, called = comps[comp][instr]
        if called in roots:
            return moved(called, roots[called])
        if op in ("copy", "dynamic-slice"):
            return size
        if op == "dynamic-update-slice" and operands[1] in comps[comp]:
            return comps[comp][operands[1]][0]
        return 0

    return [instr for comp, instrs in comps.items() if comp not in fused
            for instr in instrs if moved(comp, instr) >= layer_bytes]


class ServingEngine:
    """Bucketed AOT prefill/decode over a GPTModel's weight stacks.

    ``serve_group(prompts, max_new_tokens)`` is the whole request path:
    pad to the nearest (batch, seq) bucket, one prefill dispatch, one
    decode dispatch per generated token, greedy (or temperature)
    sampling on host — every dispatch hits a pre-compiled program.
    """

    def __init__(self, model, batch_buckets=None, prefill_floor=8,
                 mesh=None, tp_axis="tp", dtype=None):
        self._W = model._max_length
        self._mesh = mesh
        self._tp_axis = tp_axis
        self._dtype = dtype
        self.batch_buckets = tuple(sorted(
            batch_buckets if batch_buckets is not None
            else batch_buckets_from_env()))
        self.prefill_buckets = prefill_buckets_for(self._W,
                                                   floor=prefill_floor)
        (stacks, lnf, tok, pos, num_heads,
         act) = extract_decoder_stacks(model)
        self._H = num_heads
        self._act = act
        self._C = int(tok.shape[1])
        self._L = int(stacks["qkv_stack_weight"].shape[0])
        self._vocab = int(tok.shape[0])
        if mesh is not None:
            n_tp = mesh.shape[tp_axis]
            F = int(stacks["ffn1_stack_weight"].shape[1])
            if num_heads % n_tp or F % n_tp:
                raise MXNetError(
                    f"ServingEngine: tp axis size {n_tp} must divide "
                    f"num_heads={num_heads} and ffn hidden={F}")
        self._reload_lock = threading.Lock()
        self.generation = 0
        self._weights = self._prepare_weights(stacks, lnf, tok, pos)
        self._programs = {}
        self._step = self._make_step()

    # -- weight plumbing -------------------------------------------------------

    def _shard(self, arr, spec):
        if self._mesh is None:
            return arr
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(arr,
                              NamedSharding(self._mesh, P(*spec)))

    def _prepare_weights(self, stacks, lnf, tok, pos):
        """Head-/hidden-major restructure + serving dtype + tp placement
        (the same Megatron column/row layout CachedDecoder._build
        derives, but produced as a flat argument tuple so the compiled
        programs take weights as inputs — the hot-reload contract)."""
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

    def reload_from_model(self, model, step=None):
        """Swap in a live model's weights (shapes must match)."""
        stacks, lnf, tok, pos, H, act = extract_decoder_stacks(model)
        if H != self._H or act != self._act:
            raise MXNetError(
                f"serving reload: incompatible model "
                f"(heads {H} vs {self._H}, act {act!r} vs {self._act!r})")
        self._swap(stacks, lnf, tok, pos, step=step)

    def reload_from_state(self, state, step=None, expect_fp=None):
        """Swap in weights from an AsyncCheckpointer state dict
        (``state_for_serving`` convention).

        ``expect_fp``: optional integrity fingerprint (u64, the
        training side's attested `integrity.fingerprint_host` of this
        state).  When given, the state is re-fingerprinted here and a
        mismatch REJECTS the reload (``serving_reload_rejected``)
        instead of serving corrupt weights — end-to-end coverage of
        the restore path itself, past the per-shard CRCs."""
        if expect_fp is not None:
            from .. import integrity, telemetry

            got = integrity.fingerprint_host(state)
            if got != int(expect_fp):
                telemetry.event(
                    "serving_reload_rejected", step=step,
                    reason=f"state fingerprint {integrity.fp_hex(got)} "
                           f"!= attested "
                           f"{integrity.fp_hex(int(expect_fp))}")
                raise MXNetError(
                    "serving reload: restored state fingerprint does "
                    "not match the attested fingerprint — refusing to "
                    "serve corrupt weights")
        stacks, lnf, tok, pos = _stacks_from_state(state)
        self._swap(stacks, lnf, tok, pos, step=step)

    def _swap(self, stacks, lnf, tok, pos, step=None):
        from .. import telemetry

        got = tuple(stacks["qkv_stack_weight"].shape)
        want = (self._L, 3 * self._C, self._C)
        if got != want:
            raise MXNetError(
                f"serving reload: weight mismatch — qkv stack {got} vs "
                f"compiled {want}; a mismatched swap would force a "
                f"retrace on the request path")
        import jax

        # a checkpoint state arrives as host arrays: the swapped-in
        # weights go where the compiled programs' weights live
        new_w = tuple(
            jax.device_put(new, old.sharding) for new, old in
            zip(self._prepare_weights(stacks, lnf, tok, pos),
                self._weights))
        for old, new in zip(self._weights, new_w):
            if tuple(old.shape) != tuple(new.shape) \
                    or old.dtype != new.dtype:
                raise MXNetError(
                    f"serving reload: weight mismatch "
                    f"{tuple(new.shape)}/{new.dtype} vs compiled "
                    f"{tuple(old.shape)}/{old.dtype} — a mismatched "
                    f"swap would force a retrace on the request path")
        with self._reload_lock:
            self._weights = new_w
            self.generation += 1
            gen = self.generation
        telemetry.event("serving_reload", generation=gen, step=step)

    # -- cache -----------------------------------------------------------------

    def _cache_sharding(self):
        from ..parallel.sharding import serving_cache_sharding

        return serving_cache_sharding(self._mesh, tp_axis=self._tp_axis)

    def init_cache(self, B):
        """Fresh zeroed (ck, cv) for batch bucket B: stage-major
        (L, B, H, W, Dh), serving dtype, head-sharded under tp."""
        import jax.numpy as jnp

        tok = self._weights[0]
        shape = (self._L, B, self._H, self._W, self._C // self._H)
        # committed next to the weights: the engine serves from the
        # device(s) the model was placed on, never from the process
        # default
        where = tok.sharding if self._mesh is None \
            else self._cache_sharding()
        return (jnp.zeros(shape, tok.dtype, device=where),
                jnp.zeros(shape, tok.dtype, device=where))

    # -- the traced block step -------------------------------------------------

    def _make_step(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from jax.experimental.layout import with_layout_constraint

        from ..ops.nn import layer_norm

        H, W = self._H, self._W
        Dh = self._C // H
        act = self._act
        mesh = self._mesh
        cache_ns = self._cache_sharding() if mesh is not None else None
        # how this platform lays a cache out on the device (a v5e puts W
        # minor-most where Dh is under 128): read off one, not assumed
        cache_layout = self.init_cache(1)[0].format.layout

        def keep_layout(c):
            return with_layout_constraint(c, cache_layout)

        if mesh is not None:
            # the constraint has no partitioning rule (the partitioner
            # would gather the cache to apply it), so each shard pins
            # its own
            keep_layout = jax.shard_map(
                keep_layout, mesh=mesh, in_specs=cache_ns.spec,
                out_specs=cache_ns.spec)

        def step(w, ck, cv, pos, toks):
            """ck/cv (L, B, H, W, Dh) donated; pos (B,) per-row write
            offsets; toks (B, S) int32.  Returns (ck', cv', logits
            (B, S, vocab)).  S = seq bucket for prefill, 1 for decode."""
            _mark_trace()
            (tok_e, pos_e, qkvw, qkvb, pwh, pb, f1w, f1b, f2w, f2b,
             g1s, b1s, g2s, b2s, lnf_g, lnf_b) = w
            B, S = toks.shape
            with jax.named_scope("serve.embed"):
                positions = pos[:, None] + jnp.arange(S)[None, :]  # (B, S)
                x = (jnp.take(tok_e, toks, axis=0) +
                     jnp.take(pos_e, positions, axis=0)
                     ).astype(jnp.float32)                     # (B, S, C)

            def write(c, new, l):
                """Row b's new (H, S, Dh) block into the carried stack
                at [l, b, :, pos[b]:pos[b] + S, :], and nothing else: one
                dynamic_update_slice a row, each at that row's own
                offset (a start that would run past W is clamped)."""
                new = new.astype(c.dtype)
                zero = jnp.int32(0)
                for b in range(B):
                    c = lax.dynamic_update_slice(
                        c, new[b][None, None],
                        (l, jnp.int32(b), zero, pos[b], zero))
                # keep the stack in the layout the donated buffer came
                # in: left to itself the TPU compiler re-lays the whole
                # cache around the loop to make these writes cheaper
                return keep_layout(c)

            def layer(carry, per):
                x, ck, cv = carry
                (qw, qb, pw, pb_l, f1w_l, f1b_l, f2w_l, f2b_l,
                 g1, b1, g2, b2, l) = per
                with jax.named_scope("serve.attn_qkv"):
                    h = layer_norm(x, g1, b1)
                    qkv = jnp.einsum("bsc,thdc->bsthd", h, qw) + qb
                    qh = qkv[:, :, 0].swapaxes(1, 2)     # (B, H, S, Dh)
                    kh = qkv[:, :, 1].swapaxes(1, 2)
                    vh = qkv[:, :, 2].swapaxes(1, 2)
                with jax.named_scope("serve.cache_write"):
                    ck = write(ck, kh, l)
                    cv = write(cv, vh, l)
                with jax.named_scope("serve.attn"):
                    ck_l = lax.dynamic_index_in_dim(ck, l, 0, keepdims=False)
                    cv_l = lax.dynamic_index_in_dim(cv, l, 0, keepdims=False)
                    scores = jnp.einsum("bhsd,bhwd->bhsw", qh, ck_l) \
                        * (Dh ** -0.5)
                    # per-row causal mask: row b at block offset s may
                    # see cache slots <= pos[b] + s (stale pad garbage
                    # beyond is invisible — the overwrite-before-attend
                    # invariant)
                    mask = jnp.arange(W)[None, None, :] <= \
                        (pos[:, None, None] +
                         jnp.arange(S)[None, :, None])         # (B, S, W)
                    scores = jnp.where(mask[:, None], scores, -1e30)
                    p = jax.nn.softmax(scores, axis=-1)
                    attn = jnp.einsum("bhsw,bhwd->bhsd", p, cv_l)
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
                h = layer_norm(x, lnf_g, lnf_b)
                logits = h @ tok_e.T
            if cache_ns is not None:
                # pin the donated buffers' output layout to the input
                # layout, so the next AOT call sees identical shardings
                ck2 = lax.with_sharding_constraint(ck2, cache_ns)
                cv2 = lax.with_sharding_constraint(cv2, cache_ns)
            return ck2, cv2, logits

        def named(name):
            # one traced function under two names: a program is called
            # ``jit_<__name__>`` in its HLO and in every device trace
            def fn(w, ck, cv, pos, toks):
                return step(w, ck, cv, pos, toks)

            fn.__name__ = fn.__qualname__ = name
            return fn

        return {"prefill": named("serve_prefill"),
                "decode": named("serve_decode")}

    # -- AOT compilation -------------------------------------------------------

    def _aval(self, arr):
        import jax

        return jax.ShapeDtypeStruct(tuple(arr.shape), arr.dtype,
                                    sharding=arr.sharding)

    def _input_sharding(self):
        """Where the per-call int inputs go: the weights' own device,
        or replicated over the mesh."""
        if self._mesh is None:
            return self._weights[0].sharding
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self._mesh, P())

    def _int_aval(self, shape):
        import jax
        import numpy as np

        return jax.ShapeDtypeStruct(shape, np.int32,
                                    sharding=self._input_sharding())

    def _compile(self, B, S):
        """One donated program for bucket (B, S), compiled ahead of time
        (``lower(*avals).compile()``, as gluon/captured.py compiles the
        train step)."""
        global _COMPILE_COUNT
        import jax

        w_avals = tuple(self._aval(x) for x in self._weights)
        ck, cv = self.init_cache(B)
        jfn = jax.jit(self._step["decode" if S == 1 else "prefill"],
                      donate_argnums=(1, 2))
        compiled = jfn.lower(w_avals, self._aval(ck), self._aval(cv),
                             self._int_aval((B,)),
                             self._int_aval((B, S))).compile()
        with _LOCK:
            _COMPILE_COUNT += 1
        self._programs[(B, S)] = compiled
        return compiled

    def warmup(self):
        """Pre-compile every (batch × prefill) program plus the S=1
        decode program per batch bucket; afterwards the request path is
        retrace-free (``trace_count()`` is pinned)."""
        t0 = time.perf_counter()
        for B in self.batch_buckets:
            for S in self.prefill_buckets + (1,):
                if (B, S) not in self._programs:
                    self._compile(B, S)
        from .. import telemetry

        telemetry.event(
            "serving_warmup", programs=len(self._programs),
            compile_ms=round((time.perf_counter() - t0) * 1e3, 1))
        return self

    def program_count(self):
        return len(self._programs)

    def _call(self, B, S, ck, cv, pos, toks):
        global _DISPATCH_COUNT
        import jax
        import numpy as np

        compiled = self._programs.get((B, S))
        if compiled is None:
            compiled = self._compile(B, S)
        where = self._input_sharding()
        pos = jax.device_put(np.asarray(pos, np.int32), where)
        toks = jax.device_put(np.asarray(toks, np.int32), where)
        with _LOCK:
            _DISPATCH_COUNT += 1
        with self._reload_lock:
            w = self._weights
        return compiled(w, ck, cv, pos, toks)

    # -- request path ----------------------------------------------------------

    def _pick_bucket(self, buckets, n, what):
        for b in buckets:
            if b >= n:
                return b
        raise MXNetError(
            f"serving: {what} {n} exceeds the largest bucket "
            f"{buckets[-1]} (buckets {buckets})")

    def serve_group(self, prompts, max_new_tokens, temperature=None,
                    rng=None):
        """Serve one coalesced group.  ``prompts``: list of 1-D int
        sequences (mixed lengths OK); ``max_new_tokens``: int or
        per-request list.  Returns ``(outputs, timings)`` where
        outputs[i] is the i-th request's generated tokens (np.int32)
        and timings carries the per-request record fields
        (prefill_us, decode_us_per_token, bucket, padded_fraction, the
        per-step split of decode and ``token_t_us``;
        docs/observability.md has the table).  Each phase runs under a
        `profiler.scope` (``serve.prefill.dispatch`` ...
        ``serve.decode.readback``), and the timings are sums of those
        spans' own clock reads."""
        import numpy as np

        n = len(prompts)
        if n == 0:
            return [], {}
        per_req = [max_new_tokens] * n \
            if isinstance(max_new_tokens, int) else list(max_new_tokens)
        if len(per_req) != n or any(k < 1 for k in per_req):
            raise MXNetError("serving: max_new_tokens must be a positive "
                             "int or one per prompt")
        steps = max(per_req)
        B = self._pick_bucket(self.batch_buckets, n, "group size")
        lens = np.ones(B, np.int32)     # pad rows hold one dummy token
        for i, p in enumerate(prompts):
            if len(p) < 1:
                raise MXNetError("serving: empty prompt")
            lens[i] = len(p)
        Tmax = int(lens[:n].max())
        if Tmax + steps > self._W:
            raise MXNetError(
                f"serving: {Tmax} prompt + {steps} new tokens exceed "
                f"the cache window max_length={self._W}")
        S = self._pick_bucket(self.prefill_buckets, Tmax, "prompt length")
        toks = np.zeros((B, S), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :lens[i]] = np.asarray(p, np.int32)
        with scope("serve.prefill.dispatch") as sp_dispatch:
            ck, cv = self.init_cache(B)
            ck, cv, logits = self._call(B, S, ck, cv,
                                        np.zeros(B, np.int32), toks)
        with scope("serve.prefill.readback") as sp_readback:
            last = np.asarray(logits)[np.arange(B), lens - 1]
        t0, t1 = sp_dispatch.t0, sp_readback.t1
        prefill_us = (t1 - t0) * 1e6
        out = np.zeros((B, steps), np.int32)
        sample_s = dispatch_s = readback_s = 0.0
        token_t_us = []
        for j in range(steps):
            with scope("serve.decode.sample", step=j) as sp:
                nxt = _sample(last, temperature, rng)
                out[:, j] = nxt
            sample_s += sp.t1 - sp.t0
            token_t_us.append((sp.t1 - t1) * 1e6)
            if j < steps - 1:      # the last token needs no cache step
                with scope("serve.decode.dispatch", step=j) as sp:
                    ck, cv, logits = self._call(B, 1, ck, cv, lens + j,
                                                nxt[:, None])
                dispatch_s += sp.t1 - sp.t0
                with scope("serve.decode.readback", step=j) as sp:
                    last = np.asarray(logits)[:, 0]
                readback_s += sp.t1 - sp.t0
        decode_us = (sp.t1 - t1) * 1e6
        per_step = 1e6 / steps        # seconds summed -> us a step
        timings = {
            "prefill_us": prefill_us,
            "decode_us_per_token": decode_us / steps,
            "bucket": [int(B), int(S)],
            "padded_fraction": round(
                1.0 - float(lens[:n].sum()) / float(B * S), 4),
            "generation": self.generation,
            # wall-clock stage starts + total decode time: what groups
            # a group's records and places its request spans
            # (obs/spans.py); every duration here comes from the spans'
            # own clock reads
            "t_prefill0": wall(t0),
            "t_decode0": wall(t1),
            "decode_us": decode_us,
            "decode_sample_us_per_step": sample_s * per_step,
            "decode_dispatch_us_per_step": dispatch_s * per_step,
            "decode_readback_us_per_step": readback_s * per_step,
            "decode_host_us_per_step": (sample_s + dispatch_s) * per_step,
            # when each token was emitted, from t_decode0 (token 0 is
            # the prefill's): the gaps are what a streaming caller sees
            "token_t_us": token_t_us,
        }
        return [out[i, :per_req[i]].copy() for i in range(n)], timings

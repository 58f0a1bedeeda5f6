#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path runs on the chip.

    python3 chip_smoke.py

One process, seeded synthetic data, no network.  It drives what a user
would: a Gluon ``gpt2_small`` (12 layers, 768 wide, vocab 50,257,
T = 1,024, bf16, Pallas flash attention) placed on ``mx.tpu(0)``, trained
by ``Trainer.train_step`` (the captured whole-step program), then served
by ``ServingEngine`` + ``ContinuousBatcher``; then a second family
through the same engine, ``MiMoV2Model`` at its published widths (the
cut of ``benchmark/configs/mimo-v2.5-ep16.json``: window and full
layers, two kinds of cache, a chip's share of the routed experts); then
a third, ``KeyeVL2Model`` at small aligned sizes (a learned indexer with
a cache stack of its own, attention over its selection, softmax-routed
experts); a fourth, ``KimiK2Model`` (latent attention); a fifth,
``OuroModel`` (a dense stack run several times a token over one set of
weights, a cache slot for every (loop step, layer), held to the float32
reference's logits); a sixth, ``Cohere2MoeModel`` (Command A+: a
parallel attention-and-experts block, rotary-free full layers beside
rings of several lane blocks), small and then at the published widths of
``benchmark/configs/command-a-plus-ep16.json``, both held to the
float32 reference's logits; a seventh, ``JambaModel`` (Mamba layers
whose state and convolution tail ride in the donated cache beside an
attention layer's keys and values), held to the float32 reference's
logits; an eighth, ``GraniteHybridModel`` (Mamba-2 layers whose heads'
states ride in the donated cache, routed experts and a shared one in
every layer), after its two kernels alone against their plain paths at
the published widths (``ssd``); the held experts' grouped product alone
(``moe_grouped``: `ops/moe.py`'s kernel against ``lax.ragged_dot`` at
the five expert families' decode and prefill shapes, both timed, and a
pass's way out into the stream by its walk of the token tiles against
XLA's scatter-add at the same ten programs' shapes, both timed); with
four chips, the GPT step under ``shard_model`` fsdp and tp.  Phases, in
order: device, sync, kernel, train, serve, serve_mimo, serve_keye,
serve_kimi, serve_ouro, serve_cmda, serve_cmda_full, serve_jamba, ssd,
serve_granite, moe_grouped, sharded
(``--phases a,b``: the device phase and only those).  The first failed check raises and the process
exits non-zero; the last line of stdout is the JSON result only
when every phase passed.

``__main__`` always demands platform ``tpu``.  The phase functions take a
`Size` and the required platform so that tests/test_chip_smoke.py can
drive them tiny on the CPU.  Timings printed here are compile and
wall-clock seconds of a smoke run — set-up facts, not performance
figures.
"""

import argparse
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import threading
import time

import numpy as np


class SmokeError(RuntimeError):
    """A phase's check did not hold."""


def require(cond, msg):
    if not cond:
        raise SmokeError(msg)


def say(msg):
    print(msg, flush=True)


@dataclasses.dataclass(frozen=True)
class Size:
    model: str              # factory in gluon.model_zoo.gpt
    batch: int
    steps: int
    sharded_steps: int
    kernel_shapes: tuple    # ((B, H, T, D, causal), ...)
    sync: tuple             # (n, matmuls per call, calls)
    batch_buckets: tuple
    prefill_floor: int
    prompt_lens: tuple      # one request each, mixed prefill buckets
    new_tokens: int
    lr: float = 3e-4


FULL = Size(
    model="gpt2_small", batch=8, steps=5, sharded_steps=3,
    # BERT-base's recorded shape (b32 x 12 heads, T512), then this
    # model's own training shape
    kernel_shapes=((32, 12, 512, 64, False), (8, 12, 1024, 64, True)),
    sync=(4096, 16, 8),
    batch_buckets=(1, 4), prefill_floor=128,
    prompt_lens=(5, 17, 60, 128, 200, 33), new_tokens=8)



@dataclasses.dataclass(frozen=True)
class FamilySize:
    kwargs: dict            # the family's constructor's
    batch: int              # the one batch bucket
    prefill_floor: int
    prompt_lens: tuple      # one group: under, at and past the window
    new_tokens: int         # enough for every ring to wrap twice


def mimo_full():
    """The benchmark configuration's own constructor arguments: the
    published widths, 7 layers, 16 of 256 experts a layer."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "benchmark", "configs",
                           "mimo-v2.5-ep16.json")) as f:
        kwargs = json.load(f)["program"]["kwargs"]
    return FamilySize(kwargs=kwargs, batch=8, prefill_floor=512,
                    prompt_lens=(40, 128, 300, 500, 77, 129, 16, 260),
                    new_tokens=2 * kwargs["window"] + 5)


def keye_small():
    """The third family at the smallest sizes Mosaic's tiles take: heads
    of 128, an indexer of 2 heads of 64, top-128 of up to 1,024
    positions, 2 layers, 4 of 8 experts held."""
    kwargs = dict(vocab_size=512, units=256, num_layers=2, num_heads=4,
                  kv_heads=2, head_dim=128, index_heads=2, index_dim=64,
                  topk=128, expert_hidden=128, router_experts=8,
                  experts_per_token=2, experts_held=[2, 4],
                  max_length=1024, dtype="bfloat16", grad_req="null")
    return FamilySize(kwargs=kwargs, batch=8, prefill_floor=1024,
                    prompt_lens=(40, 128, 300, 700, 77, 513, 16, 260),
                    new_tokens=6)


def kimi_small():
    """The fourth family at small aligned sizes with every mechanism
    present: 8 heads of 128 + 64 rotated dimensions, latents of 256, a
    cache entry of 320 (2.5 lane tiles: not a multiple of 128), a dense
    layer and 2 expert layers with a shared expert, 4 of 8 experts
    held, YaRN by 8 from 128 positions, rows a prefill works off two at
    a time and token-wise products 256 positions at a time."""
    kwargs = dict(vocab_size=512, units=256, num_layers=3, num_heads=8,
                  q_rank=128, kv_rank=256, nope_dim=128, rope_dim=64,
                  v_dim=128, hidden_size=512, expert_hidden=128,
                  router_experts=8, experts_per_token=2,
                  experts_held=[2, 4], route_scale=2.5, rope_factor=8.0,
                  rope_original_length=128, mscale=1.0, mscale_all_dim=1.0,
                  max_length=1024, attn_block=256, token_chunk=256,
                  prefill_chunk_tokens=2048, dtype="bfloat16",
                  grad_req="null")
    return FamilySize(kwargs=kwargs, batch=8, prefill_floor=1024,
                    prompt_lens=(40, 128, 300, 700, 77, 513, 16, 260),
                    new_tokens=6)


def ouro_small():
    """The fifth family at small sizes with heads of the published width
    (4 of 128): 2 layers run 3 times over one set of weights, 6 cache
    slots, the cell's window and prefill bucket of 512."""
    kwargs = dict(vocab_size=512, units=256, num_layers=2, num_heads=4,
                  kv_heads=4, head_dim=128, hidden_size=512, loop_steps=3,
                  max_length=512, dtype="bfloat16", grad_req="null")
    return FamilySize(kwargs=kwargs, batch=8, prefill_floor=512,
                    prompt_lens=(40, 128, 300, 77, 129, 16, 260),
                    new_tokens=6)


def jamba_small():
    """The seventh family at small sizes with heads and channel blocks
    of the published kind (4 query heads of 128 over one key head, 1,024
    channels: two blocks of the scan kernel): one period of 14 layers
    with its attention layer at 7, the cell's prefill bucket of 512 and
    a window of 704 (stacks of 768 slots: whole lane blocks)."""
    kwargs = dict(vocab_size=512, units=512, num_layers=14, num_heads=4,
                  kv_heads=1, hidden_size=1024, attn_period=14,
                  attn_offset=7, d_state=16, d_conv=4, dt_rank=32, expand=2,
                  max_length=704, prefill_chunk_tokens=2048,
                  dtype="bfloat16", grad_req="null")
    return FamilySize(kwargs=kwargs, batch=8, prefill_floor=512,
                    prompt_lens=(40, 128, 300, 77, 129, 1, 260),
                    new_tokens=6)


@dataclasses.dataclass(frozen=True)
class SsdSize:
    """The Mamba-2 kernels' operands: H heads of P channels over N
    states, a block of R rows x S positions to ``lengths``, a stack of L
    layers x B rows of which ``live`` are; ``tiles``: the kernels'
    (chunk, heads a block, channels a tile), None for their own."""
    H: int
    P: int
    N: int
    S: int
    lengths: tuple
    L: int
    live: tuple
    tiles: tuple = None


def ssd_full():
    """Granite 4.0-H's widths (128 heads of 64 over 128 states: a row's
    state is 4 MB), a prefill block of 512 with rows that end inside a
    chunk, on a chunk's edge and at the block's, and eight rows of a
    three-layer stack of which five are live."""
    return SsdSize(H=128, P=64, N=128, S=512,
                   lengths=(1, 100, 128, 129, 300, 384, 511, 512), L=3,
                   live=(1, 0, 1, 1, 0, 0, 1, 1))


@dataclasses.dataclass(frozen=True)
class GroupedSize:
    """The grouped product's cases: (name, P rows of the buffer, M, F, n
    experts a layer, the rows of each group in the pass), each over a
    stack of ``layers`` layers, timed over ``reps`` passes; and the
    cases of a pass's way out into the stream: (name, T tokens of the
    stream, P, M, the pass's live rows, the experts they fall to, the
    tokens' rows of that length of which some are real)."""
    cases: tuple
    layers: int = 2
    reps: int = 20
    ways_out: tuple = ()


def moe_grouped_full():
    """A decode step's pass and a prefill pass of each expert family at
    its cell's widths and bucket (benchmark/configs): the buffer
    `ops/moe.py::share_pass_rows` gives, and the groups the cell's
    traffic makes of it (PERF.md section 5: Granite's 18 experts all hit
    by 107 pairs a step, MiMo's 4 of 16, Keye's 5 of 16, Kimi's 1 of 12,
    Command A+'s 2 of 8; a prefill pass holds the few experts its sorted
    rows fall to)."""
    def spread(rows, hit, n, first=0):
        """``rows`` pairs over ``hit`` of ``n`` experts, every
        ``n // hit``-th from ``first``, as evenly as they go."""
        sizes = [0] * n
        for i in range(hit):
            sizes[(first + i * (n // hit)) % n] = \
                rows // hit + (i < rows % hit)
        return tuple(sizes)

    return GroupedSize(cases=(
        ("granite.decode", 256, 4096, 768, 18, spread(107, 18, 18)),
        ("granite.prefill", 1024, 4096, 768, 18,
         (0,) * 5 + (211, 569, 244) + (0,) * 10),
        ("mimo.decode", 256, 4096, 2048, 16, spread(32, 4, 16, 1)),
        ("mimo.prefill", 1024, 4096, 2048, 16, spread(1024, 8, 8) + (0,) * 8),
        ("keye.decode", 128, 2048, 768, 16, spread(12, 5, 16)),
        ("keye.prefill", 4096, 2048, 768, 16,
         (0,) * 4 + (512, 1024, 1024, 1024, 512) + (0,) * 7),
        ("kimi.decode", 64, 7168, 2048, 12, spread(2, 1, 12, 7)),
        ("kimi.prefill", 4096, 7168, 2048, 12, spread(4096, 12, 12)),
        ("cmda.decode", 64, 4096, 4096, 8, spread(4, 2, 8, 3)),
        ("cmda.prefill", 4096, 4096, 4096, 8, (0, 0, 1024, 1024, 1024, 1024,
                                               0, 0)),
    ), ways_out=(
        # a decode step's stream is the bucket's rows, of which the
        # traffic's live share still wants a token; a prefill call's is
        # a row chunk (Keye's the whole bucket), its rows' real tokens
        ("granite.decode", 128, 256, 4096, 107, 18, 1),
        ("granite.prefill", 4096, 1024, 4096, 1024, 2, 512),
        ("mimo.decode", 64, 256, 4096, 32, 4, 1),
        ("mimo.prefill", 4096, 1024, 4096, 1024, 8, 512),
        ("keye.decode", 16, 128, 2048, 12, 5, 1),
        ("keye.prefill", 262144, 65536, 2048, 65536, 5, 16384),
        ("kimi.decode", 8, 64, 7168, 2, 1, 1),
        ("kimi.prefill", 16384, 4096, 7168, 4096, 12, 8192),
        ("cmda.decode", 8, 64, 4096, 4, 2, 1),
        ("cmda.prefill", 16384, 4096, 4096, 4096, 4, 16384),
    ))


def granite_small():
    """The eighth family at small sizes with heads of the published
    kind (Mamba-2 heads of 64 channels over 128 states, 16 of them: one
    head block of the scan kernel, eight tiles of the update's; 4 query
    heads of 128 over one key head): five layers with the attention
    layer inside, eight experts of which two are held, three a token,
    the cell's prefill bucket of 512 in two row chunks and a window of
    704."""
    kwargs = dict(vocab_size=512, units=512,
                  layer_types=["mamba", "mamba", "attention", "mamba",
                               "mamba"],
                  num_heads=4, kv_heads=1, ssm_heads=16, ssm_head_dim=64,
                  d_state=128, d_conv=4, expert_hidden=256,
                  shared_hidden=512, router_experts=8, experts_per_token=3,
                  experts_held=(0, 2), embedding_multiplier=12.0,
                  residual_multiplier=0.22, attention_multiplier=0.0078125,
                  logits_scaling=16.0, max_length=704,
                  prefill_chunk_tokens=2048, dtype="bfloat16",
                  grad_req="null")
    return FamilySize(kwargs=kwargs, batch=8, prefill_floor=512,
                    prompt_lens=(40, 128, 300, 77, 129, 1, 260),
                    new_tokens=6)


def cmda_small():
    """The sixth family at small aligned sizes with every mechanism
    present: 16 query heads over 2 key heads of 128, three window
    layers with rings of 256 slots (two lane blocks: the per-row kernel
    reads them) and a full one, 2 shared experts, 4 of 8 experts held,
    rows a prefill works off two at a time and token-wise products 256
    positions at a time; prompts under, at and past the window, one
    that wraps its ring within the answer."""
    kwargs = dict(vocab_size=512, units=256,
                  layer_types=["window", "window", "window", "full"],
                  num_heads=16, kv_heads=2, head_dim=128, window=256,
                  expert_hidden=128, router_experts=8, experts_per_token=2,
                  experts_held=[2, 4], shared_experts=2, max_length=1024,
                  token_chunk=256, prefill_chunk_tokens=2048,
                  dtype="bfloat16", grad_req="null")
    return FamilySize(kwargs=kwargs, batch=8, prefill_floor=1024,
                    prompt_lens=(40, 256, 300, 700, 77, 510, 16, 260),
                    new_tokens=6)


def cmda_full():
    """The benchmark configuration's own constructor arguments: the
    published widths, one period of 4 layers, 8 of 128 experts a layer,
    rings of 4,096, the cell's bucket of 8 x 16,384; prompts of 1-4
    windows, one that wraps its rings within the answer."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "benchmark", "configs",
                           "command-a-plus-ep16.json")) as f:
        kwargs = json.load(f)["program"]["kwargs"]
    return FamilySize(kwargs=kwargs, batch=8, prefill_floor=16384,
                    prompt_lens=(4096, 5257, 8190, 12765, 15872, 300),
                    new_tokens=6)


# the two timings of the sync phase may differ by this factor
SYNC_FACTOR = 2.0
# max |kernel - dense| / max |dense| on bf16 operands (both sides round
# probabilities to bf16 for the value matmul)
KERNEL_TOL_FWD = 2e-2
KERNEL_TOL_BWD = 4e-2
# |first loss - ln(vocab)| at Xavier init
INIT_LOSS_TOL = 0.5
# sharded vs single-chip step-0 loss, bf16 activations reduced in another
# order
SHARDED_LOSS_TOL = 5e-2
# max |served logits - float32 reference| / max |reference| in bfloat16
SERVED_LOGITS_TOL = 5e-2


def on_platform(arr, platform):
    return all(d.platform == platform for d in arr.devices())


# seconds of every XLA backend compile (or persistent-cache load) this
# process has made, appended by a jax.monitoring listener
COMPILES = []
_watching = False


def watch_compiles():
    """Start recording into COMPILES (once: jax.monitoring listeners
    cannot be removed)."""
    global _watching
    import jax

    def on_event(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILES.append(secs)

    if not _watching:
        _watching = True
        jax.monitoring.register_event_duration_secs_listener(on_event)


# -- device --------------------------------------------------------------------

def phase_device(platform):
    from importlib import metadata

    import jax
    import jaxlib

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"[device] platform={info['platform']} kind={info['kind']!r} "
        f"count={info['count']} local={jax.local_device_count()} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu} python={sys.version.split()[0]}")
    require(info["platform"] == platform,
            f"device: JAX runs on {info['platform']!r}, "
            f"{platform!r} required")
    return info


# -- sync ----------------------------------------------------------------------

def phase_sync(size, platform):
    """Does `block_until_ready` alone wait for the device?  The same
    chained bf16 matmul is timed ended by block_until_ready and ended by
    a host readback; the block_until_ready timing runs FIRST, before
    this process has read anything back."""
    import jax
    import jax.numpy as jnp

    n, chain, calls = size.sync

    @jax.jit
    def f(a, b):
        for _ in range(chain):
            a = (a @ b) * (1.0 / n)     # keeps bf16 finite down the chain
        return jnp.sum(a.astype(jnp.float32))

    ka, kb = jax.random.split(jax.random.key(0))
    a = jax.random.normal(ka, (n, n), jnp.bfloat16)
    b = jax.random.normal(kb, (n, n), jnp.bfloat16)
    require(on_platform(a, platform), "sync: operands off the device")
    f(a, b).block_until_ready()         # compile

    def timed(finish):
        best = math.inf
        for _ in range(3):
            t0 = time.perf_counter()
            out = None
            for _ in range(calls):
                out = f(a, b)
            finish(out)
            best = min(best, time.perf_counter() - t0)
        return best

    t_block = timed(lambda out: out.block_until_ready())
    t_read = timed(np.asarray)
    ratio = max(t_block, t_read) / max(min(t_block, t_read), 1e-9)
    say(f"[sync] {calls} calls x {chain} matmuls n={n}: "
        f"block_until_ready {t_block:.4f}s, readback {t_read:.4f}s, "
        f"ratio {ratio:.2f} (limit {SYNC_FACTOR})")
    require(ratio <= SYNC_FACTOR,
            f"sync: block_until_ready ({t_block:.4f}s) and readback "
            f"({t_read:.4f}s) timings disagree by {ratio:.2f}x")
    return {"block_until_ready_s": t_block, "readback_s": t_read}


# -- kernel --------------------------------------------------------------------

def phase_kernel(size, platform):
    """flash_attention forward and backward against the dense oracle,
    compiled (never interpreted) when the platform is tpu."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    require(pa._use_interpret() == (platform == "cpu"),
            f"kernel: interpret mode is {pa._use_interpret()} on "
            f"{platform}")
    out = []
    for B, H, T, D, causal in size.kernel_shapes:
        keys = jax.random.split(jax.random.key(T), 4)
        q, k, v, w = (jax.random.normal(kk, (B, H, T, D), jnp.bfloat16)
                      for kk in keys)
        scale = D ** -0.5

        def flash(q, k, v):
            return pa.flash_attention(q, k, v, causal=causal)

        def dense(q, k, v):
            return pa._dense_ref(q, k, v, causal, scale)

        def grads_of(fn):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32)
                               * w.astype(jnp.float32))
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        t0 = time.perf_counter()
        fwd = jax.jit(flash).lower(q, k, v)
        if platform == "tpu":
            require("tpu_custom_call" in fwd.as_text(),
                    "kernel: no Mosaic custom call in the lowered "
                    "forward")
        o = fwd.compile()(q, k, v)
        g = grads_of(flash)(q, k, v)
        jax.block_until_ready((o, g))
        secs = time.perf_counter() - t0
        o_ref = jax.jit(dense)(q, k, v)
        g_ref = grads_of(dense)(q, k, v)

        def err(a, b):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            require(np.isfinite(a).all(), "kernel: non-finite output")
            return float(np.abs(a - b).max() / np.abs(b).max())

        e_fwd = err(o, o_ref)
        e_bwd = [err(a, b) for a, b in zip(g, g_ref)]
        say(f"[kernel] ({B}x{H}, T{T}, D{D}) causal={causal}: compile+run "
            f"{secs:.1f}s, fwd err {e_fwd:.4f} (tol {KERNEL_TOL_FWD}), "
            f"dq/dk/dv err {e_bwd[0]:.4f}/{e_bwd[1]:.4f}/{e_bwd[2]:.4f} "
            f"(tol {KERNEL_TOL_BWD})")
        require(on_platform(o, platform), "kernel: output off the device")
        require(e_fwd <= KERNEL_TOL_FWD,
                f"kernel: forward off the dense oracle by {e_fwd}")
        require(max(e_bwd) <= KERNEL_TOL_BWD,
                f"kernel: backward off the dense oracle by {e_bwd}")
        out.append({"shape": [B, H, T, D], "causal": causal,
                    "compile_run_s": secs, "fwd_err": e_fwd,
                    "bwd_err": e_bwd})
    return out


# -- train ---------------------------------------------------------------------

def _ctx_for(platform):
    import mxnet_tpu as mx

    return mx.cpu(0) if platform == "cpu" else mx.tpu(0)


def _build(size, platform, seed=0):
    """Seeded net + trainer + one fixed batch of the learnable corpus of
    examples/gpt_pretrain_sharded.py (tok[t+1] = perm[tok[t]])."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import gpt

    ctx = _ctx_for(platform)
    mx.random.seed(seed)
    np.random.seed(seed)
    net = getattr(gpt, size.model)(scan_layers=True,
                                   attention_impl="flash", dropout=0.0)
    net.initialize(init=mx.init.Xavier(), ctx=ctx)
    net.cast("bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adamw",
                            {"learning_rate": size.lr})
    vocab, seq = net._vocab, net._max_length
    rng = np.random.RandomState(seed)
    perm = rng.permutation(vocab)
    seqs = [rng.randint(0, vocab, (size.batch,))]
    for _ in range(seq - 1):
        seqs.append(perm[seqs[-1]])
    ids = np.stack(seqs, axis=1).astype(np.float32)
    return net, trainer, mx.nd.array(ids, ctx=ctx)


def _state_arrays(trainer):
    """Every optimizer-state array the trainer holds."""
    from mxnet_tpu.ndarray.ndarray import NDArray

    def walk(s):
        if isinstance(s, NDArray):
            yield s._data
        elif isinstance(s, (list, tuple)):
            for x in s:
                yield from walk(x)

    for upd in trainer._updaters:
        for st in upd.states.values():
            yield from walk(st)


def _run_steps(tag, net, trainer, ids, steps):
    """`steps` x trainer.train_step on one batch; asserts the captured
    path took every step with ONE capture and ONE trace."""
    from mxnet_tpu.gluon import captured
    from mxnet_tpu.gluon.model_zoo import gpt
    from mxnet_tpu.optimizer import grouped

    require(steps >= 2, f"{tag}: two steps at least, to see that the "
                        "second compiles nothing")
    loss_fn = gpt.GPTLMLoss()
    watch_compiles()
    captured.reset_counters()
    grouped.reset_dispatch_count()
    losses, secs = [], []
    last = None
    for step in range(steps):
        if step == 1:
            compiles_after_step0 = len(COMPILES)
        t0 = time.perf_counter()
        last = trainer.train_step(net, loss_fn, ids, ids)
        losses.append(float(np.asarray(last._data, np.float32)))
        secs.append(time.perf_counter() - t0)
    late = len(COMPILES) - compiles_after_step0
    stats = captured.cache_stats()
    say(f"[{tag}] losses {' '.join(f'{v:.4f}' for v in losses)}; first "
        f"step (trace+compile+run) {secs[0]:.1f}s, later steps "
        f"{' '.join(f'{s:.2f}' for s in secs[1:])}s; captures "
        f"{stats['misses']}, traces {captured.trace_count()}, XLA "
        f"compiles after step 0: {late}")
    require(late == 0,
            f"{tag}: {late} XLA compile(s) after step 0 — the step "
            "program must compile once")
    require(captured.get_step(trainer, net, loss_fn, ids, ids, 1)
            is not None, f"{tag}: the step is not capturable")
    require(captured.dispatch_count() == steps
            and grouped.dispatch_count() == 0,
            f"{tag}: {captured.dispatch_count()} captured and "
            f"{grouped.dispatch_count()} eager dispatches in {steps} "
            "steps")
    require(stats["misses"] == 1 and captured.trace_count() == 1,
            f"{tag}: {stats['misses']} captures, "
            f"{captured.trace_count()} traces (1 each expected)")
    require(all(math.isfinite(v) for v in losses),
            f"{tag}: non-finite loss in {losses}")
    return losses, secs, last


def phase_train(size, platform):
    net, trainer, ids = _build(size, platform)
    losses, secs, last = _run_steps("train", net, trainer, ids, size.steps)
    uniform = math.log(net._vocab)
    require(abs(losses[0] - uniform) <= INIT_LOSS_TOL,
            f"train: first loss {losses[0]:.4f} is not within "
            f"{INIT_LOSS_TOL} of ln(vocab) = {uniform:.4f}")
    require(losses[-1] < losses[0],
            f"train: loss did not fall ({losses[0]} -> {losses[-1]})")
    params = [p.data()._data for p in net.collect_params().values()]
    states = list(_state_arrays(trainer))
    require(states, "train: the trainer holds no optimizer state")
    for what, arrs in (("parameter", params), ("optimizer state", states),
                       ("loss", [last._data])):
        require(all(on_platform(a, platform) for a in arrs),
                f"train: a {what} is not on a {platform} device")
    dev = _ctx_for(platform).jax_device
    stats = dev.memory_stats()
    require(stats is not None or platform == "cpu",
            "train: the device reports no memory stats")
    peak = stats["peak_bytes_in_use"] if stats else None
    say(f"[train] {len(params)} parameters + {len(states)} optimizer "
        f"states + loss on {platform}; peak bytes in use {peak}")
    return {"net": net, "losses": losses, "first_step_s": secs[0],
            "peak_bytes": peak}


# -- serve ---------------------------------------------------------------------

def require_fed_on_device(tag, engine, prompts, steps, served, timing):
    """A greedy group ran with no host round trip between its steps:
    every decode step took the step before's ids on the device, the
    host read 4 bytes a row of each program, and the tokens are those
    of the same prompts with the host in every step (it reads each
    program's logits, takes the argmax, puts ids and positions back)."""
    from mxnet_tpu.test_utils import serving_host_walk

    B = timing["bucket"][0]
    require(timing["decode_steps_fed_on_device"] == steps - 1
            and timing["decode_readback_bytes_per_step"] == 4 * B,
            f"{tag}: {timing['decode_steps_fed_on_device']} of "
            f"{steps - 1} decode steps fed on the device, "
            f"{timing['decode_readback_bytes_per_step']} bytes read a "
            f"step (bucket {B})")
    host, _ = serving_host_walk(engine, prompts, steps)
    for i, got in enumerate(served):
        require(np.array_equal(got, host[i, :len(got)]),
                f"{tag}: prompt {i} fed on the device {got} != picked "
                f"on the host {host[i]}")
    say(f"[{tag}] greedy group of {len(prompts)} x {steps} tokens: "
        f"{steps - 1} decode steps fed on the device, {4 * B} bytes read "
        f"a step, tokens equal the host-picked run")


def require_dead_rows_change_nothing(tag, engine, prompts, steps, served):
    """A group of unequal answers, rows that want no more token between
    rows that do: the decode steps are handed the mask (fewer live
    row-steps than row-steps), a finished row reads one cache block,
    goes to no expert and writes nothing into the cache (every row
    write the kernel made, it made knowing the live rows; the stacks of
    the rows that are not live come back bit for bit), and every
    request's tokens are those it got in ``served``, the same group
    with every answer ``steps`` long."""
    from mxnet_tpu.test_utils import serving_dead_rows_keep_their_cache

    wants = [steps if i % 2 else max(1, steps // 3)
             for i in range(len(prompts))]
    outs, timing = engine.serve_group(prompts, wants)
    told, made = (timing["decode_cache_write_live_share"],
                  timing["decode_cache_write_kernel_share"])
    require(told == made,
            f"{tag}: decode_cache_write_live_share {told} beside a kernel "
            f"share of {made}")
    serving_dead_rows_keep_their_cache(engine, prompts,
                                       [i % 2 == 1 for i in range(len(prompts))])
    for i, (got, want) in enumerate(zip(outs, served)):
        require(np.array_equal(got, want[:wants[i]]),
                f"{tag}: prompt {i} with answers of {wants}: {got} != "
                f"{want[:wants[i]]} of the group of equal answers")
    live, all_ = (timing["decode_row_steps_live"],
                  timing["decode_row_steps"])
    require(live == sum(w - 1 for w in wants) < all_
            == timing["bucket"][0] * (steps - 1),
            f"{tag}: {live} live of {all_} row-steps for answers of {wants}")
    say(f"[{tag}] answers of {wants}: {live} of {all_} row-steps live, "
        f"decode_cache_write_live_share {told}, the other rows' stacks "
        f"untouched, tokens equal the group of equal answers', "
        f"decode_attn_window_read_pct "
        f"{timing['decode_attn_window_read_pct']:.2f}")


def require_cache_kernels(tag, engine, timing, platform):
    """On the chip every cache row write of the decode program goes
    through the in-place kernel (`ops/cache_write.py`) and its text
    holds no dynamic-update-slice under ``serve.cache_write``, and
    every attention call over a window of more than one lane block
    goes through the per-row kernel (`ops/cache_attention.py`); the
    CPU has neither kernel: it writes by rows and reads whole windows."""
    reads = engine._program.cache_reads[1]
    calls = sum(reads.values())
    long_windows = sum(c for (_, W, _), c in reads.items() if W > 128)
    attn_share = timing["decode_attn_kernel_share"]
    say(f"[{tag}] decode program: decode_attn_kernel_share {attn_share} "
        f"({long_windows} of {calls} attention calls over more than one "
        f"lane block), decode_attn_window_read_pct "
        f"{timing['decode_attn_window_read_pct']:.2f}")
    want = long_windows / calls if platform == "tpu" else 0.0
    require(attn_share == want,
            f"{tag}: kernel share {attn_share} of the attention calls on "
            f"{platform}, {want} expected")
    require((timing["decode_attn_window_read_pct"] < 100.0) == (want > 0),
            f"{tag}: decode_attn_window_read_pct "
            f"{timing['decode_attn_window_read_pct']} on {platform}")
    share = timing["decode_cache_write_kernel_share"]
    text = engine._programs[(timing["bucket"][0], 1)].as_text()
    by_rows = sum("dynamic-update-slice(" in line
                  and "serve.cache_write" in line
                  for line in text.splitlines())
    say(f"[{tag}] decode program: decode_cache_write_kernel_share {share}, "
        f"{by_rows} dynamic-update-slice under serve.cache_write")
    on_chip = platform == "tpu"
    require(share == float(on_chip),
            f"{tag}: kernel share {share} of the cache writes on {platform}")
    require(not (on_chip and by_rows),
            f"{tag}: {by_rows} row writes left in the decode program")


def phase_serve(size, platform, net):
    import jax.numpy as jnp

    from mxnet_tpu import serving

    vocab = net._vocab
    t0 = time.perf_counter()
    engine = serving.ServingEngine(
        net, batch_buckets=size.batch_buckets,
        prefill_floor=size.prefill_floor, dtype=jnp.bfloat16)
    engine.warmup()
    warm = time.perf_counter() - t0
    pinned = serving.trace_count()
    say(f"[serve] warmup: {engine.program_count()} AOT programs in "
        f"{warm:.1f}s (batch {engine.batch_buckets} x prefill "
        f"{engine.prefill_buckets} + decode)")
    ck, cv = engine.init_cache(1)
    require(all(on_platform(a, platform)
                for a in engine._weights + (ck, cv)),
            f"serve: weights or cache not on a {platform} device")
    # the decode step carries the cache: nothing in its compiled
    # program copies or slices out a layer of it (B x H x Dh x W)
    big = max(size.batch_buckets)
    layer = big * (ck.nbytes // ck.shape[0])
    moved = serving.whole_layer_ops(
        engine._programs[(big, 1)].as_text(), layer)
    say(f"[serve] decode program, batch {big}: {len(moved)} copy / "
        f"dynamic-slice / dynamic-update-slice of a cache layer "
        f"({layer} bytes) or more")
    require(not moved,
            f"serve: the decode program moves whole cache layers: {moved}")
    del ck, cv

    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, vocab, n).tolist()
               for n in size.prompt_lens]
    n_clients = 2
    batcher = serving.ContinuousBatcher(
        engine, max_delay_ms=20.0, max_batch=max(size.batch_buckets))
    results, errors = [None] * len(prompts), []

    def client(idx):
        try:
            for j in range(idx, len(prompts), n_clients):
                results[j] = batcher.submit(
                    prompts[j], size.new_tokens).result(timeout=300)
        except BaseException as exc:    # re-raised on the main thread
            errors.append(exc)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
    finally:
        batcher.close()
    require(not any(th.is_alive() for th in threads),
            "serve: a client thread did not finish")
    if errors:
        raise errors[0]
    for j, rec in enumerate(results):
        toks = None if rec is None else rec["tokens"]
        require(toks is not None and len(toks) == size.new_tokens
                and all(0 <= int(t) < vocab for t in toks),
                f"serve: request {j} resolved to {toks}")
    say(f"[serve] {len(prompts)} requests (prompt lengths "
        f"{size.prompt_lens}) from {n_clients} clients in "
        f"{time.perf_counter() - t0:.2f}s, "
        f"{batcher.groups_served} groups, buckets "
        f"{sorted({tuple(r['bucket']) for r in results})}")

    # a coalesced group == the same prompts one by one through the SAME
    # (batch, prefill) bucket, bitwise; a lone prompt would otherwise
    # pick the smallest batch bucket, a different program
    big = max(size.batch_buckets)
    group = [p for p in prompts
             if len(p) <= engine.prefill_buckets[0]][:big]
    require(len(group) >= 2, "serve: size gives no group to coalesce")
    together, timing = engine.serve_group(group, size.new_tokens)
    require_fed_on_device("serve", engine, group, size.new_tokens,
                          together, timing)
    require_cache_kernels("serve", engine, timing, platform)
    require_dead_rows_change_nothing("serve", engine, group,
                                     size.new_tokens, together)
    engine.batch_buckets = (big,)
    try:
        alone = [engine.serve_group([p], size.new_tokens)
                 for p in group]
    finally:
        engine.batch_buckets = tuple(sorted(size.batch_buckets))
    for j, (a, (b, tm)) in enumerate(zip(together, alone)):
        require(tm["bucket"] == timing["bucket"],
                f"serve: buckets differ {tm['bucket']} {timing['bucket']}")
        require(np.array_equal(a, b[0]),
                f"serve: prompt {j} coalesced {a} != alone {b[0]}")
    again = engine.serve_group([prompts[0]], size.new_tokens)[0][0]
    once = engine.serve_group([prompts[0]], size.new_tokens)[0][0]
    require(np.array_equal(again, once),
            f"serve: a repeated request differs: {again} {once}")
    require(serving.trace_count() == pinned,
            f"serve: {serving.trace_count() - pinned} retraces after "
            "warmup")
    say(f"[serve] coalesced == one-by-one through bucket "
        f"{timing['bucket']} for {len(group)} prompts, repeat "
        f"identical, 0 retraces after warmup")
    return {"warmup_s": warm, "programs": engine.program_count()}


# -- serve, a second family ----------------------------------------------------

@functools.lru_cache(maxsize=None)
def _normal_maker(shape, dtype, std, sharding):
    """One compiled draw per distinct leaf: most layers share theirs."""
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda key: (std * jax.random.normal(key, shape, jnp.float32)
                     ).astype(dtype),
        out_shardings=sharding)


def _seed_normal(net, seed=0):
    """Seeded values made on the parameters' own device, one leaf at a
    time (a host draw of 3.4B values would take minutes): matrices
    normal(0.02), sink logits normal(1), correction biases normal(0.1),
    gains as `initialize` left them."""
    import jax

    key = jax.random.key(seed)
    for i, (name, p) in enumerate(net.collect_params().items()):
        if name.endswith("gamma"):
            continue
        std = 1.0 if name.endswith("sink_bias") else \
            0.1 if name.endswith("router_bias") else 0.02
        old = p.data()._data
        draw = _normal_maker(tuple(old.shape), old.dtype, std, old.sharding)
        p.set_data(draw(jax.random.fold_in(key, i)))


def serve_family(tag, model, size, platform, stacks, counters_hold):
    """What every further family's phase requires of ``model(**kwargs)``
    through `ServingEngine`: the engine's tuple is the parameters' own
    buffers on the platform, the decode program moves no layer-sized
    piece of the cache's first ``stacks`` arrays, every request resolves
    to tokens, ``counters_hold(timing, lens, pads)`` (the family's own
    counters of the first group, ``lens`` its prompts' lengths; the
    bucket's ``pads`` pad rows hold one token each, which the prefill
    counts, and want none, so no decode step does), a coalesced group
    equals its requests alone, a repeated group is identical, it is fed
    on the device, its cache ops take their kernels, and rows that want
    no more token change nothing for the others.  Returns (net, engine,
    timing, what the phase reports)."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import serving

    t0 = time.perf_counter()
    net = model(**size.kwargs)
    net.initialize(init=mx.init.One(), ctx=_ctx_for(platform))
    _seed_normal(net)
    n_params = sum(int(np.prod(p.shape))
                   for p in net.collect_params().values())
    engine = serving.ServingEngine(
        net, batch_buckets=(size.batch,), prefill_floor=size.prefill_floor,
        dtype=jnp.dtype(size.kwargs.get("dtype", "float32")))
    say(f"[{tag}] {n_params / 1e9:.2f}B parameters placed and seeded "
        f"in {time.perf_counter() - t0:.1f}s")
    own = {id(p.data()._data) for p in net.collect_params().values()}
    require(all(id(a) in own for a in engine._weights),
            f"{tag}: the engine holds a second copy of a parameter")
    require(all(on_platform(a, platform)
                for a in engine._weights + engine.init_cache(1)),
            f"{tag}: weights or cache not on a {platform} device")
    vocab = net._vocab
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, vocab, n).tolist() for n in size.prompt_lens]
    t0 = time.perf_counter()
    together, timing = engine.serve_group(prompts, size.new_tokens)
    first = time.perf_counter() - t0
    pinned = serving.trace_count()
    B = timing["bucket"][0]
    text = engine._programs[(B, 1)].as_text()
    for c in engine.init_cache(B)[:stacks]:
        moved = serving.whole_layer_ops(text, c.nbytes // c.shape[0])
        require(not moved, f"{tag}: the decode program moves whole "
                           f"layers of a {tuple(c.shape)} stack: {moved}")
    for j, toks in enumerate(together):
        require(len(toks) == size.new_tokens
                and all(0 <= int(t) < vocab for t in toks),
                f"{tag}: request {j} resolved to {toks}")
    require(counters_hold(timing, size.prompt_lens, B - len(prompts)),
            f"{tag}: counters {timing}")
    # a coalesced group == each request alone through the same bucket
    for j in (0, len(prompts) - 1):
        alone, tm = engine.serve_group([prompts[j]], size.new_tokens)
        require(tm["bucket"][0] == B, f"{tag}: another batch bucket")
        if tm["bucket"] == timing["bucket"]:
            require(np.array_equal(alone[0], together[j]),
                    f"{tag}: prompt {j} coalesced {together[j]} != "
                    f"alone {alone[0]}")
    again, timing = engine.serve_group(prompts, size.new_tokens)
    require(all(np.array_equal(a, b) for a, b in zip(again, together)),
            f"{tag}: a repeated group differs")
    require_fed_on_device(tag, engine, prompts, size.new_tokens, again,
                          timing)
    require_cache_kernels(tag, engine, timing, platform)
    require_dead_rows_change_nothing(tag, engine, prompts, size.new_tokens,
                                     again)
    stats = _ctx_for(platform).jax_device.memory_stats()
    peak = stats["peak_bytes_in_use"] if stats else None
    say(f"[{tag}] group of {len(prompts)} (prompts {size.prompt_lens}) x "
        f"{size.new_tokens} tokens through bucket {timing['bucket']}: "
        f"first call {first:.1f}s, then "
        f"{timing['decode_us_per_token'] / 1e3:.2f} ms a decode step; "
        f"counters { {k: v for k, v in timing.items() if k.startswith(('moe', 'attn', 'loop', 'ssm'))} }; "
        f"peak bytes in use {peak}")
    return net, engine, timing, {
        "params": n_params, "programs": engine.program_count(),
        "retraces": serving.trace_count() - pinned, "peak_bytes": peak}


def moe_rows_hold(timing, platform):
    """The grouped product was given at least the rows its pairs need,
    and on the chip every held experts' call of the group's two programs
    went through the kernels of `ops/moe.py`, its products and its way
    out (elsewhere none: the kernels run interpreted in
    tests/test_moe_grouped.py)."""
    return timing["moe_rows_computed_decode"] \
        >= timing["moe_pairs_decode"] > 0 \
        and timing["moe_grouped_kernel_share"] == float(platform == "tpu") \
        and timing["moe_combine_kernel_share"] == float(platform == "tpu")


def packed_positions(kwargs, tile, real, S):
    """The positions a packed prefill's token-wise tiles work, one
    layer's worth: each row chunk's real tokens (``real``: every row's
    length, the pad rows' too) in whole tiles, up to the one that holds
    its last."""
    import types

    from mxnet_tpu.gluon.model_zoo import _decoder_ops

    R = _decoder_ops.chunk_rows(types.SimpleNamespace(**kwargs), len(real), S)
    tile = min(tile, R * S)
    return sum(-(-sum(real[r:r + R]) // tile) * tile
               for r in range(0, len(real), R))


def phase_serve_mimo(size, platform):
    import inspect

    from mxnet_tpu.gluon.model_zoo import mimo_v2

    layers = sum(1 for m in size.kwargs["moe_layers"] if m)
    # the constructor's own default where the benchmark's file gives none
    chunk = {"prefill_chunk_tokens": inspect.signature(
        mimo_v2.MiMoV2Model).parameters["prefill_chunk_tokens"].default,
        **size.kwargs}

    def counters_hold(timing, lens, pads):
        real = lens + (1,) * pads
        return 0 < timing["moe_pairs_prefill"] <= sum(size.prompt_lens) \
            * layers * size.kwargs["experts_per_token"] \
            and moe_rows_hold(timing, platform) \
            and timing["prefill_positions"] == sum(real) \
            and timing["prefill_positions_worked"] == packed_positions(
                chunk, mimo_v2._TILE, real, timing["bucket"][1])

    # four stacks: two kinds of cache, keys and values
    return serve_family("serve_mimo", mimo_v2.MiMoV2Model, size, platform,
                        4, counters_hold)[3]


# -- serve, a third family -----------------------------------------------------

def require_selection_equals_the_reference(tag, z, S):
    """The program's two selections on this platform against the plain
    reference's sort: the prefill kernel for every query of a block of
    ``S`` positions, the decode path for each row's last query.  The
    operands hold bfloat16 values, so both sides make the same products
    and differ in the order of their float32 sums alone."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import indexed_attention

    from benchmark.references import keye_vl2 as ref

    B, Hi, di, k = 2, z.index_heads, z.index_dim, z.topk
    rng = np.random.RandomState(3)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    qi, ki, w = draw(B, Hi, S, di), draw(B, di, S), draw(B, S, Hi)
    last = jnp.asarray([S - 1, S // 3], jnp.int32)
    got = np.asarray(jax.jit(
        lambda *a: indexed_attention.select_prefill(*a, k))(
            qi, w.astype(jnp.float32), ki, last)) != 0
    f32 = jnp.float32
    want = np.asarray(ref.select(
        qi.astype(f32).transpose(0, 2, 1, 3), w.astype(f32),
        ki.astype(f32).swapaxes(1, 2), 0, {"topk": k}, ref.product))
    rows = np.arange(B)
    index = indexed_attention.index_scores_decode(
        qi[rows, :, last], w.astype(f32)[rows, last], ki)
    live = jnp.arange(S)[None, :] <= last[:, None]
    got_decode = np.asarray(jax.jit(
        lambda i, l: indexed_attention.select_topk(i, l, k))(index, live))
    for b, n in enumerate(np.asarray(last)):
        require(np.array_equal(got[b, :n + 1], want[b, :n + 1]),
                f"{tag}: row {b}: the prefill kernel's selection differs "
                f"from the reference's in "
                f"{int((got[b, :n + 1] != want[b, :n + 1]).sum())} places")
        require(np.array_equal(got_decode[b], want[b, n]),
                f"{tag}: row {b}: the decode selection differs from the "
                f"reference's")
    say(f"[{tag}] selection of top-{k} over {S} positions: the prefill "
        f"kernel's {int(got[0].sum())} + {int(got[1, :S // 3 + 1].sum())} "
        f"keys and the decode path's equal the reference's sort")


def phase_serve_keye(size, platform):
    from mxnet_tpu.gluon.model_zoo import keye_vl2

    L, topk = size.kwargs["num_layers"], size.kwargs["topk"]

    def counters_hold(timing, lens, pads):
        lens = lens + (1,) * pads
        live = L * sum(n * (n + 1) // 2 for n in lens)
        least = L * sum(min(t + 1, topk) for n in lens for t in range(n))
        # and every prefill attention call went through the flash
        # forward kernel under the selection (ops/pallas_attention.py)
        return timing["attn_keys_live_prefill"] == live \
            and least <= timing["attn_keys_selected_prefill"] < live \
            and 0 < timing["attn_keys_selected_decode"] \
            < timing["attn_keys_live_decode"] \
            and timing["prefill_attn_kernel_share"] == 1.0 \
            and moe_rows_hold(timing, platform)

    # three stacks: keys, values, the indexer's keys
    net, engine, timing, out = serve_family(
        "serve_keye", keye_vl2.KeyeVL2Model, size, platform, 3,
        counters_hold)
    big = engine.init_cache(1)
    require(len(big) == 5 and big[2].shape[2:4]
            == (1, size.kwargs["index_dim"]),
            f"serve_keye: cache {[tuple(c.shape) for c in big]}")
    require_selection_equals_the_reference("serve_keye", net._sizes,
                                           timing["bucket"][1])
    return out


def require_short_blocks(tag, net, size, counters_hold):
    """Kimi-K2's prefill attention at blocks shorter than the kernel's
    128-position tiles, which `ServingEngine`'s default buckets (from 8
    positions) are: the forward entry against the dense oracle at the
    model's head widths, each row to its own length and zero past it;
    then short prompts through an engine with the default floor, whose
    counters must hold as the long buckets' do."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import serving
    from mxnet_tpu.ops import pallas_attention as pa

    z = net._sizes
    D, Dv = z.nope_dim + z.rope_dim, z.v_dim
    for T in (8, 64, 200):
        keys = jax.random.split(jax.random.key(T), 3)
        q, k, v = (jax.random.normal(kk, (2, z.num_heads, T, d),
                                     jnp.bfloat16)
                   for kk, d in zip(keys, (D, D, Dv)))
        lens = (T, max(T // 3, 1))
        out = np.asarray(jax.jit(lambda q, k, v, n: pa.flash_attention_forward(
            q, k, v, n, scale=D ** -0.5))(q, k, v, jnp.asarray(lens)),
            np.float32)
        ref = np.asarray(pa._dense_ref(q, k, v, True, D ** -0.5), np.float32)
        for b, n in enumerate(lens):
            err = float(np.abs(out[b, :, :n] - ref[b, :, :n]).max()
                        / np.abs(ref[b]).max())
            require(np.isfinite(out).all() and err <= KERNEL_TOL_FWD
                    and not out[b, :, n:].any(),
                    f"{tag}: a block of {T} positions, row of {n}: off the "
                    f"dense oracle by {err}, or not zero past the length")
    engine = serving.ServingEngine(
        net, batch_buckets=(size.batch,),
        dtype=jnp.dtype(size.kwargs.get("dtype", "float32")))
    rng = np.random.RandomState(4)
    buckets = []
    for lens in ((5,), (3, 8, 21, 40)):
        prompts = [rng.randint(0, net._vocab, n).tolist() for n in lens]
        toks, timing = engine.serve_group(prompts, size.new_tokens)
        B, S = timing["bucket"]
        require(S < 128 and all(
            len(t) == size.new_tokens
            and all(0 <= int(x) < net._vocab for x in t) for t in toks)
            and counters_hold(timing, lens, B - len(lens)),
            f"{tag}: prompts of {lens} through bucket {(B, S)}: {toks}, "
            f"{timing}")
        buckets.append(S)
    say(f"[{tag}] blocks of 8, 64 and 200 positions equal the dense "
        f"oracle; short prompts served through buckets {buckets}")


def phase_serve_kimi(size, platform):
    from mxnet_tpu.gluon.model_zoo import kimi_k2

    L = size.kwargs["num_layers"]

    def counters_hold(timing, lens, pads):
        # and every prefill attention call went through the flash
        # forward kernel (ops/pallas_attention.py)
        return timing["attn_latent_positions_prefill"] \
            == L * sum(n * (n + 1) // 2 for n in lens + (1,) * pads) \
            and timing["attn_latent_positions_decode"] \
            == L * sum(n + j + 1 for n in lens
                       for j in range(size.new_tokens - 1)) \
            and timing["prefill_attn_kernel_share"] == 1.0 \
            and moe_rows_hold(timing, platform)

    # one stack with no heads, and none for the values
    net, engine, _, out = serve_family(
        "serve_kimi", kimi_k2.KimiK2Model, size, platform, 1, counters_hold)
    z, big = net._sizes, engine.init_cache(1)
    require(len(big) == 3 and big[0].shape
            == (L, 1, 1, z.kv_rank + z.rope_dim, engine._W),
            f"serve_kimi: cache {[tuple(c.shape) for c in big]}")
    require_short_blocks("serve_kimi", net, size, counters_hold)
    return out


# -- serve, a fifth family: a looped stack --------------------------------------

def require_served_logits_equal_the_reference(tag, net, engine, size, ref,
                                              config):
    """Prefill then decode through the family's caches against its plain
    float32 reference's full forward (``ref``, a module of
    benchmark/references, given the model's own weights and its sizes
    under the source's keys, ``config``) at every served position of the
    shortest, the middle and the longest prompt: the reference compiles
    anew for every length."""
    import jax.numpy as jnp

    from mxnet_tpu.test_utils import serving_host_walk

    z = net._sizes
    values = {n: getattr(net, n).data()._data for n in net._names}
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, z.vocab, n).tolist()
               for n in size.prompt_lens]
    toks, logits = serving_host_walk(engine, prompts, size.new_tokens)
    worst = 0.0
    order = np.argsort(size.prompt_lens)
    for i in order[[0, len(order) // 2, -1]]:
        p = prompts[i]
        full = jnp.asarray([list(p) + list(toks[i, :-1])])
        want = ref.logits(values, full, config)[0, len(p) - 1:]
        worst = max(worst, float(np.abs(logits[i] - want).max()
                                 / np.abs(want).max()))
    require(worst <= SERVED_LOGITS_TOL,
            f"{tag}: served logits off the reference by {worst} of its "
            f"largest")
    say(f"[{tag}] 3 x {size.new_tokens} served positions equal the "
        f"float32 reference to {worst:.4f} of its largest logit")


def phase_serve_ouro(size, platform):
    from mxnet_tpu.gluon.model_zoo import ouro

    T, L = size.kwargs["loop_steps"], size.kwargs["num_layers"]
    steps = size.new_tokens - 1

    def counters_hold(timing, lens, pads):
        # every pass runs, every row leaves at the last step, and every
        # prefill attention call went through the flash forward kernel
        return timing["loop_passes_prefill"] == T \
            and timing["loop_passes_decode"] == T * steps \
            and timing["loop_exit_step_prefill"] == [0] * (T - 1) \
            + [len(lens) + pads] \
            and timing["loop_exit_step_decode"] == [0] * (T - 1) \
            + [len(lens) * steps] \
            and timing["attn_positions_prefill"] \
            == T * L * sum(n * (n + 1) // 2 for n in lens + (1,) * pads) \
            and timing["attn_positions_decode"] \
            == T * L * sum(n + j + 1 for n in lens for j in range(steps)) \
            and timing["prefill_attn_kernel_share"] == 1.0

    # two stacks, a slot for every (loop step, layer)
    net, engine, _, out = serve_family(
        "serve_ouro", ouro.OuroModel, size, platform, 2, counters_hold)
    z, big = net._sizes, engine.init_cache(1)
    require(len(big) == 3 and big[0].shape == big[1].shape
            == (T * L, 1, z.kv_heads, z.head_dim, engine._W)
            and net.qkv_weight.shape[0] == L,
            f"serve_ouro: cache {[tuple(c.shape) for c in big]}")
    from benchmark.references import ouro as ref

    require_served_logits_equal_the_reference(
        "serve_ouro", net, engine, size, ref, {
            "hidden_size": z.units, "num_hidden_layers": z.num_layers,
            "num_attention_heads": z.num_heads,
            "num_key_value_heads": z.kv_heads, "head_dim": z.head_dim,
            "intermediate_size": z.hidden_size,
            "total_ut_steps": z.loop_steps,
            "early_exit_threshold": z.exit_threshold,
            "vocab_size": z.vocab, "rms_norm_eps": z.eps,
            "rope_theta": z.rope_theta, "hidden_act": "silu",
            "tie_word_embeddings": False})
    return out


# -- serve, a sixth family -----------------------------------------------------

def phase_serve_cmda(size, platform, tag="serve_cmda"):
    from mxnet_tpu.gluon.model_zoo import cohere2_moe

    kw = size.kwargs
    R = kw["window"]
    types = kw["layer_types"]
    n_window, n_full = types.count("window"), types.count("full")
    steps = size.new_tokens - 1

    def band(n):
        m = min(n, R)
        return m * (m + 1) // 2 + (n - m) * R

    def counters_hold(timing, lens, pads):
        # the pairs each kind of layer was asked to score, the band's on
        # the window layers; the pad rows' one token counts in a prefill
        # and wants nothing after it; every prefill attention call went
        # through the flash forward kernel
        rows = lens + (1,) * pads
        causal = sum(n * (n + 1) // 2 for n in rows)
        return timing["attn_window_pairs_prefill"] \
            == n_window * sum(band(n) for n in rows) \
            and timing["attn_window_pairs_causal_prefill"] \
            == n_window * causal \
            and timing["attn_full_pairs_prefill"] == n_full * causal \
            and timing["attn_window_pairs_decode"] == n_window * sum(
                min(n + j + 1, R) for n in lens for j in range(steps)) \
            and timing["attn_full_pairs_decode"] == n_full * sum(
                n + j + 1 for n in lens for j in range(steps)) \
            and timing["prefill_attn_kernel_share"] == 1.0 \
            and 0 < timing["moe_pairs_prefill"] <= sum(lens) \
            * len(types) * kw["experts_per_token"] \
            and moe_rows_hold(timing, platform)

    # four stacks: two kinds of cache, keys and values
    net, engine, _, out = serve_family(
        tag, cohere2_moe.Cohere2MoeModel, size, platform, 4, counters_hold)
    z, big = net._sizes, engine.init_cache(1)
    require(len(big) == 6 and big[0].shape == big[1].shape
            == (n_full, 1, z.kv_heads, z.head_dim, engine._W)
            and big[2].shape == big[3].shape
            == (n_window, 1, z.kv_heads, z.head_dim, R),
            f"{tag}: cache {[tuple(c.shape) for c in big]}")
    from benchmark.references import cohere2_moe as ref

    require_served_logits_equal_the_reference(tag, net, engine, size, ref, {
        "hidden_size": z.units, "num_hidden_layers": len(types),
        "layer_types": [{"window": "sliding_attention",
                         "full": "full_attention"}[t] for t in types],
        "num_attention_heads": z.num_heads,
        "num_key_value_heads": z.kv_heads, "head_dim": z.head_dim,
        "sliding_window": R, "rope_theta": z.rope_theta, "rotary_pct": 1,
        "position_embedding_type": "rope_gptj",
        "intermediate_size": z.expert_hidden,
        "num_experts": z.experts_held[1],
        "router_experts": z.router_experts,
        "experts_held": list(z.experts_held),
        "num_experts_per_tok": z.experts_per_token,
        "num_shared_experts": z.shared_experts,
        "shared_expert_combination_strategy": "average",
        "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
        "first_k_dense_replace": 0, "use_parallel_block": True,
        "use_qk_norm": False, "attention_bias": False,
        "use_gated_activation": True, "hidden_act": "silu",
        "tie_word_embeddings": True, "logit_scale": z.logit_scale,
        "layer_norm_eps": z.eps, "vocab_size": z.vocab})
    return out


# -- sharded -------------------------------------------------------------------

SHARDED_LAYOUTS = (({"dp": 4}, "fsdp"), ({"tp": 2, "dp": 2}, "tp"))


# -- serve, a seventh family: states beside the stacks --------------------------

def require_states_in_place(tag, engine, B, block):
    """On the chip the states are updated where they lie: the decode
    program of batch bucket ``B`` makes no value of a layer's states'
    shape ``(B,) + block`` float32 at all (a byte threshold would also
    catch a small size's weights)."""
    import re

    dims = ",".join(str(d) for d in (B,) + tuple(block))
    layer = re.compile(r" = f32\[(1,)?%s\]" % dims)
    made = [line.split(" = ")[0].strip() for line in engine._programs[
        (B, 1)].as_text().splitlines() if layer.search(line)]
    require(not made, f"{tag}: the decode program makes a layer of the "
                      f"states: {made[:8]}")
    say(f"[{tag}] decode program, batch {B}: no value of a layer's states "
        f"({dims} float32)")


def phase_serve_jamba(size, platform):
    from mxnet_tpu.gluon.model_zoo import jamba
    from mxnet_tpu.ops import ssm

    steps = size.new_tokens - 1
    S, kw = size.prefill_floor, size.kwargs
    La = sum(i % kw["attn_period"] == kw["attn_offset"]
             for i in range(kw["num_layers"]))
    Lm = kw["num_layers"] - La

    def counters_hold(timing, lens, pads):
        # the scan stopped at each row's length (the kernel walks whole
        # chunks to it, the plain path the bucket), every live row's
        # state moved on once a step and layer, and on the chip both
        # went through their kernels, the update told the live rows
        real = lens + (1,) * pads
        Tc = ssm.scan_chunk(S)
        walked = sum(-(-n // Tc) * Tc for n in real) \
            if platform == "tpu" else len(real) * S
        worked = packed_positions(kw, jamba._TILE, real, S)
        on = float(platform == "tpu")
        return timing["ssm_positions_prefill"] == Lm * sum(real) \
            and timing["prefill_positions"] == sum(real) \
            and timing["prefill_positions_worked"] == worked \
            and timing["ssm_positions_scanned_prefill"] == Lm * walked \
            and timing["ssm_row_updates_decode"] == Lm * len(lens) * steps \
            and timing["attn_pairs_prefill"] \
            == La * sum(n * (n + 1) // 2 for n in real) \
            and timing["attn_positions_decode"] \
            == La * sum(n + j + 1 for n in lens for j in range(steps)) \
            and timing["prefill_attn_kernel_share"] == 1.0 \
            and timing["prefill_state_scan_kernel_share"] == on \
            and timing["decode_state_update_kernel_share"] == on \
            and timing["decode_state_update_live_share"] == on

    # two stacks, of which no layer moves; the CPU's plain update writes
    # a layer's states whole, at any size, so it is checked nothing
    net, engine, timing, out = serve_family(
        "serve_jamba", jamba.JambaModel, size, platform,
        2 if platform == "tpu" else 0, counters_hold)
    z, big = net._sizes, engine.init_cache(1)
    if platform == "tpu":
        require_states_in_place("serve_jamba", engine, timing["bucket"][0],
                                (z.d_state, z.inner))
    require([tuple(c.shape) for c in big] == [
        (1, 1, z.kv_heads, z.head_dim, -(-engine._W // 128) * 128)] * 2
        + [(Lm, 1, z.d_state, z.inner),
           (Lm, 1, (z.d_conv - 1) * z.inner), (7,)]
        and str(big[2].dtype) == "float32",
        f"serve_jamba: cache {[(tuple(c.shape), c.dtype) for c in big]}")
    from benchmark.references import jamba as ref

    require_served_logits_equal_the_reference(
        "serve_jamba", net, engine, size, ref, {
            "hidden_size": z.units, "num_hidden_layers": z.num_layers,
            "num_attention_heads": z.num_heads,
            "num_key_value_heads": z.kv_heads,
            "intermediate_size": z.hidden_size, "vocab_size": z.vocab,
            "rms_norm_eps": z.eps, "hidden_act": "silu",
            "tie_word_embeddings": True, "mamba_expand": z.expand,
            "mamba_d_state": z.d_state, "mamba_dt_rank": z.dt_rank,
            "mamba_d_conv": z.d_conv, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "attn_layer_period": z.attn_period,
            "attn_layer_offset": z.attn_offset, "num_experts": 1})
    return out


# -- the Mamba-2 kernels, and an eighth family: heads' states and experts -------

# the kernels against their plain paths, of the largest value: the
# update on the vector unit in float32 (the sums in another order); the
# scan's products with float32 operands, which the MXU makes of several
# bfloat16 passes (on the v5e 1.1e-4 to 3.0e-4 over two draws, PR 49;
# interpreted 1e-5), and with bfloat16 operands (1.8e-3 to 2.5e-3)
SSD_TOL_F32, SSD_TOL_SCAN_F32, SSD_TOL_BF16 = 1e-4, 1e-3, 2e-2


def phase_ssd(size, platform):
    """`ops/ssm.py`'s Mamba-2 scan and one-position update through their
    kernels (compiled on the chip, interpreted elsewhere) against their
    plain paths: ragged lengths, the state returned, ``live``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import ssm

    H, P, N, S = size.H, size.P, size.N, size.S
    R, B = len(size.lengths), len(size.live)
    here = platform != "tpu"
    chunk, heads, rows = size.tiles or (None, None, None)
    require(here or (ssm._mamba2_scan_fits(H, P, N)
                     and ssm._mamba2_update_fits(H * P, N)),
            "ssd: the kernels would not take these sizes on the chip")
    ks = jax.random.split(jax.random.key(S), 7)
    x = jax.random.normal(ks[0], (R, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (R, S, H)) - 1.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=-6.0, maxval=6.0))
    Bm, Cm = (0.3 * jax.random.normal(k, (R, S, N)) for k in ks[3:5])
    D = jax.random.normal(ks[5], (H,))
    n = jnp.array(size.lengths, jnp.int32)

    def err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        require(np.isfinite(a).all(), "ssd: non-finite output")
        return float(np.abs(a - b).max() / np.abs(b).max())

    def real(y):
        return jnp.where(jnp.arange(S)[None, :, None, None]
                         < n[:, None, None, None], y, 0.0)

    out = {}
    y0, s0 = jax.jit(ssm._mamba2_scan_plain)(x, dt, A, Bm, Cm, D, n)
    for name, operands, tol in (("float32", None, SSD_TOL_SCAN_F32),
                                ("bfloat16", jnp.bfloat16, SSD_TOL_BF16)):
        t0 = time.perf_counter()
        scan = jax.jit(functools.partial(
            ssm._mamba2_scan_kernel_call, operands=operands, interpret=here,
            chunk=chunk, heads=heads)).lower(x, dt, A, Bm, Cm, D, n)
        require(here or "tpu_custom_call" in scan.as_text(),
                "ssd: no Mosaic custom call in the lowered scan")
        y1, s1 = scan.compile()(x, dt, A, Bm, Cm, D, n)
        jax.block_until_ready(s1)
        secs = time.perf_counter() - t0
        e = (err(real(y1), real(y0)), err(s1, s0))
        say(f"[ssd] scan ({R} x {S}, {H} heads of {P} over {N}), {name} "
            f"operands: compile+run {secs:.1f}s, y err {e[0]:.5f}, state "
            f"err {e[1]:.5f} (tol {tol})")
        require(max(e) <= tol, f"ssd: the scan's {name} products off the "
                               f"plain path by {e}")
        require(on_platform(s1, platform), "ssd: state off the device")
        out["scan_" + name] = e
    state = jax.random.normal(ks[6], (size.L, B, H * P, N))
    live = jnp.array(size.live, bool)
    args = (x[:B, 0], dt[:B, 0], A, Bm[:B, 0], Cm[:B, 0], D, live)
    ya, sa = jax.jit(lambda st, *a: ssm._mamba2_update_plain(st, 1, *a))(
        state, *args)
    update = jax.jit(lambda st, *a: ssm._mamba2_update_kernel_call(
        st, 1, *a, interpret=here, rows=rows), donate_argnums=(0,))
    yb, sb = update(state + 0, *args)
    e = (err(yb, ya), err(sb, sa))
    dead = [r for r in range(B) if not size.live[r]]
    require(max(e) <= SSD_TOL_F32,
            f"ssd: the update off the plain path by {e}")
    require(all(np.array_equal(np.asarray(sb[1, r]), np.asarray(state[1, r]))
                for r in dead)
            and np.array_equal(np.asarray(sb[0]), np.asarray(state[0])),
            "ssd: a row that is not live, or another layer, changed")
    say(f"[ssd] update ({size.L} x {B} x {H * P} x {N}, {B - len(dead)} "
        f"live): y err {e[0]:.6f}, state err {e[1]:.6f} (tol "
        f"{SSD_TOL_F32}); {len(dead)} dead rows bit for bit")
    out["update"] = e
    return out


MOE_GROUPED_TOL = 1e-4


def phase_moe_grouped(size, platform):
    """`ops/moe.py`'s grouped product through its kernel (compiled on
    the chip, interpreted elsewhere) against ``lax.ragged_dot`` over the
    same layer of the same stack, both products of a pass (the second
    fed the same rows), at layer 1 of a stack so that the offset into it
    is used: the largest difference over the rows that hold a pair, as a
    share of the largest value, and on the chip the time of a pass by
    each path beside the time the hit experts' bytes take at the chip's
    memory rate."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import moe

    here = platform != "tpu"
    L, reps = size.layers, size.reps
    out = {}
    for name, P, M, F, n, sizes in size.cases:
        require(here or moe._fits(P, M, F, n, jnp.bfloat16),
                f"moe_grouped: the kernel would not take {name} on the chip")
        ks = jax.random.split(jax.random.key(P + M + F), 3)
        w13 = jax.random.normal(ks[0], (L, n, M, 2 * F), jnp.bfloat16) \
            * M ** -0.5
        w2 = jax.random.normal(ks[1], (L, n, F, M), jnp.bfloat16) * F ** -0.5
        x = jax.random.normal(ks[2], (P, M), jnp.bfloat16)
        hi = jnp.cumsum(jnp.array(sizes, jnp.int32))
        lo = hi - jnp.array(sizes, jnp.int32)
        total = int(hi[-1])

        def act(h):
            return (jax.nn.silu(h[:, :F]) * h[:, F:]).astype(jnp.bfloat16)

        def by_kernel(x, w13, w2, l, h=None):
            walk = moe._walk(lo, hi, l * n, P)
            g = moe._grouped_kernel_call(
                x, w13.reshape(L * n, M, 2 * F), walk, interpret=here)
            return g, moe._grouped_kernel_call(
                act(g) if h is None else h, w2.reshape(L * n, F, M), walk,
                interpret=here)

        def by_ragged(x, w13, w2, l, h=None):
            # the parent's call: the stack whole, layer l's groups in it
            every = lax.dynamic_update_slice(
                jnp.zeros((L * n,), jnp.int32), hi - lo, (l * n,))
            g = lax.ragged_dot(x, w13.reshape(L * n, M, 2 * F), every,
                               preferred_element_type=jnp.float32)
            return g, lax.ragged_dot(
                act(g) if h is None else h, w2.reshape(L * n, F, M), every,
                preferred_element_type=jnp.float32)

        g0, _ = jax.jit(by_ragged)(x, w13, w2, 1)
        h = act(g0)
        want = jax.jit(by_ragged)(x, w13, w2, 1, h)
        got = jax.jit(by_kernel)(x, w13, w2, jnp.int32(1), h)
        require(here or "tpu_custom_call" in jax.jit(by_kernel).lower(
            x, w13, w2, 1).as_text(),
            "moe_grouped: no Mosaic custom call in the lowered product")
        err = []
        for a, b in zip(got, want):
            a, b = (np.asarray(v[:total], np.float32) for v in (a, b))
            require(np.isfinite(a).all(), f"moe_grouped: {name} not finite")
            err.append(float(np.abs(a - b).max() / np.abs(b).max()))
        require(max(err) <= MOE_GROUPED_TOL,
                f"moe_grouped: {name} off lax.ragged_dot by {err}")
        out[name] = {"err": err}
        line = f"[moe_grouped] {name} ({P} x {M} x {2 * F}, " \
            f"{sum(s > 0 for s in sizes)} of {n} hit by {total}): " \
            f"err {err[0]:.1e}, {err[1]:.1e} (tol {MOE_GROUPED_TOL})"
        if not here:
            def passes(path):
                def many(x, w13, w2):
                    def one(i, acc):
                        return acc + path(x, w13, w2, i % L)[1][0, 0]
                    return lax.fori_loop(0, reps, one, jnp.float32(0))
                run = jax.jit(many)
                jax.block_until_ready(run(x, w13, w2))
                t0 = time.perf_counter()
                jax.block_until_ready(run(x, w13, w2))
                return (time.perf_counter() - t0) / reps * 1e6

            us = {"kernel_us": passes(by_kernel),
                  "ragged_us": passes(by_ragged),
                  "bytes_us": sum(s > 0 for s in sizes) * 3 * M * F * 2
                  / 819e9 * 1e6}
            out[name].update(us)
            line += "; a pass {kernel_us:.0f} us by the kernel, " \
                "{ragged_us:.0f} by lax.ragged_dot, {bytes_us:.0f} the hit " \
                "experts' bytes at 819 GB/s".format(**us)
        say(line)
        del w13, w2
    for case in size.ways_out:
        out[case[0] + ".way_out"] = way_out_holds(case, reps, platform)
    return out


def way_out_holds(case, reps, platform):
    """A pass's way out into the stream (`ops/moe.py`): the walk of the
    token tiles (compiled on the chip, interpreted elsewhere) against
    XLA's scatter-add over the same rows, the rows past the pairs NaN:
    the largest difference as a share of the largest value, the tiles no
    row falls in bit for bit, and on the chip the time of a pass by each
    path beside the time its bytes take (the touched tiles read and
    written, the rows read, and gathered before where the stream is
    more than a tile)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import moe

    name, T, P, M, live, experts, row = case
    here = platform != "tpu"
    require(here or moe._combine_fits(T, P, M),
            f"moe_grouped: the way out's kernel would not take {name}")
    rng = np.random.RandomState(T + P)
    # each row of the stream real to a length of its own; the pass's
    # tokens in expert order, an expert's a sorted draw of the real ones
    # (a decode step's rows are one token long: half of them live)
    real = np.sort(rng.choice(T, max(1, T // 2), replace=False)) \
        if row == 1 else np.concatenate([r * row + np.arange(rng.randint(
            row // 4, row + 1)) for r in range(T // row)])
    tok = np.full(P, T, np.int32)
    at = 0
    for e in range(experts):
        n = min(live // experts + (e < live % experts), len(real))
        tok[at:at + n] = np.sort(rng.choice(real, n, replace=False))
        at += n
    tt = moe._combine_tiles(T, P, M)[0]
    tiles = np.unique(tok[:at] // tt)
    ks = jax.random.split(jax.random.key(T + M), 3)
    y = jax.random.normal(ks[0], (T, M), jnp.float32)
    o = jnp.where((jnp.arange(P) < at)[:, None],
                  jax.random.normal(ks[1], (P, M), jnp.float32), jnp.nan)
    w = jax.random.uniform(ks[2], (P,), jnp.float32)
    tok = jnp.asarray(tok)

    def by_kernel(y, o, w, tok):
        return moe._combine_kernel_call(y, o, w, tok, interpret=here)

    want = np.asarray(jax.jit(moe._combine_plain)(y, o, w, tok))
    got = np.asarray(jax.jit(by_kernel)(y, o, w, tok))
    require(np.isfinite(got).all(), f"moe_grouped: {name}'s way out not "
            f"finite")
    err = float(np.abs(got - want).max() / np.abs(want).max())
    require(err <= MOE_GROUPED_TOL,
            f"moe_grouped: {name}'s way out off the scatter-add by {err}")
    kept = np.ones(T // tt, bool)
    kept[tiles] = False
    kept = np.repeat(kept, tt)
    require((got[kept] == np.asarray(y)[kept]).all(),
            f"moe_grouped: {name}'s way out moved a tile no row falls in")
    out = {"err": err, "tiles": int(len(tiles))}
    line = f"[moe_grouped] {name}'s way out ({at} of {P} rows into " \
        f"{len(tiles)} of {T // tt} tiles of {tt} x {M}): err {err:.1e}"
    del want, got
    if not here:
        def passes(path):
            # the tokens shifted by whole tiles and the weights scaled
            # with the pass, so that nothing is hoisted out of the loop
            def one(i, y):
                t = jnp.where(tok < T, (tok + i * tt) % T, T)
                return path(y, o, w * (1.0 + 0.001 * i), t)
            run = jax.jit(lambda y: lax.fori_loop(0, reps, one, y),
                          donate_argnums=(0,))
            warm = jax.block_until_ready(run(jnp.array(y)))
            t0 = time.perf_counter()
            jax.block_until_ready(run(warm))
            return (time.perf_counter() - t0) / reps * 1e6

        out.update(
            kernel_us=passes(by_kernel), scatter_us=passes(moe._combine_plain),
            bytes_us=(2 * len(tiles) * tt + (3 if T > tt else 1) * at)
            * M * 4 / 819e9 * 1e6)
        line += "; a pass {kernel_us:.0f} us by the walk, {scatter_us:.0f} " \
            "by the scatter-add, {bytes_us:.0f} its bytes at 819 GB/s" \
            .format(**out)
    say(line)
    return out


def phase_serve_granite(size, platform):
    from mxnet_tpu.gluon.model_zoo import granite_hybrid
    from mxnet_tpu.ops import ssm

    steps = size.new_tokens - 1
    S, kw = size.prefill_floor, size.kwargs
    La = kw["layer_types"].count("attention")
    Lm = len(kw["layer_types"]) - La

    def counters_hold(timing, lens, pads):
        # Jamba's counters under Jamba's names (the scan to each row's
        # length in whole chunks of the Mamba-2 kernel, every live row's
        # heads moved on once a step and layer, both through their
        # kernels on the chip, the update told the live rows) and the
        # expert families'
        real = lens + (1,) * pads
        Tc = ssm.mamba2_chunk()
        walked = sum(-(-n // Tc) * Tc for n in real) \
            if platform == "tpu" else len(real) * S
        worked = packed_positions(kw, granite_hybrid._TILE, real, S)
        on = float(platform == "tpu")
        return timing["ssm_positions_prefill"] == Lm * sum(real) \
            and timing["prefill_positions"] == sum(real) \
            and timing["prefill_positions_worked"] == worked \
            and timing["ssm_positions_scanned_prefill"] == Lm * walked \
            and timing["ssm_row_updates_decode"] == Lm * len(lens) * steps \
            and timing["attn_pairs_prefill"] \
            == La * sum(n * (n + 1) // 2 for n in real) \
            and timing["attn_positions_decode"] \
            == La * sum(n + j + 1 for n in lens for j in range(steps)) \
            and timing["prefill_attn_kernel_share"] == 1.0 \
            and timing["prefill_state_scan_kernel_share"] == on \
            and timing["decode_state_update_kernel_share"] == on \
            and timing["decode_state_update_live_share"] == on \
            and 0 < timing["moe_pairs_prefill"] \
            <= (Lm + La) * sum(real) * kw["experts_held"][1] \
            and moe_rows_hold(timing, platform)

    # two stacks, of which no layer moves; the CPU's plain update writes
    # a layer's states whole, at any size, so it is checked nothing
    net, engine, timing, out = serve_family(
        "serve_granite", granite_hybrid.GraniteHybridModel, size, platform,
        2 if platform == "tpu" else 0, counters_hold)
    z, big = net._sizes, engine.init_cache(1)
    if platform == "tpu":
        require_states_in_place("serve_granite", engine,
                                timing["bucket"][0], (z.inner, z.d_state))
    require([tuple(c.shape) for c in big] == [
        (1, 1, z.kv_heads, z.head_dim, -(-engine._W // 128) * 128)] * 2
        + [(Lm, 1, z.inner, z.d_state), (Lm, 1, (z.d_conv - 1) * z.conv_dim),
           (Lm + La, 2, z.experts_held[1] + 3), (7,)]
        and str(big[2].dtype) == "float32",
        f"serve_granite: cache {[(tuple(c.shape), c.dtype) for c in big]}")
    from benchmark.references import granite_hybrid as ref

    require_served_logits_equal_the_reference(
        "serve_granite", net, engine, size, ref, {
            "hidden_size": z.units, "num_hidden_layers": Lm + La,
            "layer_types": list(z.layer_types),
            "num_attention_heads": z.num_heads,
            "num_key_value_heads": z.kv_heads,
            "intermediate_size": z.expert_hidden,
            "shared_intermediate_size": z.shared_hidden,
            "num_local_experts": z.experts_held[1],
            "router_experts": z.router_experts,
            "experts_held": list(z.experts_held),
            "num_experts_per_tok": z.experts_per_token,
            "vocab_size": z.vocab, "rms_norm_eps": z.eps,
            "hidden_act": "silu", "tie_word_embeddings": True,
            "mamba_expand": z.inner / z.units,
            "mamba_n_heads": z.ssm_heads, "mamba_d_head": z.ssm_head_dim,
            "mamba_d_state": z.d_state, "mamba_n_groups": 1,
            "mamba_d_conv": z.d_conv, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "attention_bias": False,
            "position_embedding_type": "nope",
            "embedding_multiplier": z.embedding_multiplier,
            "residual_multiplier": z.residual_multiplier,
            "attention_multiplier": z.attention_multiplier,
            "logits_scaling": z.logits_scaling})
    return out


def phase_sharded(size, platform, single_loss0, single_peak):
    """The train path again under shard_model, on four devices: same
    seed, so step 0 must reproduce the single-chip loss."""
    import jax

    from mxnet_tpu import parallel

    devs = jax.devices()[:4]
    out = []
    try:
        for axes, mode in SHARDED_LAYOUTS:
            tag = f"sharded {mode} {axes}"
            net, trainer, ids = _build(size, platform)
            mesh = parallel.make_mesh(axes=axes, devices=devs)
            parallel.shard_model(net, mesh, mode=mode)
            losses, secs, _ = _run_steps(tag, net, trainer, ids,
                                         size.sharded_steps)
            for name, p in net.collect_params().items():
                n = len(p.data()._data.sharding.device_set)
                require(n == 4, f"{tag}: {name} spans {n} device(s)")
            spec = ids._data.sharding.spec
            require(len(spec) > 0 and spec[0] == "dp",
                    f"{tag}: the batch is laid out {spec}, not split on "
                    "dp")
            require(abs(losses[0] - single_loss0) <= SHARDED_LOSS_TOL,
                    f"{tag}: step-0 loss {losses[0]} vs single-chip "
                    f"{single_loss0}")
            out.append({"mode": mode, "axes": axes, "losses": losses,
                        "first_step_s": secs[0]})
            del net, trainer, ids
            gc.collect()
    finally:
        parallel.set_default_mesh(None)
    # peak_bytes_in_use is a high-water mark since process start: device
    # 0 still carries the single-chip phases, so the other three speak
    # for the sharded runs
    stats = [d.memory_stats() for d in devs]
    require(all(s is not None for s in stats) or platform == "cpu",
            "sharded: a device reports no memory stats")
    if all(s is not None for s in stats):
        peaks = [s["peak_bytes_in_use"] for s in stats]
        say(f"[sharded] peak bytes in use per device {peaks}; "
            f"single-chip peak {single_peak}")
        rest = peaks[1:]
        require(max(rest) <= 2 * min(rest),
                f"sharded: peaks differ in order across chips: {peaks}")
        require(max(rest) < single_peak,
                f"sharded: per-chip peak {max(rest)} is not below the "
                f"single-chip peak {single_peak}")
    return out


# -- main ----------------------------------------------------------------------

def main(argv=()):
    """``--phases a,b`` runs the device phase and then only those named
    (``serve`` needs ``train``): a builder who changed one family spends
    the chip's minutes on that one."""
    platform = "tpu"    # not configurable: this script proves the chip
    t_start = time.perf_counter()
    timings = {}
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--phases", default="")
    only = set(filter(None, ap.parse_args(list(argv)).phases.split(",")))

    def run(name, fn, *args):
        if only and name != "device" and name not in only:
            say(f"[{name}] skipped: --phases {sorted(only)}")
            return None
        t0, c0 = time.perf_counter(), len(COMPILES)
        res = fn(*args)
        timings[name] = {
            "seconds": round(time.perf_counter() - t0, 1),
            "compiles": len(COMPILES) - c0,
            "compile_seconds": round(sum(COMPILES[c0:]), 1)}
        say(f"[{name}] ok: {json.dumps(timings[name])}")
        return res

    device = run("device", phase_device, platform)
    watch_compiles()
    from mxnet_tpu import engine

    say(f"[device] compile cache: {engine.ensure_compile_cache()}")
    run("sync", phase_sync, FULL, platform)
    run("kernel", phase_kernel, FULL, platform)
    train = run("train", phase_train, FULL, platform)
    if train is not None:
        run("serve", phase_serve, FULL, platform, train.pop("net"))
    gc.collect()
    run("serve_mimo", phase_serve_mimo, mimo_full(), platform)
    gc.collect()
    run("serve_keye", phase_serve_keye, keye_small(), platform)
    gc.collect()
    run("serve_kimi", phase_serve_kimi, kimi_small(), platform)
    gc.collect()
    run("serve_ouro", phase_serve_ouro, ouro_small(), platform)
    gc.collect()
    run("serve_cmda", phase_serve_cmda, cmda_small(), platform)
    gc.collect()
    run("serve_cmda_full", phase_serve_cmda, cmda_full(), platform,
        "serve_cmda_full")
    gc.collect()
    run("serve_jamba", phase_serve_jamba, jamba_small(), platform)
    gc.collect()
    run("ssd", phase_ssd, ssd_full(), platform)
    gc.collect()
    run("serve_granite", phase_serve_granite, granite_small(), platform)
    gc.collect()
    run("moe_grouped", phase_moe_grouped, moe_grouped_full(), platform)
    gc.collect()
    import jax

    if train is None:
        say("[sharded] skipped: it is held to the train phase's loss")
    elif jax.local_device_count() >= 4:
        run("sharded", phase_sharded, FULL, platform,
            train["losses"][0], train["peak_bytes"])
    else:
        say(f"[sharded] skipped: {jax.local_device_count()} local "
            "device(s), four needed")
    say(json.dumps({"phases": timings, "total_seconds": round(
        time.perf_counter() - t_start, 1)}))
    say(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main(sys.argv[1:])

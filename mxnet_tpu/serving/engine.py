"""AOT bucketed serving engine for the decoder-only model zoo.

The training side captures the whole step as ONE donated jit program
(gluon/captured.py); this module applies the same discipline to the
request path.  Four properties, all pinned by tests/test_serving.py:

- **Zero retraces after warmup.**  Every (batch bucket × seq bucket)
  pair gets ONE ahead-of-time program via the same
  ``jit(...).lower(*avals).compile()`` path ``CapturedStep`` uses for
  the train step; requests are padded to the nearest bucket and run
  through the pre-compiled executable directly — the jit tracing
  machinery is never re-entered on the request path.  A module-level
  trace counter (incremented as a Python side effect inside the traced
  function, so it ticks exactly once per compile) makes the pin
  checkable: ``trace_count()`` must not move after ``warmup()``.
- **A cached step the model provides.**  The engine holds no model's
  layer body: ``model.decoder_program()`` hands it the family's weights
  (a flat tuple), its cache (a flat tuple, every array donated to each
  step and handed back by it) and one traced step (`ServingEngine`'s
  docstring has the contract; `gluon/model_zoo/gpt.py` and
  `mimo_v2.py` each provide one).  A family carries its cache whole
  through its layers and writes only the new rows, so a step's outputs
  are its inputs' buffers (tests pin the aliasing and, with
  ``whole_layer_ops``, that the compiled decode program moves no
  layer-sized buffer).  Prefill (S = seq bucket) and decode (S = 1)
  are separate bucketed programs of the SAME traced function, and the
  step returns the logits of one position a row: the host never reads
  ``(B, S, vocab)``.
- **A decode loop that does not wait for the host.**  Around the
  family's step each compiled program also picks the greedy token
  (float32 ``argmax``, lowest index on ties) and the next position, so
  every input of decode step j+1 is an output of step j or a constant
  of the group that is already on the device.  ``serve_group``
  dispatches ``_STEPS_IN_FLIGHT`` steps ahead and reads the ``(B, 1)``
  ids of a step while the next one runs; the ``(B, vocab)`` logits are
  fetched only for a request with a ``temperature``, for which the
  host draws the token.  How many tokens each row still wants is
  carried the same way, so a decode step knows which rows are live: a
  row past its answer, or a pad row, attends to nothing, writes
  nothing into the cache and goes to no expert (docs/serving.md, "The
  decoder program").
- **Hot reload without recompile.**  Weights are *arguments* to the
  compiled programs, not closed-over constants: swapping in new
  weights (from a live model or an AsyncCheckpointer state dict) is an
  array replacement under a lock — no retrace, no dropped requests
  (serving/replica.py swaps between batches).

The step takes a **per-row position vector**, so a coalesced batch can
mix prompt lengths: each row's cache writes land at its own offset and
under its own causal mask.  Every op of a family's step is
row-independent, which is what makes a coalesced batch bitwise equal to
the same requests served one-by-one through the same batch bucket — pad
rows can never leak into real rows.

Tensor-parallel serving (``mesh=``) is the family's to place: GPT
shards its stacks and its cache over ``tp_axis``; a family that serves
from one chip refuses a mesh.
"""

from __future__ import annotations

import collections
import gc
import os
import re
import threading

from .. import telemetry
from ..base import MXNetError
from ..obs.spans import wall
from ..profiler import scope
from ..ops.sampling import _sample

# -- counters (the retrace-free pin) -------------------------------------------

_LOCK = threading.Lock()
_TRACE_COUNT = 0      # ticks inside the traced fn: once per (re)trace
_COMPILE_COUNT = 0    # lower().compile() calls
_DISPATCH_COUNT = 0   # compiled-program invocations

# Decode steps of a greedy group dispatched and not yet read.  With two,
# the device finds step j+1 queued when step j ends, while the host
# reads the ids of step j: a dispatch costs well under a millisecond of
# a step of several.  A constant, not an option: a deeper queue buys
# nothing once the device never waits, and hands a streaming caller its
# tokens later.
_STEPS_IN_FLIGHT = 2


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
    log2(W) programs cover every prompt length."""
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


def _window_read_pct(reads, lens, steps, left=None):
    """Of the ``B x W`` positions a decode attention call could read,
    over a group's ``steps`` decode steps, the percentage in the lane
    blocks it was asked for.  ``reads``: the program's
    ``cache_reads[1]``, calls by (path, W, lanes); step d of a row
    with a prompt of ``lens[b]`` tokens holds ``lens[b] + d + 1``
    positions and is read in whole blocks of ``lanes``.  ``left``
    (B,): the decode steps row b is live in (None: all); past them it
    is asked for no position and read in its first block."""
    import numpy as np

    held = lens[:, None].astype(np.int64) + np.arange(1, steps + 1)[None, :]
    if left is not None:
        held = np.where(np.arange(steps)[None, :] < left[:, None], held, 1)
    asked = whole = 0
    for (_, W, lanes), calls in reads.items():
        asked += calls * int(np.minimum(-(-held // lanes) * lanes, W).sum())
        whole += calls * held.size * W
    return 100.0 * asked / whole


# -- the memory ledger: what the chip holds, by name ---------------------------

# the runtime's ``memory_stats()`` under the ledger's names
_MEMORY_STATS = (("memory_in_use_bytes", "bytes_in_use"),
                 ("memory_peak_bytes", "peak_bytes_in_use"),
                 ("memory_limit_bytes", "bytes_limit"),
                 ("memory_largest_free_block_bytes",
                  "largest_free_block_bytes"),
                 ("memory_num_allocs", "num_allocs"))


def _device_memory(device):
    """What the runtime says ``device`` holds at this instant, under
    the ledger's names; ``{}`` on a backend that keeps no such count
    (the CPU)."""
    stats = device.memory_stats()
    if not stats:
        return {}
    return {name: int(stats[key]) for name, key in _MEMORY_STATS
            if key in stats}


def _in_use_and_peak(now, suffix=""):
    """A `_device_memory` reading's two counts as a span's attributes,
    ``in_use`` and ``peak``; none off a chip."""
    return {k + suffix: now[f"memory_{k}_bytes"] for k in ("in_use", "peak")
            if f"memory_{k}_bytes" in now}


# the gauges a group's end sets, and the ledger's field each shows
_GROUP_GAUGES = (("memory.cache_bytes_reserved", "cache_bytes_reserved"),
                 ("memory.cache_bytes_written", "cache_bytes_written"),
                 ("memory.in_use_bytes", "memory_in_use_bytes"),
                 ("memory.unaccounted_bytes", "memory_unaccounted_bytes"))


def _held(weights):
    """By device of this process: the bytes of ``weights`` that lie on
    it, a buffer that several leaves share counted once."""
    at = {}
    for w in weights:
        for shard in w.addressable_shards:
            at.setdefault(shard.device, {})[
                shard.data.unsafe_buffer_pointer()] = shard.data.nbytes
    return {device: sum(each.values()) for device, each in at.items()}


class ServingEngine:
    """Bucketed AOT prefill/decode over a model's decoder program.

    The engine owns buckets, ahead-of-time compilation, donation, hot
    reload, the request path with its spans and counters.  The model
    owns its layers: ``model.decoder_program(dtype=, mesh=, tp_axis=)``
    returns the family's program (docs/serving.md, "The decoder
    program"):

    - ``weights()`` → a flat tuple of arrays, the compiled programs'
      first argument (swapped on reload, never closed over);
    - ``init_cache(B)`` → a flat tuple of arrays, every one donated to
      each step and handed back by it;
    - ``step(w, cache, pos, last, toks, live=None)`` → ``(cache,
      logits (B, vocab))``: ``pos`` (B,) each row's first position,
      ``last`` (B,) the index in the block of each row's last real
      token, ``toks`` (B, S) with S a prefill bucket or 1, ``live``
      (B,) bool in a decode step the rows that still want a token
      (None: all).  The engine compiles it with two more outputs, the
      next step's own inputs: the greedy ids ``(B, 1)`` and the next
      positions ``pos + last + 1``; the decode program also takes
      ``left`` (B,), the tokens each row wants beyond the one it is
      fed, makes ``live = left > 0`` of it and returns it one lower;
    - ``window`` and ``vocab``; optionally ``weights_from_state(state)``
      (a checkpoint convention), ``counters(cache)`` (a dict read back
      once a group, merged into the timings), ``cache_writes`` (by
      block length S, the row writes `ops/cache_write.py` counted by
      path while the step was traced), ``cache_reads`` (likewise the
      attention calls `ops/cache_attention.py` counted),
      ``block_attends`` (likewise a prefill block's attention calls
      inside itself, by path: ``"kernel"`` through
      `ops/pallas_attention.py`), ``state_updates`` (likewise the rows
      whose state `ops/ssm.py` moved on, by path), ``grouped_products``
      (likewise the held experts' calls, by the path `ops/moe.py` took
      for their grouped products and by the one their passes took into
      the stream) and
      ``signature`` (what a reloaded model must share beyond shapes).

    ``serve_group(prompts, max_new_tokens)`` is the whole request path:
    pad to the nearest (batch, seq) bucket, one prefill dispatch, one
    decode dispatch per further token, every one a pre-compiled program.
    Greedy tokens are picked inside the programs: a step is fed the
    step before's ids and positions on the device, the host dispatches
    ``_STEPS_IN_FLIGHT`` steps ahead and reads ``(B, 1)`` ids behind
    them.  With a ``temperature`` the host reads each step's logits and
    draws the token itself, so that step is not run ahead.
    """

    def __init__(self, model, batch_buckets=None, prefill_floor=8,
                 mesh=None, tp_axis="tp", dtype=None):
        import jax

        from .. import engine

        engine.watch_compiles()
        # the decoder program's ``weights()`` stacks and re-lays the
        # model's leaves: most of this span, and of what the chip holds
        # more at its end than at its start
        with scope("startup.engine") as span:
            before = {d: _device_memory(d) for d in jax.local_devices()} \
                if telemetry.enabled() else {}
            self._mesh = mesh
            self._tp_axis = tp_axis
            self._dtype = dtype
            self._program = self._program_of(model)
            self._W = self._program.window
            self.batch_buckets = tuple(sorted(
                batch_buckets if batch_buckets is not None
                else batch_buckets_from_env()))
            self.prefill_buckets = prefill_buckets_for(
                self._W, floor=prefill_floor)
            self._reload_lock = threading.Lock()
            self.generation = 0
            self._weights = tuple(self._program.weights())
            self._programs = {}
            self._cache_avals = {}      # by batch bucket: `_compile`
            self._cache_ledgers = {}    # likewise: `_account_cache`
            self.program_memory = {}    # by (B, S): `_account_program`
            self._step = self._make_step()
            self._ledger = self._account_weights()
            if telemetry.enabled():
                # what the engine's stacked and re-laid weights cost
                # beside the model's own leaves, and whether the
                # process's peak was made before the engine was
                span.set(weights_bytes=self._ledger["weights_bytes"],
                         **_in_use_and_peak(before.get(self._device, {}),
                                            "_before"),
                         **_in_use_and_peak(_device_memory(self._device)))

    def _program_of(self, model):
        make = getattr(model, "decoder_program", None)
        if make is None:
            raise MXNetError(
                f"ServingEngine: {type(model).__name__} provides no "
                "decoder program (a model_zoo family serves through "
                "its decoder_program(); docs/serving.md)")
        return make(dtype=self._dtype, mesh=self._mesh,
                    tp_axis=self._tp_axis)

    # -- weight plumbing -------------------------------------------------------

    def reload_from_model(self, model, step=None):
        """Swap in a live model's weights (shapes must match), through
        its family's ``weights()``."""
        program = self._program_of(model)
        want = getattr(self._program, "signature", None)
        got = getattr(program, "signature", None)
        if type(program) is not type(self._program) or got != want:
            raise MXNetError(
                f"serving reload: incompatible model "
                f"({type(program).__name__} {got} vs compiled "
                f"{type(self._program).__name__} {want})")
        self._swap(program.weights(), step=step)

    def reload_from_state(self, state, step=None, expect_fp=None):
        """Swap in weights from an AsyncCheckpointer state dict
        (``state_for_serving`` convention).  Served for the families
        whose program reads one (``weights_from_state``): GPT's
        scanned-trunk names; the others reload from a live model.

        ``expect_fp``: optional integrity fingerprint (u64, the
        training side's attested `integrity.fingerprint_host` of this
        state).  When given, the state is re-fingerprinted here and a
        mismatch REJECTS the reload (``serving_reload_rejected``)
        instead of serving corrupt weights — end-to-end coverage of
        the restore path itself, past the per-shard CRCs."""
        if expect_fp is not None:
            from .. import integrity

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
        from_state = getattr(self._program, "weights_from_state", None)
        if from_state is None:
            raise MXNetError(
                f"serving reload: {type(self._program).__name__} has no "
                "checkpoint-state convention; reload from a live model "
                "via reload_from_model")
        self._swap(from_state(state), step=step)

    def _swap(self, weights, step=None):
        import jax

        weights = tuple(weights)
        if len(weights) != len(self._weights):
            raise MXNetError(
                f"serving reload: weight mismatch — {len(weights)} arrays "
                f"vs compiled {len(self._weights)}")
        # a checkpoint state arrives as host arrays: the swapped-in
        # weights go where the compiled programs' weights live
        new_w = tuple(jax.device_put(new, old.sharding)
                      for new, old in zip(weights, self._weights))
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
        self._ledger = self._account_weights()
        telemetry.event("serving_reload", generation=gen, step=step)

    # -- the memory ledger -----------------------------------------------------

    def _account_weights(self):
        """The weights' lines of the ledger, at ``__init__`` and at a
        swap: their bytes on a device (a buffer that two leaves share
        once; under a mesh the largest device's sum) and how many
        leaves.  The ledger reads the memory of the first device from
        then on, and holds what it says against that device's own
        sum.  Nothing with telemetry off."""
        if not telemetry.enabled():
            self._device = None
            return {}
        held = _held(self._weights)
        device = min(held, key=lambda d: d.id)
        self._weights_here, self._device = held[device], device
        ledger = {"weights_bytes": max(held.values()),
                  "weights_leaves": len(self._weights)}
        telemetry.gauge_set("memory.weights_bytes", ledger["weights_bytes"])
        return ledger

    def _account_cache(self, B, avals):
        """What a cache of batch bucket B reserves on a device, by kind
        (the family's ``cache_shapes(B)`` says which leaf is a stack, a
        state or a counter; ``avals`` their types and placement), and
        what `_account_group` needs to say how much of it a group
        wrote: a stack's bytes a (row, position) and its last axis,
        the states' bytes a row, the counters'.  None for a family that
        states its cache in another form."""
        import numpy as np

        shapes = getattr(self._program, "cache_shapes", None)
        if shapes is None or not telemetry.enabled():
            return None
        stacks, *states, counters = shapes(B)
        kinds = ["stack"] * len(stacks) + ["state"] * sum(
            len(s) for s in states) + ["counter"] * len(counters)
        if len(kinds) != len(avals):
            return None
        nbytes = [int(np.prod(a.sharding.shard_shape(a.shape)))
                  * np.dtype(a.dtype).itemsize for a in avals]
        by_kind = {k: sum(n for n, kind in zip(nbytes, kinds) if kind == k)
                   for k in ("stack", "state", "counter")}
        fields = {"cache_bytes_reserved": sum(nbytes),
                  "cache_stack_bytes": by_kind["stack"],
                  "cache_state_bytes": by_kind["state"],
                  "cache_counter_bytes": by_kind["counter"]}
        # a ring's last axis is its window: a row that wrapped filled it
        positions = [(n // (B * a.shape[-1]), a.shape[-1])
                     for n, a in zip(nbytes, avals[:len(stacks)])]
        return fields, positions, by_kind["state"] // B, by_kind["counter"]

    def _account_program(self, span, compiled, program, B, S):
        """What program (B, S) needs beside its arguments, as the
        compiler reckons it, and what the chip holds now that it is
        compiled: on its ``serve.compile`` span, kept by (B, S) and as
        one ``program_memory`` event."""
        needs = telemetry.memory_of_compiled(compiled)
        if needs is None or self._device is None:
            return
        now = _in_use_and_peak(_device_memory(self._device))
        self.program_memory[(B, S)] = needs
        span.set(**needs, **now)
        telemetry.event("program_memory", program=program, B=B, S=S,
                        **needs, **now)

    def _account_group(self, B, held):
        """The ledger's fields of one group, read once its last
        readback is in and the device has nothing of it in flight:
        ``held`` (n,) the positions each real row wrote (its prompt and
        the decode steps it was live in).  Host arithmetic over shapes
        and one ``memory_stats()``.  ``{}`` for an engine made with
        telemetry off."""
        import numpy as np

        if self._device is None:
            return {}
        out = dict(self._ledger)
        reserved = self._cache_ledgers.get(B)
        if reserved is not None:
            fields, positions, state_row, counters = reserved
            out.update(fields)
            out["cache_bytes_written"] = sum(
                each * int(np.minimum(held, W).sum())
                for each, W in positions) + state_row * len(held) + counters
        now = _device_memory(self._device)
        out.update(now)
        if reserved is not None and "memory_in_use_bytes" in now:
            # in use less what the ledger names of it, this device's
            # weights and this group's cache: signed, so that a buffer
            # counted twice shows and is not clamped away
            out["memory_unaccounted_bytes"] = now["memory_in_use_bytes"] \
                - self._weights_here - out["cache_bytes_reserved"]
        for gauge, field in _GROUP_GAUGES:
            if field in out:
                telemetry.gauge_set(gauge, out[field])
        return out

    # -- cache -----------------------------------------------------------------

    def init_cache(self, B):
        """A fresh cache of the family's for batch bucket B: a flat
        tuple of arrays beside the weights, every one donated to each
        step."""
        return tuple(self._program.init_cache(B))

    # -- the traced block step -------------------------------------------------

    def _make_step(self):
        import jax
        import jax.numpy as jnp

        program = self._program
        # under a mesh the two small outputs come back as the programs
        # take them in, on every chip
        replicated = None if self._mesh is None else self._input_sharding()

        def named(name):
            # one traced function under two names: a program is called
            # ``jit_<__name__>`` in its HLO and in every device trace
            def fn(w, cache, pos, last, toks, left=None):
                _mark_trace()
                # ``left`` (B,), the decode program's: the tokens a row
                # wants beyond the one this step is fed.  A row with
                # none left is not live: its answer is complete (or it
                # is padding) and nothing it computes is returned
                cache, logits = program.step(w, cache, pos, last, toks) \
                    if left is None else program.step(
                        w, cache, pos, last, toks, live=left > 0)
                # what the next decode step takes, made where it is
                # used: the greedy token by the rule the host samples
                # by (`_sample`: float32 argmax, first index on ties,
                # as NumPy's), each row's next position and what it
                # then still wants
                with jax.named_scope("serve.sample"):
                    ids = _sample(logits.astype(jnp.float32), None,
                                  None)[:, None]
                    small = (ids, pos + last + 1)
                    if left is not None:
                        small += (jnp.maximum(left - 1, 0),)
                if replicated is not None:
                    small = jax.lax.with_sharding_constraint(
                        small, replicated)
                return (tuple(cache), logits) + small

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

        program = "decode" if S == 1 else "prefill"
        with scope("serve.compile", B=B, S=S, program=program) as span:
            w_avals = tuple(self._aval(x) for x in self._weights)
            # the cache's shapes are read off an allocated one, once a
            # batch bucket: a cache can be most of the chip's memory,
            # and a later program of the bucket (the decode program,
            # compiled behind the group's first prefill) is compiled
            # while a group's own cache is alive
            c_avals = self._cache_avals.get(B)
            if c_avals is None:
                c_avals = self._cache_avals[B] = tuple(
                    self._aval(c) for c in self.init_cache(B))
                self._cache_ledgers[B] = self._account_cache(B, c_avals)
            jfn = jax.jit(self._step[program], donate_argnums=(1,))
            # pos, last, toks; the decode program also takes ``left``
            ints = [(B,), (B,), (B, S)] + [(B,)] * (program == "decode")
            compiled = jfn.lower(w_avals, c_avals, *(
                self._int_aval(shape) for shape in ints)).compile()
            # a trace and its lowering leave the cyclic collector
            # hundreds of thousands of objects to count, and its next
            # full pass due soon: 46-50 ms with the interpreter stopped
            # on a v5e host, which among the first requests outlasts a
            # batcher's delay and splits a closed loop's round for good
            # (PERF.md, PR 40).  Made here, beside seconds of compile.
            gc.collect()
            if telemetry.enabled():
                self._account_program(span, compiled, program, B, S)
        with _LOCK:
            _COMPILE_COUNT += 1
        self._programs[(B, S)] = compiled
        return compiled

    def warmup(self):
        """Pre-compile every (batch × prefill) program plus the S=1
        decode program per batch bucket; afterwards the request path is
        retrace-free (``trace_count()`` is pinned).  Each program is a
        ``serve.compile`` span and a ``compile`` event
        (`engine.watch_compiles`)."""
        for B in self.batch_buckets:
            for S in self.prefill_buckets + (1,):
                if (B, S) not in self._programs:
                    self._compile(B, S)
        return self

    def program_count(self):
        return len(self._programs)

    def _place(self, ints):
        """A per-call int input where the programs take it.  A device
        array is a step's own output (or was placed before): it goes
        in as it is."""
        import jax
        import numpy as np

        if isinstance(ints, jax.Array):
            return ints
        return jax.device_put(np.asarray(ints, np.int32),
                              self._input_sharding())

    def _call(self, B, S, cache, pos, last, toks, left=None):
        """One dispatch of bucket (B, S): returns ``(cache, logits (B,
        vocab), ids (B, 1), next pos (B,))``, all on the device; the
        last two are the greedy tokens and the positions a decode step
        that follows takes as ``toks`` and ``pos``.  ``cache`` is
        donated.  ``left`` (B,), for a decode step: the tokens each row
        wants beyond the one it is fed; the step treats a row with none
        as not live and hands back a fifth output, what the rows want
        after it.  Without it every row is live."""
        global _DISPATCH_COUNT
        import numpy as np

        compiled = self._programs.get((B, S))
        if compiled is None:
            compiled = self._compile(B, S)
        ints = [pos, last, toks]
        if S == 1:
            ints.append(np.full(B, np.iinfo(np.int32).max)
                        if left is None else left)
        ints = [self._place(x) for x in ints]
        with _LOCK:
            _DISPATCH_COUNT += 1
        with self._reload_lock:
            w = self._weights
        out = compiled(w, tuple(cache), *ints)
        return out if left is not None else out[:4]

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
        spans' own clock reads.

        Token j is read from program j, the prefill or decode step
        j - 1.  Greedy, a step takes the ids and positions the program
        before it left on the device, the host keeps
        ``_STEPS_IN_FLIGHT`` steps dispatched and reads the ids of the
        oldest; with a ``temperature`` it reads that program's logits,
        draws with ``rng`` and dispatches one step."""
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
        host_picks = bool(temperature)
        ahead = 1 if host_picks else _STEPS_IN_FLIGHT

        flight = collections.deque()    # dispatched, not yet read

        def dispatch(width, cache, pos, last, block, left=None):
            cache, logits, ids, pos, *left = self._call(
                B, width, cache, pos, last, block, left)
            # what the host will read of this program sets out for the
            # host as soon as the program has made it
            flight.append(logits if host_picks else ids)
            flight[-1].copy_to_host_async()
            return (cache, pos, ids, *left)

        with scope("serve.prefill.dispatch") as sp_dispatch:
            # the prefill program exists before the group's cache does:
            # a bucket's first `_compile` allocates a cache of its own
            # to read the shapes off, and two caches do not fit beside
            # the weights where one is most of the chip's memory
            if (B, S) not in self._programs:
                self._compile(B, S)
            cache, pos, ids = dispatch(S, self.init_cache(B),
                                       np.zeros(B, np.int32), lens - 1,
                                       toks)
            # a decode block is one token: placed once a group, as is
            # what each row wants beyond the first token the decode
            # steps are fed (a pad row nothing): from there on the
            # count lives on the device beside the positions
            step_last = self._place(np.zeros(B, np.int32))
            wanted = np.zeros(B, np.int32)
            wanted[:n] = np.asarray(per_req, np.int32) - 1
            left = self._place(wanted)
        with scope("serve.prefill.readback") as sp_readback:
            read = np.asarray(flight.popleft())
        t0, t1 = sp_dispatch.t0, sp_readback.t1
        prefill_us = (t1 - t0) * 1e6
        out = np.zeros((B, steps), np.int32)
        sample_s = dispatch_s = readback_s = 0.0
        token_t_us = []
        dispatched = 0
        for j in range(steps):
            with scope("serve.decode.sample", step=j) as sp:
                nxt = _sample(read, temperature, rng) if host_picks \
                    else read[:, 0]
                out[:, j] = nxt
            sample_s += sp.t1 - sp.t0
            token_t_us.append((sp.t1 - t1) * 1e6)
            if j == steps - 1:     # the last token needs no cache step
                break
            with scope("serve.decode.dispatch", step=j) as sp:
                if host_picks:
                    ids = self._place(nxt[:, None])
                while dispatched < steps - 1 and len(flight) < ahead:
                    cache, pos, ids, left = dispatch(
                        1, cache, pos, step_last, ids, left)
                    dispatched += 1
            dispatch_s += sp.t1 - sp.t0
            with scope("serve.decode.readback", step=j) as sp:
                read = np.asarray(flight.popleft())
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
            # decode steps whose token and position never left the
            # device, and what the host read of each program
            "decode_steps_fed_on_device": 0 if host_picks else dispatched,
            "decode_readback_bytes_per_step": int(read.nbytes),
            # the bucket's rows x the decode steps dispatched, and those
            # of them a row still wanted a token in: the others read one
            # cache block a layer and went to no expert
            "decode_row_steps": int(B) * dispatched,
            "decode_row_steps_live": int(
                np.minimum(wanted, dispatched).sum()),
            # when the host held each token, from t_decode0 (token 0 is
            # the prefill's): the gaps are what a streaming caller sees
            "token_t_us": token_t_us,
        }
        # of the decode program's cache row writes, the share that the
        # in-place kernel made, and the share it made knowing which rows
        # still want a token, so that the others moved no block
        # (ops/cache_write.py counted them when the program was traced)
        writes = getattr(self._program, "cache_writes", {}).get(1)
        if writes:
            made = writes["kernel"] + writes["rows"]
            timings["decode_cache_write_kernel_share"] = \
                writes["kernel"] / made
            timings["decode_cache_write_live_share"] = \
                writes["kernel_live"] / made
        # of its attention calls over the cache, the share that read
        # each row to its length (ops/cache_attention.py's kernel), and
        # what share of the rows' windows their blocks were
        reads = getattr(self._program, "cache_reads", {}).get(1)
        if reads:
            timings["decode_attn_kernel_share"] = sum(
                c for (path, _, _), c in reads.items()
                if path == "kernel") / sum(reads.values())
            if dispatched:
                timings["decode_attn_window_read_pct"] = \
                    _window_read_pct(reads, lens, dispatched, wanted)
        # of the rows whose state (a state-space layer's) a decode step
        # moved on, the share the in-place kernel moved, and the share it
        # moved knowing which rows still want a token, so that another
        # row's state stayed where it was; of a prefill's scans, the
        # share that ran in the kernel (ops/ssm.py counted them)
        updates = getattr(self._program, "state_updates", {})
        moved, scanned = updates.get(1), updates.get(int(S))
        if moved:
            made = moved["kernel"] + moved["plain"]
            timings["decode_state_update_kernel_share"] = \
                moved["kernel"] / made
            timings["decode_state_update_live_share"] = \
                moved["kernel_live"] / made
        if scanned:
            timings["prefill_state_scan_kernel_share"] = \
                scanned["kernel"] / (scanned["kernel"] + scanned["plain"])
        # of the prefill program's attention calls inside its block, the
        # share that went through the flash forward kernel
        # (ops/pallas_attention.py), as the family's program counted
        # them while it was traced
        attends = getattr(self._program, "block_attends", {}).get(int(S))
        if attends:
            timings["prefill_attn_kernel_share"] = \
                attends["kernel"] / sum(attends.values())
        # of the held experts' calls in the group's two programs, the
        # share whose grouped products went through the kernel of
        # ops/moe.py (a hit expert's weights read once, where they lie)
        # and not through lax.ragged_dot, and the share whose passes
        # reached the stream by its walk of the token tiles and not by
        # XLA's scatter-add
        products = collections.Counter()
        for s in {1, int(S)}:
            products.update(getattr(self._program, "grouped_products",
                                    {}).get(s, {}))
        if products:
            timings["moe_grouped_kernel_share"] = products["kernel"] / (
                products["kernel"] + products["plain"])
            timings["moe_combine_kernel_share"] = \
                products["combine_kernel"] / (
                    products["combine_kernel"] + products["combine_plain"])
        counters = getattr(self._program, "counters", None)
        if counters is not None:
            # what the family counted in its donated carry: one small
            # readback a group, after the last step's logits are in
            timings.update(counters(cache))
        if telemetry.enabled():
            timings.update(self._account_group(
                B, lens[:n] + np.minimum(wanted[:n], dispatched)))
        return [out[i, :per_req[i]].copy() for i in range(n)], timings

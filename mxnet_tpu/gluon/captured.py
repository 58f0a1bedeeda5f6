"""Whole-step graph capture for the imperative Gluon Trainer.

`ShardedTrainer` already compiles its entire step into one XLA program;
the imperative path — the one the tests, examples and the training
cell exercise — paid 4+ dispatches per step: the CachedOp forward, the
tape backward, the health reduction, and one GroupedUpdater program per
param group (plus per-microbatch grad-accumulate dispatches).  This
module is the CachedOp idea applied to the *whole step*: given a
hybridized block, a loss, and the Trainer's configuration, trace

    forward → loss → backward → (accumulate over microbatches)
    → health guard → global-norm clip → optimizer update

into ONE donated `jax.jit` program, cached per signature with the same
keying discipline `GroupedUpdater` established.  Per-step scalars (lr,
wd, rescale_grad, loss scale, t-folded coefficients) enter as traced
arrays (`optimizer.grouped.dyn_columns`), so LR schedules and
loss-scale changes never retrace.  ``grad_accum=k`` becomes a
`lax.scan` over microbatches inside the program, with BatchNorm-style
aux state threaded through the carry exactly as the eager path writes
it back between microbatches.

Pipeline parallelism (PR 17) lives INSIDE the same program: when the
parameters sit on a mesh with a ``pp`` axis (PPRules claims the scanned
trunk's leading layer-stack dim), the grad-accum scan is restructured
into a 1F1B-style shifted-carry schedule over ``grad_accum ×
pp_microbatches`` slices — each tick drains the previous microbatch's
gradients (handed to their stages with `with_sharding_constraint` on
the pp axis) while the current microbatch's stages compute, letting XLA
overlap cross-stage traffic with compute.  Still ONE donated jit, one
dispatch + one readback per step; ``MXTPU_PP=0`` or pp=1 degenerates to
the flat scan byte-for-byte.

Bitwise-parity discipline (PR 2/4): the eager multi-dispatch path stays
as the oracle behind ``MXTPU_CAPTURED_STEP=0``.  The captured trace
re-uses the exact same math homes — `block.param_override_scope` +
`random.key_scope` for the forward, `numerics.health_of` for the guard,
`optimizer.grouped.build_group_step` for the update — and reproduces
every eager *program boundary* with `_cut` (a custom-vjp
`lax.optimization_barrier`), because XLA's fusion/FMA-contraction
decisions are free to differ across a program boundary but not inside
one.  Cuts sit where the eager path materializes arrays: the CachedOp
forward output, the backward's gradient outputs, each grad-accumulate
sum, the loss-scale seed, and the health array.  Skip-step semantics
ride on the same `lax.cond` branches as the eager grouped programs, and
the host still performs EXACTLY one readback per step, after the update
dispatch (`numerics.StepGuard`).

Row-sparse embedding gradients (PR 18) run INSIDE the program too, for
`embedding.ShardedEmbedding` tables under SGD/Adam lazy updates: the
host computes unique ids + inverse index per step (`embedding.prep`),
pads the unique count to a power-of-two bucket folded into the capture
key, and the program pre-gathers just the touched rows, differentiates
through a gather-by-inverse lookup, and scatters the row update back
with `optimizer.grouped.sparse_row_kernel` — still one dispatch + one
readback.  ``MXTPU_SPARSE_CAPTURED=0`` pins sparse configs to the
eager row-sparse oracle.

What cannot be captured falls back to the eager oracle, per-step:
non-hybridized blocks, optimizers outside the fused-plan table,
multi-precision params, remat-enabled blocks, kvstore-backed reduction
(`kvstore.captured_step_compatible`), batch sizes not divisible by
``grad_accum``, sparse tables under a pipeline schedule or overflowing
a fixed MXTPU_UNIQUE_BUCKET, and steps with a pending ``nan_grad``
fault injection (the poison has no gradient buffer to land in on the
captured path).  A sparse fallback is never silent — the trainer emits
a ``sparse_fallback{reason}`` telemetry event.
"""

from __future__ import annotations

import os

_SENTINEL_UNSET = object()


def captured_step_enabled() -> bool:
    """MXTPU_CAPTURED_STEP gate (default on); 0/false/off routes
    `Trainer.train_step` to the eager multi-dispatch oracle."""
    return os.environ.get("MXTPU_CAPTURED_STEP", "1").lower() \
        not in ("0", "false", "off", "")


def pp_enabled() -> bool:
    """MXTPU_PP gate (default on); 0/false/off keeps the captured step
    on the flat grad-accum scan even when the mesh has a pp axis — the
    degenerate path is byte-identical to the pre-pipeline program."""
    return os.environ.get("MXTPU_PP", "1").lower() \
        not in ("0", "false", "off", "")


def resolve_pp_schedule(mesh, grad_accum, batch):
    """(pp_stages, pp_microbatches, total_slices) for this step.

    The 1F1B schedule is active only when the params sit on a mesh with
    a pp axis of size > 1 AND `pp_enabled()`; otherwise (1, 1, k) — the
    flat grad-accum scan.  ``pp_microbatches`` comes from the autotune
    knob (MXTPU_PP_MICROBATCHES; 0 = auto = the stage count), and the
    total slice count n = k*m must divide the batch: unlike the silent
    eager fallback for a batch indivisible by ``grad_accum`` alone, an
    indivisible microbatch split is a configuration the user asked for
    explicitly, so it raises UP FRONT naming both knobs.
    """
    k = int(grad_accum)
    stages = 1 if mesh is None else int(mesh.shape.get("pp", 1))
    if stages <= 1 or not pp_enabled():
        return 1, 1, k
    from ..autotune import space as _tune_space

    knob = _tune_space.KNOBS.get("pp_microbatches")
    try:
        m = int(knob.current()) if knob is not None else 0
    except ValueError:
        m = 0
    if m <= 0:
        m = stages
    n = k * m
    if batch % n != 0:
        raise ValueError(
            f"pipeline schedule: batch {batch} is not divisible by "
            f"grad_accum ({k}) * pp_microbatches ({m}) = {n} slices — "
            "pick grad_accum / MXTPU_PP_MICROBATCHES whose product "
            "divides the batch, or set MXTPU_PP=0")
    return stages, m, n


# -- accounting (regression-tested) --------------------------------------------
#
# dispatch: exactly ONE per captured step.  trace: increments only when
# jit actually re-traces train_step (a python side effect in the traced
# body) — the retrace-regression tests pin this at one per signature.
# hits/misses: Trainer-level capture-cache stats (`cache_stats()`:
# chip_smoke.py's train phase and tests/test_captured_step.py read them).

_DISPATCH_COUNT = 0
_TRACE_COUNT = 0
_CACHE_HITS = 0
_CACHE_MISSES = 0


def dispatch_count() -> int:
    return _DISPATCH_COUNT


def trace_count() -> int:
    return _TRACE_COUNT


def cache_stats() -> dict:
    return {"hits": _CACHE_HITS, "misses": _CACHE_MISSES}


def reset_counters() -> None:
    global _DISPATCH_COUNT, _TRACE_COUNT, _CACHE_HITS, _CACHE_MISSES
    _DISPATCH_COUNT = 0
    _TRACE_COUNT = 0
    _CACHE_HITS = 0
    _CACHE_MISSES = 0


# -- the program-boundary cut --------------------------------------------------

_CUT = None


def _cut_fn():
    """Identity with an `optimization_barrier` on both the primal and the
    cotangent: XLA may not fuse or FMA-contract across it, in either
    direction.  Placed wherever the eager oracle crosses a compiled
    program boundary (a materialized array), so the captured program's
    arithmetic is partitioned exactly like the eager dispatch chain —
    the PR 2 lesson ("XLA FMA contraction differs across eager
    dispatches") applied in reverse."""
    global _CUT
    if _CUT is None:
        import jax

        @jax.custom_vjp
        def cut(x):
            return jax.lax.optimization_barrier(x)

        def cut_fwd(x):
            return jax.lax.optimization_barrier(x), None

        def cut_bwd(_res, ct):
            return (jax.lax.optimization_barrier(ct),)

        cut.defvjp(cut_fwd, cut_bwd)
        _CUT = cut
    return _CUT


# -- eligibility ---------------------------------------------------------------

def _raw(x):
    return getattr(x, "_data", x)


def ineligible_reason(trainer, block, loss_fn, data, grad_accum):
    """Why this (trainer, block, loss) combination cannot be captured,
    or None when it can.  Cheap checks only — group planning happens in
    `get_step` (it shares `plan_items` with the eager path)."""
    from ..optimizer import grouped as _grouped
    from . import block as _blockmod

    if not _grouped.fused_step_enabled():
        return "fused step disabled (MXTPU_FUSED_STEP=0)"
    from .. import kvstore as _kvs

    if not _kvs.captured_step_compatible(trainer._kvstore):
        return "kvstore reduction outside the program"
    if trainer._update_on_kvstore:
        return "update_on_kvstore"
    if type(trainer._optimizer) not in _grouped._PLANS:
        return f"optimizer {type(trainer._optimizer).__name__} has no " \
               "fused plan"
    if not isinstance(block, _blockmod.HybridBlock):
        return "block is not a HybridBlock"
    if not block._active:
        return "block is not hybridized"
    if not callable(loss_fn):
        return "loss is not callable"
    if isinstance(loss_fn, _blockmod.Block) \
            and not isinstance(loss_fn, _blockmod.HybridBlock):
        return "loss block is not a HybridBlock"
    k = int(grad_accum)
    if k < 1:
        return "grad_accum < 1"
    if data.shape[0] % k != 0:
        return f"batch {data.shape[0]} not divisible by grad_accum {k}"
    sparse = [(i, p) for i, p in enumerate(trainer._params)
              if p._grad_req != "null"
              and getattr(p, "_grad_stype", None) == "row_sparse"]
    if sparse:
        from .. import embedding as _embedding

        return _embedding.sparse_capture_reason(trainer, block, sparse)
    return None


def _mesh_sharding_of(trainer):
    """(mesh, fingerprint) of the trainer's parameter placements, or
    (None, None) when params are single-device.  The fingerprint —
    mesh axis sizes + every param's PartitionSpec string — joins the
    capture cache key: re-sharding a model (shard_model, a mesh
    reshape after gang recovery) MUST miss the cache, because the
    donated program's layouts were inferred from the old placements."""
    from jax.sharding import NamedSharding

    from ..parallel.sharding import mesh_of_params

    params = list(trainer._params)
    mesh = mesh_of_params(params)
    if mesh is None:
        return None, None
    fp = []
    for i, p in enumerate(params):
        raw = getattr(getattr(p, "_data", None), "_data", None)
        sh = getattr(raw, "sharding", None)
        if isinstance(sh, NamedSharding):
            # the program hands a donated (None, 'dp', None) param back
            # as (None, 'dp'): the same placement, so trailing Nones stay
            # out of the key or step 1 would capture the step again
            spec = tuple(sh.spec)
            while spec and spec[-1] is None:
                spec = spec[:-1]
            fp.append((i, str(spec)))
    return mesh, (tuple(sorted(mesh.shape.items())), tuple(fp))


def _tree_version(block):
    """DFS tuple of ``_cache_version`` over a block tree: any
    `_clear_cached_op` anywhere in the tree (parameter set, child
    registration, hybridize, cast, LoRA attach/detach/merge) changes
    this tuple and therefore misses the capture cache — even when the
    mutating code only cleared the leaf it touched."""
    versions = [getattr(block, "_cache_version", 0)]
    for child in getattr(block, "_children", {}).values():
        versions.extend(_tree_version(child))
    return tuple(versions)


def _collect_blocks_params(block, loss_fn):
    """Ordered (name, param) pairs over block + loss params, deduped by
    identity — the forward override map must cover every parameter the
    trace can read."""
    from . import block as _blockmod

    pairs, seen = [], set()
    sources = [block.collect_params()]
    if isinstance(loss_fn, _blockmod.Block):
        sources.append(loss_fn.collect_params())
    for params in sources:
        for name, p in params.items():
            if id(p) not in seen:
                seen.add(id(p))
                pairs.append((name, p))
    return pairs


# -- capture cache -------------------------------------------------------------

_MAX_CACHE = 8


def capture_cache_size():
    """FIFO capacity of the per-trainer capture cache.  Overridable via
    MXTPU_CAPTURE_CACHE (min 1): the default of 8 is enough for training
    configurations, but a process that also serves holds one AOT program
    per (batch × seq) bucket and needs head-room."""
    from ..base import getenv_int

    return max(1, getenv_int("MXTPU_CAPTURE_CACHE", _MAX_CACHE))


def get_step(trainer, block, loss_fn, data, label, grad_accum):
    """Return the (possibly cached) `CapturedStep` for this call
    signature, or None when the step must run on the eager oracle.

    The cache key is GroupedUpdater's keying discipline extended to the
    whole step: (block cache-version, loss cache-version, grad_req
    layout, optimizer group plans [kernel + static hyper-params +
    dtype], guard/clip/amp flags, batch shapes, grad_accum, device
    fingerprint).  Anything that invalidates the block's CachedOp —
    parameter set, child registration, hybridize, cast, LoRA
    attach/detach — bumps ``_cache_version`` and therefore misses here
    too.  Per-step scalars (lr, t, wd, rescale, loss scale) are NOT in
    the key: they enter the program as traced arrays.
    """
    global _CACHE_HITS, _CACHE_MISSES
    from .. import kvstore as _kvs
    from .. import numerics
    from ..optimizer import grouped as _grouped

    trainer._sparse_fallback_reason = None
    reason = ineligible_reason(trainer, block, loss_fn, data, grad_accum)
    if reason is not None:
        return None
    block._ensure_initialized(data)

    upd = trainer._updaters[0]
    trained = [(i, p) for i, p in enumerate(trainer._params)
               if p._grad_req != "null"]
    if not trained:
        return None
    block_param_ids = {id(p) for _n, p
                       in _collect_blocks_params(block, loss_fn)}
    if any(id(p) not in block_param_ids for _i, p in trained):
        return None  # trainer optimizes params the forward never sees
    indices = [i for i, _p in trained]
    weights = [p.data() for _i, p in trained]
    sparse_params = [(i, p) for i, p in trained
                     if getattr(p, "_grad_stype", None) == "row_sparse"]
    sparse_idx = {i for i, _p in sparse_params}
    # weights stand in for the DENSE grads: the captured cotangents are
    # cast to the parameter dtype, so groupability is decided by the
    # weight.  Row-sparse params pass their actual RowSparseNDArray
    # grad buffer so plan_items picks the sparse_row_kernel variant.
    grad_standins = [p._grad if i in sparse_idx else p.data()
                     for i, p in trained]
    groups, fallback = _grouped.plan_items(upd, indices, grad_standins,
                                           weights)
    if fallback:
        return None

    guard_on = numerics.grad_guard_enabled()
    clip = trainer._clip_norm()
    has_scaler = getattr(trainer, "_amp_loss_scaler", None) is not None
    k = int(grad_accum)
    plan_sig = tuple(
        gkey + (tuple(i for i, *_r in items),)
        for gkey, items in groups.items())
    mesh, mesh_fp = _mesh_sharding_of(trainer)
    # program-affecting knobs (remat policy from block flags or the
    # MXTPU_REMAT/autotune env, optimizer group splitting): a changed
    # value must MISS here and re-capture — the traced program differs.
    # Non-program knobs (bucket MB, prefetch, ...) stay out of the key:
    # their consumers re-read env at dispatch time, so a recompile
    # would buy nothing.
    from .. import integrity as _integrity
    from .. import remat as _remat
    from ..autotune import space as _tune_space

    remat_policy = _remat.env_default(dict(block._flags).get("remat"))
    # pipeline schedule: raises (does NOT fall back) on an indivisible
    # grad_accum × pp_microbatches split; n_micro lands in the key both
    # directly and via mesh_fp + the pp_microbatches program knob
    pp_stages, _pp_m, n_micro = resolve_pp_schedule(
        mesh, k, int(data.shape[0]))
    # sparse-table host prep runs EVERY call, before the key: the
    # padded unique-count bucket is part of the capture signature, so
    # retraces are bounded by the number of distinct buckets a workload
    # produces, not by per-batch unique counts
    sparse_meta, sparse_key = [], ()
    trainer._sparse_prep = None
    if sparse_params:
        if pp_stages > 1:
            # gradients live in the 1F1B shifted carry; a rows-shaped
            # pending slot per stage is a different schedule — decline
            trainer._sparse_fallback_reason = \
                "pipeline schedule with row-sparse tables"
            return None
        from .. import embedding as _embedding
        from .. import telemetry as _telemetry

        preps, why, lookup_us = _embedding.prepare_step(
            block, data, sparse_params)
        if preps is None:
            trainer._sparse_fallback_reason = why
            return None
        pos = {i: j for j, (i, _p) in enumerate(trained)}
        sparse_meta = [(pos[i], id(p)) for i, p in sparse_params]
        sparse_key = tuple((pos[i], pr.bucket)
                           for (i, _p), pr in zip(sparse_params, preps))
        n_ids = sum(pr.n_ids for pr in preps)
        _telemetry.note(
            lookup_us=float(lookup_us),
            unique_fraction=sum(pr.n_real for pr in preps)
            / max(n_ids, 1))
        trainer._sparse_prep = preps
    key = (
        id(block), _tree_version(block),
        id(loss_fn), _tree_version(loss_fn),
        bool(getattr(loss_fn, "_active", False)),
        tuple((i, p._grad_req) for i, p in enumerate(trainer._params)),
        plan_sig, guard_on, clip, has_scaler, k,
        tuple(data.shape), str(_raw(data).dtype),
        None if label is None else (tuple(label.shape),
                                    str(_raw(label).dtype)),
        _kvs.device_fingerprint(), mesh_fp,
        pp_stages, n_micro, sparse_key,
        remat_policy, _tune_space.program_knob_values(),
        # integrity attestation adds a program output (the state
        # fingerprint) — a toggled flag must re-capture, and the
        # disabled program is bitwise-identical to the pre-integrity one
        _integrity.fingerprint_enabled(),
    )
    cache = getattr(trainer, "_captured_cache", None)
    if cache is None:
        cache = trainer._captured_cache = {}
    step = cache.get(key)
    if step is not None:
        _CACHE_HITS += 1
        step._groups = groups  # fresh state/param references, same plan
        return step
    _CACHE_MISSES += 1
    step = CapturedStep(trainer, block, loss_fn, trained, groups,
                        guard_on=guard_on, clip=clip,
                        has_scaler=has_scaler, grad_accum=k,
                        has_label=label is not None, mesh=mesh,
                        remat=remat_policy, pp_stages=pp_stages,
                        n_micro=n_micro, sparse_meta=sparse_meta)
    cap = capture_cache_size()
    while len(cache) >= cap:
        evicted_key = next(iter(cache))
        cache.pop(evicted_key)
        # an eviction means the NEXT hit on that signature recompiles —
        # on a serving/training hybrid that is a latency cliff, so it is
        # always worth a telemetry line
        from .. import telemetry as _telemetry

        _telemetry.event("capture_cache_evict", cache_size=cap,
                         kept=len(cache))
    cache[key] = step
    return step


class CapturedStep:
    """One compiled train-step program + the host bookkeeping around it.

    The donated jit consumes (trained params, other/aux params,
    optimizer states, per-step dyn scalars, batch, keys, loss scale)
    and returns (new params, new others, new states, per-microbatch
    losses, health).  Host side per step: update-count bump + dyn
    column build (shared with GroupedUpdater), ONE dispatch, write-back
    of the donated outputs, then the guarded finalize with its single
    readback (`Trainer._finalize_guarded_step`).
    """

    def __init__(self, trainer, block, loss_fn, trained, groups,
                 guard_on, clip, has_scaler, grad_accum, has_label,
                 mesh=None, remat=None, pp_stages=1, n_micro=None,
                 sparse_meta=None):
        # [(position in `trained`, table param id)] for row-sparse
        # embedding tables whose lookup + update run in-program — the
        # program then takes trailing (sp_uniq, sp_inv) index tuples
        self._sparse = list(sparse_meta or [])
        # resolved remat policy (remat.py registry): checkpoint-style
        # policies wrap the per-microbatch forward+loss closure below;
        # 'save_every_k:N' instead applies inside the scanned trunk
        # (ops/attention.py reads the env at trace time)
        self._remat = remat
        # mesh the parameters are committed over (None = single-device):
        # batch inputs are placed over its dp axis, and the program's
        # param/state outputs are pinned to the input shardings so the
        # donated buffers round-trip without a layout change (a drifting
        # output sharding would retrace NEXT step's jit)
        self._mesh = mesh
        self._block = block
        self._loss_fn = loss_fn
        self._trained = trained          # [(trainer_index, Parameter)]
        self._groups = groups            # plan_items layout
        self._guard_on = bool(guard_on)
        self._clip = clip
        self._want_guard = bool(guard_on) or clip is not None
        self._has_scaler = bool(has_scaler)
        self._grad_accum = int(grad_accum)
        # 1F1B pipeline schedule (resolve_pp_schedule): total microbatch
        # slices the in-program scan runs over — grad_accum *
        # pp_microbatches when the mesh has a pp axis, else grad_accum
        self._pp_stages = int(pp_stages)
        self._n_micro = int(n_micro) if n_micro else int(grad_accum)
        self._has_label = bool(has_label)
        from . import block as _blockmod

        self._loss_keyed = isinstance(loss_fn, _blockmod.HybridBlock) \
            and bool(loss_fn._active)
        pairs = _collect_blocks_params(block, loss_fn)
        trained_ids = {id(p) for _i, p in trained}
        self._others = [(name, p) for name, p in pairs
                        if id(p) not in trained_ids]
        self._pos = {i: j for j, (i, _p) in enumerate(trained)}
        # the step's executables, compiled ahead of time from the FIRST
        # dispatch's arguments and called directly afterwards: one
        # capture is one XLA compile (jit's own cache would compile the
        # same trace again when freshly created optimizer state comes
        # back committed after step 0).  Keyed by the static ``attest``
        # flag — None when the program has no fingerprint output.
        self._executables = {}
        # MFU accounting (mxnet_tpu/telemetry.py) reads the dispatch
        # executable's own analyses, lazily, ONCE per capture signature
        self._flops = _SENTINEL_UNSET
        self._collective_bytes = _SENTINEL_UNSET
        self._peak_bytes = _SENTINEL_UNSET
        from .. import integrity as _integrity

        # integrity plane (integrity.py): when enabled, the program
        # grows a trailing STATIC ``attest`` flag and a sixth output —
        # the parameter+optimizer-state fingerprint, computed in-program
        # (zero extra dispatches) only by the attest-step specialization;
        # the non-attest specialization is the plain step plus a
        # constant-zeros output
        self._want_fp = _integrity.fingerprint_enabled()
        self._fn = self._build()

    # -- trace ------------------------------------------------------------------

    def _build(self):
        import jax
        import jax.numpy as jnp

        from .. import autograd as _ag
        from .. import numerics
        from .. import random as _random
        from ..optimizer import grouped as _grouped
        from . import block as _blockmod

        cut = _cut_fn()
        blk, loss_fn = self._block, self._loss_fn
        k = self._n_micro
        pp_sched = self._pp_stages > 1
        want_guard, guard_on, clip = \
            self._want_guard, self._guard_on, self._clip
        has_scaler, has_label = self._has_scaler, self._has_label
        loss_keyed = self._loss_keyed
        mesh = self._mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(mesh, PartitionSpec())

            def _sh(p):
                s = p.data()._data.sharding
                return s if isinstance(s, NamedSharding) else repl

            train_shs = [_sh(p) for _i, p in self._trained]
            other_shs = [_sh(p) for _n, p in self._others]
        else:
            train_shs = other_shs = None
        train_ids = [id(p) for _i, p in self._trained]
        train_dtypes = [p.data()._data.dtype for _i, p in self._trained]
        # row-sparse tables: position in train_vals → slot in the
        # trailing (sp_uniq, sp_inv) argument tuples
        sparse_pos = [p for p, _pid in self._sparse]
        sparse_param_ids = [pid for _p, pid in self._sparse]
        sp_of = {p: j for j, p in enumerate(sparse_pos)}
        from contextlib import nullcontext

        if sparse_pos:
            from ..embedding import prep as _embprep
        other_ids = [id(p) for _n, p in self._others]
        other_names = [n for n, _p in self._others]
        group_meta = []                 # (pure group fn, grad positions)
        for gkey, items in self._groups.items():
            kernel, static_items = gkey[0], gkey[1]
            if want_guard:
                gfn = _grouped.build_group_step(
                    kernel, static_items, guarded=guard_on, clip=clip)
            else:
                gfn = _grouped.build_group_step(kernel, static_items)
            group_meta.append((gfn, [self._pos[i] for i, *_r in items]))

        from .. import remat as _remat

        remat_policy = self._remat

        def micro(train_vals, others, x_mb, y_mb, kb, kl, scale,
                  invs=()):
            base_pm = dict(zip(other_ids, others))

            def fwd(tv):
                pm = dict(base_pm)
                pm.update(zip(train_ids, tv))
                aux = {}
                # the capture scope hands ShardedEmbedding its
                # microbatch inverse-index tracer; it must wrap the
                # forward INSIDE fwd so a remat replay re-enters it
                scope = _embprep.capture_scope(
                    dict(zip(sparse_param_ids, invs))) if sparse_pos \
                    else nullcontext()
                with scope, _blockmod.param_override_scope(pm, aux), \
                        _ag.train_mode():
                    with _random.key_scope(kb):
                        out = blk.forward(x_mb)
                    # the eager CachedOp materializes `out` between the
                    # forward and loss programs (and the loss→block
                    # cotangent on the way back)
                    out = cut(out)
                    if loss_keyed:
                        with _random.key_scope(kl):
                            loss = loss_fn(out, y_mb) \
                                if y_mb is not None else loss_fn(out)
                    else:
                        loss = loss_fn(out, y_mb) \
                            if y_mb is not None else loss_fn(out)
                return loss, aux

            if remat_policy:
                # checkpoint-style remat around forward+loss: the
                # backward recomputes the wrapped region instead of
                # saving residuals.  Bitwise-neutral (jax.checkpoint
                # replays identical HLO), proven by
                # tests/test_autotune.py parity.  save_every_k is a
                # no-op here — it lives inside the scanned trunk.
                fwd = _remat.wrap(fwd, remat_policy)
            (loss, aux), vjp_fn = jax.vjp(fwd, list(train_vals))
            if has_scaler:
                # eager: `loss * loss_scale` is its own program, and
                # backward seeds ones over THAT — i.e. a full(scale)
                seed = cut(jnp.ones_like(loss)
                           * scale.astype(loss.dtype))
            else:
                seed = jnp.ones_like(loss)
            aux_zero = jax.tree_util.tree_map(jnp.zeros_like, aux)
            (tv_ct,) = vjp_fn((seed, aux_zero))
            gs = [cut(g if g.dtype == dt else g.astype(dt))
                  for g, dt in zip(tv_ct, train_dtypes)]
            new_others = [aux.get(n, ov)
                          for n, ov in zip(other_names, others)]
            return loss, gs, new_others

        def train_step(train_vals, other_vals, state_vals, dyn_list,
                       xs, ys, keys_b, keys_l, scale, sp_uniq, sp_inv):
            # the function's name is the program's: ``jit_train_step``
            # in the HLO and in every device trace; the three
            # ``train.*`` scopes below name its parts there (metadata
            # only)
            global _TRACE_COUNT
            _TRACE_COUNT += 1  # python side effect: fires at trace only
            with jax.named_scope("train.forward_backward"):
                losses, grads, new_others = forward_backward(
                    train_vals, other_vals, xs, ys, keys_b, keys_l,
                    scale, sp_uniq, sp_inv)
            if want_guard:
                with jax.named_scope("train.guard"):
                    health = guard(train_vals, grads, sp_uniq)
            else:
                health = None
            with jax.named_scope("train.optimizer"):
                new_train, new_states = optimizer(
                    train_vals, state_vals, dyn_list, grads, sp_uniq,
                    health)
            if train_shs is not None:
                # pin param/aux outputs to their INPUT shardings: the
                # donated buffers must round-trip layout-stable or the
                # next dispatch sees new input shardings and retraces
                # (sits at the program tail, outside every cut/cond —
                # no fusion decision changes upstream of it)
                new_train = [jax.lax.with_sharding_constraint(v, s)
                             for v, s in zip(new_train, train_shs)]
                new_others = [jax.lax.with_sharding_constraint(v, s)
                              for v, s in zip(new_others, other_shs)]
            return new_train, new_others, new_states, losses, health

        def forward_backward(train_vals, other_vals, xs, ys, keys_b,
                             keys_l, scale, sp_uniq, sp_inv):
            # sparse tables enter the forward as their PRE-GATHERED
            # unique rows (the out-of-range sentinel id clamps to the
            # last row under mode='clip' — deterministic filler no
            # inverse-index entry ever targets);
            # the vjp below then differentiates w.r.t. the ROWS, so
            # cotangents and the grad-accum carry are (bucket, dim)
            # shaped, never the full table
            lookup_vals = list(train_vals)
            for j, p in enumerate(sparse_pos):
                lookup_vals[p] = cut(jnp.take(
                    train_vals[p], sp_uniq[j], axis=0, mode="clip"))
            if k == 1:
                losses, grads, new_others = micro(
                    lookup_vals, other_vals, xs, ys, keys_b, keys_l,
                    scale, list(sp_inv))
            elif not pp_sched:
                def body(carry, sl):
                    acc, others = carry
                    loss, gs, others = micro(
                        lookup_vals, others, sl["x"], sl.get("y"),
                        sl["kb"], sl.get("kl"), scale,
                        [sl[f"si{j}"] for j in range(len(sparse_pos))])
                    # one eager `grad += ct` dispatch per microbatch
                    acc = [cut(a + g) for a, g in zip(acc, gs)]
                    return (acc, others), loss

                sl = {"x": xs, "kb": keys_b}
                if has_label:
                    sl["y"] = ys
                if loss_keyed:
                    sl["kl"] = keys_l
                for j in range(len(sparse_pos)):
                    sl[f"si{j}"] = sp_inv[j]
                acc0 = [jnp.zeros_like(v) for v in lookup_vals]
                (grads, new_others), losses = jax.lax.scan(
                    body, (acc0, list(other_vals)), sl)
            else:
                # 1F1B-style shifted-carry schedule: the carry holds the
                # PREVIOUS microbatch's gradients, and each tick drains
                # them into the accumulator while the CURRENT
                # microbatch's stages compute — the accumulate has no
                # data dependence on this tick's micro(), so XLA is free
                # to overlap its cross-stage (pp-axis) traffic with
                # microbatch i+1's stage-s compute, exactly the
                # comm/compute-overlap the schedule exists for.  The
                # sharding constraint hands each gradient slice to its
                # stage's devices (train_shs carries the pp placement of
                # the *_stack_* params).  Bitwise: tick 0 adds an exact
                # +0 array, after which the add chain sees operand-for-
                # operand the same barriered sums as the flat scan — so
                # captured(k, m) equals the eager oracle at
                # grad_accum=k*m (pinned by tests/test_pipeline_*).
                def body(carry, sl):
                    acc, pending, others = carry
                    acc = [cut(a + p) for a, p in zip(acc, pending)]
                    loss, gs, others = micro(
                        train_vals, others, sl["x"], sl.get("y"),
                        sl["kb"], sl.get("kl"), scale)
                    gs = [jax.lax.with_sharding_constraint(g, s)
                          for g, s in zip(gs, train_shs)]
                    return (acc, gs, others), loss

                sl = {"x": xs, "kb": keys_b}
                if has_label:
                    sl["y"] = ys
                if loss_keyed:
                    sl["kl"] = keys_l
                acc0 = [jnp.zeros_like(v) for v in train_vals]
                pend0 = [jnp.zeros_like(v) for v in train_vals]
                ((acc, pending, new_others), losses) = jax.lax.scan(
                    body, (acc0, pend0, list(other_vals)), sl)
                # cooldown drain: the last microbatch's grads are still
                # in flight when the scan ends
                grads = [cut(a + p) for a, p in zip(acc, pending)]
            return losses, grads, new_others

        def guard(train_vals, grads, sp_uniq):
            hg = grads
            if sparse_pos:
                # the eager guard reads the DENSE gradient view
                # (RowSparseNDArray._data = zeros.at[ids].add(vals),
                # its own dispatch): same formula here, with the
                # out-of-bounds sentinel rows dropped by the scatter
                hg = list(grads)
                for j, p in enumerate(sparse_pos):
                    hg[p] = cut(jnp.zeros(
                        train_vals[p].shape,
                        grads[p].dtype).at[sp_uniq[j]].add(grads[p]))
            return cut(numerics.health_of(hg))

        def optimizer(train_vals, state_vals, dyn_list, grads, sp_uniq,
                      health):
            new_train = list(train_vals)
            new_states = []
            for (gfn, pos), states, dyn in zip(group_meta, state_vals,
                                               dyn_list):
                ws = [train_vals[p] for p in pos]
                # a row-sparse grad reaches its kernel as (ids, values)
                gsl = [(sp_uniq[sp_of[p]], grads[p]) if p in sp_of
                       else grads[p] for p in pos]
                if want_guard:
                    nw, ns = gfn(ws, gsl, states, dyn, health)
                else:
                    nw, ns = gfn(ws, gsl, states, dyn)
                for p, w in zip(pos, nw):
                    new_train[p] = w
                if train_shs is not None:
                    # states shard with their weight (grouped kernels
                    # only ever see weight-shaped state)
                    ns = [[jax.lax.with_sharding_constraint(
                               a, train_shs[p]) for a in item_states]
                          for p, item_states in zip(pos, ns)]
                new_states.append(ns)
            return new_train, new_states

        if not self._want_fp:
            return jax.jit(train_step, donate_argnums=(0, 1, 2))

        from .. import integrity as _integrity

        def train_step_fp(train_vals, other_vals, state_vals, dyn_list,
                          xs, ys, keys_b, keys_l, scale, sp_uniq,
                          sp_inv, attest):
            # ``attest`` is STATIC: jit specializes into exactly two
            # executables (one trace + compile each, cached by jit).
            # The non-attest executable is the plain step plus a
            # constant-zeros output — XLA dead-code-eliminates the
            # whole fingerprint, so steady-state overhead is ~0.  (A
            # traced predicate under lax.cond was measurably worse:
            # every param+state array becomes a conditional operand,
            # which blocks fusion/aliasing on EVERY step.)
            new_train, new_others, new_states, losses, health = \
                train_step(train_vals, other_vals, state_vals,
                           dyn_list, xs, ys, keys_b, keys_l, scale,
                           sp_uniq, sp_inv)
            if attest:
                flat_states = [a for group in new_states
                               for item in group for a in item]
                fp = _integrity.fingerprint_arrays(
                    list(new_train) + flat_states)
            else:
                fp = jnp.zeros((2,), jnp.uint32)
            return (new_train, new_others, new_states, losses, health,
                    fp)

        train_step_fp.__name__ = train_step_fp.__qualname__ = "train_step"
        return jax.jit(train_step_fp, donate_argnums=(0, 1, 2),
                       static_argnums=(11,))

    # -- per-step host driver ---------------------------------------------------

    def __call__(self, trainer, data, label, batch_size):
        global _DISPATCH_COUNT
        import numpy as _np

        import jax.numpy as jnp

        from .. import numerics, profiler
        from .. import random as _random
        from ..ndarray import _from_jax
        from ..optimizer import grouped as _grouped

        o = trainer._optimizer
        with profiler.annotate("captured_host_prep"):
            trainer._set_rescale(batch_size)
            indices = [i for i, _p in self._trained]
            snapshot = trainer._snapshot_update_counts(indices) \
                if self._guard_on else None
            for i in indices:
                o._update_count(i)
            state_vals, dyn_list = [], []
            for gkey, items in self._groups.items():
                state_vals.append([[s._data for s in st]
                                   for _i, _w, _g, st, _d in items])
                dyn_list.append(_grouped.dyn_columns(
                    o, items, _np.dtype(gkey[2])))
            # the in-program scan runs over n_micro slices (grad_accum ×
            # pp_microbatches under the pipeline schedule): one RNG key
            # per slice, batch reshaped to (n, b//n, ...) — matching the
            # key-draw count of the eager oracle at grad_accum=n_micro
            k = self._n_micro
            kbs, kls = [], []
            # the key split is a program of its own on the device
            with profiler.annotate("captured_keys"):
                for _ in range(k):
                    kbs.append(_random.next_key())
                    if self._loss_keyed:
                        kls.append(_random.next_key())
        with profiler.annotate("captured_data"):
            if k == 1:
                keys_b = kbs[0]
                keys_l = kls[0] if kls else kbs[0]
                xs = _raw(data)
                ys = None if label is None else _raw(label)
            else:
                keys_b = jnp.stack(kbs)
                keys_l = jnp.stack(kls) if kls else keys_b
                xr = _raw(data)
                xs = xr.reshape((k, xr.shape[0] // k) + xr.shape[1:])
                ys = None
                if label is not None:
                    yr = _raw(label)
                    ys = yr.reshape((k, yr.shape[0] // k) + yr.shape[1:])
            if self._mesh is not None:
                # split the (micro)batch dim over dp: committed batch
                # placement, so GSPMD infers the data-parallel layout
                # instead of replicating the batch (leading=1 under
                # grad-accum — dim 0 is the scan axis)
                import jax

                from ..parallel.sharding import batch_sharding

                lead = 0 if k == 1 else 1
                xs = jax.device_put(xs, batch_sharding(
                    self._mesh, xs.shape[lead], leading=lead))
                if ys is not None:
                    ys = jax.device_put(ys, batch_sharding(
                        self._mesh, ys.shape[lead], leading=lead))
            # host-prepared sparse lookup indices (get_step ran
            # embedding.prepare_step before the cache lookup — possibly
            # just consuming the DevicePrefetcher's stash); the inverse
            # index reshapes to (n_micro, ids/micro) so each scan slice
            # sees exactly its microbatch's flat ids, batch-major like
            # the xs reshape above
            sp_uniq = sp_inv = ()
            if self._sparse:
                preps = trainer._sparse_prep
                trainer._sparse_prep = None
                sp_uniq = tuple(jnp.asarray(pr.uniq) for pr in preps)
                if k == 1:
                    sp_inv = tuple(jnp.asarray(pr.inv) for pr in preps)
                else:
                    sp_inv = tuple(jnp.asarray(pr.inv.reshape(
                        (k, pr.inv.size // k))) for pr in preps)
                if self._mesh is not None:
                    import jax
                    from jax.sharding import (NamedSharding,
                                              PartitionSpec)

                    repl = NamedSharding(self._mesh, PartitionSpec())
                    sp_uniq = tuple(jax.device_put(u, repl)
                                    for u in sp_uniq)
                    sp_inv = tuple(jax.device_put(v, repl)
                                   for v in sp_inv)
        scaler = getattr(trainer, "_amp_loss_scaler", None)
        scale = _np.float32(scaler.loss_scale if scaler else 1.0)
        train_raws = [p.data()._data for _i, p in self._trained]
        other_raws = [p.data()._data for _n, p in self._others]
        args = (train_raws, other_raws, state_vals, dyn_list,
                xs, ys, keys_b, keys_l, scale, sp_uniq, sp_inv)
        fp = None
        with profiler.annotate("captured_step"):
            if self._want_fp:
                attest = bool(trainer._integrity_due())
                (new_train, new_others, new_states, losses, health,
                 fp) = self._executable(args, attest)(*args)
                if not attest:
                    fp = None
            else:
                new_train, new_others, new_states, losses, health = \
                    self._executable(args)(*args)
        _DISPATCH_COUNT += 1
        with profiler.annotate("captured_commit"):
            for (_i, p), nw in zip(self._trained, new_train):
                p.data()._set_data(nw)
            for (_n, p), nv in zip(self._others, new_others):
                p.data()._set_data(nv)
            for (_gkey, items), ns_group in \
                    zip(self._groups.items(), new_states):
                for (_i, _w, _g, st, _d), ns in zip(items, ns_group):
                    for s_nd, s_new in zip(st, ns):
                        s_nd._set_data(s_new)
        from .. import resilience as _resilience

        if _resilience.fault_armed("bit_flip_param"):
            # memory-SDC injection: corrupt the LIVE post-step state
            # after the program committed — the in-program fingerprint
            # is clean, so the flip surfaces at the NEXT attestation
            # (within one interval) and a shadow replay disagrees with
            # the live state (kind="memory")
            from .. import integrity as _integrity

            _integrity.maybe_bit_flip_param(
                params=[p for _i, p in self._trained])
        trainer._step_count += 1
        if self._want_guard:
            guard = numerics.StepGuard(health, skip=self._guard_on,
                                       clip=self._clip, extra=fp)
            trainer._finalize_guarded_step(guard, snapshot)
        elif fp is not None:
            # no numerics guard: the attestation readback is the step's
            # one host sync instead
            from .. import integrity as _integrity

            trainer._integrity_attest(
                _integrity.combine(_np.asarray(fp)))
        return _from_jax(losses)

    def _executable(self, args, attest=None):
        """The compiled program for this dispatch: lowered and compiled
        from the first dispatch's real arguments (their placements
        included), then reused.  Static args are baked in at `lower`
        and left out of the call."""
        exe = self._executables.get(attest)
        if exe is None:
            from .. import profiler

            with profiler.scope("train.compile"):
                lowered = self._fn.lower(*args) if attest is None \
                    else self._fn.lower(*args, attest)
                exe = self._executables[attest] = lowered.compile()
        return exe

    # -- program accounting (mxnet_tpu/telemetry.py) ----------------------------

    def _compiled_for_stats(self):
        """The executable every steady-state step runs (under the
        integrity plane: the non-attest specialization), or None before
        the first dispatch."""
        return self._executables.get(False if self._want_fp else None)

    def cost_flops(self):
        """Total FLOPs of the compiled step program via XLA cost
        analysis, or None when unavailable."""
        if self._flops is _SENTINEL_UNSET:
            from .. import telemetry

            compiled = self._compiled_for_stats()
            if compiled is None:
                return None
            self._flops = telemetry.flops_of_compiled(compiled)
        return self._flops

    def memory_high_water(self):
        """Per-device memory high-water of the step program in bytes
        (arguments + outputs + XLA temp allocations, donation aliases
        counted once), or None when the compiler doesn't expose it."""
        if self._peak_bytes is _SENTINEL_UNSET:
            from .. import telemetry

            compiled = self._compiled_for_stats()
            if compiled is None:
                return None
            needs = telemetry.memory_of_compiled(compiled)
            self._peak_bytes = None if needs is None else max(
                needs["temp_size_in_bytes"]
                + needs["argument_size_in_bytes"]
                + needs["output_size_in_bytes"]
                - needs["alias_size_in_bytes"], 0)
        return self._peak_bytes

    def pipeline_stats(self):
        """Static 1F1B schedule accounting for this capture, or None on
        a non-pipelined program: stage count, microbatch slices, the
        warmup/cooldown slot counts, total schedule ticks, and the
        derived ``bubble_fraction`` = (S−1)/(n+S−1)
        (`parallel.pipeline.gpipe_bubble_fraction` — cross-checked by
        tests against `_schedule_1f1b`'s measured idle fraction).  When
        XLA cost analysis is available, ``flops_per_microbatch`` rides
        along so trace_report can sanity-check the bubble against the
        program's actual per-slice work."""
        if self._pp_stages <= 1:
            return None
        from ..parallel.pipeline import gpipe_bubble_fraction

        s, n = self._pp_stages, self._n_micro
        out = {
            "stages": s,
            "microbatches": n,
            "warmup": s - 1,
            "cooldown": s - 1,
            "ticks": n + s - 1,
            "bubble_fraction": float(gpipe_bubble_fraction(s, n)),
        }
        flops = self.cost_flops()
        if flops:
            out["flops_per_microbatch"] = float(flops) / max(n, 1)
        return out

    def collective_bytes_by_axis(self):
        """{axis: bytes-moved-per-device} over the step program's
        collectives (telemetry.collective_bytes_by_axis), or None on a
        single-device capture / when HLO is unavailable."""
        if self._mesh is None:
            return None
        if self._collective_bytes is _SENTINEL_UNSET:
            from .. import telemetry

            compiled = self._compiled_for_stats()
            if compiled is None:
                return None
            self._collective_bytes = \
                telemetry.collective_bytes_by_axis(compiled, self._mesh)
        return self._collective_bytes

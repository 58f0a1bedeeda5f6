"""Gluon Trainer.

Reference parity: python/mxnet/gluon/trainer.py — Trainer(params, optimizer,
optimizer_params, kvstore, update_on_kvstore), step/allreduce_grads/update,
learning-rate control, optimizer-state save/load.

TPU-first: with one logical array per parameter, `allreduce_grads` is the
cross-process reduce (kvstore dist types → ICI/DCN all-reduce); the
single-chip path applies fused optimizer ops directly.  For whole-step
compilation (grad + reduce + update in ONE XLA program) see
mxnet_tpu.parallel.DataParallelTrainer, this class's jit-native sibling.
"""

from __future__ import annotations

import logging

from .. import numerics
from .. import optimizer as opt
from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .parameter import Parameter, ParameterDict

_LOG = logging.getLogger("mxnet_tpu.gluon.trainer")

_MAX_SKIP_RECORDS = 1000


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None, clip_global_norm=None):
        from .. import engine, obs, profiler

        # the compile cache (and the client's start, where nothing
        # touched a device before), the optimizer and its updaters
        with profiler.scope("startup.trainer"):
            engine.ensure_compile_cache()
            obs.ensure_from_env()          # MXTPU_METRICS_PORT, if set
            if isinstance(params, (dict, ParameterDict)):
                params = list(params.values())
            if not isinstance(params, (list, tuple)):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    f"got {type(params)}.")
            self._params = []
            self._param2idx = {}
            for i, param in enumerate(params):
                if not isinstance(param, Parameter):
                    raise ValueError(
                        "First argument must be a list or dict of Parameters, "
                        f"got list of {type(param)}.")
                self._param2idx[param.name] = i
                self._params.append(param)
            self._compression_params = compression_params
            optimizer_params = optimizer_params if optimizer_params else {}
            self._scale = float(optimizer_params.get("rescale_grad", 1.0))
            self._init_optimizer(optimizer, optimizer_params)
            self._kvstore_params = {
                "kvstore": kvstore, "update_on_kvstore": update_on_kvstore}
            self._kv_initialized = False
            self._kvstore = None
            self._update_on_kvstore = None
            self._params_to_init = []
            self._contains_sparse_weight = False
            # numerical-health guard (mxnet_tpu/numerics.py): clip_global_norm
            # falls back to MXTPU_CLIP_GLOBAL_NORM when not given; skipped
            # steps are recorded here (bounded deque-style list)
            self._clip_global_norm = None if clip_global_norm is None \
                else float(clip_global_norm)
            self.divergence_monitor = None
            self.skipped_steps = []
            self._step_count = 0
            # resumable input pipeline (gluon/data/state.py): when attached,
            # each guarded step tags the divergence monitor with the batch
            # that fed it, so a rollback can quarantine the poisoned batch
            self._data_pipeline = None
            # integrity plane (mxnet_tpu/integrity.py): attach_integrity
            # makes the captured step fingerprint the state every
            # plane.every steps and attest it against the gang
            self._integrity_plane = None

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an " \
                "Optimizer instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)]
        # multi-tensor path: shares each Updater's state dict, so
        # save/load_states round-trip regardless of which path stepped
        self._grouped_updaters = [opt.GroupedUpdater(u)
                                  for u in self._updaters]

    def _init_kvstore(self):
        config = self._kvstore_params
        kvstore = config["kvstore"]
        update_on_kvstore = config["update_on_kvstore"]
        kv = None
        if kvstore:
            from .. import kvstore as kvs

            kv = kvs.create(kvstore) if isinstance(kvstore, str) else kvstore
            if kv.num_workers == 1 and not kvstore_requires_store(kv):
                kv = None  # single worker: local fused update path
        if kv is not None:
            if update_on_kvstore is None:
                update_on_kvstore = True
            if update_on_kvstore:
                kv.set_optimizer(self._optimizer)
            for i, param in enumerate(self._params):
                if param._grad_req != "null":
                    kv.init(i, param.data())
        self._kvstore = kv
        self._update_on_kvstore = bool(update_on_kvstore) and kv is not None
        self._kv_initialized = True

    @property
    def learning_rate(self):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its "
                              "learning rate can be accessed.")
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its "
                              "learning rate is mutated.")
        self._optimizer.set_learning_rate(lr)

    def _clip_norm(self):
        return self._clip_global_norm \
            if self._clip_global_norm is not None \
            else numerics.clip_global_norm_env()

    def _set_rescale(self, batch_size):
        # amp: fold the loss-scaler's unscale into rescale_grad, so the
        # division happens inside the fused step instead of a separate
        # pass over the gradients (DynamicLossScaler.unscale returns new
        # arrays and is only needed on manual paths)
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None:
            self._scale = 1.0 / scaler.loss_scale
        self._optimizer.rescale_grad = self._scale / batch_size

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce_grads + update (reference: Trainer.step)."""
        from .. import telemetry

        # no-op (returns None) when train_step already opened the record
        acc = telemetry.step_begin(path="manual")
        n_skipped = len(self.skipped_steps)
        try:
            if not self._kv_initialized:
                self._init_kvstore()
            self._set_rescale(batch_size)
            health = self._allreduce_grads()
            self._update(ignore_stale_grad, health=health)
        except BaseException:
            telemetry.step_abort(acc)
            raise
        telemetry.step_end(acc, step=self._step_count,
                           skipped=len(self.skipped_steps) > n_skipped)

    def train_step(self, block, loss_fn, data, label=None, batch_size=None,
                   grad_accum=1, ignore_stale_grad=False):
        """One full training step — forward, loss, backward, gradient
        accumulation, health guard, clip, optimizer update — returning
        the (per-microbatch, when ``grad_accum > 1``) loss.

        When the configuration is capturable (hybridized block, fused
        optimizer, local reduce — see `gluon.captured`), the entire
        step runs as ONE donated jit program with a single host
        readback after the update; otherwise (or under
        ``MXTPU_CAPTURED_STEP=0``) it runs the eager multi-dispatch
        path, which doubles as the captured path's bitwise oracle.

        The captured path never touches the parameters' gradient
        buffers — gradients live only inside the program — so
        ``ignore_stale_grad`` only applies to the eager fallback, and
        manual ``backward()`` + ``step()`` flows should not be
        interleaved with ``train_step`` on the same trainer step.

        The whole call runs under the ``train_step`` span (the parent
        of ``captured_host_prep`` ... ``guard_readback``), so that the
        capture lookup before them and the step's bookkeeping after
        them are the program's in a device trace too.
        """
        from .. import profiler

        with profiler.scope("train_step"):
            return self._train_step(block, loss_fn, data, label,
                                    batch_size, grad_accum,
                                    ignore_stale_grad)

    def _train_step(self, block, loss_fn, data, label, batch_size,
                    grad_accum, ignore_stale_grad):
        from .. import resilience
        from .. import telemetry
        from . import captured as _captured

        if not self._kv_initialized:
            self._init_kvstore()
        if batch_size is None:
            batch_size = data.shape[0]
        # autotune consult (MXTPU_AUTOTUNE=replay|search|off): replay a
        # stored winner or search the knob space ONCE per capture
        # signature, before this step's capture lookup sees the knobs
        from .. import autotune as _autotune

        k = _autotune.maybe_tune(self, block, loss_fn, data, label,
                                 int(grad_accum))
        self._maybe_shard_batch(data, label)
        acc = telemetry.step_begin()
        n_skipped = len(self.skipped_steps)
        step = None
        try:
            # a pending nan_grad / bit_flip_grad injection needs a
            # materialized gradient buffer to land in: route that step
            # to the eager oracle
            if _captured.captured_step_enabled() \
                    and not resilience.fault_armed("nan_grad") \
                    and not resilience.fault_armed("bit_flip_grad"):
                hits0 = _captured.cache_stats()["hits"] if acc else 0
                step = _captured.get_step(self, block, loss_fn, data,
                                          label, k)
                if step is not None and acc is not None:
                    telemetry.note_path("captured")
                    telemetry.note(
                        cache_hit=_captured.cache_stats()["hits"] > hits0)
            if step is not None:
                result = step(self, data, label, batch_size)
                if acc is not None:
                    telemetry.note(flops=step.cost_flops())
                    peak = step.memory_high_water()
                    if peak is not None:
                        telemetry.note(device_peak_bytes=peak)
                    coll = step.collective_bytes_by_axis()
                    if coll:
                        telemetry.note(collective_bytes_by_axis=coll)
                    pstats = step.pipeline_stats()
                    if pstats is not None:
                        telemetry.note(
                            bubble_fraction=pstats["bubble_fraction"])
            else:
                self._note_sparse_fallback(block, loss_fn, data, k)
                result = self._eager_train_step(
                    block, loss_fn, data, label, batch_size, k,
                    ignore_stale_grad)
        except BaseException:
            telemetry.step_abort(acc)
            raise
        telemetry.step_end(acc, step=self._step_count,
                           skipped=len(self.skipped_steps) > n_skipped)
        return result

    def _note_sparse_fallback(self, block, loss_fn, data, grad_accum):
        """A sparse_grad=True model landing on the eager oracle is a
        performance cliff (multi-dispatch, host-side coalesce) the user
        explicitly tried to avoid — emit a ``sparse_fallback{reason}``
        telemetry event rather than degrading silently.  Dense models
        fall back silently as before."""
        if not any(p._grad_req != "null"
                   and getattr(p, "_grad_stype", None) == "row_sparse"
                   for p in self._params):
            return
        from .. import resilience
        from .. import telemetry
        from . import captured as _captured
        if not _captured.captured_step_enabled():
            reason = "captured step disabled (MXTPU_CAPTURED_STEP=0)"
        elif resilience.fault_armed("nan_grad") \
                or resilience.fault_armed("bit_flip_grad"):
            reason = "pending gradient fault injection"
        else:
            reason = getattr(self, "_sparse_fallback_reason", None)
            self._sparse_fallback_reason = None
            if reason is None:
                reason = _captured.ineligible_reason(
                    self, block, loss_fn, data, grad_accum) \
                    or "capture declined"
        telemetry.event("sparse_fallback", reason=reason)

    def _maybe_shard_batch(self, data, label):
        """When the parameters are committed over a multi-device mesh
        (`parallel.shard_model`), place the batch over its dp axis
        IN-PLACE, before the captured/eager branch — both paths must
        see the identical committed placement or the eager oracle's
        programs would lay data out differently and break bitwise
        parity with the captured program."""
        from ..ndarray import NDArray
        from ..parallel.sharding import batch_sharding, mesh_of_params

        mesh = mesh_of_params(self._params)
        if mesh is None:
            return
        import jax

        for nd in (data, label):
            if isinstance(nd, NDArray) and nd.ndim >= 1:
                sh = batch_sharding(mesh, nd.shape[0])
                nd._set_data(jax.device_put(nd._data, sh))

    def _eager_train_step(self, block, loss_fn, data, label, batch_size,
                          grad_accum, ignore_stale_grad):
        """The multi-dispatch step the captured program is checked
        against: per-microbatch forward/backward with grad buffers,
        then the regular guarded `step`."""
        from .. import autograd as ag

        scaler = getattr(self, "_amp_loss_scaler", None)
        k = grad_accum
        if k == 1:
            with ag.record():
                out = block(data)
                loss = loss_fn(out, label) if label is not None \
                    else loss_fn(out)
                scaled = loss * scaler.loss_scale \
                    if scaler is not None else loss
            scaled.backward()
            result = loss
        else:
            if data.shape[0] % k:
                raise ValueError(
                    f"batch size {data.shape[0]} is not divisible by "
                    f"grad_accum {k}")
            m = data.shape[0] // k
            params = [p for p in self._params if p._grad_req != "null"]
            losses = []
            with ag.accumulate_grads(params):
                for j in range(k):
                    xs = data[j * m:(j + 1) * m]
                    ys = None if label is None \
                        else label[j * m:(j + 1) * m]
                    with ag.record():
                        out = block(xs)
                        loss = loss_fn(out, ys) if ys is not None \
                            else loss_fn(out)
                        scaled = loss * scaler.loss_scale \
                            if scaler is not None else loss
                    scaled.backward()
                    losses.append(loss)
            import jax.numpy as jnp

            from ..ndarray import _from_jax

            result = _from_jax(jnp.stack([l._data for l in losses]))
        self.step(batch_size, ignore_stale_grad)
        return result

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise AssertionError(
                "allreduce_grads() when parameters are updated on kvstore "
                "is not supported. Try setting `update_on_kvstore` to False "
                "when creating trainer.")
        self._allreduce_grads()

    def _allreduce_grads(self):
        """Cross-process gradient reduce.  Returns the fused ``(2,)``
        health array when `bucketed_pushpull` computed it post-reduce
        (avoiding a second pass over the gradients), else None — the
        guarded `_update` then runs its own health reduction."""
        if self._kvstore is None:
            return None
        if self._update_on_kvstore:
            for i, param in enumerate(self._params):
                if param._grad_req != "null":
                    # push grad; pull updated weight (server-side optimizer)
                    self._kvstore.push(i, param.list_grad(), priority=-i)
            return None
        keys = [i for i, param in enumerate(self._params)
                if param._grad_req != "null"]
        from ..parallel.sharding import mesh_of_params

        if mesh_of_params(self._params) is not None:
            # GSPMD owns the collectives when params live on a mesh:
            # the bucketed host-side pushpull would flat-concat the
            # grads, silently all-gathering every shard — per-key
            # pushpull keeps each reduce shard-shaped
            for i in keys:
                self._kvstore.pushpull(i, self._params[i].list_grad(),
                                       out=self._params[i].list_grad(),
                                       priority=-i)
            return None
        if opt.grouped.fused_step_enabled() \
                and hasattr(self._kvstore, "bucketed_pushpull"):
            grads = [self._params[i].list_grad() for i in keys]
            bp = self._kvstore.bucketed_pushpull
            want = numerics.grad_guard_enabled() \
                or self._clip_norm() is not None
            code = getattr(getattr(bp, "__func__", bp), "__code__", None)
            if want and code is not None and "health" in \
                    code.co_varnames[:code.co_argcount
                                     + code.co_kwonlyargcount]:
                return bp(keys, grads, outs=grads, health=True)
            bp(keys, grads, outs=grads)
            return None
        for i in keys:
            self._kvstore.pushpull(i, self._params[i].list_grad(),
                                   out=self._params[i].list_grad(),
                                   priority=-i)
        return None

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        assert not self._update_on_kvstore, \
            "update() when parameters are updated on kvstore is not " \
            "supported. Try setting `update_on_kvstore` to False when " \
            "creating trainer."
        self._set_rescale(batch_size)
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False, health=None):
        updates = []
        for i, param in enumerate(self._params):
            if param._grad_req == "null":
                continue
            if not ignore_stale_grad:
                data = param.data()
                if hasattr(data, "_fresh_grad") and not data._fresh_grad:
                    raise UserWarning(
                        f"Gradient of Parameter `{param.name}` on context "
                        "has not been updated by backward since last step.")
            if self._update_on_kvstore:
                self._kvstore.pull(i, param.list_data(), priority=-i)
            else:
                updates.append((i, param.grad(), param.data()))
        self._step_count += 1
        if not updates:
            return
        indices, grads, weights = map(list, zip(*updates))
        fused = opt.grouped.fused_step_enabled()
        guard_on = numerics.grad_guard_enabled()
        clip = self._clip_norm()
        if fused and (guard_on or clip is not None):
            # nan_grad / bit_flip_grad fault sites; a fired injection
            # invalidates any health computed during the allreduce
            from .. import integrity as _integrity

            flipped = _integrity.maybe_bit_flip_grad(grads=grads)
            if numerics.maybe_inject_nan_grad(grads) or flipped \
                    or health is None:
                health = numerics.grad_health(
                    [g._data if isinstance(g, NDArray) else g
                     for g in grads])
            guard = numerics.StepGuard(health, skip=guard_on, clip=clip)
            snapshot = self._snapshot_update_counts(indices) \
                if guard_on else None
            self._grouped_updaters[0](indices, grads, weights, guard=guard)
            self._finalize_guarded_step(guard, snapshot)
        elif fused:
            # one jitted dispatch per (kernel, hyper-params, dtype) group
            self._grouped_updaters[0](indices, grads, weights)
        else:
            for i, g, w in updates:
                self._updaters[0](i, g, w)

    def attach_data_pipeline(self, pipeline):
        """Attach a resumable input pipeline (a ``DataLoader`` built
        with ``seed=``, or a ``DevicePrefetcher`` wrapping one).  The
        guarded step then (a) passes the just-delivered batch id to the
        divergence monitor — a rollback quarantines the streak's
        batches so replay skips them — and (b) notes ``samples_seen``
        on each step's telemetry record.  Also wired into an attached
        ``divergence_monitor`` so its rollback rewinds the pipeline to
        the restored checkpoint's sample offset.  Returns self."""
        self._data_pipeline = pipeline
        if self.divergence_monitor is not None:
            self.divergence_monitor.data_pipeline = pipeline
        return self

    def _batch_ids(self):
        """[(epoch, batch_idx)] of the last-delivered batch, or None."""
        p = self._data_pipeline
        if p is None:
            return None
        bid = p.last_batch_id()
        return None if bid is None else [bid]

    # -- integrity plane plumbing (mxnet_tpu/integrity.py) ---------------------

    def attach_integrity(self, plane):
        """Attach an `integrity.IntegrityPlane`: with MXTPU_INTEGRITY
        on, the captured step fingerprints the parameter+optimizer
        state every ``plane.every`` steps (in-program, read back with
        the StepGuard's single sync) and attests it against the
        plane's peers.  Returns self for chaining."""
        self._integrity_plane = plane
        return self

    def _integrity_due(self):
        """Does the step ABOUT to dispatch attest?  Read pre-dispatch
        (the traced ``attest`` predicate of the captured program)."""
        plane = self._integrity_plane
        return plane is not None and plane.due(self._step_count + 1)

    def _integrity_attest(self, fp):
        """One attestation round for the step that just committed."""
        plane = self._integrity_plane
        if plane is None or fp is None:
            return None
        return plane.attest(self._step_count, fp)

    # -- numerical-health guard plumbing (mxnet_tpu/numerics.py) ---------------

    def _snapshot_update_counts(self, indices):
        """Host-side optimizer step counters, captured BEFORE the guarded
        update bumps them — a skipped step must leave Adam's
        bias-correction `t` (and friends) exactly as if the bad batch
        never existed."""
        o = self._optimizer
        return (o.num_update,
                {i: o._index_update_count.get(i) for i in indices})

    def _restore_update_counts(self, snapshot):
        o = self._optimizer
        num_update, per_index = snapshot
        o.num_update = num_update
        for i, v in per_index.items():
            if v is None:
                o._index_update_count.pop(i, None)
            else:
                o._index_update_count[i] = v

    def _finalize_guarded_step(self, guard, snapshot):
        """The step's ONE host readback happens here, AFTER the update
        dispatch, so XLA pipelines the guard with the step.  On an
        unhealthy step the fused programs already returned the donated
        weights/states unchanged; this rolls back the host-side step
        counters, halves the amp loss scale and emits a StepSkipped."""
        from .. import telemetry

        scaler = getattr(self, "_amp_loss_scaler", None)
        monitor = self.divergence_monitor
        if not guard.skip:
            # clipping-only: no host decision needed unless a monitor or
            # scaler wants the scalars
            if monitor is not None:
                monitor.observe(step=self._step_count,
                                grad_norm=guard.grad_norm, healthy=True,
                                batch_indices=self._batch_ids())
            self._note_guard_scalars(guard, scaler)
            self._integrity_attest(guard.fingerprint)
            return
        healthy = guard.healthy
        if not healthy:
            self._restore_update_counts(snapshot)
            rec = numerics.StepSkipped(
                step=self._step_count, reason="non-finite gradients",
                grad_norm=guard.grad_norm,
                loss_scale=scaler.loss_scale if scaler else None)
            self.skipped_steps.append(rec)
            del self.skipped_steps[:-_MAX_SKIP_RECORDS]
            _LOG.warning("skipped optimizer step: %r", rec)
            telemetry.count("step.skipped")
            telemetry.event("step_skipped", step=rec.step,
                            reason=rec.reason, grad_norm=rec.grad_norm,
                            loss_scale=rec.loss_scale)
        if scaler is not None:
            scaler.update_scale(not healthy)
            self._scale = 1.0 / scaler.loss_scale
        if monitor is not None:
            monitor.observe(step=self._step_count,
                            grad_norm=guard.grad_norm, healthy=healthy,
                            batch_indices=self._batch_ids())
        self._note_guard_scalars(guard, scaler)
        self._integrity_attest(guard.fingerprint)

    def _note_guard_scalars(self, guard, scaler):
        """Attach guard scalars to the open StepStats record — only via
        `StepGuard.peek()`, so telemetry never adds a host readback the
        step didn't already pay for."""
        from .. import telemetry

        host = guard.peek()
        if host is not None:
            import math as _math
            _, sq = host
            telemetry.note(grad_norm=_math.sqrt(sq) if sq >= 0.0
                           else float("nan"))
        if scaler is not None:
            telemetry.note(loss_scale=scaler.loss_scale)
        if self._data_pipeline is not None:
            telemetry.note(samples_seen=int(
                self._data_pipeline.samples_seen))

    def save_states(self, fname):
        """Save optimizer/updater states (reference: Trainer.save_states)."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updaters[0].get_states(
                    dump_optimizer=True))

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            self._updaters[0].set_states(states)
            self._updaters[0].optimizer = self._optimizer
            self._validate_updater_states(fname)
        self._optimizer.param_dict = {
            i: param for i, param in enumerate(self._params)}

    def _validate_updater_states(self, fname):
        """Loaded states are keyed by parameter INDEX; if the param list
        changed (count or shapes) since save, applying them would silently
        step the wrong arrays — fail loudly instead."""

        def _leaves(state):
            if state is None:
                return []
            if isinstance(state, (list, tuple)):
                return [a for s in state for a in _leaves(s)]
            return [state] if isinstance(state, NDArray) else []

        states = self._updaters[0].states
        nparams = len(self._params)
        for idx, state in states.items():
            if not isinstance(idx, int) or idx < 0 or idx >= nparams:
                raise MXNetError(
                    f"Trainer.load_states: '{fname}' holds optimizer state "
                    f"for parameter index {idx!r}, but this trainer has "
                    f"only {nparams} parameters. The parameter list "
                    "changed since the states were saved.")
            param = self._params[idx]
            pshape = tuple(param.shape) if param.shape else None
            for arr in _leaves(state):
                if pshape is not None and tuple(arr.shape) != pshape:
                    raise MXNetError(
                        f"Trainer.load_states: state shape "
                        f"{tuple(arr.shape)} for parameter index {idx} "
                        f"('{param.name}') does not match the parameter "
                        f"shape {pshape}. The parameter list changed "
                        "since the states were saved.")


def kvstore_requires_store(kv):
    """dist types always go through the store (cross-process reduce)."""
    return kv.type.startswith("dist")

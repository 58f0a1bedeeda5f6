"""Whole-step-compiled sharded training.

TPU-first centerpiece (SURVEY.md §7): where the reference runs
forward → backward → kvstore-reduce → optimizer as separate engine pushes
(gluon/trainer.py + src/kvstore/), ``ShardedTrainer`` compiles the ENTIRE
training step — forward, backward, gradient reduction, optimizer update,
BatchNorm aux updates — into ONE XLA program over a device mesh:

- the batch is a single global array sharded on the ``dp`` axis;
- parameters carry PartitionSpecs (sharding.py TP rules) and GSPMD inserts
  all collectives (dp grad psum, Megatron tp all-reduces) over ICI;
- optimizer state shards exactly like its parameter;
- input/param/opt buffers are donated — no per-step reallocation.

This is simultaneously the analog of CachedOp bulked execution, kvstore
all-reduce, and the fused optimizer ops, in one compiled artifact.
"""

from __future__ import annotations

import math

import numpy as _np

from ..base import MXNetError
from ..ndarray.ndarray import NDArray, _from_jax
from ..ops import optimizer_op as _op
from .mesh import DP, data_parallel_mesh
from .sharding import (ShardingRules, annotate_block, fsdp_rules,
                       param_sharding)


class _PureOptimizer:
    """Pure-functional optimizer over a list of param arrays.

    Mirrors the stateful mxnet_tpu.optimizer registry; state is a pytree
    sharded like its parameters.
    """

    def __init__(self, name, lr=0.01, momentum=0.0, wd=0.0, beta1=0.9,
                 beta2=0.999, epsilon=1e-8, clip_gradient=None,
                 lr_scheduler=None, gamma1=None, rho=None, gamma2=0.9,
                 centered=False, lower_bound=None, upper_bound=None,
                 clip_weights=None, lazy_update=True, **unknown):
        self.name = name.lower()
        self.lr = lr
        self.momentum = momentum
        self.wd = wd
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.clip_gradient = clip_gradient
        self.lr_scheduler = lr_scheduler
        # rmsprop decay: reference calls it gamma1, torch-style calls rho
        self.gamma1 = gamma1 if gamma1 is not None else \
            (rho if rho is not None else 0.9)
        self.gamma2 = gamma2
        self.centered = bool(centered)
        self.lower_bound = lower_bound
        self.upper_bound = upper_bound
        self.clip_weights = clip_weights
        if unknown:
            # reference-compatible knobs with no effect here (grads are
            # mean-normalized inside the compiled step; compute dtype is
            # set by block.cast) — warn, don't crash ported scripts
            import warnings

            warnings.warn(
                f"ShardedTrainer: ignoring optimizer hyperparameters "
                f"{sorted(unknown)} for {name}", stacklevel=3)
        if self.name not in ("sgd", "nag", "adam", "adamw", "lamb",
                             "rmsprop", "adagrad"):
            raise MXNetError(f"ShardedTrainer: unsupported optimizer "
                             f"{name}")

    def lr_at(self, num_update):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(num_update)
        return self.lr

    def n_states(self):
        if self.name == "rmsprop":
            return 3 if self.centered else 1
        return {"sgd": 1, "nag": 1, "adagrad": 1,
                "adam": 2, "adamw": 2, "lamb": 2}[self.name]

    def init_state(self, param_vals):
        import jax.numpy as jnp

        n = self.n_states()
        return [tuple(jnp.zeros_like(p) for _ in range(n))
                for p in param_vals]

    def apply(self, param_vals, grads, states, lr, t, wd_mults, lr_mults,
              rescale):
        """One pure update over all params; returns (new_params,
        new_states)."""
        import jax.numpy as jnp

        kw = {"rescale_grad": rescale}
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        new_p, new_s = [], []
        for p, g, s, wm, lm in zip(param_vals, grads, states, wd_mults,
                                   lr_mults):
            wd = self.wd * wm
            plr = lr * lm
            if self.name == "sgd":
                if self.momentum:
                    w, mom = _op.sgd_mom_update_pure(
                        p, g, s[0], lr=plr, momentum=self.momentum, wd=wd,
                        **kw)
                    s_out = (mom,)
                else:
                    (w,) = _op.sgd_update_pure(p, g, lr=plr, wd=wd, **kw)
                    s_out = s
            elif self.name == "nag":
                w, mom = _op.nag_mom_update_pure(
                    p, g, s[0], lr=plr, momentum=self.momentum, wd=wd, **kw)
                s_out = (mom,)
            elif self.name in ("adam", "adamw"):
                coef1 = 1.0 - self.beta1 ** t
                coef2 = 1.0 - self.beta2 ** t
                lr_t = plr * jnp.sqrt(coef2) / coef1
                fn = _op.adam_update_pure if self.name == "adam" else \
                    _op.adamw_update_pure
                w, m, v = fn(p, g, s[0], s[1], lr=lr_t, beta1=self.beta1,
                             beta2=self.beta2, epsilon=self.epsilon, wd=wd,
                             **kw)
                s_out = (m, v)
            elif self.name == "lamb":
                gnew, m, v = _op.lamb_update_phase1_pure(
                    p, g, s[0], s[1], t=t, beta1=self.beta1,
                    beta2=self.beta2, epsilon=self.epsilon, wd=wd, **kw)
                r1 = jnp.linalg.norm(p)
                r2 = jnp.linalg.norm(gnew)
                bounds = {}
                if self.lower_bound is not None:
                    bounds["lower_bound"] = self.lower_bound
                if self.upper_bound is not None:
                    bounds["upper_bound"] = self.upper_bound
                (w,) = _op.lamb_update_phase2_pure(p, gnew, r1, r2, lr=plr,
                                                   **bounds)
                s_out = (m, v)
            elif self.name == "rmsprop":
                cw = {"clip_weights": self.clip_weights} \
                    if self.clip_weights is not None else {}
                if self.centered:
                    w, n, gm, d = _op.rmspropalex_update_pure(
                        p, g, s[0], s[1], s[2], lr=plr, gamma1=self.gamma1,
                        gamma2=self.gamma2, epsilon=self.epsilon, wd=wd,
                        **kw, **cw)
                    s_out = (n, gm, d)
                else:
                    w, n = _op.rmsprop_update_pure(
                        p, g, s[0], lr=plr, gamma1=self.gamma1,
                        epsilon=self.epsilon, wd=wd, **kw, **cw)
                    s_out = (n,)
            elif self.name == "adagrad":
                w, h = _op.adagrad_update_pure(
                    p, g, s[0], lr=plr, epsilon=self.epsilon, wd=wd, **kw)
                s_out = (h,)
            # the f32 lr scalar promotes the update math to f32 — cast
            # back so bf16 weights stay bf16 across steps (the reference
            # updaters preserve weight dtype; dtype drift would also
            # retrace the jitted step every call)
            w = w.astype(p.dtype)
            s_out = tuple(s_new.astype(s_old.dtype)
                          for s_new, s_old in zip(s_out, s))
            new_p.append(w)
            new_s.append(s_out)
        return new_p, new_s


class ShardedTrainer:
    """Train a gluon Block with one compiled step over a Mesh.

    Usage::

        mesh = parallel.make_mesh(dp=4, tp=2)
        trainer = parallel.ShardedTrainer(net, loss_fn, 'adam',
                                          {'learning_rate': 1e-3},
                                          mesh=mesh,
                                          rules=parallel.TRANSFORMER_TP_RULES)
        loss = trainer.step(x, y)   # one XLA program per step
    """

    def __init__(self, block, loss_fn, optimizer="sgd",
                 optimizer_params=None, mesh=None, rules=None,
                 batch_axis=DP, grad_accum=1, remat=None, mode=None):
        import jax

        from .. import engine
        engine.ensure_compile_cache()
        self.block = block
        self.loss_fn = loss_fn
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        self.batch_axis = batch_axis
        self.remat = remat
        opt_kwargs = dict(optimizer_params or {})
        lr = opt_kwargs.pop("learning_rate", opt_kwargs.pop("lr", 0.01))
        self.optimizer = _PureOptimizer(optimizer, lr=lr, **opt_kwargs)
        if mode == "fsdp" and rules is None:
            # FSDP over the batch axis: rules resolve per-shape, so
            # annotation is deferred to _stage (after deferred init)
            rules = fsdp_rules(mesh=self.mesh, axis=batch_axis)
        elif mode not in (None, "tp", "fsdp"):
            raise MXNetError(f"ShardedTrainer: unknown mode {mode!r} "
                             "(expected 'tp' or 'fsdp')")
        self._rules = rules
        if rules is not None:
            annotate_block(block, rules)
        self._grad_accum = int(grad_accum)
        assert self._grad_accum >= 1
        self._num_update = 0
        self._step_fn = None
        self._initialized = False

    # -- parameter staging -----------------------------------------------------

    def _stage(self, example):
        """Collect params (after deferred init), lay them on the mesh."""
        import jax

        # materialize deferred shapes with one throwaway eager pass
        from .. import autograd as _ag
        from ..gluon.block import _TRACE

        needs = any(p._deferred_init
                    for p in self.block.collect_params().values())
        if needs:
            prev = _TRACE.force_eager
            _TRACE.force_eager = True
            try:
                with _ag.pause():
                    self.block(example)
            finally:
                _TRACE.force_eager = prev
        if self._rules is not None:
            # re-resolve with materialized shapes: shape-driven rules
            # (FSDPRules) see None for deferred params at __init__ time
            annotate_block(self.block, self._rules)
        allp = list(self.block.collect_params().items())
        self._trainable = [(n, p) for n, p in allp if p.grad_req != "null"]
        self._aux = [(n, p) for n, p in allp if p.grad_req == "null"]
        self._param_shardings = [param_sharding(p, self.mesh)
                                 for _, p in self._trainable]
        self._param_vals = [
            jax.device_put(p.data()._data, s)
            for (_, p), s in zip(self._trainable, self._param_shardings)]
        from jax.sharding import NamedSharding, PartitionSpec

        repl = NamedSharding(self.mesh, PartitionSpec())
        self._aux_vals = {n: jax.device_put(p.data()._data, repl)
                          for n, p in self._aux}
        self._opt_state = self.optimizer.init_state(self._param_vals)
        self._opt_state = [
            tuple(jax.device_put(s, sh) for s in states)
            for states, sh in zip(self._opt_state, self._param_shardings)]
        self._wd_mults = [p.wd_mult for _, p in self._trainable]
        self._lr_mults = [p.lr_mult for _, p in self._trainable]
        self._initialized = True

    def _build_step(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from .. import autograd as _ag
        from .. import random as _random
        from ..gluon.block import _TRACE

        block = self.block
        loss_block = self.loss_fn
        optimizer = self.optimizer
        t_ids = [id(p) for _, p in self._trainable]
        a_names = [n for n, _ in self._aux]
        a_ids = [id(p) for _, p in self._aux]
        wd_mults = tuple(self._wd_mults)
        lr_mults = tuple(self._lr_mults)

        grad_accum = self._grad_accum

        def pure_step(param_vals, opt_state, aux_vals, x, y, key, lr, t):
            def loss_of(pv, aux_cur, xb, yb, kb):
                from ..gluon.block import param_override_scope

                pm = dict(zip(t_ids, pv))
                pm.update({i: aux_cur[n]
                           for i, n in zip(a_ids, a_names)})
                aux_upd = {}
                with param_override_scope(pm, aux_upd), \
                        _random.key_scope(kb), _ag.train_mode():
                    out = block.forward(xb)
                    loss = loss_block(out, yb) \
                        if loss_block is not None else out
                return jnp.mean(loss), aux_upd

            # remat='full'|'dots'|... or MXNET_BACKWARD_DO_MIRROR: the
            # backward recomputes activations (reference mirror pass)
            from .. import remat as _remat

            loss_of = _remat.wrap(loss_of, self.remat)

            if grad_accum == 1:
                (loss, aux_upd), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(param_vals, aux_vals, x, y, key)
                new_aux = dict(aux_vals)
                new_aux.update(aux_upd)
            else:
                # microbatch the leading dim; one optimizer update from
                # the averaged gradients (reference grad_req='add' +
                # delayed trainer.step semantics, compiled).  Aux (BN
                # running stats) threads through the scan carry so each
                # microbatch applies its momentum update to the stats the
                # previous microbatch produced — k sequential updates per
                # step, matching the reference's k forward passes.
                def reshape(a):
                    return a.reshape((grad_accum, -1) + a.shape[1:])

                xm = jax.tree_util.tree_map(reshape, x)
                ym = jax.tree_util.tree_map(reshape, y)
                keys = jax.random.split(key, grad_accum)

                def body(carry, micro):
                    l_acc, g_acc, aux_cur = carry
                    xb, yb, kb = micro
                    (l, aux_upd), g = jax.value_and_grad(
                        loss_of, has_aux=True)(param_vals, aux_cur, xb,
                                               yb, kb)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    aux_next = dict(aux_cur)
                    aux_next.update(aux_upd)
                    return (l_acc + l, g_acc, aux_next), None

                g0 = jax.tree_util.tree_map(jnp.zeros_like, param_vals)
                (l_tot, g_tot, new_aux), _ = jax.lax.scan(
                    body, (0.0, g0, dict(aux_vals)), (xm, ym, keys))
                loss = l_tot / grad_accum
                grads = jax.tree_util.tree_map(
                    lambda g: g / grad_accum, g_tot)
            # loss_of returns the MEAN loss → grads are already
            # batch-normalized; rescale_grad stays 1 (the reference's
            # rescale=1/batch applies to summed grads)
            new_p, new_s = optimizer.apply(
                param_vals, grads, opt_state, lr, t, wd_mults, lr_mults,
                1.0)
            return new_p, new_s, new_aux, loss

        repl = NamedSharding(self.mesh, PartitionSpec())
        batch_spec = NamedSharding(self.mesh,
                                   PartitionSpec(self.batch_axis))
        self._batch_sharding = batch_spec
        in_shardings = (
            self._param_shardings,
            [tuple(sh for _ in states) for states, sh in
             zip(self._opt_state, self._param_shardings)],
            {n: repl for n, _ in self._aux},
            batch_spec, batch_spec, repl, None, None)
        out_shardings = (
            self._param_shardings,
            [tuple(sh for _ in states) for states, sh in
             zip(self._opt_state, self._param_shardings)],
            {n: repl for n, _ in self._aux},
            repl)
        with self.mesh:
            self._step_fn = jax.jit(
                pure_step,
                in_shardings=in_shardings,
                out_shardings=out_shardings,
                donate_argnums=(0, 1, 2))

    # -- public API ------------------------------------------------------------

    def step(self, data, label):
        """Run ONE compiled train step; returns the (replicated) loss.
        `data`/`label` may be arrays or pytrees of arrays (e.g. BERT's
        (mlm_labels, nsp_labels) tuple), batch-major on dim 0.  With
        grad_accum=k the batch is split into k microbatches inside the
        compiled step."""
        import jax
        import jax.numpy as jnp
        import jax.tree_util as jtu

        from .. import random as _random

        def to_raw(v):
            return v._data if isinstance(v, NDArray) else jnp.asarray(v)

        x = jtu.tree_map(to_raw, data)
        y = jtu.tree_map(to_raw, label)
        if not self._initialized:
            self._stage(jtu.tree_map(_from_jax, x))
            # autotune DB consult at capture time (replay-only on the
            # sharded path): a stored winner's knobs (bucket MB, FSDP
            # min size, remat, ...) must be in env BEFORE the step
            # program is traced
            from .. import autotune as _autotune

            _autotune.replay_for_sharded(
                _autotune.sharded_signature(self, x), self.mesh)
            self._build_step()
        x = jax.device_put(x, self._batch_sharding)
        y = jax.device_put(y, self._batch_sharding)
        self._num_update += 1
        t = self._num_update
        lr = self.optimizer.lr_at(t)
        key = _random.next_key()
        # MXTPU_STEP_TIMEOUT arms a watchdog around the dispatch: a step
        # wedged inside the runtime (lost device, stuck collective) dumps
        # thread stacks and errors out instead of hanging the driver
        from .. import resilience

        with resilience.guard_step(f"train_step {t}"):
            self._param_vals, self._opt_state, self._aux_vals, loss = \
                self._step_fn(self._param_vals, self._opt_state,
                              self._aux_vals, x, y, key,
                              jnp.asarray(lr, jnp.float32),
                              jnp.asarray(t, jnp.float32))
        return _from_jax(loss)

    def state_dict(self):
        """Full train state as a pytree (params + optimizer + step) for
        checkpointing; valid after the first step (or _stage).  The
        resilience.run_resilient get_state hook for sharded training."""
        from .. import checkpoint

        return checkpoint.trainer_state(self)

    def load_state_dict(self, state):
        """Load a state_dict()/checkpoint pytree back onto the mesh (the
        run_resilient set_state hook)."""
        from .. import checkpoint

        checkpoint.load_trainer_state(self, state)

    def state_template(self):
        """Elastic-restore template: `state_dict()`'s structure with this
        trainer's shardings at every array position.  Pass it to
        ``checkpoint.AsyncCheckpointer.restore(step, template=...)`` to
        re-lay a checkpoint written under a different world size or mesh
        onto this trainer's layout."""
        from .. import checkpoint

        return checkpoint.trainer_state_template(self)

    def reshape_mesh(self, mesh=None):
        """Re-lay this trainer onto a new mesh (the elastic N→M reshape,
        `resilience.ElasticGang`).

        After a gang membership change the device topology the step
        program compiled against is gone; this snapshots the full train
        state to host, rebuilds the mesh (default: a fresh
        data-parallel mesh over the CURRENT device set), recomputes the
        shardings, re-places every buffer, and recompiles the step —
        state values are preserved exactly, so the post-reshape loss
        trajectory matches a fresh trainer restored from the same
        snapshot."""
        if not self._initialized:
            self.mesh = mesh if mesh is not None else data_parallel_mesh()
            return self
        from .. import checkpoint

        state = checkpoint.trainer_state(self)
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        self._param_shardings = [param_sharding(p, self.mesh)
                                 for _, p in self._trainable]
        checkpoint.load_trainer_state(self, state)
        self._step_fn = None
        self._build_step()
        return self

    def sync_params(self):
        """Write the mesh-resident values back into the gluon Parameters
        (handle swap, no host transfer)."""
        for (name, p), val in zip(self._trainable, self._param_vals):
            p.data()._set_data(val)
        for name, p in self._aux:
            p.data()._set_data(self._aux_vals[name])

    @property
    def learning_rate(self):
        return self.optimizer.lr_at(self._num_update)

    def set_learning_rate(self, lr):
        self.optimizer.lr = lr
        self.optimizer.lr_scheduler = None


# DataParallelTrainer: the common case — pure DP mesh, no TP rules
class DataParallelTrainer(ShardedTrainer):
    def __init__(self, block, loss_fn, optimizer="sgd",
                 optimizer_params=None, n_devices=None):
        super().__init__(block, loss_fn, optimizer, optimizer_params,
                         mesh=data_parallel_mesh(n_devices))

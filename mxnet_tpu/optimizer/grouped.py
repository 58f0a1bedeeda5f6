"""Grouped (multi-tensor) optimizer stepping for the imperative Trainer.

Reference parity: the `multi_sgd_update` / `multi_mp_sgd_update` /
`multi_lamb` family (src/operator/optimizer_op.cc ≥1.6) plus Gluon's
`Trainer` aggregation (`MXNET_OPTIMIZER_AGGREGATION_SIZE`): instead of one
kernel launch per parameter, whole groups of parameters step in a single
fused call.

TPU-first design: `GroupedUpdater` partitions a Trainer's parameters into
groups keyed by (update kernel, static hyper-params, dtype) and applies
each group in ONE cached `jax.jit` program — pytrees of weights, grads and
states in, pytrees out, with weights and states donated so XLA updates
in place.  Per-step scalars (lr, wd, rescale_grad and the host-folded
step-count coefficients) enter as traced f32/f16 scalars cast to the
group dtype on the host, which keeps LR schedules from retracing AND
keeps the arithmetic bitwise-identical to the eager per-parameter loop
(a Python float in eager mode is weakly typed and rounds to the array
dtype in one step — exactly what the host-side cast does).

Anything the grouped kernels cannot express bitwise-identically — the
inline-eager optimizers (Nadam, Adamax, DCASGD, SGLD, Test), row-sparse
gradients, multi-precision fp16 master weights — falls back to the legacy
`Updater` per-parameter path, so numerics never change silently.
"""

from __future__ import annotations

import math
import os
import warnings

import numpy as _np

from ..ndarray.ndarray import NDArray
from ..ops import optimizer_op as _op
from . import optimizer as _optmod

# CPU/older backends cannot honor buffer donation; jax warns per call.
# The fallback (a copy) is correct, so the warning is pure noise here.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def fused_step_enabled() -> bool:
    """MXTPU_FUSED_STEP gate (default on); 0/false/off restores the
    legacy per-parameter loop."""
    return os.environ.get("MXTPU_FUSED_STEP", "1").lower() \
        not in ("0", "false", "off", "")


def group_max_items() -> int:
    """MXTPU_GROUP_MAX_ITEMS: cap on params fused into one optimizer
    group (0 = unlimited).  An autotune knob (autotune/space.py):
    re-read on every `plan_items` call, so a mid-run change re-plans —
    and, because the plan signature keys the capture cache, re-captures
    — the next step.  Splitting is bitwise-neutral: the group kernel
    loops per item, so chunk boundaries change fusion, never math."""
    try:
        return max(0, int(os.environ.get("MXTPU_GROUP_MAX_ITEMS", "0")))
    except ValueError:
        return 0


# -- dispatch accounting (regression-tested: one jit call per group/step) ------

_DISPATCH_COUNT = 0


def dispatch_count() -> int:
    """Number of grouped optimizer-update XLA dispatches since the last
    reset — exactly one per (kernel, static hyper-params, dtype) group
    per step."""
    return _DISPATCH_COUNT


def reset_dispatch_count() -> None:
    global _DISPATCH_COUNT
    _DISPATCH_COUNT = 0


# -- per-optimizer grouping plans ----------------------------------------------
#
# A plan maps one (optimizer, index, weight, state) item to
# (kernel, static_kwargs, state_ndarrays, dyn_fn).  `static_kwargs` are
# Python constants baked into the trace (identical to the eager call's
# keyword constants); `dyn_fn(opt, index)` runs AFTER the update count is
# bumped and returns the per-step host scalars, matching the exact float64
# expressions the eager optimizers compute before entering their kernels.


def _cg(opt):
    # pure kernels treat clip_gradient<0 as "no clipping", same as the
    # eager path omitting the kwarg
    return -1.0 if opt.clip_gradient is None else float(opt.clip_gradient)


def _dyn_lrwd(opt, index):
    return {"lr": opt._get_lr(index), "wd": opt._get_wd(index),
            "rescale_grad": opt.rescale_grad}


def _dyn_wd(opt, index):
    return {"wd": opt._get_wd(index), "rescale_grad": opt.rescale_grad}


def _dyn_adam(opt, index):
    d = _dyn_lrwd(opt, index)
    t = opt._index_update_count[index]
    coef1 = 1.0 - opt.beta1 ** t
    coef2 = 1.0 - opt.beta2 ** t
    d["lr"] = d["lr"] * (math.sqrt(coef2) / coef1)
    return d


def _dyn_lamb(opt, index):
    d = _dyn_lrwd(opt, index)
    t = opt._index_update_count[index]
    if opt.bias_correction:
        d["denom1"] = 1.0 - opt.beta1 ** t
        d["denom2"] = 1.0 - opt.beta2 ** t
    else:
        # x / 1.0 is an IEEE identity → bitwise-equal to the
        # uncorrected eager branch
        d["denom1"] = 1.0
        d["denom2"] = 1.0
    return d


def _dyn_ftml(opt, index):
    lr = opt._get_lr(index)
    t = opt._index_update_count[index]
    return {"c_over_lr": (1.0 - opt.beta1 ** t) / lr,
            "coef2": 1.0 - opt.beta2 ** t,
            "wd": opt._get_wd(index),
            "rescale_grad": opt.rescale_grad}


def _plan_sgd(o, i, w, state):
    if state is not None:
        return (_op.sgd_mom_update_pure,
                {"momentum": o.momentum, "clip_gradient": _cg(o)},
                [state], _dyn_lrwd)
    return (_op.sgd_update_pure, {"clip_gradient": _cg(o)}, [], _dyn_lrwd)


def _plan_nag(o, i, w, state):
    if state is not None:
        return (_op.nag_mom_update_pure,
                {"momentum": o.momentum, "clip_gradient": _cg(o)},
                [state], _dyn_lrwd)
    return (_op.sgd_update_pure, {"clip_gradient": _cg(o)}, [], _dyn_lrwd)


def _plan_adam(o, i, w, state):
    return (_op.adam_update_pure,
            {"beta1": o.beta1, "beta2": o.beta2, "epsilon": o.epsilon,
             "clip_gradient": _cg(o)},
            list(state), _dyn_adam)


def _plan_adamw(o, i, w, state):
    return (_op.adamw_update_pure,
            {"beta1": o.beta1, "beta2": o.beta2, "epsilon": o.epsilon,
             "clip_gradient": _cg(o)},
            list(state), _dyn_adam)


def _plan_rmsprop(o, i, w, state):
    cw = float(o.clip_weights) if o.clip_weights else -1.0
    if o.centered:
        return (_op.rmspropalex_update_pure,
                {"gamma1": o.gamma1, "gamma2": o.gamma2,
                 "epsilon": o.epsilon, "clip_gradient": _cg(o),
                 "clip_weights": cw},
                list(state), _dyn_lrwd)
    return (_op.rmsprop_update_pure,
            {"gamma1": o.gamma1, "epsilon": o.epsilon,
             "clip_gradient": _cg(o), "clip_weights": cw},
            list(state), _dyn_lrwd)


def _plan_adagrad(o, i, w, state):
    return (_op.adagrad_update_pure,
            {"epsilon": o.float_stable_eps, "clip_gradient": _cg(o)},
            [state], _dyn_lrwd)


def _plan_adadelta(o, i, w, state):
    return (_op.adadelta_update_pure,
            {"rho": o.rho, "epsilon": o.epsilon, "clip_gradient": _cg(o)},
            list(state), _dyn_wd)


def _plan_ftrl(o, i, w, state):
    return (_op.ftrl_update_pure,
            {"lamda1": o.lamda1, "beta": o.beta, "clip_gradient": _cg(o)},
            list(state), _dyn_lrwd)


def _plan_signum(o, i, w, state):
    if state is not None:
        return (_op.signum_update_pure,
                {"momentum": o.momentum, "wd_lh": o.wd_lh,
                 "clip_gradient": _cg(o)},
                [state], _dyn_lrwd)
    return (_op.signsgd_update_pure, {"clip_gradient": _cg(o)}, [],
            _dyn_lrwd)


def _plan_lamb(o, i, w, state):
    lb = -1.0 if o.lower_bound is None else float(o.lower_bound)
    ub = -1.0 if o.upper_bound is None else float(o.upper_bound)
    return (_op.lamb_fused_update_pure,
            {"beta1": o.beta1, "beta2": o.beta2, "epsilon": o.epsilon,
             "clip_gradient": _cg(o), "lower_bound": lb, "upper_bound": ub},
            list(state), _dyn_lamb)


def _plan_lars(o, i, w, state):
    # 1-D params (biases, norm scales) take the plain momentum step —
    # the optimizer's own skip list
    if len(w.shape) <= 1:
        return (_op.sgd_mom_update_pure,
                {"momentum": o.momentum, "clip_gradient": _cg(o)},
                [state], _dyn_lrwd)
    return (_op.lars_update_pure,
            {"momentum": o.momentum, "eta": o.eta, "epsilon": o.epsilon,
             "clip_gradient": _cg(o)},
            [state], _dyn_lrwd)


def _plan_ftml(o, i, w, state):
    return (_op.ftml_fused_update_pure,
            {"beta1": o.beta1, "beta2": o.beta2, "epsilon": o.epsilon,
             "clip_grad": _cg(o)},
            list(state), _dyn_ftml)


# -- row-sparse (lazy-update) kernel wrappers ----------------------------------

_SPARSE_KERNELS = {}


def sparse_row_kernel(kernel):
    """Row-sparse lazy-update variant of a dense update kernel.

    The wrapped kernel sees ``grad`` as a ``(row_ids, row_values)`` pair:
    it gathers the touched rows of the weight and every state, runs the
    SAME elementwise dense kernel on just those rows (the exact call
    `Optimizer._apply`'s eager sparse branch makes, including the
    values-to-weight-dtype cast), and scatters the results back with
    ``.at[ids].set``.  Untouched rows never enter the arithmetic, so
    they stay bit-identical — lazy-update semantics.

    Out-of-range ids are the captured step's padding convention
    (sentinel id == vocab): the gather may fill those rows with
    garbage, but JAX scatter DROPS out-of-bounds updates, so padded
    rows write nothing.  One wrapper per dense kernel is cached so the
    group key — ``(kernel, static_items, dtype)`` — stays stable across
    plans and capture signatures."""
    fn = _SPARSE_KERNELS.get(kernel)
    if fn is None:
        import jax.numpy as jnp

        def row_step(weight, grad, *states, **kw):
            ids, vals = grad
            w_rows = jnp.take(weight, ids, axis=0)
            s_rows = [jnp.take(s, ids, axis=0) for s in states]
            res = kernel(w_rows, vals.astype(w_rows.dtype), *s_rows,
                         **kw)
            return (weight.at[ids].set(res[0]),
                    *[s.at[ids].set(r) for s, r in zip(states,
                                                       res[1:])])

        row_step.__name__ = "row_sparse_" \
            + getattr(kernel, "__name__", "kernel")
        _SPARSE_KERNELS[kernel] = fn = row_step
    return fn


def _sparse_groupable(opt, weight, grad):
    """Row-sparse items the grouped row kernel reproduces bitwise
    against the eager sparse oracle: SGD/Adam lazy-update on a dense
    float weight.  Everything else (other optimizers, lazy_update=False
    densification, fp16 master weights) keeps the legacy per-parameter
    path."""
    from ..ndarray.sparse import RowSparseNDArray

    if not isinstance(grad, RowSparseNDArray) \
            or isinstance(weight, RowSparseNDArray):
        return False
    if type(opt) not in (_optmod.SGD, _optmod.Adam):
        return False
    if not getattr(opt, "lazy_update", True):
        return False
    import jax.numpy as jnp

    w_raw = _raw(weight)
    if not jnp.issubdtype(w_raw.dtype, jnp.floating):
        return False
    if opt.multi_precision and w_raw.dtype == _np.float16:
        return False
    return True


# exact-type dispatch: a user SUBCLASS of a registered optimizer may
# override update() arbitrarily, so it must take the legacy loop
_PLANS = {
    _optmod.SGD: _plan_sgd,
    _optmod.NAG: _plan_nag,
    _optmod.Adam: _plan_adam,
    _optmod.AdamW: _plan_adamw,
    _optmod.RMSProp: _plan_rmsprop,
    _optmod.AdaGrad: _plan_adagrad,
    _optmod.AdaDelta: _plan_adadelta,
    _optmod.Ftrl: _plan_ftrl,
    _optmod.Signum: _plan_signum,
    _optmod.LAMB: _plan_lamb,
    _optmod.LARS: _plan_lars,
    # LBSGD only overrides the HOST-side lr warmup (_get_lr), which the
    # dyn scalars already route through — device math is LARS's
    _optmod.LBSGD: _plan_lars,
    _optmod.FTML: _plan_ftml,
}


def _groupable(opt, weight, grad):
    """Items the grouped kernels reproduce bitwise; everything else
    falls back to the per-parameter Updater."""
    from ..ndarray.sparse import RowSparseNDArray

    if isinstance(grad, RowSparseNDArray) \
            or isinstance(weight, RowSparseNDArray):
        return False
    w_raw = weight._data if isinstance(weight, NDArray) else weight
    g_raw = grad._data if isinstance(grad, NDArray) else grad
    import jax.numpy as jnp

    if not jnp.issubdtype(w_raw.dtype, jnp.floating):
        return False
    if w_raw.dtype != g_raw.dtype:
        return False
    if opt.multi_precision and w_raw.dtype == _np.float16:
        return False
    return True


# -- the jitted group program --------------------------------------------------

_GROUP_FN_CACHE = {}


def build_group_step(kernel, static_items, guarded=False, clip=None):
    """Build the PURE (unjitted) group-step function — the single home
    of the fused update math.  `_group_fn` jits it for the eager
    multi-dispatch path; the whole-step capture (`gluon/captured.py`)
    inlines the SAME function into its one donated program, so the two
    paths share every arithmetic decision (clip formula, cond
    branching, kernel unroll order) and stay bitwise-identical.

    Signatures: ``(weights, grads, states, dyn)`` when unguarded and
    unclipped, else ``(weights, grads, states, dyn, health)``; returns
    ``(new_weights, new_states)``.
    """
    import jax
    import jax.numpy as jnp

    static = dict(static_items)

    def run_updates(weights, grads, states, dyn, health):
        coef = None
        if clip is not None:
            norm = jnp.sqrt(health[1])
            coef = jnp.minimum(jnp.float32(1.0),
                               jnp.float32(clip) / (norm + 1e-8))
        new_w, new_s = [], []
        for j in range(len(weights)):
            kw = dict(static)
            for name, col in dyn.items():
                kw[name] = col[j]
            g = grads[j]
            if coef is not None:
                if isinstance(g, tuple):
                    # row-sparse (ids, values): clip scales the values,
                    # ids pass through untouched
                    g = (g[0], g[1] * coef.astype(g[1].dtype))
                else:
                    g = g * coef.astype(g.dtype)
            res = kernel(weights[j], g, *states[j], **kw)
            new_w.append(res[0])
            new_s.append(list(res[1:]))
        return new_w, new_s

    if not guarded and clip is None:
        def group_step(weights, grads, states, dyn):
            return run_updates(weights, grads, states, dyn, None)
    elif not guarded:
        def group_step(weights, grads, states, dyn, health):
            return run_updates(weights, grads, states, dyn, health)
    else:
        def group_step(weights, grads, states, dyn, health):
            ok = (health[0] > 0) & jnp.isfinite(health[1])

            def do_step(ops):
                return run_updates(*ops)

            def skip_step(ops):
                weights, _, states, _, _ = ops
                return list(weights), [list(s) for s in states]

            return jax.lax.cond(
                ok, do_step, skip_step,
                (weights, grads, states, dyn, health))

    return group_step


def _group_fn(kernel, static_items, guarded=False, clip=None):
    """One cached jit program per (kernel, static hyper-params, guard
    config).  Inside the trace the per-item kernels unroll into a single
    XLA module; weights (arg 0) and states (arg 2) are donated so the
    update is in-place on backends that support donation.

    With ``guarded`` the program takes the step's ``(2,)`` health array
    ``[all_finite, global_sq_norm]`` (numerics.grad_health) and branches
    on the health predicate with `jax.lax.cond` — an unhealthy step
    returns the donated inputs bitwise-unchanged, a healthy step runs
    the update math inside the cond's true branch, which XLA compiles as
    its own computation scope so fusion/contraction decisions match the
    unguarded program bitwise (a `jnp.where` over the outputs would pull
    the select INTO the kernel fusion and perturb FMA contraction).
    With ``clip`` (a static float) gradients are pre-scaled by
    ``min(1, clip / (norm + 1e-8))`` — the `gluon.utils.clip_global_norm`
    formula — inside the same program, reusing the already-computed norm.
    """
    key = (kernel, static_items, guarded, clip)
    fn = _GROUP_FN_CACHE.get(key)
    if fn is None:
        import jax

        fn = jax.jit(build_group_step(kernel, static_items,
                                      guarded=guarded, clip=clip),
                     donate_argnums=(0, 2))
        _GROUP_FN_CACHE[key] = fn
    return fn


def _raw(x):
    return x._data if isinstance(x, NDArray) else x


def _place_state_like(state, weight):
    """Lay freshly-created optimizer state over the owning weight's
    NamedSharding (parallel/sharding.py shard_model): same-shaped
    moments shard with the weight so grouped updates run shard-local —
    no gather, no replicated state copy.  Shared by both update paths
    because state creation is shared; a replicated / single-device
    weight leaves the state untouched."""
    from jax.sharding import NamedSharding

    raw_w = _raw(weight)
    sh = getattr(raw_w, "sharding", None)
    if not isinstance(sh, NamedSharding) or sh.mesh.size <= 1:
        return state

    import jax

    def place(s):
        if isinstance(s, (list, tuple)):
            return type(s)(place(v) for v in s)
        if isinstance(s, NDArray) and s.shape == raw_w.shape:
            s._set_data(jax.device_put(s._data, sh))
        return s

    return place(state)


def plan_items(updater, index, grad, weight):
    """Partition ``(index, grad, weight)`` triples into fused groups,
    creating optimizer states on demand through the SAME
    ``create_state_multi_precision`` call as the legacy loop.

    Returns ``(groups, fallback)``: ``groups`` maps
    ``(kernel, static_items, dtype_str)`` to item lists of
    ``(i, w, g, state_nds, dyn_fn)``; ``fallback`` holds the triples
    the kernels cannot express bitwise.  Shared by
    `GroupedUpdater.__call__` and the whole-step capture
    (`gluon/captured.py`), so both agree on what is groupable and on
    the group keying.
    """
    upd = updater
    o = upd.optimizer
    plan = _PLANS.get(type(o))
    groups = {}
    fallback = []
    fresh = [(i, w) for i, w in zip(index, weight) if i not in upd.states]
    if fresh:
        from ..profiler import scope

        # a trainer's first step: its optimizer state is made here
        with scope("startup.optimizer", leaves=len(fresh)):
            for i, w in fresh:
                if i in upd.states:     # an index given twice
                    continue
                upd.states[i] = o.create_state_multi_precision(i, w)
                upd.states_synced[i] = True
                _place_state_like(upd.states[i], w)
    for i, g, w in zip(index, grad, weight):
        item = None
        if plan is not None and _groupable(o, w, g):
            item = plan(o, i, w, upd.states[i])
        elif plan is not None and _sparse_groupable(o, w, g):
            kernel, static, state_nds, dyn_fn = \
                plan(o, i, w, upd.states[i])
            item = (sparse_row_kernel(kernel), static, state_nds,
                    dyn_fn)
        if item is None:
            fallback.append((i, g, w))
            continue
        kernel, static, state_nds, dyn_fn = item
        static_items = tuple(sorted(static.items()))
        gkey = (kernel, static_items, str(_raw(w).dtype))
        groups.setdefault(gkey, []).append((i, w, g, state_nds, dyn_fn))
    cap = group_max_items()
    if cap > 0:
        # split oversize groups into chunks of <= cap items; the chunk
        # ordinal extends the key (consumers index gkey[0..2], so the
        # extra element is invisible to them)
        split = {}
        for gkey, items in groups.items():
            if len(items) <= cap:
                split[gkey] = items
            else:
                for ci in range(0, len(items), cap):
                    split[gkey + (ci,)] = items[ci:ci + cap]
        groups = split
    return groups, fallback


def dyn_columns(optimizer, items, dtype):
    """Stack one step's per-item host scalars into one ``(n,)`` array
    per scalar name, cast host-side to the group dtype (the rounding a
    weakly-typed Python float would get inside the eager kernel).  Runs
    AFTER the update-count bump; shared by the eager grouped dispatch
    and the captured whole-step program so per-step scalars are
    bit-identical on both paths."""
    dyn_rows = [dyn_fn(optimizer, i) for i, _, _, _, dyn_fn in items]
    return {name: _np.asarray([row[name] for row in dyn_rows], dtype)
            for name in dyn_rows[0]}


class GroupedUpdater:
    """Multi-tensor drop-in for `Updater` on the Trainer's local path.

    Shares the wrapped Updater's `states` dict (and creates states through
    the same `create_state_multi_precision` call), so `save_states` /
    `load_states` and `set_states` round-trip identically whichever path
    ran the steps.
    """

    def __init__(self, updater):
        self._updater = updater

    @property
    def optimizer(self):
        return self._updater.optimizer

    @property
    def states(self):
        return self._updater.states

    def __call__(self, index, grad, weight, guard=None):
        from .. import profiler

        upd = self._updater
        o = upd.optimizer
        if guard is not None and not guard.skip and guard.clip is None:
            guard = None  # nothing for the programs to do with it
        if not isinstance(index, (list, tuple)):
            index, grad, weight = [index], [grad], [weight]
        groups, fallback = plan_items(upd, index, grad, weight)
        # legacy per-parameter loop for whatever the kernels can't express;
        # guarded steps skip these host-side (the guard's one readback —
        # shared with the Trainer's finalize via the StepGuard cache)
        if fallback and guard is not None and guard.skip \
                and not guard.healthy:
            fallback = []
        for i, g, w in fallback:
            upd(i, g, w)
        if not groups:
            return
        # bump every grouped index first (the eager loop bumps one at a
        # time, but num_update is a running max, so the per-item lr/wd
        # read below sees the same value either way)
        for items in groups.values():
            for i, *_ in items:
                o._update_count(i)
        global _DISPATCH_COUNT
        for gkey, items in groups.items():
            kernel, static_items = gkey[0], gkey[1]
            dtype = _raw(items[0][1]).dtype
            from ..ndarray.sparse import RowSparseNDArray

            w_raws = [_raw(w) for _, w, _, _, _ in items]
            # row-sparse grads enter as (ids, values) pairs — NOT the
            # dense ._data view, which would materialize the full table
            g_raws = [(g._rs_indices, g._rs_values)
                      if isinstance(g, RowSparseNDArray) else _raw(g)
                      for _, _, g, _, _ in items]
            s_raws = [[_raw(s) for s in st] for _, _, _, st, _ in items]
            # host-side cast + STACK into one (n,) array per name so the
            # jit pytree carries 1 leaf per scalar name, not n (the
            # per-leaf dispatch cost of n tiny args would eat the
            # fusion win)
            dyn = dyn_columns(o, items, dtype)
            if guard is None:
                fn = _group_fn(kernel, static_items)
                with profiler.annotate("optimizer_update"):
                    new_w, new_s = fn(w_raws, g_raws, s_raws, dyn)
            else:
                fn = _group_fn(kernel, static_items,
                               guarded=guard.skip, clip=guard.clip)
                with profiler.annotate("optimizer_update"):
                    new_w, new_s = fn(w_raws, g_raws, s_raws, dyn,
                                      guard.health)
            _DISPATCH_COUNT += 1
            for (_, w, _, st, _), nw, ns in zip(items, new_w, new_s):
                w._set_data(nw)
                for s_nd, s_new in zip(st, ns):
                    s_nd._set_data(s_new)

    # -- Updater API passthroughs (save/load states) ---------------------------
    def sync_state_context(self, state, context):
        return self._updater.sync_state_context(state, context)

    def set_states(self, states):
        self._updater.set_states(states)

    def get_states(self, dump_optimizer=False):
        return self._updater.get_states(dump_optimizer)

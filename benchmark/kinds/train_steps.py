"""Traffic kind ``train_steps``: the captured ``Trainer.train_step`` on
seeded batches, one step after another.

Set-up builds ONE object (net, trainer and the compiled step with its
state), drives it from the seed through its first three steps through
the window's own call and feed, and hands that same object to the
window.  What those three steps gave (each loss, the first gradient as
the optimizer got it, the parameters' change) is what `verify` holds
against the plain reference once the window has closed and the program's
state is freed.

The traffic file gives ``batch``, ``batches`` (distinct batches, cycled),
``data`` (a generator of `data.py`), ``optimizer`` with its parameters,
``train_step_batch_size`` (what ``train_step`` divides gradients by: 1
where the loss is already a mean) and, for several chips, ``parallel``
(``{"axes": {...}, "mode": ...}`` for ``shard_model``).
"""

import gc
import statistics

import numpy as np

CHECK_STEPS = 3
# leaves up to this many elements have their first gradient kept whole,
# to be compared element by element (biases, norm gains, positions)
SAMPLE_MAX = 2 ** 20


def _norm(x):
    import jax.numpy as jnp

    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def _build(ctx):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    from benchmark import data, program

    cell, traffic = ctx["cell"], ctx["cell"]["traffic"]
    config = cell["config"]
    net, leaves = program.build_net(cell, ctx["seed"], ctx["platform"])
    net.hybridize()
    if "parallel" in traffic:
        from mxnet_tpu import parallel

        mesh = parallel.make_mesh(axes=traffic["parallel"]["axes"],
                                  devices=ctx["devices"])
        parallel.shard_model(net, mesh, mode=traffic["parallel"]["mode"])
    trainer = gluon.Trainer(net.collect_params(), traffic["optimizer"],
                            dict(traffic["optimizer_params"]))
    loss_fn = program.resolve(config["program"]["loss"])()
    x, y = data.GENERATORS[traffic["data"]](
        ctx["seed"], traffic["batches"], traffic["batch"], traffic, config)
    mxctx = program.context(ctx["platform"])
    feed = []
    for i in range(traffic["batches"]):
        if y is None:       # a language model reads its labels off its ids
            ids = mx.nd.array(np.asarray(x[i], np.float32), ctx=mxctx)
            feed.append((ids, ids))
        else:
            feed.append((mx.nd.array(x[i], ctx=mxctx,
                                     dtype=config["program"]["dtype"]),
                         mx.nd.array(y[i], ctx=mxctx, dtype="float32")))
    return net, leaves, trainer, loss_fn, feed


def _step(state, i):
    """The one call the set-up steps and the window's steps share."""
    x, y = state["feed"][i % len(state["feed"])]
    loss = state["trainer"].train_step(
        state["net"], state["loss_fn"], x, y,
        batch_size=state["batch_size"])
    return float(np.mean(np.asarray(loss._data, np.float32)))


def _optimizer_states(trainer):
    """{parameter name: raw state} from the trainer's updater."""
    from mxnet_tpu.ndarray.ndarray import NDArray

    def raw(s):
        if isinstance(s, NDArray):
            return s._data
        if isinstance(s, (list, tuple)):
            return tuple(raw(v) for v in s)
        return s

    states = trainer._updaters[0].states
    return {p.name: raw(states[i]) for i, p in enumerate(trainer._params)
            if i in states}


def setup(ctx):
    from benchmark import optimizers

    traffic = ctx["cell"]["traffic"]
    net, leaves, trainer, loss_fn, feed = _build(ctx)
    ctx["lap"]("model built, seeded weights and data placed")
    state = {"net": net, "trainer": trainer, "loss_fn": loss_fn,
             "feed": feed, "leaves": leaves, "steps": 0,
             "batch_size": traffic["train_step_batch_size"]}
    start = {k: p.data()._data + 0 for k, p in leaves.items()}
    seen = {"loss": [], "first_gradient_norm": {},
            "first_gradient_sample": {}, "parameter_change_norm": {}}
    for i in range(CHECK_STEPS):
        seen["loss"].append(_step(state, i))
        ctx["lap"](f"step {i + 1}" + (" (captures, compiles or loads)"
                                      if i == 0 else ""))
        if i == 0:
            opt = _optimizer_states(trainer)
            for k, p in leaves.items():
                if p.name not in opt:       # not trained: no state
                    continue
                g = optimizers.first_gradient(
                    traffic["optimizer"], opt[p.name],
                    traffic["optimizer_params"], start[k])
                seen["first_gradient_norm"][k] = _norm(g)
                if g.size <= SAMPLE_MAX:
                    seen["first_gradient_sample"][k] = np.asarray(
                        g, np.float32)
    for k, p in leaves.items():
        if k not in seen["first_gradient_norm"]:
            continue
        seen["parameter_change_norm"][k] = _norm(
            p.data()._data.astype("float32") - start[k].astype("float32"))
    del start
    state["steps"] = CHECK_STEPS
    state["seen"] = seen
    ctx["log"]("first steps' losses " +
               " ".join(f"{v:.4f}" for v in seen["loss"]))
    return state


def arrays(state):
    """Every device array the window worked on, as it stands now."""
    return [p.data()._data for p in state["leaves"].values()] \
        + [a._data for pair in state["feed"] for a in pair]


def drive(state, window, ctx):
    work = ctx["cell"]["traffic"]["work_per_step"]
    records = []
    while window.submit(0):
        loss = _step(state, state["steps"])
        state["steps"] += 1
        took = window.complete(0, work)
        records.append({"step_ms": took * 1e3, "loss": loss})
    bad = [r for r in records if not np.isfinite(r["loss"])]
    return {"records": records, "attempted": len(records),
            "failed": len(bad), "faults": {}}


def close(state):
    from mxnet_tpu import parallel

    parallel.set_default_mesh(None)     # shard_model sets it


# -- correct -------------------------------------------------------------------

def reference_numbers(ctx, prod=None):
    """The reference through the same first steps: losses, first
    gradient norm and parameter change norm by leaf."""
    import jax.numpy as jnp

    from benchmark import data, optimizers, weights

    cell, traffic = ctx["cell"], ctx["cell"]["traffic"]
    ref, config = cell["reference"], cell["config"]
    store = jnp.dtype(config["program"]["dtype"])
    params = {k: v.astype(jnp.float32) for k, v in weights.make(
        ctx["seed"], ref.param_spec(config), store).items()}
    trained = [k for k in params
               if getattr(ref, "trainable", lambda k: True)(k)]
    start = params
    x, y = data.GENERATORS[traffic["data"]](
        ctx["seed"], traffic["batches"], traffic["batch"], traffic, config)
    init, step = optimizers.OPTIMIZERS[traffic["optimizer"]]
    opt = init({k: params[k] for k in trained})
    out = {"loss": [], "first_gradient_norm": {},
           "first_gradient_sample": {}, "parameter_change_norm": {}}
    kw = {} if prod is None else {"prod": prod}
    for i in range(CHECK_STEPS):
        b = i % traffic["batches"]
        batch = (jnp.asarray(x[b]),) if y is None \
            else (jnp.asarray(x[b]).astype(store).astype(jnp.float32),
                  jnp.asarray(y[b]))
        loss, grads = ref.loss_and_grads(
            params, *batch, config, rows=traffic["reference_rows"], **kw)
        out["loss"].append(float(loss))
        if i == 0:
            out["first_gradient_norm"] = {k: _norm(g)
                                          for k, g in grads.items()}
            out["first_gradient_sample"] = {
                k: np.asarray(g, np.float32) for k, g in grads.items()
                if g.size <= SAMPLE_MAX}
        new, opt = step({k: params[k] for k in trained}, grads, opt,
                        i + 1, traffic["optimizer_params"], store)
        params = dict(params, **new)
    out["parameter_change_norm"] = {k: _norm(params[k] - start[k])
                                    for k in trained}
    return out


def _worst_leaf(seen, ref):
    """The widest gap between the two norms of one leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    median = statistics.median(ref.values())
    worst, where = 0.0, None
    for k, r in ref.items():
        gap = abs(seen[k] - r) / max(r, median, 1e-30)
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def _worst_sample(seen, ref):
    """The widest norm of the difference of one sampled leaf's first
    gradient, against the reference's norm of that leaf or of the median
    sampled leaf: unlike a gap between norms, rounding noise shows in
    it in full."""
    norms = {k: float(np.linalg.norm(r)) for k, r in ref.items()}
    median = statistics.median(norms.values())
    worst, where = 0.0, None
    for k, r in ref.items():
        err = float(np.linalg.norm(seen[k] - r)) / max(norms[k], median,
                                                       1e-30)
        if err >= worst:
            worst, where = err, k
    return worst, where


def compare(seen, ref, limits, log):
    out = []
    loss_gap = max(abs(a - b) for a, b in zip(seen["loss"], ref["loss"]))
    log("losses " + " ".join(f"{v:.5f}" for v in seen["loss"])
        + " against the reference's "
        + " ".join(f"{v:.5f}" for v in ref["loss"]))
    out.append({"name": "loss_gap_max", "value": loss_gap,
                "limit": limits["loss_gap_max"]})
    for name in ("first_gradient_norm", "parameter_change_norm"):
        worst, where = _worst_leaf(seen[name], ref[name])
        log(f"{name}: worst leaf {where}: {seen[name][where]:.6g} against "
            f"{ref[name][where]:.6g}")
        out.append({"name": name + "_gap_max", "value": worst,
                    "limit": limits[name + "_gap_max"]})
    worst, where = _worst_sample(seen["first_gradient_sample"],
                                 ref["first_gradient_sample"])
    log(f"first gradient, element by element: worst sampled leaf {where}")
    out.append({"name": "first_gradient_error_max", "value": worst,
                "limit": limits["first_gradient_error_max"]})
    return out


def verify(state, result, ctx):
    seen = state["seen"]
    # the program's state is freed before the reference makes its own
    for k in ("net", "trainer", "feed", "leaves", "loss_fn"):
        state.pop(k, None)
    close(state)
    gc.collect()
    return compare(seen, reference_numbers(ctx),
                   ctx["cell"]["traffic"]["limits"], ctx["log"])


def control(state, result, ctx):
    """The reference in float8 in the program's place."""
    ref = ctx["cell"]["reference"]
    return compare(reference_numbers(ctx, ref.low_precision),
                   reference_numbers(ctx),
                   ctx["cell"]["traffic"]["limits"], ctx["log"])

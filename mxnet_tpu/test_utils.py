"""Testing utilities.

Reference parity: python/mxnet/test_utils.py — the testing backbone
(SURVEY.md §4): assert_almost_equal, check_numeric_gradient,
check_consistency, rand_ndarray, default_context, simple_forward.

The reference's CPU↔GPU consistency oracle maps to CPU-jax ↔ TPU here
(``check_consistency`` runs the same function on both backends when both
are visible).
"""

from __future__ import annotations

import os

import numpy as np

from .base import MXNetError
from .context import Context, cpu, current_context
from .ndarray.ndarray import NDArray, _from_jax


def default_context():
    """Env-switchable test context (reference: default_context +
    MXNET_TEST_DEFAULT_CONTEXT)."""
    name = os.environ.get("MXNET_TEST_DEFAULT_CONTEXT", "")
    if name:
        dev, _, idx = name.partition("(")
        idx = int(idx.rstrip(")")) if idx else 0
        return Context(dev.strip(), idx)
    return current_context()


def default_dtype():
    return np.float32


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_child_env(devices=1, **extra):
    """Environment for a child process that runs on the host CPU whatever
    the parent runs on.  One process holds a chip at a time, so a child
    that does not need the chip must not initialise the accelerator
    backend: ``JAX_PLATFORMS=cpu``, with ``devices`` virtual CPU devices.

    The child imports this checkout (PYTHONPATH) and inherits no
    ``MXTPU_*`` switch from the parent; pass what it needs in ``extra``.
    """
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("MXTPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def _padded_group(engine, prompts):
    """``prompts`` in the buckets `serve_group` would pick: ``(B, each
    row's length (B,), the padded block (B, S))``."""
    B = engine._pick_bucket(engine.batch_buckets, len(prompts), "group size")
    lens = np.ones(B, np.int32)         # pad rows hold one dummy token
    lens[:len(prompts)] = [len(p) for p in prompts]
    S = engine._pick_bucket(engine.prefill_buckets, int(lens.max()),
                            "prompt length")
    toks = np.zeros((B, S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    return B, lens, toks


def serving_host_walk(engine, prompts, steps, temperature=None, rng=None):
    """One group through a `ServingEngine`'s compiled programs with the
    host in every step, the reference for `serve_group`'s device-fed
    loop: read each program's logits, pick on the host
    (`ops.sampling._sample`: greedy, or drawn with ``rng``), put ids and
    positions back.  Holds each program's own greedy ids and next
    positions to what the host computes from the same step
    (AssertionError otherwise).  The group runs in the buckets
    `serve_group` would pick.  Returns ``(tokens (n, steps), logits (n,
    steps, vocab))``."""
    from .ops.sampling import _sample

    n = len(prompts)
    B, lens, toks = _padded_group(engine, prompts)
    pos, last = np.zeros(B, np.int32), lens - 1
    cache, out, logits = engine.init_cache(B), [], []
    for j in range(steps):
        cache, lg, ids, nxt_pos = engine._call(B, toks.shape[1], cache,
                                               pos, last, toks)
        lg = np.asarray(lg)
        assert lg.dtype == np.float32 and lg.ndim == 2, (lg.dtype, lg.shape)
        assert ids.dtype == np.int32 and ids.shape == (B, 1), ids
        np.testing.assert_array_equal(
            np.asarray(ids)[:, 0], lg.argmax(-1),
            err_msg=f"program {j}: ids are not the argmax of its logits")
        np.testing.assert_array_equal(np.asarray(nxt_pos), lens + j)
        nxt = _sample(lg, temperature, rng)
        out.append(nxt[:n])
        logits.append(lg[:n])
        pos, last, toks = lens + j, np.zeros(B, np.int32), nxt[:, None]
    return np.stack(out, 1), np.stack(logits, 1)


# What the families' tests hand `serving_unequal_answers` as ``wants``:
# answers of unequal length (a row that ends between rows that go on), of
# equal length (every row live in every step: the counts of a group
# before there was a mask), and three requests in a bucket of four with
# one that wants a single token (dead, like the pad row, from the first
# decode step)
UNEQUAL_ANSWERS = ([2, 9, 5, 9], [9, 9, 9, 9], [5, 1, 7])


def serving_unequal_answers(engine, prompts, wants):
    """One group whose requests want ``wants[i]`` tokens each through
    `ServingEngine.serve_group`, held to what makes a finished row
    harmless (AssertionError otherwise): every request gets exactly the
    tokens it gets served alone and in a group whose rows all run to
    ``max(wants)``, so a row's tokens depend neither on its own end nor
    on its neighbours'; and the engine's two counts of row-steps are
    the bucket's and the wanted ones.  Returns the group's timings and
    ``live``, the ``(row, decode step)`` pairs in which a row still
    wanted a token: what a family's decode counters sum over."""
    outs, timings = engine.serve_group(prompts, wants)
    steps = max(wants)
    equal, _ = engine.serve_group(prompts, steps)
    for i, (p, k) in enumerate(zip(prompts, wants)):
        alone, _ = engine.serve_group([p], k)
        assert len(outs[i]) == k, (i, outs[i])
        np.testing.assert_array_equal(
            outs[i], alone[0], err_msg=f"request {i} of {wants}: alone")
        np.testing.assert_array_equal(
            outs[i], equal[i][:k],
            err_msg=f"request {i} of {wants}: in a group of equal answers")
    live = [(i, j) for i, k in enumerate(wants) for j in range(k - 1)]
    assert timings["decode_row_steps"] == timings["bucket"][0] * (steps - 1)
    assert timings["decode_row_steps_live"] == len(live)
    return timings, live


def serving_dead_rows_keep_their_cache(engine, prompts, live):
    """A prefill of ``prompts`` and one decode step handed ``live`` (a
    bool a prompt: does the row still want a token), through the
    engine's own programs: in every stack of the cache (its arrays
    ``(L, B, K, D, W)``) and every state (its floating arrays ``(L, B,
    ...)`` of fewer axes: a state-space layer's state and tail) a row
    that is not live, a pad row among them, comes back bit for bit what
    the prefill left, in every layer, and the live rows' come back
    changed (AssertionError otherwise)."""
    import jax.numpy as jnp

    B, lens, toks = _padded_group(engine, prompts)
    zero = np.zeros(B, np.int32)
    cache, _, ids, pos = engine._call(B, toks.shape[1], engine.init_cache(B),
                                      zero, lens - 1, toks)
    left = np.zeros(B, np.int32)
    left[:len(prompts)] = np.where(live, 2, 0)
    rows = {kind: np.flatnonzero((left > 0) == (kind == "live"))
            for kind in ("live", "dead")}

    def stacks(cache):
        # gathered into buffers of their own: the step below is given
        # the cache's
        return {kind: [c[:, at] for c in cache if c.ndim == 5 or (
            c.ndim in (3, 4) and c.shape[1] == B
            and jnp.issubdtype(c.dtype, jnp.floating))]
                for kind, at in rows.items()}

    before = stacks(cache)
    cache, *_ = engine._call(B, 1, cache, pos, zero, ids, left)
    after = stacks(cache)
    assert before["dead"], "a cache with no stack (L, B, K, D, W)"
    for i, (a, b) in enumerate(zip(before["dead"], after["dead"])):
        assert bool(jnp.array_equal(a, b)), \
            f"stack {i}: a row of {rows['dead']} that wants no token " \
            f"was written (live {list(live)})"
    if len(rows["live"]):
        for i, (a, b) in enumerate(zip(before["live"], after["live"])):
            assert not bool(jnp.array_equal(a, b)), \
                f"stack {i}: the live rows {rows['live']} wrote nothing"


def jaxpr_loops(jaxpr):
    """Every scan/while equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scan", "while"):
            yield eqn
        for sub in eqn.params.values():
            inner = getattr(sub, "jaxpr", None)
            if inner is not None:
                yield from jaxpr_loops(inner)


def _as_np(x):
    if isinstance(x, NDArray):
        return x.asnumpy()
    return np.asarray(x)


def same(a, b):
    return np.array_equal(_as_np(a), _as_np(b))


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    a, b = _as_np(a), _as_np(b)
    return np.allclose(a, b,
                       rtol=1e-5 if rtol is None else rtol,
                       atol=1e-20 if atol is None else atol,
                       equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Dtype-aware tolerance comparison (reference:
    assert_almost_equal)."""
    a_np, b_np = _as_np(a), _as_np(b)
    if rtol is None or atol is None:
        dt = np.result_type(a_np.dtype, b_np.dtype)
        defaults = {np.dtype(np.float16): (1e-2, 1e-3),
                    np.dtype(np.float32): (1e-4, 1e-5),
                    np.dtype(np.float64): (1e-6, 1e-7)}
        d_rtol, d_atol = defaults.get(np.dtype(dt), (1e-4, 1e-5))
        rtol = rtol if rtol is not None else d_rtol
        atol = atol if atol is not None else d_atol
    np.testing.assert_allclose(a_np, b_np, rtol=rtol, atol=atol,
                               equal_nan=equal_nan,
                               err_msg=f"{names[0]} vs {names[1]}")


def rand_shape_nd(ndim, dim=10):
    return tuple(np.random.randint(1, dim + 1, size=ndim))


def rand_ndarray(shape, stype="default", density=None, dtype=np.float32,
                 scale=1.0):
    from . import ndarray as nd

    arr = nd.array(np.random.uniform(-scale, scale,
                                     shape).astype(dtype))
    return arr.tostype(stype) if stype != "default" else arr


def random_arrays(*shapes):
    arrays = [np.random.randn(*s).astype(np.float32) for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def list_gpus():
    """Reference: mx.test_utils.list_gpus — accelerator indices."""
    import jax

    try:
        return [d.id for d in jax.devices() if d.platform != "cpu"]
    except Exception:
        return []


def simple_forward(fn, *inputs, **kwargs):
    from . import ndarray as nd

    arrays = [nd.array(np.asarray(i)) if not isinstance(i, NDArray) else i
              for i in inputs]
    out = fn(*arrays, **kwargs)
    if isinstance(out, (list, tuple)):
        return [o.asnumpy() for o in out]
    return out.asnumpy()


def check_numeric_gradient(fn, inputs, eps=1e-4, rtol=1e-2, atol=1e-4,
                           argnums=None):
    """Finite-difference check of autograd gradients (reference:
    check_numeric_gradient — the op-level correctness oracle).

    fn: callable over NDArrays returning one NDArray (any shape; gradient
    of sum is checked).  inputs: list of numpy arrays.
    """
    from . import autograd
    from . import ndarray as nd

    inputs = [np.asarray(x, dtype=np.float64).astype(np.float32)
              for x in inputs]
    if argnums is None:
        argnums = range(len(inputs))

    arrs = [nd.array(x) for x in inputs]
    for a in arrs:
        a.attach_grad()
    with autograd.record():
        out = fn(*arrs)
        loss = out.sum() if hasattr(out, "sum") else sum(
            o.sum() for o in out)
    loss.backward()
    analytic = [a.grad.asnumpy() for a in arrs]

    for i in argnums:
        x = inputs[i]
        numeric = np.zeros_like(x)
        flat = x.reshape(-1)
        num_flat = numeric.reshape(-1)
        for j in range(flat.size):
            orig = flat[j]
            flat[j] = orig + eps
            plus = _loss_of(fn, inputs, nd)
            flat[j] = orig - eps
            minus = _loss_of(fn, inputs, nd)
            flat[j] = orig
            num_flat[j] = (plus - minus) / (2 * eps)
        np.testing.assert_allclose(
            analytic[i], numeric, rtol=rtol, atol=atol,
            err_msg=f"gradient mismatch for input {i}")


def _loss_of(fn, inputs, nd):
    out = fn(*[nd.array(x) for x in inputs])
    if isinstance(out, (list, tuple)):
        return float(sum(float(o.sum().asscalar()) for o in out))
    return float(out.sum().asscalar())


def check_symbolic_forward(fn, inputs, expected, rtol=1e-4, atol=1e-5):
    """Run fn on inputs, compare with expected numpy outputs."""
    from . import ndarray as nd

    out = fn(*[nd.array(np.asarray(x)) for x in inputs])
    outs = out if isinstance(out, (list, tuple)) else [out]
    expected = expected if isinstance(expected, (list, tuple)) \
        else [expected]
    for o, e in zip(outs, expected):
        assert_almost_equal(o, e, rtol=rtol, atol=atol)


def check_symbolic_backward(fn, inputs, out_grads, expected_grads,
                            rtol=1e-4, atol=1e-5):
    from . import autograd
    from . import ndarray as nd

    arrs = [nd.array(np.asarray(x)) for x in inputs]
    for a in arrs:
        a.attach_grad()
    with autograd.record():
        out = fn(*arrs)
    out.backward(nd.array(np.asarray(out_grads[0]))
                 if out_grads else None)
    for a, e in zip(arrs, expected_grads):
        if e is None:
            continue
        assert_almost_equal(a.grad, e, rtol=rtol, atol=atol)


def check_consistency(fn, inputs, backends=("cpu",), rtol=1e-4,
                      atol=1e-5):
    """Cross-backend consistency oracle (reference: the CPU↔GPU sweep in
    tests/python/gpu/test_operator_gpu.py; here CPU-jax ↔ TPU)."""
    import jax

    results = []
    for backend in backends:
        try:
            devs = jax.devices(backend)
        except RuntimeError:
            continue
        import jax.numpy as jnp

        args = [jax.device_put(jnp.asarray(np.asarray(x)), devs[0])
                for x in inputs]
        results.append((backend, np.asarray(fn(*args))))
    for (b1, r1), (b2, r2) in zip(results, results[1:]):
        np.testing.assert_allclose(
            r1, r2, rtol=rtol, atol=atol,
            err_msg=f"inconsistent between {b1} and {b2}")
    return results


def discover_type(dtype):
    return np.dtype(dtype)

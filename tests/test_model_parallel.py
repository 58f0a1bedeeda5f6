"""Tensor-parallel + FSDP training through the captured step
(parallel/sharding.py shard_model + gluon/captured.py).

Everything runs on the virtual 8-device CPU mesh (conftest forces
``--xla_force_host_platform_device_count=8``), so these sharding paths
execute on every tier-1 pass.  The load-bearing claims:

- `shard_model` places params, grads and optimizer state per the rules,
  in both TP and FSDP modes, and a model too big for one device's
  budget fits per-device once sharded;
- the sharded captured path stays ONE dispatch + ONE readback per
  healthy step (the PR 6 regression discipline, extended to tp>1);
- dp-only sharded runs are bitwise equal to the eager oracle
  (``MXTPU_CAPTURED_STEP=0``) on the same mesh.
"""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding

import mxnet_tpu as mx
from mxnet_tpu import gluon, numerics, parallel, telemetry
from mxnet_tpu.gluon import captured, nn
from mxnet_tpu.gluon.model_zoo.bert import TransformerEncoder
from mxnet_tpu.optimizer import grouped


def _transformer(layers=2, units=32, hidden=64, seed=7):
    mx.random.seed(seed)
    net = TransformerEncoder(num_layers=layers, units=units,
                             num_heads=4, hidden_size=hidden,
                             dropout=0.0)
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    return net


def _train(net, steps=3, n=8, t=6, units=32, seed=3):
    rng = np.random.RandomState(seed)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-3})
    losses = []
    for _ in range(steps):
        x = mx.nd.array(rng.normal(size=(n, t, units)).astype(np.float32))
        y = mx.nd.array(rng.randint(0, units, size=(n, t))
                        .astype(np.float32))
        losses.append(tr.train_step(net, loss_fn, x, y).asnumpy())
    return tr, losses


def _assert_state_sharded_like_weight(trainer, p, i):
    w = p.data()._data
    st = trainer._updaters[0].states[i]
    leaves = st if isinstance(st, (list, tuple)) else [st]
    for s in leaves:
        if hasattr(s, "_data") and s.shape == p.shape:
            assert s._data.sharding.is_equivalent_to(
                w.sharding, s._data.ndim), \
                f"state of param {i} not sharded like its weight"


def _per_device_param_bytes(net):
    """Bytes of parameter shards resident on ONE device (uniform across
    the mesh), plus the total across all params unsharded."""
    per_dev = total = 0
    for p in net.collect_params().values():
        w = p.data()._data
        itemsize = np.dtype(w.dtype).itemsize
        total += int(np.prod(w.shape)) * itemsize
        shard = w.sharding.shard_shape(w.shape)
        per_dev += int(np.prod(shard)) * itemsize
    return per_dev, total


# -- placement: TP and FSDP modes ----------------------------------------------

def test_shard_model_tp_places_params_grads_state(mesh8):
    mesh = mesh8(dp=2, tp=4)
    net = _transformer(layers=1)
    specs = parallel.shard_model(net, mesh, mode="tp")
    assert any("tp" in tuple(s) for s in specs.values())
    tr, losses = _train(net)
    assert all(np.isfinite(l).all() for l in losses)
    params = list(net.collect_params().items())
    tp_seen = 0
    for i, (name, p) in enumerate(params):
        w = p.data()._data
        assert isinstance(w.sharding, NamedSharding)
        assert tuple(w.sharding.spec) == tuple(specs[name])
        if "tp" in tuple(specs[name]):
            tp_seen += 1
        _assert_state_sharded_like_weight(tr, p, i)
    assert tp_seen >= 6  # qkv/proj/ffn1/ffn2 weights+biases per layer


def test_shard_model_fsdp_places_params_grads_state(mesh8):
    mesh = mesh8(dp=8)
    net = _transformer(layers=1)
    specs = parallel.shard_model(net, mesh, mode="fsdp", min_size=64)
    assert any("dp" in tuple(s) for s in specs.values())
    tr, losses = _train(net)
    assert all(np.isfinite(l).all() for l in losses)
    for i, (name, p) in enumerate(net.collect_params().items()):
        w = p.data()._data
        assert tuple(w.sharding.spec) == tuple(specs[name])
        _assert_state_sharded_like_weight(tr, p, i)


def test_shard_model_eager_grads_shard_with_weights(mesh8, monkeypatch):
    """Eager-oracle backward writes gradients whose shardings match the
    weights' — GSPMD inference from committed placements alone."""
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "0")
    mesh = mesh8(dp=2, tp=4)
    net = _transformer(layers=1)
    specs = parallel.shard_model(net, mesh, mode="tp")
    _train(net, steps=1)
    checked = 0
    for name, p in net.collect_params().items():
        if "tp" not in tuple(specs[name]) or p._grad is None:
            continue
        g, w = p._grad._data, p.data()._data
        assert g.sharding.is_equivalent_to(w.sharding, g.ndim), \
            f"grad of {name}: {g.sharding.spec} vs {w.sharding.spec}"
        checked += 1
    assert checked >= 6


def test_shard_model_aux_params_stay_replicated(mesh8):
    """FSDP's shape heuristic must not shard BatchNorm running stats:
    grad_req='null' params are forced replicated."""
    mesh = mesh8(dp=8)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(64, activation="relu", in_units=32))
        net.add(nn.BatchNorm(axis=1))
        net.add(nn.Dense(8, in_units=64))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    net(mx.nd.array(np.random.randn(8, 32).astype(np.float32)))
    specs = parallel.shard_model(net, mesh, mode="fsdp", min_size=16)
    for name, p in net.collect_params().items():
        if p.grad_req == "null":
            assert tuple(specs[name]) == ()
            assert p.data()._data.sharding.is_fully_replicated


def test_shard_model_rejects_unknown_mode(mesh8):
    with pytest.raises(ValueError):
        parallel.shard_model(_transformer(), mesh8(dp=8), mode="zp")


# -- acceptance: over-budget model fits per-device sharded ---------------------

@pytest.mark.parametrize("mode,axes", [("tp", dict(dp=2, tp=4)),
                                       ("fsdp", dict(dp=8))])
def test_over_budget_transformer_trains_sharded(mesh8, mode, axes):
    """A transformer whose total parameter bytes EXCEED a one-device
    budget trains on the 8-device mesh with per-device shard bytes
    UNDER it — the whole point of model parallelism, checked with a
    budget set between per-device and total."""
    mesh = mesh8(**axes)
    net = _transformer(layers=2, units=64, hidden=256)
    parallel.shard_model(net, mesh, mode=mode)
    per_dev, total = _per_device_param_bytes(net)
    budget = total // 2
    assert total > budget          # does NOT fit unsharded
    assert per_dev <= budget       # fits sharded
    tr, losses = _train(net, units=64)
    assert all(np.isfinite(l).all() for l in losses)


# -- the Pallas kernel under a mesh ---------------------------------------------

def test_flash_attention_runs_on_local_shards_under_a_mesh(mesh8):
    """A compiled Pallas call cannot be partitioned by GSPMD (on TPU it
    is refused outright): under the default mesh the impl="flash" entry
    wraps it in a shard_map, batch over dp and heads over tp, so q, k
    and v reach the kernel still sharded.  Interpret mode on the CPU
    mesh would partition without it, so the jaxpr is what is checked."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.ops.attention import scaled_dot_product_attention
    from mxnet_tpu.ops.pallas_attention import _dense_ref

    parallel.set_default_mesh(mesh8(dp=2, tp=2))
    rng = np.random.RandomState(0)
    q, k, v = (jax.numpy.asarray(rng.standard_normal((4, 2, 32, 8)),
                                 "float32") for _ in range(3))

    def flash(q, k, v):
        return scaled_dot_product_attention(q, k, v, causal=True,
                                            impl="flash")

    (eqn,) = [e for e in jax.make_jaxpr(flash)(q, k, v).eqns
              if e.primitive.name == "shard_map"]
    spec = P("dp", "tp", None, None)
    assert tuple(eqn.params["in_specs"]) == (spec,) * 3
    assert tuple(eqn.params["out_specs"]) == (spec,)
    out = jax.jit(flash)(q, k, v)
    assert len(out.sharding.device_set) == 4
    np.testing.assert_allclose(
        out, _dense_ref(q, k, v, True, 8 ** -0.5), atol=2e-6)
    # an axis that does not divide its dim stays out of the spec
    (eqn,) = [e for e in jax.make_jaxpr(flash)(q[:3], k[:3], v[:3]).eqns
              if e.primitive.name == "shard_map"]
    assert tuple(eqn.params["in_specs"]) == \
        (P(None, "tp", None, None),) * 3


# -- captured-path regression discipline at tp>1 -------------------------------

def test_one_dispatch_one_readback_per_step_tp(mesh8, monkeypatch):
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "1")
    monkeypatch.setenv("MXTPU_GRAD_GUARD", "1")
    mesh = mesh8(dp=2, tp=4)
    net = _transformer(layers=1)
    parallel.shard_model(net, mesh, mode="tp")
    rng = np.random.RandomState(5)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-3})
    mk = lambda: (mx.nd.array(rng.normal(size=(8, 6, 32))
                              .astype(np.float32)),
                  mx.nd.array(rng.randint(0, 32, size=(8, 6))
                              .astype(np.float32)))
    captured.reset_counters()
    for _ in range(2):  # warmup: trace + compile
        x, y = mk()
        tr.train_step(net, loss_fn, x, y)
    # ONE capture: the placement fingerprint must not change when the
    # first update hands ('tp', None) params back as ('tp',)
    assert captured.cache_stats() == {"hits": 1, "misses": 1}
    assert captured.trace_count() == 1
    captured.reset_counters()
    grouped.reset_dispatch_count()
    numerics.reset_readback_count()
    for _ in range(4):
        x, y = mk()
        tr.train_step(net, loss_fn, x, y)
    assert captured.dispatch_count() == 4
    assert grouped.dispatch_count() == 0
    assert numerics.readback_count() == 4
    assert captured.trace_count() == 0
    assert captured.cache_stats() == {"hits": 4, "misses": 0}


def test_resharding_misses_capture_cache(mesh8, monkeypatch):
    """Moving a model onto a mesh (or a different layout) must MISS the
    capture cache: the old program's layouts are stale."""
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "1")
    net = _transformer(layers=1)
    tr, _ = _train(net, steps=1)
    captured.reset_counters()
    mesh = mesh8(dp=2, tp=4)
    parallel.shard_model(net, mesh, mode="tp", trainer=tr)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    rng = np.random.RandomState(9)
    x = mx.nd.array(rng.normal(size=(8, 6, 32)).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 32, size=(8, 6)).astype(np.float32))
    tr.train_step(net, loss_fn, x, y)
    assert captured.cache_stats()["misses"] == 1


# -- dp-only bitwise parity with the eager oracle ------------------------------

def _run_dp_sharded(monkeypatch, captured_on, steps=6):
    monkeypatch.setenv("MXTPU_CAPTURED_STEP",
                       "1" if captured_on else "0")
    np.random.seed(0)
    mesh = parallel.make_mesh(dp=8)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8))
        net.add(nn.Dense(3, in_units=16))
    mx.random.seed(11)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    parallel.shard_model(net, mesh, mode="fsdp", min_size=8)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-2})
    rng = np.random.RandomState(42)
    losses, weights = [], None
    for _ in range(steps):
        x = mx.nd.array(rng.normal(size=(16, 8)).astype(np.float32))
        y = mx.nd.array(rng.randint(0, 3, size=(16,)).astype(np.float32))
        losses.append(tr.train_step(net, loss_fn, x, y).asnumpy())
    weights = [p.data().asnumpy() for p in tr._params]
    parallel.set_default_mesh(None)
    return losses, weights


@pytest.mark.parametrize("guard", ["1", "0"])
def test_dp_sharded_bitwise_captured_vs_eager(mesh8, monkeypatch, guard):
    """dp-only sharded: captured program == eager oracle on the same
    mesh, bitwise, guard on and off (the guard-off eager oracle
    discipline extended to sharded placements)."""
    monkeypatch.setenv("MXTPU_GRAD_GUARD", guard)
    le, we = _run_dp_sharded(monkeypatch, False)
    lc, wc = _run_dp_sharded(monkeypatch, True)
    for s, (a, b) in enumerate(zip(le, lc)):
        np.testing.assert_array_equal(a, b, err_msg=f"loss step {s}")
    for i, (a, b) in enumerate(zip(we, wc)):
        np.testing.assert_array_equal(a, b, err_msg=f"weight {i}")


def test_dp_sharded_matches_single_device_allclose(mesh8, monkeypatch):
    """Sanity anchor: the sharded run computes the same math as the
    unsharded single-device run (allclose — reduction orders differ)."""
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "1")
    lc, wc = _run_dp_sharded(monkeypatch, True)

    np.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8))
        net.add(nn.Dense(3, in_units=16))
    mx.random.seed(11)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-2})
    rng = np.random.RandomState(42)
    ls = []
    for _ in range(6):
        x = mx.nd.array(rng.normal(size=(16, 8)).astype(np.float32))
        y = mx.nd.array(rng.randint(0, 3, size=(16,)).astype(np.float32))
        ls.append(tr.train_step(net, loss_fn, x, y).asnumpy())
    for a, b in zip(ls, lc):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    for (a, b) in zip([p.data().asnumpy() for p in tr._params], wc):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


# -- activation annotations ----------------------------------------------------

def test_shard_activations_constrains_output(mesh8):
    mesh = mesh8(dp=2, tp=4)
    net = nn.Dense(16, in_units=8)
    net.initialize(mx.init.Xavier())
    net.shard_activations(("dp", "tp"), mesh)
    x = mx.nd.array(np.random.randn(4, 8).astype(np.float32))
    out = net(x)
    sh = out._data.sharding
    assert isinstance(sh, NamedSharding)
    assert tuple(sh.spec) == ("dp", "tp")


def test_shard_activations_noop_without_mesh():
    net = nn.Dense(16, in_units=8)
    net.initialize(mx.init.Xavier())
    net.shard_activations(("dp", "tp"))  # default mesh: None
    parallel.set_default_mesh(None)
    x = mx.nd.array(np.random.randn(4, 8).astype(np.float32))
    out = net(x)
    assert out.shape == (4, 16)


def test_annotate_activations_by_block_name(mesh8):
    mesh = mesh8(dp=8)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8))
        net.add(nn.Dense(3, in_units=16))
    rules = parallel.ShardingRules(rules=[(r"dense0", ("dp",))])
    parallel.annotate_activations(net, rules, mesh)
    assert net[0]._act_spec is not None
    assert net[1]._act_spec is None


# -- telemetry: per-axis collective bytes + memory high-water ------------------

def test_sharded_step_telemetry_fields(mesh8, monkeypatch):
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "1")
    mesh = mesh8(dp=2, tp=4)
    net = _transformer(layers=1)
    parallel.shard_model(net, mesh, mode="tp")
    _train(net, steps=3)
    recs = [r for r in telemetry.recent_steps()
            if r.get("path") == "captured"]
    assert recs
    rec = recs[-1]
    telemetry.validate_record(rec)
    assert rec.get("device_peak_bytes", 0) > 0
    coll = rec.get("collective_bytes_by_axis")
    assert isinstance(coll, dict) and coll
    # Megatron TP moves bytes over the tp axis inside the step
    assert coll.get("tp", 0) > 0
    for v in coll.values():
        assert isinstance(v, int) and v >= 0

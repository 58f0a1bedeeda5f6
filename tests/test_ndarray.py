"""NDArray core tests (reference: tests/python/unittest/test_ndarray.py)."""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_creation():
    a = nd.zeros((2, 3))
    assert a.shape == (2, 3)
    assert a.dtype == np.float32
    assert np.allclose(a.asnumpy(), 0)
    b = nd.ones((4,), dtype="int32")
    assert b.asnumpy().tolist() == [1, 1, 1, 1]
    c = nd.full((2, 2), 7.5)
    assert np.allclose(c.asnumpy(), 7.5)
    d = nd.array([[1, 2], [3, 4]])
    assert d.shape == (2, 2) and d.dtype == np.float32
    e = nd.arange(0, 10, 2)
    assert e.asnumpy().tolist() == [0, 2, 4, 6, 8]


def test_arithmetic():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([4.0, 5.0, 6.0])
    assert np.allclose((a + b).asnumpy(), [5, 7, 9])
    assert np.allclose((a - b).asnumpy(), [-3, -3, -3])
    assert np.allclose((a * b).asnumpy(), [4, 10, 18])
    assert np.allclose((b / a).asnumpy(), [4, 2.5, 2])
    assert np.allclose((a ** 2).asnumpy(), [1, 4, 9])
    assert np.allclose((2 + a).asnumpy(), [3, 4, 5])
    assert np.allclose((1 - a).asnumpy(), [0, -1, -2])
    assert np.allclose((-a).asnumpy(), [-1, -2, -3])


def test_comparison_elementwise():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([3.0, 2.0, 1.0])
    assert (a == b).asnumpy().tolist() == [0, 1, 0]
    assert (a < b).asnumpy().tolist() == [1, 0, 0]
    assert (a >= b).asnumpy().tolist() == [0, 1, 1]


def test_inplace_version_bump():
    a = nd.zeros((3,))
    v0 = a.version
    a += 1
    assert a.version > v0
    assert np.allclose(a.asnumpy(), 1)
    a *= 3
    assert np.allclose(a.asnumpy(), 3)


def test_setitem_getitem():
    a = nd.zeros((3, 4))
    a[1] = 5.0
    assert np.allclose(a.asnumpy()[1], 5)
    a[0, 2] = 1.0
    assert a.asnumpy()[0, 2] == 1
    b = a[1]
    assert b.shape == (4,)
    c = a[0:2, 1:3]
    assert c.shape == (2, 2)
    idx = nd.array([0, 2], dtype="int32")
    d = a[idx]
    assert d.shape == (2, 4)


def test_reshape_special_codes():
    a = nd.zeros((2, 3, 4))
    assert a.reshape((-1,)).shape == (24,)
    assert a.reshape((0, -1)).shape == (2, 12)
    assert a.reshape((-2,)).shape == (2, 3, 4)
    assert a.reshape((-3, 0)).shape == (6, 4)
    assert a.reshape(6, 4).shape == (6, 4)
    assert a.reshape((-4, 1, 2, 0, 0)).shape == (1, 2, 3, 4)


def test_dot():
    a = nd.array(np.random.rand(3, 4))
    b = nd.array(np.random.rand(4, 5))
    out = nd.dot(a, b)
    assert out.shape == (3, 5)
    assert np.allclose(out.asnumpy(), a.asnumpy() @ b.asnumpy(), atol=1e-5)


def test_reduce_ops():
    x = np.random.rand(2, 3, 4).astype(np.float32)
    a = nd.array(x)
    assert np.allclose(nd.sum(a).asnumpy(), x.sum(), rtol=1e-5)
    assert np.allclose(nd.sum(a, axis=1).asnumpy(), x.sum(1), rtol=1e-5)
    assert np.allclose(nd.mean(a, axis=(0, 2)).asnumpy(), x.mean((0, 2)),
                       rtol=1e-5)
    assert np.allclose(nd.max(a, axis=1, keepdims=True).asnumpy(),
                       x.max(1, keepdims=True))
    assert np.allclose(
        nd.sum(a, axis=1, exclude=True).asnumpy(), x.sum((0, 2)), rtol=1e-5)


def test_broadcast_ops():
    a = nd.array(np.random.rand(2, 1, 4))
    b = nd.array(np.random.rand(1, 3, 4))
    out = nd.broadcast_add(a, b)
    assert out.shape == (2, 3, 4)
    assert np.allclose(out.asnumpy(), a.asnumpy() + b.asnumpy(), rtol=1e-6)
    c = nd.broadcast_to(nd.ones((1, 3)), shape=(4, 3))
    assert c.shape == (4, 3)


def test_concat_split_stack():
    a, b = nd.ones((2, 3)), nd.zeros((2, 3))
    c = nd.concat(a, b, dim=0)
    assert c.shape == (4, 3)
    parts = nd.split(c, num_outputs=2, axis=0)
    assert parts[0].shape == (2, 3)
    s = nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)


def test_unary_math():
    x = np.random.rand(5).astype(np.float32) + 0.5
    a = nd.array(x)
    assert np.allclose(nd.exp(a).asnumpy(), np.exp(x), rtol=1e-5)
    assert np.allclose(nd.log(a).asnumpy(), np.log(x), rtol=1e-5)
    assert np.allclose(nd.sqrt(a).asnumpy(), np.sqrt(x), rtol=1e-5)
    assert np.allclose(nd.rsqrt(a).asnumpy(), 1 / np.sqrt(x), rtol=1e-5)
    assert np.allclose(nd.sigmoid(a).asnumpy(), 1 / (1 + np.exp(-x)),
                       rtol=1e-5)
    assert np.allclose(nd.relu(nd.array([-1.0, 1.0])).asnumpy(), [0, 1])


def test_indexing_ops():
    w = nd.array(np.arange(12).reshape(4, 3).astype(np.float32))
    idx = nd.array([0, 2], dtype="int32")
    out = nd.take(w, idx)
    assert np.allclose(out.asnumpy(), [[0, 1, 2], [6, 7, 8]])
    oh = nd.one_hot(idx, depth=4)
    assert oh.shape == (2, 4)
    assert oh.asnumpy()[0, 0] == 1 and oh.asnumpy()[1, 2] == 1
    picked = nd.pick(w, nd.array([1, 0, 2, 1]), axis=1)
    assert np.allclose(picked.asnumpy(), [1, 3, 8, 10])


def test_topk_sort():
    a = nd.array([[3.0, 1.0, 2.0], [0.0, 5.0, 4.0]])
    idx = nd.topk(a, k=2)
    assert idx.shape == (2, 2)
    both = nd.topk(a, k=1, ret_typ="both")
    assert np.allclose(both[0].asnumpy().ravel(), [3, 5])
    s = nd.sort(a, is_ascend=False)
    assert np.allclose(s.asnumpy()[0], [3, 2, 1])


def test_astype_cast():
    a = nd.array([1.5, 2.5])
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = nd.cast(a, dtype="float16")
    assert c.dtype == np.float16


def test_context_roundtrip():
    a = nd.ones((2, 2), ctx=mx.cpu(0))
    assert a.context.device_type == "cpu"
    b = a.as_in_context(mx.cpu(0))
    assert b is a


def test_context_never_names_another_device():
    """`mx.tpu(i)` is a TPU or an error: no CPU stands in for a missing
    accelerator, and no id wraps around to an existing device."""
    import jax

    from mxnet_tpu.base import MXNetError

    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(MXNetError, match="no accelerator"):
            ctx.jax_device
    with pytest.raises(MXNetError, match="out of range"):
        mx.cpu(len(jax.devices("cpu"))).jax_device
    with pytest.raises(MXNetError):
        nd.ones((2, 2), ctx=mx.tpu(0))
    assert mx.num_tpus() == 0


def test_copyto():
    a = nd.ones((2, 2))
    b = nd.zeros((2, 2))
    a.copyto(b)
    assert np.allclose(b.asnumpy(), 1)


def test_save_load(tmp_path):
    f = str(tmp_path / "x.params")
    d = {"a": nd.array([1.0, 2.0]), "b": nd.ones((2, 3), dtype="int32")}
    nd.save(f, d)
    back = nd.load(f)
    assert set(back) == {"a", "b"}
    assert np.allclose(back["a"].asnumpy(), [1, 2])
    assert back["b"].dtype == np.int32
    lst = [nd.zeros((2,)), nd.ones((3,))]
    nd.save(f, lst)
    back = nd.load(f)
    assert isinstance(back, list) and len(back) == 2


def test_wait_and_waitall():
    a = nd.ones((100, 100))
    b = nd.dot(a, a)
    b.wait_to_read()
    nd.waitall()


def test_scalar_conversions():
    a = nd.array([3.5])
    assert float(a) == 3.5
    assert a.asscalar() == pytest.approx(3.5)
    assert int(nd.array([7], dtype="int32")) == 7
    with pytest.raises(mx.MXNetError):
        nd.zeros((2, 2)).asscalar()


def test_where_clip():
    cond = nd.array([1.0, 0.0, 1.0])
    x, y = nd.ones((3,)), nd.zeros((3,))
    assert nd.where(cond, x, y).asnumpy().tolist() == [1, 0, 1]
    assert nd.clip(nd.array([-2.0, 0.5, 9.0]), 0.0, 1.0).asnumpy().tolist() \
        == [0, 0.5, 1]


def test_random_ops():
    a = nd.random.uniform(0, 1, shape=(100,))
    assert a.shape == (100,)
    assert 0 <= float(nd.min(a)) and float(nd.max(a)) <= 1
    b = nd.random.normal(0, 1, shape=(1000,))
    assert abs(float(nd.mean(b))) < 0.2
    c = nd.random.randint(0, 10, shape=(50,))
    assert c.dtype == np.int32
    mx.random.seed(42)
    x1 = nd.random.uniform(shape=(5,)).asnumpy()
    mx.random.seed(42)
    x2 = nd.random.uniform(shape=(5,)).asnumpy()
    assert np.allclose(x1, x2)


def test_control_flow_foreach():
    data = nd.array(np.arange(6).reshape(3, 2).astype(np.float32))
    init = nd.zeros((2,))

    def body(x, state):
        new = state + x
        return new, new

    outs, final = nd.foreach(body, data, init)
    assert np.allclose(final.asnumpy(), [6, 9])
    assert outs.shape == (3, 2)


def test_linalg():
    a = np.random.rand(3, 3).astype(np.float32)
    spd = a @ a.T + 3 * np.eye(3, dtype=np.float32)
    L = nd.linalg.potrf(nd.array(spd))
    assert np.allclose(L.asnumpy() @ L.asnumpy().T, spd, atol=1e-4)
    g = nd.linalg.gemm2(nd.array(a), nd.array(a), transpose_b=True)
    assert np.allclose(g.asnumpy(), a @ a.T, atol=1e-5)


@pytest.mark.skipif(not os.environ.get("MXNET_TEST_LARGE"),
                    reason="nightly tier (reference: tests/nightly/"
                           "test_large_array.py) — set MXNET_TEST_LARGE=1; "
                           "allocates >2 GB")
def test_large_array_int64_indexing():
    """INT64_TENSOR_SIZE: element counts past 2^31 index correctly
    (reference nightly large-array tier).  Covers the three x32
    failure modes found building this: index-carry overflow, silent
    scatter drop on >2^31 dims, and int64-creation truncation."""
    n = 2_200_000_000  # > 2^31
    a = mx.nd.zeros((n,), dtype="int8")
    a[n - 1] = 7
    a[5] = 2  # small index on a HUGE dim: x32 scatter silently drops
    assert int(a[n - 1].asnumpy()) == 7
    assert int(a[5].asnumpy()) == 2
    assert int(a.sum().asnumpy()) == 9
    assert a.shape == (n,)
    idx = mx.nd.array(np.array([5, n - 1], np.int64), dtype="int64")
    assert idx.dtype == np.int64  # creation must honor int64
    assert list(mx.nd.take(a, idx).asnumpy()) == [2, 7]


def test_explicit_64bit_dtypes_roundtrip(tmp_path):
    """Explicit int64/float64 NDArrays must hold and save/load values
    past 32-bit range (jax's x32 default silently wrapped both — the
    creation and load paths route through x64)."""
    i64 = mx.nd.array(np.array([5, 2_199_999_999], np.int64),
                      dtype="int64")
    f64 = mx.nd.array(np.array([1.5, 1e300]), dtype="float64")
    assert i64.dtype == np.int64 and f64.dtype == np.float64
    assert int(i64.asnumpy()[1]) == 2_199_999_999
    assert np.isfinite(f64.asnumpy()[1])
    f = str(tmp_path / "big.params")
    mx.nd.save(f, {"i": i64, "f": f64})
    back = mx.nd.load(f)
    assert back["i"].dtype == np.int64
    np.testing.assert_array_equal(back["i"].asnumpy(), i64.asnumpy())
    assert back["f"].dtype == np.float64
    np.testing.assert_array_equal(back["f"].asnumpy(), f64.asnumpy())


def test_64bit_creators_and_casts():
    """zeros/ones/full/arange/astype/cast honor 64-bit dtypes with
    values past 32-bit range (each routed through x64_scope_if)."""
    assert nd.zeros((3,), dtype="int64").dtype == np.int64
    assert nd.ones((2,), dtype="float64").dtype == np.float64
    assert int(nd.full((2,), 2_199_999_999,
                       dtype="int64").asnumpy()[0]) == 2_199_999_999
    ar = nd.arange(2_199_999_998, 2_200_000_001, 1, dtype="int64")
    assert ar.dtype == np.int64
    assert int(ar.asnumpy()[-1]) == 2_200_000_000
    a = nd.array(np.array([2.2e9]), dtype="float64")
    assert int(a.astype("int64").asnumpy()[0]) == 2_200_000_000
    assert int(nd.cast(a, dtype="int64").asnumpy()[0]) == 2_200_000_000


def test_64bit_pickle_setitem_linspace_eye():
    """Review regressions: pickle round-trip, large scalar setitem into
    int64, linspace/eye 64-bit dtypes, and x64 getitem on the tape."""
    import pickle

    from mxnet_tpu import autograd

    a = nd.array(np.array([2_199_999_999], np.int64), dtype="int64")
    b = pickle.loads(pickle.dumps(a))
    assert b.dtype == np.int64
    assert int(b.asnumpy()[0]) == 2_199_999_999
    c = nd.zeros((4,), dtype="int64")
    c[0] = 2_200_000_000
    assert int(c.asnumpy()[0]) == 2_200_000_000
    lin = nd.linspace(0, 1e300, 3, dtype="float64")
    assert lin.dtype == np.float64 and np.isfinite(lin.asnumpy()[-1])
    assert nd.eye(3, dtype="int64").dtype == np.int64
    x = nd.array(np.arange(6, dtype=np.float32))
    x.attach_grad()
    with autograd.record():
        y = (x[1:4] * 2).sum()
    y.backward()
    assert list(x.grad.asnumpy()) == [0, 2, 2, 2, 0, 0]

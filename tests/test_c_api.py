"""Flat C API (L5) round-trip tests.

Reference parity: the role of include/mxnet/c_api.h + src/c_api/ — the
ABI a second language frontend builds on.  Two proofs:

1. ctypes round-trip (attached mode): this Python process loads
   libmxtpu.so and drives NDArray/op/autograd/KVStore through the C
   surface only — exactly what a Java/Go binding would generate.
2. embedded mode: a pure C program is compiled with g++ against
   mxtpu_c_api.h, linked to libmxtpu.so, and run as its own process with
   NO Python code of its own — it boots the runtime via MXTPUInit().
"""

import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

from mxnet_tpu.test_utils import cpu_child_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


LIB = os.path.join(ROOT, "src", "libmxtpu.so")


def _build_lib():
    if not os.path.exists(LIB):
        r = subprocess.run(["make", "-C", os.path.join(ROOT, "src")],
                           capture_output=True, text=True)
        if r.returncode != 0:
            pytest.skip(f"cannot build libmxtpu.so: {r.stderr[-300:]}")
    return LIB


@pytest.fixture(scope="module")
def capi():
    _build_lib()
    lib = ctypes.CDLL(LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p
    assert lib.MXTPUInit() == 0, lib.MXGetLastError().decode()
    return lib


def _err(lib):
    return lib.MXGetLastError().decode()


def _create(lib, arr):
    arr = np.ascontiguousarray(arr)
    shape = (ctypes.c_int64 * arr.ndim)(*arr.shape)
    h = ctypes.c_void_p()
    rc = lib.MXNDArrayCreate(
        arr.ctypes.data_as(ctypes.c_void_p), arr.nbytes, shape, arr.ndim,
        arr.dtype.name.encode(), ctypes.byref(h))
    assert rc == 0, _err(lib)
    return h


def _read(lib, h, shape, dtype=np.float32):
    out = np.empty(shape, dtype)
    rc = lib.MXNDArraySyncCopyToCPU(
        h, out.ctypes.data_as(ctypes.c_void_p), out.nbytes)
    assert rc == 0, _err(lib)
    return out


def _invoke(lib, name, handles, params=None, n_out=4):
    params = params or {}
    keys = (ctypes.c_char_p * len(params))(
        *[k.encode() for k in params])
    vals = (ctypes.c_char_p * len(params))(
        *[str(v).encode() for v in params.values()])
    ins = (ctypes.c_void_p * len(handles))(
        *[h.value for h in handles])
    outs = (ctypes.c_void_p * n_out)()
    n = ctypes.c_int(n_out)
    rc = lib.MXImperativeInvoke(name.encode(), ins, len(handles), keys,
                                vals, len(params), outs,
                                ctypes.byref(n))
    assert rc == 0, _err(lib)
    return [ctypes.c_void_p(outs[i]) for i in range(n.value)]


def test_c_api_ndarray_roundtrip(capi):
    lib = capi
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    h = _create(lib, x)
    ndim = ctypes.c_int()
    shape = (ctypes.c_int64 * 8)()
    assert lib.MXNDArrayGetShape(h, ctypes.byref(ndim), shape) == 0
    assert (ndim.value, shape[0], shape[1]) == (2, 2, 3)
    dt = ctypes.create_string_buffer(16)
    assert lib.MXNDArrayGetDType(h, dt) == 0
    assert dt.value == b"float32"
    np.testing.assert_array_equal(_read(lib, h, (2, 3)), x)
    assert lib.MXNDArrayFree(h) == 0


def test_c_api_invoke_op(capi):
    lib = capi
    x = np.linspace(-1, 1, 6, dtype=np.float32).reshape(2, 3)
    h = _create(lib, x)
    (out,) = _invoke(lib, "sin", [h])
    np.testing.assert_allclose(_read(lib, out, (2, 3)), np.sin(x),
                               rtol=1e-6)
    # op with a string-encoded param
    (t,) = _invoke(lib, "transpose", [h], {"axes": "(1, 0)"})
    np.testing.assert_array_equal(_read(lib, t, (3, 2)), x.T)
    for hh in (h, out, t):
        lib.MXNDArrayFree(hh)


def test_c_api_list_ops(capi):
    lib = capi
    count = ctypes.c_int()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXListAllOpNames(ctypes.byref(count),
                                ctypes.byref(names)) == 0
    got = {names[i].decode() for i in range(count.value)}
    assert {"sin", "FullyConnected", "Convolution"} <= got
    assert count.value > 300


def test_c_api_autograd(capi):
    lib = capi
    x = np.array([1.0, 2.0, 3.0], dtype=np.float32)
    h = _create(lib, x)
    assert lib.MXAutogradAttachGrad(h) == 0, _err(lib)
    assert lib.MXAutogradRecordStart() == 0
    (sq,) = _invoke(lib, "square", [h])
    (loss,) = _invoke(lib, "sum", [sq])
    assert lib.MXAutogradRecordStop() == 0
    assert lib.MXAutogradBackward(loss) == 0, _err(lib)
    g = ctypes.c_void_p()
    assert lib.MXNDArrayGetGrad(h, ctypes.byref(g)) == 0, _err(lib)
    np.testing.assert_allclose(_read(lib, g, (3,)), 2 * x, rtol=1e-6)
    for hh in (h, sq, loss, g):
        lib.MXNDArrayFree(hh)


def test_c_api_kvstore(capi):
    lib = capi
    kv = ctypes.c_int()
    assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0, _err(lib)
    v = np.ones(4, dtype=np.float32)
    h = _create(lib, v)
    assert lib.MXKVStoreInit(kv, 3, h) == 0, _err(lib)
    h2 = _create(lib, 2 * v)
    assert lib.MXKVStorePush(kv, 3, h2) == 0, _err(lib)
    out = ctypes.c_void_p()
    assert lib.MXKVStorePull(kv, 3, ctypes.byref(out)) == 0, _err(lib)
    np.testing.assert_allclose(_read(lib, out, (4,)), 2 * v)
    assert lib.MXKVStoreFree(kv) == 0
    for hh in (h, h2, out):
        lib.MXNDArrayFree(hh)


def test_c_api_error_reporting(capi):
    lib = capi
    x = _create(lib, np.ones(2, np.float32))
    outs = (ctypes.c_void_p * 1)()
    n = ctypes.c_int(1)
    rc = lib.MXImperativeInvoke(b"definitely_not_an_op",
                                (ctypes.c_void_p * 1)(x.value), 1,
                                None, None, 0, outs, ctypes.byref(n))
    assert rc == -1
    assert "definitely_not_an_op" in _err(lib)
    lib.MXNDArrayFree(x)


_C_SMOKE = r"""
#include <stdio.h>
#include <stdlib.h>
#include <math.h>
#include "mxtpu_c_api.h"

int main(void) {
  if (MXTPUInit() != 0) {
    fprintf(stderr, "init: %s\n", MXGetLastError());
    return 1;
  }
  float data[6] = {0.f, 1.f, 2.f, 3.f, 4.f, 5.f};
  int64_t shape[2] = {2, 3};
  NDArrayHandle x, y;
  if (MXNDArrayCreate(data, sizeof(data), shape, 2, "float32", &x) != 0) {
    fprintf(stderr, "create: %s\n", MXGetLastError());
    return 1;
  }
  NDArrayHandle outs[1];
  int n_out = 1;
  if (MXImperativeInvoke("sin", &x, 1, NULL, NULL, 0, outs, &n_out) != 0) {
    fprintf(stderr, "invoke: %s\n", MXGetLastError());
    return 1;
  }
  y = outs[0];
  float back[6];
  if (MXNDArraySyncCopyToCPU(y, back, sizeof(back)) != 0) {
    fprintf(stderr, "copy: %s\n", MXGetLastError());
    return 1;
  }
  for (int i = 0; i < 6; ++i) {
    if (fabsf(back[i] - sinf(data[i])) > 1e-5f) {
      fprintf(stderr, "value mismatch at %d: %f vs %f\n", i, back[i],
              sinf(data[i]));
      return 1;
    }
  }
  MXNDArrayFree(x);
  MXNDArrayFree(y);
  printf("C_SMOKE_OK\n");
  return 0;
}
"""


def test_c_frontend_smoke(tmp_path):
    """A second frontend exists: pure C, no Python source, drives the
    framework through libmxtpu.so alone."""
    _build_lib()
    src = tmp_path / "smoke.c"
    src.write_text(_C_SMOKE)
    exe = tmp_path / "smoke"
    build = subprocess.run(
        ["g++", "-x", "c", str(src), "-o", str(exe),
         f"-I{os.path.join(ROOT, 'src')}",
         f"-L{os.path.join(ROOT, 'src')}", "-lmxtpu",
         f"-Wl,-rpath,{os.path.join(ROOT, 'src')}"],
        capture_output=True, text=True)
    if build.returncode != 0:
        pytest.skip(f"cannot compile C smoke: {build.stderr[-300:]}")
    env = cpu_child_env()
    r = subprocess.run([str(exe)], env=env, capture_output=True,
                       text=True, timeout=240)
    assert r.returncode == 0, (r.stdout, r.stderr[-500:])
    assert "C_SMOKE_OK" in r.stdout


def test_cpp_package_linreg_example(capi):
    """The C++ binding (cpp-package/) trains linear regression through
    the C ABI only — the reference's cpp-package/example analog."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    src = os.path.join(ROOT, "cpp-package", "example", "linreg.cpp")
    inc = os.path.join(ROOT, "cpp-package", "include", "mxnet-tpu-cpp")
    binp = os.path.join(ROOT, "src", ".linreg_cpp_test")
    r = subprocess.run(
        ["g++", "-std=c++17", src, f"-I{inc}",
         f"-I{os.path.join(ROOT, 'src')}",
         f"-L{os.path.join(ROOT, 'src')}", "-lmxtpu",
         f"-Wl,-rpath,{os.path.join(ROOT, 'src')}", "-o", binp],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-500:]
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    try:
        run = subprocess.run([binp], capture_output=True, text=True,
                             env=env, timeout=240)
        assert run.returncode == 0, (run.stdout[-300:], run.stderr[-300:])
        assert "PASS" in run.stdout
    finally:
        if os.path.exists(binp):
            os.remove(binp)


def test_c_predict_api_roundtrip(capi, tmp_path):
    """MXPred* deploy surface (reference: include/mxnet/c_predict_api.h):
    export a trained net, run inference through the C predictor only,
    match the in-process output."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd

    lib = capi
    rs = np.random.RandomState(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    net.hybridize()
    x = nd.array(rs.randn(2, 5).astype("float32"))
    net(x)
    with autograd.predict_mode():
        ref = net(x).asnumpy()
    net.export(str(tmp_path / "pred"))
    sym_json = (tmp_path / "pred-symbol.json").read_text()
    param_bytes = (tmp_path / "pred-0000.params").read_bytes()

    import ctypes

    lib.MXPredCreate.restype = ctypes.c_int
    h = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    rc = lib.MXPredCreate(sym_json.encode(), param_bytes,
                          len(param_bytes), 1, 0, 1, keys,
                          ctypes.byref(h))
    assert rc == 0, _err(lib)
    data = np.ascontiguousarray(x.asnumpy(), np.float32)
    shape = (ctypes.c_int64 * 2)(2, 5)
    rc = lib.MXPredSetInput(h, b"data",
                            data.ctypes.data_as(
                                ctypes.POINTER(ctypes.c_float)),
                            shape, 2)
    assert rc == 0, _err(lib)
    assert lib.MXPredForward(h) == 0, _err(lib)
    ndim = ctypes.c_int()
    oshape = (ctypes.c_int64 * 8)()
    assert lib.MXPredGetOutputShape(h, 0, ctypes.byref(ndim),
                                    oshape) == 0, _err(lib)
    shp = tuple(oshape[i] for i in range(ndim.value))
    assert shp == (2, 3), shp
    out = np.empty(shp, np.float32)
    assert lib.MXPredGetOutput(
        h, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size) == 0, _err(lib)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    assert lib.MXPredFree(h) == 0, _err(lib)


def test_cpp_generated_op_wrappers(capi):
    """cpp-package/OpWrapperGenerator.py output compiles and the typed
    wrappers drive real ops (reference: generated mxnet-cpp op.h)."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    hpp = os.path.join(ROOT, "cpp-package", "include", "mxnet-tpu-cpp",
                       "ops.hpp")
    # regenerate to prove the generator tracks the live registry
    r = subprocess.run([sys.executable,
                        os.path.join(ROOT, "cpp-package",
                                     "OpWrapperGenerator.py")],
                       capture_output=True, text=True,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-400:]
    assert os.path.exists(hpp)
    src = os.path.join(ROOT, "src", ".ops_smoke.cpp")
    binp = os.path.join(ROOT, "src", ".ops_smoke_test")
    with open(src, "w") as f:
        f.write("""
#include <cstdio>
#include "ndarray.hpp"
#include "ops.hpp"
int main() {
  mxtpu::cpp::Init();
  mxtpu::cpp::NDArray x(std::vector<float>{-1.0f, 2.0f, -3.0f}, {3});
  auto v = mxtpu::cpp::op::abs(x)[0].ToVector();
  auto rv = mxtpu::cpp::op::activation(
      x, {{"act_type", "relu"}})[0].ToVector();
  if (v[0] == 1 && v[2] == 3 && rv[0] == 0 && rv[1] == 2) {
    std::printf("PASS\\n");
    return 0;
  }
  return 1;
}
""")
    try:
        rc = subprocess.run(
            ["g++", "-std=c++17", src,
             f"-I{os.path.join(ROOT, 'cpp-package', 'include', 'mxnet-tpu-cpp')}",
             f"-I{os.path.join(ROOT, 'src')}",
             f"-L{os.path.join(ROOT, 'src')}", "-lmxtpu",
             f"-Wl,-rpath,{os.path.join(ROOT, 'src')}", "-o", binp],
            capture_output=True, text=True)
        assert rc.returncode == 0, rc.stderr[-500:]
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        run = subprocess.run([binp], capture_output=True, text=True,
                             env=env, timeout=240)
        assert run.returncode == 0 and "PASS" in run.stdout, \
            (run.stdout[-200:], run.stderr[-200:])
    finally:
        for p in (src, binp):
            if os.path.exists(p):
                os.remove(p)


def test_perl_package_linreg_example(capi):
    """The Perl binding (perl-package/, the reference's AI::MXNet
    analog) trains linear regression through the C ABI only: XS shim
    over libmxtpu.so + generated typed op wrappers
    (OpWrapperGenerator.py over the live registry)."""
    import shutil

    if shutil.which("perl") is None:
        pytest.skip("no perl")
    pp = os.path.join(ROOT, "perl-package")
    env = cpu_child_env()
    mm = subprocess.run(["perl", "-MExtUtils::MakeMaker", "-e", "1"],
                        capture_output=True, text=True)
    if mm.returncode != 0:
        pytest.skip("no ExtUtils::MakeMaker")
    mk = subprocess.run(["perl", "Makefile.PL"], cwd=pp, env=env,
                        capture_output=True, text=True)
    assert mk.returncode == 0, mk.stderr[-500:]
    bld = subprocess.run(["make"], cwd=pp, env=env,
                         capture_output=True, text=True)
    assert bld.returncode == 0, bld.stderr[-500:]
    env["PERL5LIB"] = os.pathsep.join(
        [os.path.join(pp, "blib", "lib"),
         os.path.join(pp, "blib", "arch")])
    r = subprocess.run(["perl", os.path.join(pp, "example",
                                             "linreg.pl")],
                       cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, (r.stdout[-300:], r.stderr[-300:])
    assert "PASS" in r.stdout


def test_perl_ops_pm_is_fresh():
    """The checked-in generated wrappers match the live registry (the
    same freshness guard the cpp-package generated header has)."""
    import tempfile

    gen = os.path.join(ROOT, "perl-package", "OpWrapperGenerator.py")
    committed = os.path.join(ROOT, "perl-package", "lib", "AI",
                             "MXNetTPU", "Ops.pm")
    env = cpu_child_env()
    with tempfile.NamedTemporaryFile("r", suffix=".pm") as tmp:
        r = subprocess.run([sys.executable, gen, "-o", tmp.name],
                           env=env, capture_output=True, text=True,
                           timeout=240)
        assert r.returncode == 0, r.stderr[-400:]
        assert open(committed).read() == open(tmp.name).read(), \
            "Ops.pm is stale: re-run perl-package/OpWrapperGenerator.py"

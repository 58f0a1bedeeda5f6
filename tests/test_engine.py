"""Engine policy surface (reference: tests/python/unittest/
test_engine.py + test_exc_handling.py — NaiveEngine mode, WaitForAll,
exception propagation)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import cpu_child_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_wait_all_and_bulk():
    a = mx.nd.ones((8, 8))
    b = a * 2 + 1
    mx.engine.wait_all()          # Engine::WaitForAll analog: no hang
    np.testing.assert_allclose(b.asnumpy(), 3.0)
    with mx.engine.bulk(16):      # bulking context is a no-op policy
        c = (a + b).sum()
    assert float(c.asnumpy()) == 8 * 8 * 4.0
    prev = mx.engine.set_bulk_size(5)
    assert mx.engine.set_bulk_size(prev) == 5


@pytest.fixture
def fresh_cache_config(monkeypatch):
    """ensure_compile_cache() as a new process would meet it; the jax
    config it touches is put back afterwards."""
    import jax

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setattr(mx.engine, "_CACHE_CONFIGURED", False)
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_enable_compilation_cache", True)
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_follows_the_jax_variable(fresh_cache_config,
                                                monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX places the cache and
    this repository sets no directory of its own."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    mx.engine.ensure_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None  # left to JAX
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_defaults_to_the_checkout(fresh_cache_config,
                                                monkeypatch):
    """Without the variable the directory is <checkout>/.jax_cache, a
    fixed path; the CPU backend persists nothing there."""
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert mx.engine.ensure_compile_cache() == \
        os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_enable_compilation_cache is False
    # idempotent: a later call changes nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert mx.engine.ensure_compile_cache() == \
        os.path.join(ROOT, ".jax_cache")


def test_exception_propagation_raises_mxnet_error():
    """Invalid op invocations surface as exceptions on the issuing call
    or at readback — never a silent wrong answer (reference
    test_exc_handling: async errors re-thrown at WaitToRead)."""
    a = mx.nd.ones((3, 4))
    b = mx.nd.ones((5, 6))
    with pytest.raises(Exception):
        mx.nd.dot(a, b).asnumpy()  # inner dims mismatch
    with pytest.raises(Exception):
        mx.nd.reshape(a, shape=(7, 7)).asnumpy()  # size mismatch


def test_naive_engine_env_mode():
    """MXNET_ENGINE_TYPE=NaiveEngine puts the engine in synchronous
    mode (reference naive_engine.cc); verified in a subprocess since
    the flag is read at import."""
    code = (
        "import mxnet_tpu as mx\n"
        "assert mx.engine.is_naive()\n"
        "x = mx.nd.ones((4,)) * 3\n"
        "mx.engine.maybe_sync(x)\n"
        "print('naive ok', float(x.sum().asnumpy()))\n")
    env = cpu_child_env(MXNET_ENGINE_TYPE="NaiveEngine")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-400:]
    assert "naive ok 12.0" in r.stdout
    # and the default (this process) is NOT naive
    assert not mx.engine.is_naive()

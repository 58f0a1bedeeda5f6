"""Test harness configuration.

Mirrors the reference's test strategy (SURVEY.md §4): tests run on a virtual
8-device CPU mesh so multi-chip sharding paths execute without TPU hardware —
the analog of the reference's local dmlc tracker for fake multi-node
(tests/nightly run via `tools/launch.py --launcher local`).
"""

import os

# Must be set before jax is imported anywhere.  Append, don't setdefault:
# the container exports XLA_FLAGS="" which would defeat setdefault and
# leave the mesh at 1 device.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# force, not setdefault: tests always run on the virtual CPU mesh, whatever
# platform the environment selects for real runs.
os.environ["JAX_PLATFORMS"] = "cpu"
# Trainer constructors call engine.ensure_compile_cache(), which would
# persist every program tier-1 compiles into <checkout>/.jax_cache.  Tests
# (and the children they spawn) compile tiny programs once: no cache.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import shutil
import subprocess

import numpy as np
import pytest

# Build the native libs once per session if the toolchain exists — a
# fresh checkout carries no .so, and the native paths (recordio codec,
# jpeg decode, C API) should be exercised, not silently skipped.
_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
if shutil.which("make") and shutil.which("g++"):
    _missing = [n for n in ("libmxtpu_io.so", "libmxtpu_img.so",
                            "libmxtpu.so")
                if not os.path.exists(os.path.join(_SRC, n))]
    if _missing:
        # -k: a failing target (e.g. libmxtpu_img.so on a host without
        # libjpeg headers) must not stop the OTHER native libs building
        subprocess.run(["make", "-k", "-C", _SRC], capture_output=True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 "
                   "(`-m 'not slow'`)")
    config.addinivalue_line(
        "markers", "faults: CPU-hermetic fault-injection tests driven "
                   "by MXTPU_FAULT_INJECT (run in tier-1 by default)")


@pytest.fixture
def fault_inject(monkeypatch):
    """Arm MXTPU_FAULT_INJECT for one test and reset injection counters
    on both arm and teardown (counters are cached per env value)."""
    from mxnet_tpu import resilience

    def arm(spec):
        monkeypatch.setenv("MXTPU_FAULT_INJECT", spec)
        resilience.reset_faults()

    yield arm
    monkeypatch.delenv("MXTPU_FAULT_INJECT", raising=False)
    resilience.reset_faults()


@pytest.fixture
def mesh8():
    """Factory for multi-device meshes on the virtual 8-device CPU
    platform (the XLA_FLAGS forcing at the top of this file): tier-1
    TP/FSDP sharding tests run on every CI pass, not only on real
    hardware.  Skips when the platform somehow exposes < 8 devices
    (e.g. XLA_FLAGS was pinned by the environment before pytest
    started).  Tears down the process default mesh so a test's
    `shard_model` can't leak placements into the next test."""
    import jax

    from mxnet_tpu import parallel

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (forced-host) devices")

    def make(**axes):
        return parallel.make_mesh(**axes)

    yield make
    parallel.set_default_mesh(None)


@pytest.fixture
def mesh222():
    """The canonical 3-axis tp=2×pp=2×dp=2 mesh over the forced-host
    8-device CPU platform — the PR 17 pipeline-parallel layout, built
    through `make_mesh`'s dict form.  Same skip/teardown discipline as
    `mesh8`."""
    import jax

    from mxnet_tpu import parallel

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (forced-host) devices")
    yield parallel.make_mesh(axes={"tp": 2, "pp": 2, "dp": 2})
    parallel.set_default_mesh(None)


@pytest.fixture(autouse=True)
def _seeded():
    """Reference: @with_seed() in tests/python/unittest/common.py —
    deterministic seeds per test, logged for replay on failure."""
    import mxnet_tpu as mx

    np.random.seed(0)
    mx.random.seed(0)
    yield

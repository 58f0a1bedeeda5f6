"""Device contexts: ``mx.cpu()``, ``mx.tpu(i)``, ``mx.gpu(i)``.

Reference parity: python/mxnet/context.py (Context class, with-scope device
stack, ``current_context()``).  TPU-first change: a Context resolves to a JAX
device; ``gpu`` is kept as an alias for the accelerator so reference scripts
(`ctx=mx.gpu(0)`) run unmodified on TPU.
"""

from __future__ import annotations

import functools

from .base import MXNetError, _ThreadLocalStack


@functools.lru_cache(maxsize=None)
def _jax_devices(platform: str):
    """This process's devices on ``platform``.  LOCAL (addressable)
    only: in a multi-process run jax.devices() spans all hosts, and ctx
    cpu(0)/tpu(0) must mean THIS process's device 0 (reference: device
    ids are process-local)."""
    import jax

    return tuple(jax.local_devices(backend=platform))


def _accelerator_platform() -> str | None:
    """The accelerator platform JAX runs on here, or None on a CPU-only
    process.  A Context never guesses: no accelerator means `tpu(i)`
    raises."""
    import jax

    plat = jax.default_backend()
    return None if plat == "cpu" else plat


class Context:
    """A device context. devtype in {'cpu', 'tpu', 'gpu'}.

    ``gpu`` is an accelerator alias: on a TPU machine ``mx.gpu(0)`` is the
    first TPU chip, so reference training scripts port without edits.
    """

    devtype2mask = {"cpu": 1, "gpu": 2, "tpu": 2, "cpu_pinned": 3}
    _stack = _ThreadLocalStack()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in ("cpu", "gpu", "tpu", "cpu_pinned"):
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = "cpu" if device_type == "cpu_pinned" else device_type
        self.device_id = int(device_id)

    # -- resolution to a JAX device -------------------------------------------
    @property
    def jax_device(self):
        """The JAX device this context names, never a substitute:
        `tpu(i)`/`gpu(i)` raise when the process has no accelerator or
        fewer than i+1 of them, and `cpu(i)` raises when JAX was started
        without its CPU backend (``JAX_PLATFORMS=tpu``)."""
        plat = "cpu" if self.device_type == "cpu" \
            else _accelerator_platform()
        if plat is None:
            raise MXNetError(
                f"context {self}: JAX found no accelerator in this "
                "process (its default backend is 'cpu')")
        try:
            devs = _jax_devices(plat)
        except RuntimeError as e:
            raise MXNetError(
                f"context {self}: JAX has no {plat!r} backend in this "
                f"process: {e}") from e
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                f"context {self}: device id out of range, this process "
                f"has {len(devs)} {plat} device(s)")
        return devs[self.device_id]

    # -- identity -------------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- with-scope -----------------------------------------------------------
    def __enter__(self):
        Context._stack.push(self)
        return self

    def __exit__(self, *exc):
        Context._stack.pop()

    @classmethod
    def default_ctx(cls):
        return cls._stack.top(default=Context("cpu", 0))


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accelerator alias (reference scripts use mx.gpu); maps to TPU here."""
    return Context("gpu", device_id)


def current_context() -> Context:
    return Context.default_ctx()


def num_gpus() -> int:
    plat = _accelerator_platform()
    return len(_jax_devices(plat)) if plat else 0


def num_tpus() -> int:
    return num_gpus()

"""Collective operations over the mesh.

Reference parity: src/kvstore/comm.h (device tree reduce), kvstore_nccl.h
(NCCL all-reduce), ps-lite push/pull — all replaced by XLA collectives over
ICI/DCN (SURVEY.md §2.6).  Two surfaces:

- in-jit primitives (``psum``/``all_gather``/... from jax.lax) used inside
  shard_map'ed code — just re-exported for discoverability;
- eager helpers operating on global arrays: each is a tiny jitted program
  so the collective compiles onto ICI (used by KVStore-on-mesh and
  tools/bandwidth).
"""

from __future__ import annotations

import functools

# in-jit collective primitives (use inside shard_map with axis names)
from jax.lax import (all_gather, all_to_all, axis_index,  # noqa: F401
                     ppermute, psum, psum_scatter)


def pvary(x, axes):
    """Inside shard_map: mark ``x`` as varying over those mesh ``axes``
    (one name or several) it does not vary over yet — needed for
    scan/fori carries whose body mixes in device-dependent values;
    `lax.pcast` refuses an axis that already varies."""
    import jax

    if isinstance(axes, str):
        axes = (axes,)
    vma = jax.typeof(x).vma
    missing = tuple(a for a in axes if a not in vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


@functools.lru_cache(maxsize=None)
def _allreduce_fn(mesh, axes):
    import jax
    from jax.sharding import PartitionSpec

    spec = PartitionSpec(axes)

    def inner(x):
        return jax.lax.psum(x, axes)

    smapped = jax.shard_map(inner, mesh=mesh, in_specs=spec,
                            out_specs=spec)
    return jax.jit(smapped)


def allreduce(x, mesh, axis="dp"):
    """All-reduce a global array whose leading dim is sharded on `axis`
    (the kvstore push+pull ≡ all-reduce identity)."""
    return _allreduce_fn(mesh, axis)(x)


@functools.lru_cache(maxsize=None)
def _replicated_sum_fn(mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def inner(*xs):
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out

    return jax.jit(inner,
                   out_shardings=NamedSharding(mesh, PartitionSpec()))


def replicated_sum(xs, mesh):
    """Sum a list of replicated global arrays into a replicated result."""
    return _replicated_sum_fn(mesh)(*xs)


def device_put_sharded_batch(array, mesh, axis="dp"):
    """Lay a host batch over the mesh data axis (the TPU-native
    split_and_load: one global array, not per-device copies)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    spec = [None] * array.ndim
    spec[0] = axis
    return jax.device_put(array,
                          NamedSharding(mesh, PartitionSpec(*spec)))


def measure_allreduce_bandwidth(mesh, size_mb=64, dtype="float32",
                                iters=10, axis="dp"):
    """Achieved all-reduce algorithmic bandwidth in GB/s (reference twin:
    tools/bandwidth/measure.py — the BASELINE 'KVStore all-reduce BW'
    metric)."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    n = int(size_mb * (1 << 20) // jnp.zeros((), dtype).itemsize)
    n_dev = mesh.shape.get(axis, 1)
    n = (n // n_dev) * n_dev or n_dev
    x = jax.device_put(
        jnp.ones((n,), dtype),
        NamedSharding(mesh, PartitionSpec(axis)))
    fn = _allreduce_fn(mesh, axis)
    fn(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        x = fn(x)
    x.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    nbytes = n * jnp.zeros((), dtype).itemsize
    # ring all-reduce moves 2*(p-1)/p of the data per chip
    algo_bytes = 2 * (n_dev - 1) / max(n_dev, 1) * nbytes
    return algo_bytes / dt / 1e9

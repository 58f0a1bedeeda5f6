"""Seeded weights, made on the device in one jitted call.

A reference module lists its leaves as ``(name, shape, init)``; the same
list and the same seed give the same values wherever they are made, so
the program's copy and the reference's copy are made independently and
neither takes anything from the other.  Values are drawn in float32 and
rounded to ``dtype`` (the type the configuration states), so that a
float32 reference which widens them again starts from the same numbers.

``init`` is ``zeros``, ``ones`` or ``normal:<std>``.
"""

import functools


def key_for(seed):
    """A PRNG key from any whole number up to 2**32 and beyond (a
    driver's seed does not fit 32 signed bits)."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _draw(key, shape, init, dtype):
    import jax
    import jax.numpy as jnp

    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    kind, _, arg = init.partition(":")
    if kind == "normal":
        return (float(arg) * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -float(arg),
                                  float(arg)).astype(dtype)
    raise ValueError(f"weights: unknown init {init!r}")


@functools.lru_cache(maxsize=None)
def _maker(spec, dtype, shardings):
    import jax

    def make(key):
        return tuple(_draw(jax.random.fold_in(key, i), shape, init, dtype)
                     for i, (_, shape, init) in enumerate(spec))

    if shardings is None:
        return jax.jit(make)
    return jax.jit(make, out_shardings=shardings)


def make(seed, spec, dtype, shardings=None):
    """``{name: array}`` for ``spec``; ``shardings`` is one sharding per
    leaf, in order, or None for the default device."""
    import jax.numpy as jnp

    spec = tuple((n, tuple(s), i) for n, s, i in spec)
    sh = None if shardings is None else tuple(shardings)
    out = _maker(spec, jnp.dtype(dtype), sh)(key_for(seed))
    return {name: arr for (name, _, _), arr in zip(spec, out)}

"""Writing a step's new rows into the serving caches.

A decoder program carries its cache stacks through the layer loop,
donated and position-minor: ``(L, B, K, D, W)``, W positions on the
minor axis (what a v5e stores for a head under 128 wide whatever the
logical order says; with the logical order the same, a kernel sees the
buffer as it lies).  A call takes ``n`` stacks that share ``L``, ``B``
and ``W`` and may each have their own head count ``K`` and width ``D``:
GPT's keys and values ``(16, 64)``; MiMo's ``(4, 192)`` beside
``(4, 128)``; Keye-VL-2.0's keys and values ``(4, 128)`` beside its
indexer's keys ``(1, 64)``; Kimi-K2's one latent stack ``(1, 576)``.
``write_rows`` puts row b's new
``(K, D, S)`` block of layer ``l`` of each stack at positions
``starts[b] .. starts[b] + S`` and touches nothing else.  Two paths,
chosen on what the call can see:

- **kernel** (``S == 1``, a TPU, no mesh): one Pallas call for all the
  stacks given, each aliased to its output.  Grid over the rows: step b
  brings in, of each stack, the one lane block of row b, layer l that
  holds position ``starts[b]`` (that stack's K heads x D x 128
  positions), replaces lane
  ``starts[b] % 128`` with the new values and the pipeline writes the
  block back.  Different rows' blocks are disjoint, so the pipeline
  overlaps them.  A position outside the window is clipped into it,
  where ``dynamic_update_slice``'s clamp puts a one-position write.
- **rows** (prefill, the CPU, a program with a mesh): one
  ``lax.dynamic_update_slice`` a row and a stack.  It is also what the
  kernel is tested against (tests/test_cache_write.py, interpreted).

**Rings.**  A window layer's stack is a ring of ``W`` = window slots:
position p lives in slot ``p mod W``.  A decode step writes it through
``write_rows`` at ``starts = pos % W`` (the kernel, as any other row
write).  A prefilled block leaves a ring through ``write_ring``: of row
b's ``lengths[b]`` positions the last ``min(lengths[b], W)``, each in
its slot, which where they wrap are two pieces of the block: one
dynamic slice of ``W`` positions, turned by the slot its first
position falls to, and one ``dynamic_update_slice`` a row and a stack.

What a decode step then reads of the stacks, each row to its own
length, is `ops/cache_attention.py`.

``tally`` (a ``collections.Counter`` or None) is told at trace time how
many row writes went by which path, one a row and a stack (``B * n`` a
call): ``tally["kernel"]``, ``tally["rows"]``.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax

from ..profiler import scope

_LANE = 128


def _import_pallas():
    # on this thread's line of the start-up timeline: whether it is
    # done when the first decode compile wants it
    with scope("startup.import.pallas"):
        from jax.experimental import pallas  # noqa: F401
        from jax.experimental.pallas import tpu  # noqa: F401


# Tracing the kernel imports Pallas: 1.0-1.4 s of Python, which a server
# would pay inside the compile of its first decode program.  Begun when
# this module is imported (with the model zoo), it runs beside whatever
# the process does until then; what of it is hidden is what that work
# waits for outside the interpreter (reading a checkpoint, the device).
# A process held to the CPU never traces the kernel.
if jax.config.jax_platforms != "cpu":
    threading.Thread(target=_import_pallas, name="import-pallas",
                     daemon=True).start()


def _on_tpu():
    return jax.default_backend() == "tpu"


def write_rows(stacks, news, l, starts, mesh=None, tally=None, row=None):
    """``stacks``: ``n`` arrays ``(L, B, K_i, D_i, W)``; ``news``: as
    many arrays ``(R, K_i, D_i, S)``; ``l``: the layer, an int or a
    traced scalar; ``starts`` (R,) int32; ``row``: the stacks' row that
    the first new row goes to, an int or a traced scalar (a prefill that
    works its rows off a few at a time), None for ``R == B`` rows from
    the first.  Returns the stacks, written."""
    R, S = news[0].shape[0], news[0].shape[-1]
    news = tuple(n.astype(c.dtype) for c, n in zip(stacks, news))
    kernel = S == 1 and mesh is None and row is None and _on_tpu()
    if tally is not None:
        tally["kernel" if kernel else "rows"] += R * len(stacks)
    if kernel:
        return _write_kernel(tuple(stacks), news, l, starts)
    return tuple(_write_by_rows(c, n, l, starts, 0 if row is None else row)
                 for c, n in zip(stacks, news))


def write_ring(stacks, news, l, lengths, tally=None, row=None):
    """``stacks``: ``n`` rings ``(L, B, K_i, D_i, W)``; ``news``: as
    many blocks ``(R, K_i, D_i, S)`` holding positions ``0 .. S``;
    ``lengths`` (R,) int32, each row's real positions (1 at least).
    Slot ``s`` of row b's ring of layer ``l`` gets the latest position
    ``p < lengths[b]`` with ``p mod W == s``; a slot no such position
    falls to (a row shorter than the ring) gets whatever the block
    holds past the row's length, which nothing reads before a decode
    step has written it (`cache_attention.attend_rows` is asked for
    ``min(pos + 1, W)`` slots).  ``row`` as `write_rows`.  Returns the
    rings, written."""
    R, S = news[0].shape[0], news[0].shape[-1]
    W = stacks[0].shape[-1]
    if tally is not None:
        tally["rows"] += R * len(stacks)
    if S <= W:      # nothing wraps: the block from slot 0
        zeros = jnp.zeros((R,), jnp.int32)
        return tuple(
            _write_by_rows(c, n.astype(c.dtype), l, zeros,
                           0 if row is None else row)
            for c, n in zip(stacks, news))
    first = jnp.clip(lengths.astype(jnp.int32) - W, 0, S - W)      # (R,)
    zero = jnp.int32(0)
    out = []
    for c, new in zip(stacks, news):
        for b in range(R):
            # positions first .. first + W, the one at first + i bound
            # for slot (first + i) mod W: turned by first mod W
            kept = lax.dynamic_slice_in_dim(new[b], first[b], W, axis=-1)
            c = lax.dynamic_update_slice(
                c, jnp.roll(kept, first[b] % W, axis=-1).astype(c.dtype)[
                    None, None],
                (jnp.int32(l), jnp.int32((0 if row is None else row) + b),
                 zero, zero, zero))
        out.append(c)
    return tuple(out)


def _write_by_rows(c, new, l, starts, row=0):
    """One dynamic_update_slice a row, each at that row's own offset (a
    start that would run past W is clamped by the operation)."""
    zero = jnp.int32(0)
    for b in range(new.shape[0]):
        c = lax.dynamic_update_slice(
            c, new[b][None, None],
            (jnp.int32(l), jnp.int32(row + b), zero, zero, starts[b]))
    return c


def _kernel(l_ref, at_ref, *refs, n, lanes):
    """refs: n stack blocks (K, D, lanes), n new rows (K, D) as the
    program's products leave them, n output blocks.  The rows are turned
    here (in float32, which the chip transposes at any small shape):
    head k's values are then one column, broadcast over the block's
    lanes and kept at one of them."""
    from jax.experimental import pallas as pl

    del l_ref
    lane = at_ref[pl.program_id(0)] % lanes
    for c_ref, new_ref, o_ref in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        K, D, _ = c_ref.shape
        here = lax.broadcasted_iota(jnp.int32, (D, lanes), 1) == lane
        new = new_ref[...].astype(jnp.float32).T.astype(o_ref.dtype)
        for k in range(K):
            o_ref[k] = jnp.where(here, new[:, k:k + 1], c_ref[k])


def _write_kernel(stacks, news, l, starts, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = len(stacks)
    W = stacks[0].shape[-1]
    lanes = min(_LANE, W)
    at = jnp.clip(starts.astype(jnp.int32), 0, W - 1)
    layer = jnp.asarray(l, jnp.int32).reshape(1)
    B = news[0].shape[0]

    def block_of(c):
        K, D = c.shape[2:4]
        return pl.BlockSpec(
            (None, None, K, D, lanes),
            lambda b, l_ref, at_ref: (l_ref[0], b, 0, 0,
                                      at_ref[b] // lanes))

    def row_of(c):
        K, D = c.shape[2:4]
        return pl.BlockSpec((None, K, D), lambda b, l_ref, at_ref: (b, 0, 0))

    blocks = [block_of(c) for c in stacks]
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=blocks + [row_of(c) for c in stacks],
            out_specs=blocks),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in stacks],
        # operand i (after the two prefetched scalars) is output i
        input_output_aliases={2 + i: i for i in range(n)},
        interpret=interpret,
    )(layer, at, *stacks, *(x[..., 0] for x in news))
    return tuple(out)

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
``starts[b] .. starts[b] + S`` and touches nothing else; told which of
a decode step's rows still want a token (``live``), it leaves the
stacks of the others bit for bit what they were.  Two paths, chosen on
what the call can see:

- **kernel** (``S == 1``, a TPU, no mesh): one Pallas call for all the
  stacks given, each aliased to its output and left in HBM.  It is
  invoked once a call and walks the rows that are live (all of them
  where it is told of none) with its own copies: of each stack the one
  lane block of row b, layer l that holds position ``starts[b]`` (that
  stack's K heads x D x 128 positions) comes into VMEM, lane
  ``starts[b] % 128`` is replaced with the new values and the block
  goes back, the next row's blocks on their way in while this one's go
  out.  A row that is not live starts no copy.  A position outside the
  window is clipped into it, where ``dynamic_update_slice``'s clamp
  puts a one-position write.  That is a read-modify-write of whole lane
  blocks, so a call costs its live rows (16.8 MB at GPT-2 medium's 16
  rows and at Ouro's 8, all live).  (A grid over the rows, the Pallas
  pipeline moving the blocks, is no faster with every row live and
  cannot skip a dead row between two live ones without breaking its
  overlap: measured on the chip, PERF.md, PR 40.)
- **rows** (prefill, the CPU, a program with a mesh): one
  ``lax.dynamic_update_slice`` a row and a stack; ``live`` decides each
  by what the platform's compiler keeps in place (`_write_by_rows`).
  It is also what the kernel is tested against
  (tests/test_cache_write.py, interpreted).

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
call): ``tally["kernel"]``, ``tally["rows"]``; and, beside them,
``tally["kernel_live"]``: the kernel's row writes that were handed
``live``.

**A written stack stays in the layout its donated buffer came in.**
Left to itself the TPU compiler re-lays the whole cache around the layer
loop to make row writes cheaper.  `layouts_of` reads the formats off
allocated stacks; handed back as ``pins``, they are what `write_rows`
and `write_ring` constrain their results to.  That is this module's
decision and no model file's: none imports ``jax.experimental.layout``.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax

from ..profiler import scope

_LANE = 128
# the sets of block buffers the kernel walks the rows with: a row's
# blocks on their way in, one's being changed, one's on their way out
# (with two, a call of 16 live rows took 31.7 us where the Pallas
# pipeline over a grid of rows took 29.2 and three take 29.6: PERF.md,
# PR 40)
_SLOTS = 3


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


def layouts_of(stacks):
    """The formats (device layout and sharding) the allocated ``stacks``
    lie in, one a stack: the ``pins`` of the writes into them."""
    return [c.format for c in stacks]


def _pinned(stacks, pins, mesh=None):
    """Each stack under its pin's layout (``pins``: one format a stack,
    None for no constraint; or None for none at all)."""
    if pins is None:
        return tuple(stacks)
    from jax.experimental.layout import with_layout_constraint

    out = []
    for c, pin in zip(stacks, pins):
        if pin is not None:
            def keep_layout(c, pin=pin):
                return with_layout_constraint(c, pin.layout)

            if mesh is not None:
                # the constraint has no partitioning rule (the
                # partitioner would gather the cache to apply it), so
                # each shard pins its own
                keep_layout = jax.shard_map(
                    keep_layout, mesh=mesh, in_specs=pin.sharding.spec,
                    out_specs=pin.sharding.spec)
            c = keep_layout(c)
        out.append(c)
    return tuple(out)


def write_rows(stacks, news, l, starts, mesh=None, tally=None, row=None,
               live=None, pins=None):
    """``stacks``: ``n`` arrays ``(L, B, K_i, D_i, W)``; ``news``: as
    many arrays ``(R, K_i, D_i, S)``; ``l``: the layer, an int or a
    traced scalar; ``starts`` (R,) int32; ``row``: the stacks' row that
    the first new row goes to, an int or a traced scalar (a prefill that
    works its rows off a few at a time), None for ``R == B`` rows from
    the first; ``live`` (R,) bool, a decode step's rows that still want
    a token (``S == 1`` only; None: all): the stacks of a row that is
    not live come back bit for bit what they were; ``pins``: the
    stacks' formats (`layouts_of`), None for no constraint.  Returns the
    stacks, written, each in its pin's layout."""
    R, S = news[0].shape[0], news[0].shape[-1]
    if live is not None and S != 1:
        raise ValueError(
            f"live is a decode step's (one position a row); got blocks "
            f"of {S} positions")
    news = tuple(n.astype(c.dtype) for c, n in zip(stacks, news))
    kernel = S == 1 and mesh is None and row is None and _on_tpu()
    if tally is not None:
        tally["kernel" if kernel else "rows"] += R * len(stacks)
        if kernel and live is not None:
            tally["kernel_live"] += R * len(stacks)
    if kernel:
        out = _write_kernel(tuple(stacks), news, l, starts, live)
    else:
        out = tuple(
            _write_by_rows(c, n, l, starts, 0 if row is None else row, live)
            for c, n in zip(stacks, news))
    return _pinned(out, pins, mesh)


def write_ring(stacks, news, l, lengths, tally=None, row=None, pins=None):
    """``stacks``: ``n`` rings ``(L, B, K_i, D_i, W)``; ``news``: as
    many blocks ``(R, K_i, D_i, S)`` holding positions ``0 .. S``;
    ``lengths`` (R,) int32, each row's real positions (1 at least).
    Slot ``s`` of row b's ring of layer ``l`` gets the latest position
    ``p < lengths[b]`` with ``p mod W == s``; a slot no such position
    falls to (a row shorter than the ring) gets whatever the block
    holds past the row's length, which nothing reads before a decode
    step has written it (`cache_attention.attend_rows` is asked for
    ``min(pos + 1, W)`` slots).  ``row``, ``pins`` as `write_rows`.
    Returns the rings, written."""
    R, S = news[0].shape[0], news[0].shape[-1]
    W = stacks[0].shape[-1]
    if tally is not None:
        tally["rows"] += R * len(stacks)
    if S <= W:      # nothing wraps: the block from slot 0
        zeros = jnp.zeros((R,), jnp.int32)
        return _pinned(tuple(
            _write_by_rows(c, n.astype(c.dtype), l, zeros,
                           0 if row is None else row)
            for c, n in zip(stacks, news)), pins)
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
    return _pinned(out, pins)


def _write_by_rows(c, new, l, starts, row=0, live=None):
    """One dynamic_update_slice a row, each at that row's own offset (a
    start that would run past W is clamped by the operation).  A row
    that is not live leaves the stack as it is, by the one way the
    platform's compiler keeps the stack in place (each compiled for a
    v5e and for the CPU, PERF.md, PR 40): XLA:TPU copies a stack that
    goes through a conditional, and takes a select between the new
    value and the position as it was, read before the first write;
    XLA:CPU copies the stack for those reads, and takes a condition a
    row."""
    zero = jnp.int32(0)
    rows = range(new.shape[0])
    at = [(jnp.int32(l), jnp.int32(row + b), zero, zero, starts[b])
          for b in rows]
    new = [new[b][None, None] for b in rows]
    select = live is not None and _on_tpu()
    if select:
        new = [jnp.where(live[b], new[b],
                         lax.dynamic_slice(c, at[b], new[b].shape))
               for b in rows]
    for b in rows:
        def put(c, b=b):
            return lax.dynamic_update_slice(c, new[b], at[b])
        c = put(c) if live is None or select \
            else lax.cond(live[b], put, lambda c: c, c)
    return c


def _kernel(l_ref, at_ref, *refs, n, lanes):
    """One invocation a call; ``at_ref[b]`` is row b's position, or -1
    where the row is not live.  refs: the n stacks whole, in HBM (read
    through the outputs they are aliased to); n arrays of new rows
    (B, K, D); the n outputs; scratch: for each stack ``_SLOTS`` block
    buffers (K, D, lanes), the copies' semaphores (in / out, stack, buffer),
    the live rows in order.  The live rows are walked with the next
    one's blocks on their way in while this one's are changed and sent
    back; a row that is not live starts no copy.  The new rows come as
    the program's products leave them and are turned here (in float32,
    which the chip transposes at any small shape): head k's values are
    then one column, broadcast over the block's lanes and kept at one
    of them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    news, outs, bufs = refs[n:2 * n], refs[2 * n:3 * n], refs[3 * n:4 * n]
    sem, rows = refs[4 * n:]
    l, B = l_ref[0], at_ref.shape[0]

    def count(b, k):
        @pl.when(at_ref[b] >= 0)
        def _():
            rows[k] = b
        return k + (at_ref[b] >= 0).astype(jnp.int32)

    n_live = lax.fori_loop(0, B, count, jnp.int32(0))

    def copies(k, way):
        """Live row k's blocks in (way 0) or back out (way 1)."""
        b, slot = rows[k], k % _SLOTS
        at = pl.ds(pl.multiple_of(at_ref[b] // lanes * lanes, lanes), lanes)
        out = []
        for i in range(n):
            there, here = outs[i].at[l, b, :, :, at], bufs[i].at[slot]
            out.append(pltpu.make_async_copy(
                *((there, here) if way == 0 else (here, there)),
                sem.at[way, i, slot]))
        return out

    @pl.when(n_live > 0)
    def _open():
        for c in copies(0, 0):
            c.start()

    def row(k, carry):
        @pl.when(k + 1 < n_live)
        def _ahead():
            # the next row's buffers: free once the blocks of the row
            # that had them last are back
            @pl.when(k + 1 >= _SLOTS)
            def _():
                for c in copies(k + 1 - _SLOTS, 1):
                    c.wait()
            for c in copies(k + 1, 0):
                c.start()

        for c in copies(k, 0):
            c.wait()
        b, slot = rows[k], k % _SLOTS
        lane = at_ref[b] % lanes
        for new_ref, buf in zip(news, bufs):
            _, K, D, _ = buf.shape
            here = lax.broadcasted_iota(jnp.int32, (D, lanes), 1) == lane
            new = new_ref[b].astype(jnp.float32).T.astype(buf.dtype)
            for h in range(K):
                buf[slot, h] = jnp.where(here, new[:, h:h + 1], buf[slot, h])
        for c in copies(k, 1):
            c.start()
        return carry

    lax.fori_loop(0, n_live, row, 0)
    for back in range(_SLOTS, 0, -1):    # the last rows' blocks still going out
        @pl.when(n_live >= back)
        def _():
            for c in copies(n_live - back, 1):
                c.wait()


def _write_kernel(stacks, news, l, starts, live=None, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = len(stacks)
    W = stacks[0].shape[-1]
    lanes = min(_LANE, W)
    at = jnp.clip(starts.astype(jnp.int32), 0, W - 1)
    if live is not None:
        at = jnp.where(live, at, -1)
    layer = jnp.asarray(l, jnp.int32).reshape(1)
    B = news[0].shape[0]
    rows = tuple(x[..., 0] for x in news)
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    return tuple(pl.pallas_call(
        functools.partial(_kernel, n=n, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[in_place] * n + [
                pl.BlockSpec(x.shape, lambda i, l_ref, at_ref: (0, 0, 0))
                for x in rows],
            out_specs=[in_place] * n,
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, *c.shape[2:4], lanes), c.dtype)
                for c in stacks] + [
                    pltpu.SemaphoreType.DMA((2, n, _SLOTS)),
                    pltpu.SMEM((B,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in stacks],
        # operand i (after the two prefetched scalars) is output i
        input_output_aliases={2 + i: i for i in range(n)},
        interpret=interpret,
    )(layer, at, *stacks, *rows))

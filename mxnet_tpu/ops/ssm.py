"""A selective state-space layer's recurrence (Mamba-1), for serving.

For a row's positions t with input ``c_t`` (E channels), step ``dt_t``
(E, after its softplus), ``B_t`` and ``C_t`` (N states) and the layer's
``A`` (negative) and ``D``:

    h_t = exp(dt_t A) * h_{t-1} + (dt_t c_t) B_t        (N x E, float32)
    y_t = sum_n h_t C_t + D c_t

A row carries two things between blocks: the state ``h`` (N, E) float32
and the convolution's tail, its last ``k - 1`` inputs.  **Channels are
the minor axis everywhere** (a state is ``(N, E)``, ``A`` is ``(N, E)``,
the taps are ``(k, E)``): the 16 states on sublanes, the channels on
lanes; with the states minor a float32 array pads each 16 to a 128-lane
tile in HBM, eight times its size.

- `selective_scan_rows`: a block of S positions a row, each row to its
  own length.  Past ``lengths[r]`` the state does not change (``dt`` is
  taken as 0 there: the decay is 1 and the input 0), so the state that
  comes back is the one after the row's last real token whatever the
  block was padded to.  Two paths:

  - **kernel** (a TPU): one Pallas call, grid (rows, channel blocks,
    time chunks), the time chunks in order with the ``(N, channels)``
    state resident in the output block; a chunk past the row's length
    is neither copied in nor worked (its ``y`` is stored as zeros), and
    the ``(S, E, N)`` expansion exists a position at a time in
    registers, never in HBM.
  - **plain**: a ``lax.scan`` over the positions: the oracle the kernel
    is tested against (tests/test_ssm_ops.py, interpreted), and the
    path on a platform without the kernel.

- `state_update_rows`: one position for every row of layer ``l`` of a
  stack of states ``(L, B, N, E)``, in place.  Told ``live``, the state
  of a row that is not live comes back bit for bit what it was; the
  **kernel** (a TPU) walks the live rows with its own copies, as
  `cache_write`'s does, each row's ``(N, E)`` block in, changed, and
  back out, and starts no copy for another row.  **plain**: a select
  and one ``dynamic_update_slice`` of the layer.

- `causal_conv_rows`, `conv_step`: the depthwise causal convolution
  ``silu(b + sum_j w[j] a_{t-k+1+j})`` before the scan, for a block
  (zero history; the tail it leaves is the row's last ``k - 1`` *real*
  inputs, zeros where the row is shorter) and for one position from a
  stack of tails ``(L, B, (k - 1) E)`` (a row's tail flat, oldest
  first: no axis of 3 to pad).  Both are plain XLA: a position reads
  four inputs.

``tally`` (a ``collections.Counter`` or None) is told at trace time how
many rows went by which path: ``"kernel"``, ``"plain"`` and, beside the
first, ``"kernel_live"``: the kernel's rows that were handed ``live``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .cache_write import _LANE, _SLOTS, _on_tpu

# positions a time chunk of the scan kernel, channels a block, and the
# positions of a chunk that are unrolled (a float32 tile's sublanes)
_TIME = 128
_CHANNELS = 512
_UNROLL = 8


# -- the convolution -----------------------------------------------------------

def causal_conv_rows(a, w, b, lengths):
    """``a`` (R, S, E); ``w`` (k, E) taps, the last on the position
    itself; ``b`` (E,); ``lengths`` (R,) int32.  Returns (c (R, S, E)
    float32, tail (R, (k - 1) E) in a's type): the row's inputs at
    ``lengths - k + 1 .. lengths - 1``, zeros before its first."""
    R, S, E = a.shape
    k = w.shape[0]
    ap = jnp.pad(a, ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    c = b.astype(jnp.float32) + sum(
        wf[j] * ap[:, j:j + S].astype(jnp.float32) for j in range(k))
    tail = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
        row, n, k - 1, axis=0))(ap, jnp.clip(lengths.astype(jnp.int32), 0, S))
    return jax.nn.silu(c), tail.reshape(R, (k - 1) * E)


def conv_step(tail, l, a, w, b, live=None):
    """One position a row from layer ``l`` of the tails ``(L, B,
    (k - 1) E)``: ``a`` (B, E) the new input.  Returns (c (B, E)
    float32, the tails with layer ``l``'s moved on one position); the
    tail of a row that is not ``live`` (B,) bool comes back as it
    was."""
    E = a.shape[-1]
    k = w.shape[0]
    old = lax.dynamic_index_in_dim(tail, l, 0, keepdims=False)
    new = jnp.concatenate([old[:, E:], a.astype(tail.dtype)], axis=-1)
    wf = w.astype(jnp.float32)
    c = b.astype(jnp.float32) + wf[k - 1] * a.astype(jnp.float32) + sum(
        wf[j] * old[:, j * E:(j + 1) * E].astype(jnp.float32)
        for j in range(k - 1))
    if live is not None:
        new = jnp.where(live[:, None], new, old)
    return jax.nn.silu(c), lax.dynamic_update_slice(
        tail, new[None], (jnp.int32(l), jnp.int32(0), jnp.int32(0)))


# -- a block -------------------------------------------------------------------

def scan_chunk(S):
    """Positions a time chunk of the kernel at a block of ``S``."""
    return min(_TIME, S)


def selective_scan_rows(c, dt, A, B, C, D, lengths, state0=None,
                        tally=None):
    """``c``, ``dt`` (R, S, E); ``A`` (N, E) float32; ``B``, ``C`` (R,
    S, N); ``D`` (E,); ``lengths`` (R,) int32; ``state0`` (R, N, E)
    float32 or None for zeros.  Returns (y (R, S, E) float32, state (R,
    N, E) float32 after each row's last real position); ``y`` past a
    row's length is finite and means nothing."""
    R, S, E = c.shape
    kernel = _on_tpu() and _kernel_fits(S, E)
    if tally is not None:
        tally["kernel" if kernel else "plain"] += R
    scan = _scan_kernel_call if kernel else _scan_plain
    return scan(c, dt, A, B, C, D, lengths, state0)


def _kernel_fits(S, E):
    """Whether the kernel's blocks are whole tiles: a block of more
    than one chunk is padded to whole chunks, a shorter one is one
    chunk of whole sublanes."""
    return (S > _TIME or S % _UNROLL == 0) and E % _LANE == 0 \
        and E % min(_CHANNELS, E) == 0


def _scan_plain(c, dt, A, B, C, D, lengths, state0=None):
    R, S, E = c.shape
    f32 = jnp.float32
    c = c.astype(f32)
    real = jnp.arange(S)[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], dt.astype(f32), 0.0)
    A, D = A.astype(f32), D.astype(f32)
    h0 = jnp.zeros((R,) + A.shape, f32) if state0 is None \
        else state0.astype(f32)

    def step(h, at):
        c_t, dt_t, B_t, C_t = at                    # (R, E) x 2, (R, N) x 2
        h = jnp.exp(dt_t[:, None, :] * A) * h \
            + (dt_t * c_t)[:, None, :] * B_t[:, :, None]
        return h, jnp.sum(h * C_t[:, :, None], axis=1) + D * c_t

    h, y = lax.scan(step, h0, tuple(
        x.astype(f32).swapaxes(0, 1) for x in (c, dt, B, C)))
    return y.swapaxes(0, 1), h


def _scan_kernel(len_ref, c_ref, dt_ref, b_ref, cc_ref, a_ref, d_ref, *refs,
                 chunk, carried):
    """A grid step: time chunk ``t`` of channel block ``e`` of row
    ``r``.  c, dt (chunk, channels); b, cc (N, chunk): a position's
    states are a column; a (N, channels), d (1, channels); the state
    the row starts from (N, channels) where ``carried``; y (chunk,
    channels); the state (N, channels), resident over the row's chunks:
    it is the carry."""
    from jax.experimental import pallas as pl

    s0_ref = refs[0] if carried else None
    y_ref, st_ref = refs[-2:]
    r, t = pl.program_id(0), pl.program_id(2)
    n, t0 = len_ref[r], t * chunk

    @pl.when(t == 0)
    def _first():
        st_ref[...] = s0_ref[...] if carried \
            else jnp.zeros(st_ref.shape, jnp.float32)

    @pl.when(t0 >= n)
    def _past():
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    @pl.when(t0 < n)
    def _walk():
        A, D = a_ref[...], d_ref[...]
        Bt, Ct = b_ref[...], cc_ref[...]
        column = lax.broadcasted_iota(jnp.int32, Bt.shape, 1)
        rows = lax.broadcasted_iota(jnp.int32, (_UNROLL, 1), 0)

        def group(g, h):
            at = pl.multiple_of(g * _UNROLL, _UNROLL)
            c8 = c_ref[pl.ds(at, _UNROLL), :]
            # past the row's length a position leaves the state alone
            dt8 = jnp.where(t0 + at + rows < n,
                            dt_ref[pl.ds(at, _UNROLL), :], 0.0)
            x8, skip8 = dt8 * c8, D * c8
            for j in range(_UNROLL):
                here = column == at + j
                b = jnp.sum(jnp.where(here, Bt, 0.0), axis=1, keepdims=True)
                cc = jnp.sum(jnp.where(here, Ct, 0.0), axis=1, keepdims=True)
                h = jnp.exp(dt8[j:j + 1] * A) * h + x8[j:j + 1] * b
                y_ref[pl.ds(at + j, 1), :] = jnp.sum(
                    h * cc, axis=0, keepdims=True) + skip8[j:j + 1]
            return h

        st_ref[...] = lax.fori_loop(0, chunk // _UNROLL, group, st_ref[...])


def _scan_kernel_call(c, dt, A, B, C, D, lengths, state0=None,
                      interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, S, E = c.shape
    N = A.shape[0]
    f32 = jnp.float32
    Tc, Ec = scan_chunk(S), min(_CHANNELS, E)
    Sp = -(-S // Tc) * Tc
    # a position's states as a column: (R, N, S)
    c, dt, Bt, Ct = (x.astype(f32) for x in (c, dt, B.swapaxes(1, 2),
                                             C.swapaxes(1, 2)))
    if Sp != S:     # a block that is no whole number of chunks
        c, dt = (jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0))) for x in (c, dt))
        Bt, Ct = (jnp.pad(x, ((0, 0), (0, 0), (0, Sp - S))) for x in (Bt, Ct))
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, S)

    def held(r, t, lens):
        # no further than the row's last real chunk: a chunk that is
        # not worked keeps the blocks that are resident
        return jnp.minimum(t, jnp.maximum(pl.cdiv(lens[r], Tc), 1) - 1)

    by_time = pl.BlockSpec((None, Tc, Ec),
                           lambda r, e, t, lens: (r, held(r, t, lens), e))
    by_state = pl.BlockSpec((None, N, Tc),
                            lambda r, e, t, lens: (r, 0, held(r, t, lens)))
    of_layer = lambda rows: pl.BlockSpec((rows, Ec),
                                         lambda r, e, t, lens: (0, e))
    of_row = pl.BlockSpec((None, N, Ec), lambda r, e, t, lens: (r, 0, e))
    operands = [c, dt, Bt, Ct, A.astype(f32), D.astype(f32)[None]]
    in_specs = [by_time, by_time, by_state, by_state, of_layer(N),
                of_layer(1)]
    if state0 is not None:
        operands.append(state0.astype(f32))
        in_specs.append(of_row)
    kw = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=Tc,
                          carried=state0 is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, E // Ec, Sp // Tc),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((None, Tc, Ec),
                                    lambda r, e, t, lens: (r, t, e)),
                       of_row]),
        out_shape=[jax.ShapeDtypeStruct((R, Sp, E), f32),
                   jax.ShapeDtypeStruct((R, N, E), f32)],
        interpret=interpret, **kw,
    )(lengths, *operands)
    return y[:, :S], state


# -- one position --------------------------------------------------------------

def state_update_rows(state, l, c, dt, A, B, C, D, live=None, tally=None):
    """``state`` (L, B, N, E) float32, donated; ``l`` the layer, an int
    or a traced scalar; ``c``, ``dt`` (B, E); ``A`` (N, E); ``B``,
    ``C`` (B, N); ``D`` (E,); ``live`` (B,) bool or None for all.
    Returns (y (B, E) float32, zero for a row that is not live; the
    states, layer ``l``'s live rows moved on one position)."""
    R, E = c.shape
    kernel = _on_tpu() and E % _LANE == 0
    if tally is not None:
        tally["kernel" if kernel else "plain"] += R
        if kernel and live is not None:
            tally["kernel_live"] += R
    update = _update_kernel_call if kernel else _update_plain
    return update(state, l, c, dt, A, B, C, D, live)


def _update_plain(state, l, c, dt, A, B, C, D, live=None):
    f32 = jnp.float32
    c, dt, B, C = (x.astype(f32) for x in (c, dt, B, C))
    old = lax.dynamic_index_in_dim(state, l, 0, keepdims=False)
    h = jnp.exp(dt[:, None, :] * A.astype(f32)) * old.astype(f32) \
        + (dt * c)[:, None, :] * B[:, :, None]
    y = jnp.sum(h * C[:, :, None], axis=1) + D.astype(f32) * c
    h = h.astype(state.dtype)
    if live is not None:
        h = jnp.where(live[:, None, None], h, old)
        y = jnp.where(live[:, None], y, 0.0)
    zero = jnp.int32(0)
    return y, lax.dynamic_update_slice(state, h[None],
                                       (jnp.int32(l), zero, zero, zero))


def _update_kernel(l_ref, live_ref, st_hbm, c_ref, dt_ref, b_ref, cc_ref,
                   a_ref, d_ref, out_hbm, y_ref, buf, sem, rows):
    """One invocation a call.  The states whole, in HBM (read through
    the output they are aliased to); c, dt (B, E); b, cc (B, N, 1): a
    row's states are a column; a (N, E), d (1, E); y (B, E); scratch:
    ``_SLOTS`` state buffers (N, E), the copies' semaphores (in / out,
    buffer), the live rows in order.  The live rows are walked with the
    next one's state on its way in while this one's is changed and sent
    back; a row that is not live starts no copy."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del st_hbm
    l, n_rows = l_ref[0], live_ref.shape[0]
    y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    def count(b, k):
        @pl.when(live_ref[b] != 0)
        def _():
            rows[k] = b
        return k + (live_ref[b] != 0).astype(jnp.int32)

    n_live = lax.fori_loop(0, n_rows, count, jnp.int32(0))

    def copy(k, way):
        """Live row k's state in (way 0) or back out (way 1)."""
        slot = k % _SLOTS
        there, here = out_hbm.at[l, rows[k]], buf.at[slot]
        return pltpu.make_async_copy(
            *((there, here) if way == 0 else (here, there)),
            sem.at[way, slot])

    @pl.when(n_live > 0)
    def _open():
        copy(0, 0).start()

    def row(k, carry):
        @pl.when(k + 1 < n_live)
        def _ahead():
            # the next row's buffer: free once the state of the row
            # that had it last is back
            @pl.when(k + 1 >= _SLOTS)
            def _():
                copy(k + 1 - _SLOTS, 1).wait()
            copy(k + 1, 0).start()

        copy(k, 0).wait()
        b, slot = rows[k], k % _SLOTS
        dt, c = dt_ref[pl.ds(b, 1), :], c_ref[pl.ds(b, 1), :]
        h = jnp.exp(dt * a_ref[...]) * buf[slot] + (dt * c) * b_ref[b]
        buf[slot] = h.astype(buf.dtype)
        y_ref[pl.ds(b, 1), :] = jnp.sum(h * cc_ref[b], axis=0,
                                        keepdims=True) + d_ref[...] * c
        copy(k, 1).start()
        return carry

    lax.fori_loop(0, n_live, row, 0)
    for back in range(_SLOTS, 0, -1):   # the last rows' states still going out
        @pl.when(n_live >= back)
        def _():
            copy(n_live - back, 1).wait()


def _update_kernel_call(state, l, c, dt, A, B, C, D, live=None,
                        interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, E = c.shape
    N = A.shape[0]
    f32 = jnp.float32
    live = jnp.ones((R,), jnp.int32) if live is None \
        else live.astype(jnp.int32)
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    kw = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=64 * 1024 * 1024)}
    state, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[in_place] + [whole] * 6,
            out_specs=[in_place, whole],
            scratch_shapes=[pltpu.VMEM((_SLOTS, N, E), state.dtype),
                            pltpu.SemaphoreType.DMA((2, _SLOTS)),
                            pltpu.SMEM((R,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((R, E), f32)],
        # operand 0 (after the two prefetched scalars) is output 0
        input_output_aliases={2: 0},
        interpret=interpret, **kw,
    )(jnp.asarray(l, jnp.int32).reshape(1), live, state,
      c.astype(f32), dt.astype(f32), B.astype(f32)[..., None],
      C.astype(f32)[..., None], A.astype(f32), D.astype(f32)[None])
    return y, state

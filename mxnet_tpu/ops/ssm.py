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


def _walk_live_rows(l_ref, live_ref, out_hbm, buf, sem, rows, change):
    """What both update kernels do around a row's arithmetic: the live
    rows of layer ``l_ref[0]`` of the states ``out_hbm`` (L, B, ...)
    walked in order with ``_SLOTS`` buffers ``buf``, the next one's
    state on its way in while ``change(b, slot)`` works row ``b``'s in
    ``buf[slot]`` and it is sent back; a row that is not live starts no
    copy.  ``sem``: the copies' semaphores (in / out, buffer); ``rows``:
    the live rows in order (SMEM)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    l, n_rows = l_ref[0], live_ref.shape[0]

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
        change(rows[k], k % _SLOTS)
        copy(k, 1).start()
        return carry

    lax.fori_loop(0, n_live, row, 0)
    for back in range(_SLOTS, 0, -1):   # the last rows' states still going out
        @pl.when(n_live >= back)
        def _():
            copy(n_live - back, 1).wait()


def _update_kernel(l_ref, live_ref, st_hbm, c_ref, dt_ref, b_ref, cc_ref,
                   a_ref, d_ref, out_hbm, y_ref, buf, sem, rows):
    """One invocation a call.  The states whole, in HBM (read through
    the output they are aliased to); c, dt (B, E); b, cc (B, N, 1): a
    row's states are a column; a (N, E), d (1, E); y (B, E); scratch:
    ``_SLOTS`` state buffers (N, E), the copies' semaphores and the
    live rows in order (`_walk_live_rows`, which says how the rows are
    walked)."""
    from jax.experimental import pallas as pl

    del st_hbm
    y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    def change(b, slot):
        dt, c = dt_ref[pl.ds(b, 1), :], c_ref[pl.ds(b, 1), :]
        h = jnp.exp(dt * a_ref[...]) * buf[slot] + (dt * c) * b_ref[b]
        buf[slot] = h.astype(buf.dtype)
        y_ref[pl.ds(b, 1), :] = jnp.sum(h * cc_ref[b], axis=0,
                                        keepdims=True) + d_ref[...] * c

    _walk_live_rows(l_ref, live_ref, out_hbm, buf, sem, rows, change)


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


# -- Mamba-2: one decay a head, a state (H P, N) -------------------------------
#
# Dao & Gu, arXiv:2405.21060.  A layer has H heads of P channels; head h
# of a row carries a state (P, N) float32, and a position's B_t and C_t
# (N) are shared by all heads.  With dt_t (H, after its softplus), the
# layer's A (H, negative) and D (H):
#
#     S_t^h = exp(dt_t^h A^h) S_{t-1}^h + dt_t^h x_t^h B_t^T
#     y_t^h = S_t^h C_t + D^h x_t^h
#
# A row's state lies as (H P, N), **the N states minor**: Granite 4.0-H's
# N = 128 is exactly the lane width, so nothing pads, and a head's (P,
# N) block is whole sublane tiles.
#
# - `mamba2_scan_rows`: a prefill block, each row to its own length.
#   **kernel** (a TPU): the chunked matrix form.  Grid (rows, head
#   blocks, time chunks), the chunks in order with the head block's
#   states resident in the output block.  With c_t the cumulative
#   log-decay inside a chunk (float32, made by the caller's XLA: a
#   cumulative sum over 128 positions), a chunk's output is
#   ``((B C^T) * exp(c_t - c_s)[s <= t]) (dt x)`` plus ``exp(c_t) S C_t``,
#   and the state moves to ``exp(c_last) S + (exp(c_last - c_s) dt_s
#   x_s) B``: on the MXU, the decay-masked product a (head, chunk) and
#   the other two once a (head block, chunk) against operands all heads
#   share (``C``, ``B``), their operands in the type ``operands`` names
#   and their sums float32; ``B C^T`` is formed once a grid step and
#   shared by the block's heads.  A chunk past the row's
#   length is neither copied in nor worked; past ``lengths[r]`` inside a
#   chunk ``dt`` is taken as 0.  **plain**: a ``lax.scan`` over the
#   positions, the oracle (tests/test_ssd_ops.py) and the path off the
#   TPU.
# - `mamba2_update_rows`: one position for every row of layer ``l`` of a
#   stack ``(L, B, H P, N)``, in place, told ``live`` as
#   `state_update_rows` is.  The **kernel** walks the live rows with its
#   own copies, a row's whole state (4 MB at Granite's sizes) in,
#   changed a tile of 128 channels at a time on the vector unit, and
#   back out; what is a column against the state's tiles (the decay and
#   ``dt x`` a channel, the output) is handed over and given back as
#   tiles ``(128, H P / 128)``, a block's column a lane.

# positions a chunk and heads a block of the Mamba-2 scan kernel;
# channels a tile of the update kernel
_CHUNK = 128
_HEADS = 16
_ROWS = 128


def mamba2_chunk():
    """Positions a time chunk of the Mamba-2 scan kernel."""
    return _CHUNK


def mamba2_scan_rows(x, dt, A, B, C, D, lengths, operands=None,
                     tally=None):
    """``x`` (R, S, H, P); ``dt`` (R, S, H) after its softplus; ``A``
    (H,) negative; ``B``, ``C`` (R, S, N); ``D`` (H,); ``lengths`` (R,)
    int32.  Returns (y (R, S, H, P) float32, state (R, H P, N) float32
    after each row's last real position, from an empty one); ``y`` past
    a row's length is finite and means nothing.  The kernel's products
    take their operands in the type ``operands`` (None: ``x``'s) and sum
    in float32; the plain path is float32 throughout."""
    R, _, H, P = x.shape
    kernel = _on_tpu() and _mamba2_scan_fits(H, P, B.shape[-1])
    if tally is not None:
        tally["kernel" if kernel else "plain"] += R
    if kernel:
        return _mamba2_scan_kernel_call(x, dt, A, B, C, D, lengths,
                                        operands=operands)
    return _mamba2_scan_plain(x, dt, A, B, C, D, lengths)


def _mamba2_scan_fits(H, P, N):
    """Whole tiles: a head's channels whole sublane tiles, the states
    whole lane tiles, a head block a whole number of lane tiles when it
    is turned."""
    hb = min(_HEADS, H)
    return P % 8 == 0 and N % _LANE == 0 and H % hb == 0 \
        and (hb * P) % _LANE == 0


def _mamba2_scan_plain(x, dt, A, B, C, D, lengths):
    R, S, H, P = x.shape
    f32 = jnp.float32
    real = jnp.arange(S)[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], dt.astype(f32), 0.0)
    A, D = A.astype(f32), D.astype(f32)

    def step(h, at):
        x_t, dt_t, B_t, C_t = at            # (R, H, P), (R, H), (R, N) x 2
        h = jnp.exp(dt_t * A)[:, :, None, None] * h \
            + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, None, None, :]
        return h, jnp.sum(h * C_t[:, None, None, :], axis=-1) \
            + D[:, None] * x_t

    h, y = lax.scan(step, jnp.zeros((R, H, P, B.shape[-1]), f32), tuple(
        a.astype(f32).swapaxes(0, 1) for a in (x, dt, B, C)))
    return y.swapaxes(0, 1), h.reshape(R, H * P, -1)


def _mxu(spec_dims, a, b):
    """A kernel's product: float32 sums; float32 operands at full
    precision, narrower ones as they are."""
    return lax.dot_general(
        a, b, (spec_dims, ((), ())), preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if a.dtype == jnp.float32 else None)


def _mamba2_scan_kernel(len_ref, x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref,
                        y_ref, st_ref, xt, yt, *, chunk, heads, width):
    """A grid step: time chunk ``t`` of head block ``e`` of row ``r``.
    x, y (chunk, heads * width) float32, a position a row; dt, cs, d
    (heads, chunk): a head's steps, cumulative log-decays and D along
    the lanes; b, c (chunk, N); the state (heads * width, N), resident
    over the row's chunks: it is the carry.  Scratch: the block turned,
    channels on the sublanes (heads * width, chunk), in and out."""
    from jax.experimental import pallas as pl

    r, t = pl.program_id(0), pl.program_id(2)
    n, t0 = len_ref[r], t * chunk
    Q, P = chunk, width

    @pl.when(t == 0)
    def _first():
        st_ref[...] = jnp.zeros(st_ref.shape, jnp.float32)

    @pl.when(t0 >= n)
    def _past():
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    @pl.when(t0 < n)
    def _walk():
        Bm, Cm = b_ref[...], c_ref[...]
        mxu = Bm.dtype
        # [s, t] = B_s . C_t, once for the block's heads
        bct = _mxu(((1,), (1,)), Bm, Cm)
        s_at = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        t_at = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        xt[...] = x_ref[...].T
        # what the chunk's first state puts out, every head of the block
        # in one product: a head scales its rows by exp(c_t) below
        yt[...] = _mxu(((1,), (1,)), st_ref[...].astype(mxu), Cm)

        def head(j, carry):
            at = pl.ds(pl.multiple_of(j * P, P), P)
            cs, dt = cs_ref[pl.ds(j, 1), :], dt_ref[pl.ds(j, 1), :]
            # the cumulative log-decay at s, down the sublanes
            cs_s = jnp.sum(jnp.where(s_at == t_at, cs, 0.0), axis=1,
                           keepdims=True)
            g = jnp.where(s_at <= t_at,
                          jnp.exp(jnp.minimum(cs - cs_s, 0.0)), 0.0) * bct
            xh = xt[at, :]
            yt[at, :] = _mxu(((1,), (0,)), (xh * dt).astype(mxu),
                             g.astype(mxu)) + yt[at, :] * jnp.exp(cs) \
                + d_ref[pl.ds(j, 1), :] * xh
            # the chunk's whole log-decay: the sums never rise
            last = jnp.min(cs, axis=1, keepdims=True)
            st_ref[at, :] = jnp.exp(last) * st_ref[at, :]
            # x weighed by what is left of a position at the chunk's end
            xt[at, :] = xh * (jnp.exp(last - cs) * dt)
            return carry

        lax.fori_loop(0, heads, head, 0)
        # the positions' parts of the new states, the block's heads in
        # one product
        st_ref[...] = st_ref[...] + _mxu(((1,), (0,)),
                                         xt[...].astype(mxu), Bm)
        y_ref[...] = yt[...].T


def _mamba2_scan_kernel_call(x, dt, A, B, C, D, lengths, operands=None,
                             interpret=False, chunk=None, heads=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, S, H, P = x.shape
    N = B.shape[-1]
    f32 = jnp.float32
    Q, hb = chunk or _CHUNK, heads or min(_HEADS, H)
    Sp = -(-S // Q) * Q
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, S)
    mxu = jnp.dtype(operands or x.dtype)
    # past a row's length a position leaves the state alone; the
    # cumulative log-decay inside each chunk, float32
    dt = jnp.where(jnp.arange(S)[None, :, None] < lengths[:, None, None],
                   dt.astype(f32), 0.0)
    x = x.reshape(R, S, H * P).astype(f32)
    if Sp != S:
        dt, x, B, C = (jnp.pad(a, ((0, 0), (0, Sp - S), (0, 0)))
                       for a in (dt, x, B, C))
    cs = jnp.cumsum((dt * A.astype(f32)).reshape(R, Sp // Q, Q, H),
                    axis=2).reshape(R, Sp, H)

    def held(r, t, lens):
        # no further than the row's last real chunk: a chunk that is
        # not worked keeps the blocks that are resident
        return jnp.minimum(t, jnp.maximum(pl.cdiv(lens[r], Q), 1) - 1)

    by_time = pl.BlockSpec((None, Q, hb * P),
                           lambda r, e, t, lens: (r, held(r, t, lens), e))
    by_head = pl.BlockSpec((None, hb, Q),
                           lambda r, e, t, lens: (r, e, held(r, t, lens)))
    by_state = pl.BlockSpec((None, Q, N),
                            lambda r, e, t, lens: (r, held(r, t, lens), 0))
    kw = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}
    y, state = pl.pallas_call(
        functools.partial(_mamba2_scan_kernel, chunk=Q, heads=hb, width=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, H // hb, Sp // Q),
            in_specs=[by_time, by_head, by_head, by_state, by_state,
                      pl.BlockSpec((hb, Q), lambda r, e, t, lens: (e, 0))],
            out_specs=[pl.BlockSpec((None, Q, hb * P),
                                    lambda r, e, t, lens: (r, t, e)),
                       pl.BlockSpec((None, hb * P, N),
                                    lambda r, e, t, lens: (r, e, 0))],
            scratch_shapes=[pltpu.VMEM((hb * P, Q), f32)] * 2),
        out_shape=[jax.ShapeDtypeStruct((R, Sp, H * P), f32),
                   jax.ShapeDtypeStruct((R, H * P, N), f32)],
        interpret=interpret, **kw,
    )(lengths, x, dt.swapaxes(1, 2), cs.swapaxes(1, 2), B.astype(mxu),
      C.astype(mxu), jnp.broadcast_to(D.astype(f32)[:, None], (H, Q)))
    return y[:, :S].reshape(R, S, H, P), state


def mamba2_update_rows(state, l, x, dt, A, B, C, D, live=None, tally=None):
    """``state`` (L, B, H P, N) float32, donated; ``l`` the layer, an
    int or a traced scalar; ``x`` (B, H, P); ``dt`` (B, H); ``A``, ``D``
    (H,); ``B``, ``C`` (B, N); ``live`` (B,) bool or None for all.
    Returns (y (B, H, P) float32, zero for a row that is not live; the
    states, layer ``l``'s live rows moved on one position)."""
    R, H, P = x.shape
    kernel = _on_tpu() and _mamba2_update_fits(H * P, B.shape[-1])
    if tally is not None:
        tally["kernel" if kernel else "plain"] += R
        if kernel and live is not None:
            tally["kernel_live"] += R
    update = _mamba2_update_kernel_call if kernel else _mamba2_update_plain
    return update(state, l, x, dt, A, B, C, D, live)


def _mamba2_update_fits(E, N):
    """Whole tiles of ``_ROWS`` channels, whose two columns a tile (the
    decay and ``dt x``) fit one lane tile side by side."""
    return E % _ROWS == 0 and 2 * (E // _ROWS) <= _LANE and N % _LANE == 0


def _mamba2_update_plain(state, l, x, dt, A, B, C, D, live=None):
    f32 = jnp.float32
    R, H, P = x.shape
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    old = lax.dynamic_index_in_dim(state, l, 0, keepdims=False)
    h = jnp.exp(dt * A.astype(f32))[:, :, None, None] \
        * old.astype(f32).reshape(R, H, P, -1) \
        + (dt[:, :, None] * x)[..., None] * B[:, None, None, :]
    y = jnp.sum(h * C[:, None, None, :], axis=-1) \
        + D.astype(f32)[:, None] * x
    h = h.reshape(old.shape).astype(state.dtype)
    if live is not None:
        h = jnp.where(live[:, None, None], h, old)
        y = jnp.where(live[:, None, None], y, 0.0)
    zero = jnp.int32(0)
    return y, lax.dynamic_update_slice(state, h[None],
                                       (jnp.int32(l), zero, zero, zero))


def _mamba2_update_kernel(l_ref, live_ref, st_hbm, cols_ref, b_ref, c_ref,
                          out_hbm, y_ref, buf, sem, rows, *, tiles):
    """One invocation a call.  The states whole, in HBM (read through
    the output they are aliased to); cols (B, rows a tile, 2 tiles): a
    row's decays a channel and then its ``dt x``, a tile's column a
    lane; b, c (B, N); y (B, rows a tile, tiles), a tile's column a
    lane; scratch: ``_SLOTS`` state buffers (H P, N), the copies'
    semaphores and the live rows in order (`_walk_live_rows`)."""
    from jax.experimental import pallas as pl

    del st_hbm
    sub = cols_ref.shape[1]
    y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, y_ref.shape[1:], 1)

    def change(b, slot):
        cols = cols_ref[b]
        Bt, Ct = b_ref[pl.ds(b, 1), :], c_ref[pl.ds(b, 1), :]
        y = jnp.zeros(y_ref.shape[1:], jnp.float32)
        for i in range(tiles):
            at = pl.ds(i * sub, sub)
            h = cols[:, i:i + 1] * buf[slot, at, :] \
                + cols[:, tiles + i:tiles + i + 1] * Bt
            buf[slot, at, :] = h
            y = jnp.where(lane == i, jnp.sum(h * Ct, axis=1, keepdims=True),
                          y)
        y_ref[b] = y

    _walk_live_rows(l_ref, live_ref, out_hbm, buf, sem, rows, change)


def _mamba2_update_kernel_call(state, l, x, dt, A, B, C, D, live=None,
                               interpret=False, rows=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, H, P = x.shape
    E, N = state.shape[2:]
    f32 = jnp.float32
    sub = rows or _ROWS
    tiles = E // sub
    x, dt = x.astype(f32), dt.astype(f32)
    live = jnp.ones((R,), jnp.int32) if live is None \
        else live.astype(jnp.int32)

    def columns(a):
        """(R, E) a channel → (R, sub, tiles): a tile's column a lane."""
        return a.reshape(R, tiles, sub).swapaxes(1, 2)

    decay = jnp.broadcast_to(jnp.exp(dt * A.astype(f32))[:, :, None],
                             (R, H, P)).reshape(R, E)
    cols = jnp.concatenate(
        [columns(decay), columns((dt[:, :, None] * x).reshape(R, E))],
        axis=-1)
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    kw = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=96 * 1024 * 1024)}
    state, y = pl.pallas_call(
        functools.partial(_mamba2_update_kernel, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[in_place] + [whole] * 3,
            out_specs=[in_place, whole],
            scratch_shapes=[pltpu.VMEM((_SLOTS, E, N), state.dtype),
                            pltpu.SemaphoreType.DMA((2, _SLOTS)),
                            pltpu.SMEM((R,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((R, sub, tiles), f32)],
        # operand 0 (after the two prefetched scalars) is output 0
        input_output_aliases={2: 0},
        interpret=interpret, **kw,
    )(jnp.asarray(l, jnp.int32).reshape(1), live, state, cols,
      B.astype(f32), C.astype(f32))
    y = y.swapaxes(1, 2).reshape(R, H, P) + D.astype(f32)[:, None] * x
    return jnp.where(live[:, None, None] != 0, y, 0.0), state

"""One query position a row over the serving caches: the decode step's
attention, for every decoder program.

The stacks are what `ops/cache_write.py` writes: carried, donated,
position-minor ``(L, B, K, D, W)`` keys beside ``(L, B, K, Dv, W)``
values.  ``attend_rows`` takes row b's query ``(K, G, D)`` (``G`` query
heads a key head, scaled) over layer ``l``'s positions ``< lengths[b]``,
optionally only those a selection ``mask (B, W)`` keeps (Keye-VL-2.0)
and with a ``sink (K, G)`` in the softmax's maximum and denominator
(MiMo-V2's window layers), and returns ``(B, K, G, Dv)`` float32; a row
that sees no position comes out zero.  Where there is no stack of
values (``cv`` None, ``leading = Dv``) the values are the first ``Dv``
rows of each key: Kimi-K2's latent stack ``(L, B, 1, 576, W)``, whose
512-wide latent is key and value to all 64 query heads; a block is then
read once.  Two paths, chosen on what the call can see:

- **kernel** (a TPU, no mesh, a window of more than one lane block):
  one Pallas call, grid over the rows, the stacks left where they are
  (``pl.ANY``) with ``l`` and ``lengths`` as scalar prefetch.  Row b
  walks its lane blocks ``0 .. ceil(lengths[b] / lanes) - 1`` and none
  beyond (an empty row its first, all of it masked): each block's
  ``(K, D, lanes)`` keys and ``(K, Dv, lanes)`` values come in by two
  asynchronous copies (one, where the values are rows of the keys) into
  one of two buffers while the block before is worked, and a row's last
  block starts the next row's first, so the copies never wait for a
  grid step.  A running softmax in float32
  (maximum and denominator in scratch, the accumulator in the output
  block), all heads of a block in one batched product on the MXU in the
  stacks' type.  Heads narrower than a 128-deep tile are worked ``r``
  side by side (``(16, 64, n)`` read as ``(8, 128, n)``, the query
  block-diagonal): a key tile is loaded as weights for one short query,
  so a half-deep tile costs what a full one does.
- **XLA** (the CPU, a program with a mesh, a window of one lane block
  such as MiMo-V2's rings): the masked contraction over the whole
  window.  It is also what the kernel is tested against
  (tests/test_cache_attention.py, interpreted).

``tally`` (a ``collections.Counter`` or None) is told at trace time
which path a call took and in what blocks it reads a row:
``tally[("kernel", W, lanes)]``, ``tally[("xla", W, W)]``, one a call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .cache_write import _LANE, _on_tpu

_MASKED = -1e30
# what a block costs beside its bytes (starting and awaiting its copies,
# the products' latency: some 0.4 us), as bytes read at the rate the
# kernel reaches
_BLOCK_COST_BYTES = 240 * 1024


def block_lanes(ck, cv):
    """Positions a block (``cv`` None: a position's bytes are its
    key's).  A row of ``n`` positions costs ``n / lanes`` blocks and
    reads ``lanes / 2`` positions past its length; taking a
    row to hold a quarter of its window, the two balance at
    ``sqrt(W / 2 * block cost / a position's bytes)``.  The power-of-two
    multiple of 128 under that which divides W."""
    K, D, W = ck.shape[2:]
    a_position = K * (D + (0 if cv is None else cv.shape[3])) \
        * ck.dtype.itemsize
    best = (W / 2 * _BLOCK_COST_BYTES / a_position) ** 0.5
    lanes = _LANE
    while lanes * 2 <= best and W % (lanes * 2) == 0:
        lanes *= 2
    return min(lanes, W)


def attend_rows(q, ck, cv, l, lengths, mask=None, sink=None, mesh=None,
                tally=None, leading=None):
    """``q`` (B, K, G, D) scaled; ``ck`` (L, B, K, D, W); ``cv``
    (L, B, K, Dv, W), or None with ``leading = Dv`` for values that are
    the keys' first ``Dv`` rows; ``l`` the layer, an int or a traced
    scalar; ``lengths`` (B,) int32; ``mask`` (B, W) bool or None;
    ``sink`` (K, G) or None.  Returns (B, K, G, Dv) float32."""
    W = ck.shape[-1]
    kernel = mesh is None and _on_tpu() and W > _LANE and W % _LANE == 0
    lanes = block_lanes(ck, cv) if kernel else W
    if tally is not None:
        tally[("kernel" if kernel else "xla", W, lanes)] += 1
    if kernel:
        return _attend_kernel(q, ck, cv, l, lengths, mask, sink, lanes,
                              leading=leading)
    ck = lax.dynamic_index_in_dim(ck, l, 0, keepdims=False)
    cv = ck[:, :, :leading] if cv is None else \
        lax.dynamic_index_in_dim(cv, l, 0, keepdims=False)
    return _attend_xla(q, ck, cv, lengths, mask, sink)


def _attend_xla(q, ck, cv, lengths, mask, sink):
    """The masked contraction over a layer's whole window: ck (B, K, D,
    W), cv (B, K, Dv, W).  The probabilities take the query's type."""
    seen = jnp.arange(ck.shape[-1])[None, :] < lengths[:, None]
    if mask is not None:
        seen = seen & mask
    s = jnp.einsum("bkgd,bkdw->bkgw", q, ck,
                   preferred_element_type=jnp.float32)
    s = jnp.where(seen[:, None, None, :], s, _MASKED)
    m = jnp.max(s, axis=-1)
    if sink is not None:
        m = jnp.maximum(m, sink[None])
    p = jnp.exp(s - m[..., None])
    denom = jnp.sum(p, axis=-1)
    if sink is not None:
        denom = denom + jnp.exp(sink[None] - m)
    a = jnp.einsum("bkgw,bkdw->bkgd", p.astype(q.dtype), cv,
                   preferred_element_type=jnp.float32)
    # a row that sees nothing attends to nothing
    return jnp.where(jnp.any(seen, axis=-1)[:, None, None, None],
                     a / denom[..., None], 0.0)


def heads_a_tile(K, D, Dv):
    """Key heads worked side by side: as many as fill a 128-deep tile
    (a divisor of K; 1 for heads 128 wide or wider)."""
    r = max(1, _LANE // max(D, Dv))
    while K % r:
        r -= 1
    return r


def _kernel(l_ref, len_ref, q_ref, k_hbm, *refs, lanes, masked, sunk,
            leading):
    """A grid step: one row.  q (Kp, rows, Dp); the stacks whole, in
    HBM (the values' only where ``leading`` is None); then the row's
    mask (1, W) and the sink (Kp, rows, 1), where given; the output
    block (Kp, rows, Dvp), which is the accumulator; scratch: two
    buffers of keys (2, Kp, Dp, lanes) and of values (none where the
    values are the keys' first ``leading`` rows), their copies'
    semaphores (stack, buffer), the running maximum and denominator
    (Kp, rows, 1), and which buffer the row's first block is in."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    v_hbm = refs.pop(0) if leading is None else None
    mask_ref = refs.pop(0) if masked else None
    sink_ref = refs.pop(0) if sunk else None
    o_ref, k_buf = refs.pop(0), refs.pop(0)
    v_buf = refs.pop(0) if leading is None else None
    sem, m_scr, l_scr, first = refs
    b, l = pl.program_id(0), l_ref[0]
    n = len_ref[b]
    blocks = jnp.maximum(pl.cdiv(n, lanes), 1)

    def copies(row, j, slot):
        at = pl.ds(pl.multiple_of(j * lanes, lanes), lanes)
        keys = pltpu.make_async_copy(k_hbm.at[l, row, :, :, at],
                                     k_buf.at[slot], sem.at[0, slot])
        if v_hbm is None:
            return (keys,)
        return (keys, pltpu.make_async_copy(v_hbm.at[l, row, :, :, at],
                                            v_buf.at[slot], sem.at[1, slot]))

    @pl.when(b == 0)
    def _open():
        first[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    if sunk:    # the sink is a key of its own with no value
        m_scr[...] = sink_ref[...]
        l_scr[...] = jnp.ones(l_scr.shape, jnp.float32)
    else:
        m_scr[...] = jnp.full(m_scr.shape, _MASKED, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    base = first[0]

    def block(j, carry):
        slot = (base + j) % 2

        # the next block sets out before this one is waited for: the
        # row's own, or behind its last the next row's first
        @pl.when(j + 1 < blocks)
        def _ahead():
            for c in copies(b, j + 1, 1 - slot):
                c.start()

        @pl.when((j + 1 == blocks) & (b + 1 < pl.num_programs(0)))
        def _next_row():
            for c in copies(b + 1, 0, 1 - slot):
                c.start()

        for c in copies(b, j, slot):
            c.wait()
        at = pl.multiple_of(j * lanes, lanes)
        live = at + lax.broadcasted_iota(jnp.int32, (1, lanes), 1) < n
        if masked:
            live = live & (mask_ref[:, pl.ds(at, lanes)] != 0)
        # every head at once: their products do not wait for each other
        s = jnp.einsum("kgd,kdn->kgn", q_ref[...], k_buf[slot],
                       preferred_element_type=jnp.float32)
        s = jnp.where(live, s, _MASKED)              # (Kp, rows, lanes)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_next), 0.0)
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=2, keepdims=True)
        m_scr[...] = m_next
        v = k_buf[slot, :, :leading] if v_buf is None else v_buf[slot]
        o_ref[...] = alpha * o_ref[...] + jnp.einsum(
            "kgn,kdn->kgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return carry

    lax.fori_loop(0, blocks, block, 0)
    first[0] = (base + blocks) % 2
    denom = l_scr[...]
    o_ref[...] = jnp.where(denom > 0, o_ref[...] / denom, 0.0)


def _attend_kernel(q, ck, cv, l, lengths, mask, sink, lanes,
                   interpret=False, leading=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, K, G, D = q.shape
    L, W = ck.shape[0], ck.shape[-1]
    if cv is not None:
        leading = None
    Dv = leading or cv.shape[3]
    # values that are rows of their keys lie head by head as the keys do
    r = 1 if cv is None else heads_a_tile(K, D, Dv)
    Kp, Gp, Dp, Dvp = K // r, r * G, r * D, r * Dv
    rows = -(-Gp // 16) * 16        # a whole tile of the stacks' type
    # r heads side by side: head j's query in columns j * D .. of row
    # j * G + g, zeros beside it
    eye = jnp.eye(r, dtype=q.dtype)
    qp = (q.reshape(B, Kp, r, G, 1, D) * eye[:, None, :, None]).reshape(
        B, Kp, Gp, Dp).astype(ck.dtype)
    qp = jnp.pad(qp, ((0, 0), (0, 0), (0, rows - Gp), (0, 0)))

    def of_row(*block):
        return pl.BlockSpec((None,) + block, lambda b, l, n: (b, 0, 0, 0))

    in_place = pl.BlockSpec(memory_space=pl.ANY)
    specs = [of_row(Kp, rows, Dp), in_place]
    args = [qp, ck.reshape(L, B, Kp, Dp, W)]
    scratch = [pltpu.VMEM((2, Kp, Dp, lanes), ck.dtype)]
    if cv is not None:
        specs.append(in_place)
        args.append(cv.reshape(L, B, Kp, Dvp, W))
        scratch.append(pltpu.VMEM((2, Kp, Dvp, lanes), cv.dtype))
    if mask is not None:
        specs.append(pl.BlockSpec((None, 1, W), lambda b, l, n: (b, 0, 0)))
        args.append(mask.astype(jnp.int32)[:, None, :])
    if sink is not None:
        specs.append(pl.BlockSpec((Kp, rows, 1), lambda b, l, n: (0, 0, 0)))
        args.append(jnp.pad(sink.astype(jnp.float32).reshape(Kp, Gp),
                            ((0, 0), (0, rows - Gp)))[..., None])
    # a row's first block is begun by the row before: the rows in order
    kw = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",))}
    out = pl.pallas_call(
        functools.partial(_kernel, lanes=lanes, masked=mask is not None,
                          sunk=sink is not None, leading=leading),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,), in_specs=specs,
            out_specs=of_row(Kp, rows, Dvp),
            scratch_shapes=scratch + [
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((Kp, rows, 1), jnp.float32),
                            pltpu.VMEM((Kp, rows, 1), jnp.float32),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, Kp, rows, Dvp), jnp.float32),
        interpret=interpret, **kw,
    )(jnp.asarray(l, jnp.int32).reshape(1),
      jnp.clip(lengths.astype(jnp.int32), 0, W), *args)
    # head j's values are columns j * Dv .. of its own rows
    out = out[:, :, :Gp].reshape(B, Kp, r, G, r, Dv)
    return jnp.einsum("bkjgjd->bkjgd", out).reshape(B, K, G, Dv)

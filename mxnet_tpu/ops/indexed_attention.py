"""Attention over a learned selection of the cache (a "lightning
indexer": DeepSeek-V3.2's sparse attention, Keye-VL-2.0's ``sa_config``).

Beside keys and values a layer keeps one small **indexer key** a
position.  A query scores every earlier position with it,

    I[t, s] = sum_h w[t, h] * relu(qi[t, h] . ki[s]),

and attends only to ``S[t] = {s <= t : I[t, s] >= tau[t]}``, ``tau[t]``
the ``k``-th largest score (every ``s <= t`` while fewer than ``k``
exist).  This form needs no sort order: a mask and a gather give the same
set, and exact ties at the threshold are all kept.

- `kth_key`: the one threshold search.  Scores become int32 keys of the
  same order (`sortable_key`) and the ``k``-th largest key is built bit by
  bit from 32 counting passes (``count(keys >= candidate) >= k``): exact,
  no sort, a fixed number of passes whatever the data.  It runs on XLA
  arrays (decode: ``(B, W)`` scores) and, the same code, on a block held
  in VMEM (prefill).
- `select_prefill` (Pallas): for a block of queries the scores of every
  live key are made on the MXU into a VMEM scratch, searched there, and
  only the selection leaves as an int8 mask ``(B, S, S)``: the scores
  never reach HBM (a float32 ``(B, S, S)`` array is 17 GB at 16 x 16,384).
- attention under that mask is not here: the prefill calls
  `pallas_attention.flash_attention_forward(keep=mask)`, the flash
  forward body that Kimi-K2's, Ouro's and Command A+'s prefills run,
  with the mask one more operand (PR 43; this file's own kernel, a grid
  over key blocks too, spent four of five grid steps on nothing).
- decode (`index_scores_decode`, `select_topk`): one query a row against
  the indexer's cache, plain XLA.

On the CPU the kernels run interpreted (tests); on a TPU through Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_attention import _use_interpret

_INT_MIN = -2 ** 31
_LANE = 128
# a query block's keys for 16,384 positions are 8 MB of VMEM, beside the
# double-buffered indexer keys and mask: more than the 16 MB default
_VMEM_LIMIT = 64 * 1024 * 1024


# -- the threshold search ------------------------------------------------------

def sortable_key(x):
    """float32 → int32 with the same order (``-0.0`` as ``0.0``)."""
    x = jnp.where(x == 0.0, 0.0, x.astype(jnp.float32))
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def kth_key(count_ge, k, shape):
    """The largest int32 ``c`` with ``count_ge(c) >= k``, elementwise over
    ``shape``: the ``k``-th largest key where ``count_ge(c)`` counts the
    keys ``>= c``; ``INT_MIN`` where fewer than ``k`` keys exist.  The
    sign first, then 31 bits from the top: 32 counting passes."""
    zero = jnp.zeros(shape, jnp.int32)
    prefix = jnp.where(count_ge(zero) >= k, zero, jnp.int32(_INT_MIN))

    def bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), jnp.int32(30) - i)
        return jnp.where(count_ge(cand) >= k, cand, prefix)

    return lax.fori_loop(0, 31, bit, prefix)


def select_topk(scores, live, k):
    """``live & (scores >= the k-th largest live score)`` along the last
    axis; where fewer than ``k`` are live, ``live`` itself."""
    keys = jnp.where(live, sortable_key(scores), jnp.int32(_INT_MIN))
    thr = kth_key(
        lambda c: jnp.sum(keys >= c, axis=-1, keepdims=True,
                          dtype=jnp.int32),
        k, keys.shape[:-1] + (1,))
    return (keys >= thr) & live


# -- decode: one query a row over the indexer's cache --------------------------

def index_scores_decode(qi, w, cki):
    """qi (B, Hi, di), w (B, Hi) float32, cki (B, di, W) → I (B, W)
    float32."""
    s = jnp.einsum("bhd,bdw->bhw", qi, cki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(w[:, :, None] * jnp.maximum(s, 0.0), axis=1)


# -- prefill: the selection of a block that attends inside itself --------------

def _blocks(S):
    """(query block, key block) of the prefill kernels."""
    return min(_LANE, S), min(4 * _LANE, S)


def padded_length(T):
    """The least length from ``T`` up that the prefill kernels' blocks
    divide."""
    if T <= _LANE:
        return T
    step = _LANE if T <= 4 * _LANE else 4 * _LANE
    return -(-T // step) * step


def _select_kernel(last_ref, qi_ref, w_ref, ki_ref, mask_ref, keys_scr, *,
                   k, bq, bk, nk, heads):
    from jax.experimental import pallas as pl

    b, i = pl.program_id(0), pl.program_id(1)
    q0 = i * bq
    last = last_ref[b]
    # key blocks that hold a position some query of this block may see
    n_live = jnp.minimum((q0 + bq - 1) // bk, last // bk) + 1
    lanes = min(_LANE, bk)

    def at(j):
        return pl.ds(pl.multiple_of(j * bk, bk), bk)

    @pl.when(q0 > last)
    def _padding():
        mask_ref[...] = jnp.zeros(mask_ref.shape, mask_ref.dtype)

    @pl.when(q0 <= last)
    def _select():
        w = w_ref[...]                                       # (bq, heads)
        t = q0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

        def fill(j, carry):
            kt = ki_ref[:, at(j)]                            # (di, bk)
            acc = jnp.zeros((bq, bk), jnp.float32)
            for h in range(heads):
                s = lax.dot_general(qi_ref[h], kt, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                acc = acc + w[:, h:h + 1] * jnp.maximum(s, 0.0)
            seen = j * bk + lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1) <= t
            keys_scr[:, at(j)] = jnp.where(seen, sortable_key(acc),
                                           jnp.int32(_INT_MIN))
            return carry

        lax.fori_loop(0, n_live, fill, 0)

        def count_ge(cand):
            def body(j, part):
                hit = (keys_scr[:, at(j)] >= cand).astype(jnp.int32)
                for a in range(bk // lanes):
                    part = part + hit[:, a * lanes:(a + 1) * lanes]
                return part

            part = lax.fori_loop(0, n_live, body,
                                 jnp.zeros((bq, lanes), jnp.int32))
            return jnp.sum(part, axis=1, keepdims=True)

        thr = kth_key(count_ge, k, (bq, 1))

        def emit(j, carry):
            keys = keys_scr[:, at(j)]
            keep = (keys >= thr) & (keys != jnp.int32(_INT_MIN))
            mask_ref[:, at(j)] = jnp.where(keep, 1, 0).astype(mask_ref.dtype)
            return carry

        lax.fori_loop(0, n_live, emit, 0)

        def clear(j, carry):
            mask_ref[:, at(j)] = jnp.zeros((bq, bk), mask_ref.dtype)
            return carry

        lax.fori_loop(n_live, nk, clear, 0)


def select_prefill(qi, w, ki, last, k):
    """The selection of every query of a block of S positions that
    starts at position 0.

    qi (B, Hi, S, di) and ki (B, di, S) rotated, w (B, S, Hi) float32,
    ``last`` (B,) each row's last real position.  Returns int8
    ``(B, S, S)``: ``[b, t, s]`` is 1 where ``s`` is in ``S[t]``; rows
    ``t`` of a query block wholly past ``last[b]`` are zero."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, heads, S, di = qi.shape
    bq, bk = _blocks(S)
    if S % bq or S % bk:
        raise ValueError(f"select_prefill: {S} positions are no multiple "
                         f"of the blocks ({bq}, {bk})")
    interpret = _use_interpret()
    kw = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)}
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k, bq=bq, bk=bk, nk=S // bk,
                          heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, S // bq),
            in_specs=[
                pl.BlockSpec((None, heads, bq, di),
                             lambda b, i, last: (b, 0, i, 0)),
                pl.BlockSpec((None, bq, heads), lambda b, i, last: (b, i, 0)),
                pl.BlockSpec((None, di, S), lambda b, i, last: (b, 0, 0))],
            out_specs=pl.BlockSpec((None, bq, S),
                                   lambda b, i, last: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, S), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.int8),
        interpret=interpret, **kw,
    )(last.astype(jnp.int32), qi, w.astype(jnp.float32), ki)

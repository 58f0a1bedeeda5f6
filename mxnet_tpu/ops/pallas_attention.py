"""Flash attention as a Pallas TPU kernel — forward AND backward.

Reference parity target: the fused MHA kernels the reference gets from
contrib/transformer.cu + cuDNN; here the TPU version is a blockwise
online-softmax kernel (Flash-Attention-2) so neither the (Tq × Tk) score
matrix nor the whole K/V sequence is ever resident:

- grid (batch·heads, q blocks, kv blocks) of COARSE blocks (up to 1,024
  positions, `_block_sizes`): a grid step costs some 0.35 us whatever it
  holds, so at T <= 1,024 a head is one step; K and V stream through
  VMEM one block per step, so per-step VMEM is bounded by the block
  sizes and independent of sequence length (long-context safe), but
  for the backward's dq (below);
- inside a step the kernel walks sub-tiles (`_sub_tiles`) in
  ``fori_loop``s: for each query sub-tile the key sub-tiles up to the
  diagonal, unmasked where they lie wholly below it and masked only
  where it crosses them; none above it.  A grid step wholly above the
  diagonal (only where a head is several blocks) walks nothing;
- **one forward body, two ways its blocks arrive.**  `_fwd_tiles` (a
  key block's part of a query block's running softmax), `_fwd_open` and
  `_fwd_close` are the forward; `flash_attention` (training:
  `custom_vjp`, the logsumexp rows written for the backward, one width
  for keys and values) runs it under `_fwd_kernel`, whose blocks the
  grid brings, as since PR 33.  `flash_attention_forward` (a serving
  prefill: causal, no gradient, no logsumexp written) runs it under
  `_fwd_rows_kernel` (PR 35), which takes what Kimi-K2's expanded heads
  need.  **A value width of its own**: v is (B, H, T, Dv) and the
  output and its accumulator are Dv wide (keys 192, values 128);
  `_vmem_bytes` and `_block_sizes` count D for q and k and Dv for v, o
  and the accumulator, and at Dv == D give what they gave.  **A length
  a batch row** (``lengths`` (B,) int32, traced: scalar prefetch): a
  row's queries at and past its length come out zero.  **No grid step
  and no copy for work that does not exist**: the grid is (batch·heads,
  q blocks) alone, 1,024 steps a call at 64 heads and 16,384 positions
  where the grid over key blocks too has 16,384; a step walks its key
  blocks to the diagonal in a ``fori_loop``, each brought from HBM
  into one of two buffers by asynchronous copies while the block before
  is worked (as `ops/cache_attention.py` does), a step's last block
  starting the next step's first; a query block past the row's length
  stores zeros, copies nothing and keeps the resident query block.
  Measured on the v5e (PERF.md section 6, PR 35): a step that walks
  nothing costs 0.354 us under the grid over key blocks even with its
  index maps clamped so that it names the resident blocks and issues no
  copy (5.80 ms a call of 16,384 empty steps; 1.16 ms here, the zeros'
  268 MB included); at 8,912 live positions of 16,384 a call takes
  15.5 ms here against 22.7 ms there, at 15,872 42.6 against 50.2.
  **Why both drivers stay** (measured at one feed, PERF.md section 6,
  PR 35 after review): with nothing to skip and equal widths the rows
  driver is ahead at long heads ((1, 64, 16384, 128): 34.2 ms against
  39.8; at 256 wide 60.9 against 72.7), but at training's shapes the
  grid driver is: at (2, 8, 4096, 128) with the logsumexp written
  0.668-0.679 ms against 0.693-0.695, and at the training cell's
  (4, 16, 1024, 64) Mosaic refuses the rows driver's copies of 64-wide
  rows, and with q, k and v padded to 128 lanes it takes 0.322-0.326 ms
  against 0.274.
  **Four callers of the one rows driver, and what a caller that hands
  nothing gets.**  Kimi-K2's prefill (PR 35), Ouro's (PR 37), Command
  A+'s (PR 39: ``window=``, key heads fewer than query heads) and
  Keye-VL-2.0's (PR 43: ``keep=``, a **selection mask**).  The mask is
  int8 (B, T, T), ``[b, t, s] != 0`` where query t of row b attends to
  key s (`indexed_attention.select_prefill` writes it; it holds
  causality); it stays in HBM and the (block_q, block_k) window of it
  that belongs to a key block travels with that block into one of two
  buffers, by the same copies on the same schedule; `_fwd_tiles` masks
  every sub-tile's scores with it in place of the diagonal's iota
  comparison, and the walk stays dense to the diagonal (Keye's top-2,048
  of 4k-16k positions leave no 512 x 512 sub-tile empty).  Under a mask
  a grid step is a query block of a key head's ``G`` query heads, not of
  one: one copy of keys, values and mask, one comparison of the mask's
  sub-tile, ``G`` heads' products (measured at Keye's (1, 32 over 4,
  16384, 128), ms a call at 4,096 / 8,519 / 16,128 positions: a head a
  step 2.30 / 5.98 / 17.5, eight with the heads in a loop 2.04 / 5.41 /
  16.1, eight unrolled 1.88 / 4.76 / 13.97, the kernel of PR 30 with
  its grid over key blocks 5.71 / 8.92 / 18.87, by PR 41's builder; read
  again in PR 43: 1.86-1.90 / 4.76 / 13.91-14.01 against 5.70-5.73 /
  8.90-8.96 / 18.87-18.92, the results equal bit for bit; PERF.md
  section 6).  ``window``, a group and ``keep`` are static: with
  ``window=None``, ``keep=None`` the call builds the kernel it built
  before either existed, operand for operand and equation for equation,
  grouped or not (tests/test_pallas_attention.py counts them), and
  grouping the heads of the callers without a mask is theirs to measure
  on their own cells.
  The copies move whole lane tiles (Mosaic refuses a 192-wide or a
  64-wide row): keys or values whose width is no multiple of 128 are
  padded with zeros in front of the call unless they arrive so
  (Kimi-K2's model makes its keys 256 wide; HBM pads the rows of a
  192-wide array to 256 anyway, and a 192-deep product takes the MXU's
  two passes as a 256-deep one does), and so is a block of fewer
  positions than a tile;
- every product is fed the operands as they arrive (bfloat16 inputs go
  into the MXU as bfloat16, float32 as float32) and accumulates in
  float32; scores, the softmax, its running maximum and sum, the
  logsumexp, delta and all accumulators are float32;
- forward: m/l are kept lane-replicated (block_q, 128) in VMEM scratch
  with the output accumulator, carried across the kv grid dimension
  ("arbitrary" semantics); outputs store on the last kv step.  The
  logsumexp persisted to HBM for the backward is stored TRANSPOSED,
  (B·H, 8, T): the sequence on the lane axis, 8 sublane copies.  HBM
  tiles are (8, 128), so this costs 8·T floats per head, where a
  (T, 8) layout is padded to (T, 128) — 16× (measured on the v5e:
  1.05 GB of padding for BERT-base at b32/T512, which alone pushed
  that step past 16 GB);
- backward is the FlashAttention-2 recipe in ONE call: per sub-tile one
  s, p = exp(s − L), dp, ds and from them dv, dk and dq — five
  products and one set of exponentials.  D_i = rowsum(dO ∘ O) is formed
  once by XLA, in float32, in the logsumexp's layout.  The scores are
  held transposed (keys on sublanes, queries on lanes), so the stored
  logsumexp and delta rows broadcast as they are.  Key blocks are the
  outer grid axis: dk and dv of a block accumulate in scratch over the
  query blocks; dq of the WHOLE head accumulates in float32 VMEM
  scratch and is stored on the head's last step (T·D·(4 + 2·itemsize)
  bytes: past the default scoped VMEM the call asks for its own limit).

On the CPU (tests, the virtual mesh) the kernels run in interpret mode,
keeping one code path.  On TPU they compile through Mosaic, which needs
128-aligned tiles: `flash_attention` raises at a sequence length not
divisible by 128, and nothing is substituted for the kernel the caller
named; `flash_attention_forward` pads such a block with positions past
every row's length and cuts its result back, on the CPU as on the TPU
(a serving engine's small buckets: 8-64 positions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG = -1e30
_LANE = 128


def _use_interpret():
    """True on the CPU (Pallas interpreter), False on TPU (Mosaic).  Any
    other backend is an error, not a silent interpreter run."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            "flash attention has a TPU kernel and a CPU interpreter "
            f"mode; jax default backend is {backend!r}")
    return backend == "cpu"


# The grid's blocks and the sub-tiles a grid step walks (PR 33; measured
# on a v5e, jax 0.9.0 / libtpu 0.0.34; PERF.md section 6 has the table).
_MAX_BLOCK = 1024
# what a grid step's blocks may take of the 16 MiB of scoped VMEM that
# Mosaic grants a kernel by default, and the need past which a call
# asks for its own limit (a long head's dq, which the backward keeps
# whole, is what goes past it: 128 MiB are there)
_VMEM_BUDGET = 12 << 20
_VMEM_DEFAULT = 14 << 20
_SUB_TILE = 512


def _aligned_divisors(n, most):
    """The lane-aligned divisors of ``n`` up to ``most``, largest first."""
    return [d for d in range(min(n, most) // _LANE * _LANE, 0, -_LANE)
            if n % d == 0]


def _sub_tiles(block_q, block_k):
    """The (queries, keys) sub-tile a grid step's loops work on, forward
    and backward: the largest lane-aligned size up to 512 that divides
    the block (512 x 512 measured best for both kernels), or the block
    itself where it has none (interpret mode)."""
    return tuple((_aligned_divisors(b, _SUB_TILE) or [b])[0]
                 for b in (block_q, block_k))


def _vmem_bytes(T, D, dtype, kernel, block_q, block_k, Dv=None, keep=False,
                G=1):
    """(what a grid step's blocks take of VMEM, what stays for a whole
    head): every operand block twice (the pipeline's double buffer),
    the blocks' float32 accumulators and a sub-tile's float32
    temporaries (three of them, fitted to what Mosaic accepted at
    `T` 4,096-16,384, `D` 64-256, both types); for the backward the
    head's dq besides, its output block twice and its accumulator.
    Queries and keys are ``D`` wide; values, the output and its
    accumulator ``Dv`` (``D`` where None; the backward has one width).
    ``keep``: the forward under a selection mask holds two int8
    (block_q, block_k) windows of it besides, and ``G`` query blocks,
    output blocks and sets of sums, one a head of the step; its heads
    are unrolled, and Mosaic then holds nine sub-tile temporaries
    (19.69 MB by its count at G 8, blocks of 512, D 128)."""
    item = jnp.dtype(dtype).itemsize
    sq, sk = _sub_tiles(block_q, block_k)
    qb, kb = block_q * D * item, block_k * D * item
    rows = _LSE_ROWS * block_q * 4
    tile = (3 if G == 1 else 9) * sq * sk * 4
    if kernel == "fwd":
        Dv = D if Dv is None else Dv
        ob, vb = block_q * Dv * item, block_k * Dv * item
        blocks = G * (qb + ob) + kb + vb + rows     # q, o; k, v; lse
        scratch = G * (2 * block_q * _LANE * 4 + block_q * Dv * 4)
        if keep:
            scratch += 2 * block_q * block_k
        return 2 * blocks + scratch + tile, 0
    blocks = 2 * qb + 2 * rows + 4 * kb     # q, g; lse, delta; k, v, dk, dv
    scratch = 2 * block_k * D * 4
    return 2 * blocks + scratch + tile, 2 * T * D * item + T * D * 4


def _block_sizes(T, D, dtype, kernel, Dv=None, keep=False, G=1):
    """``(block_q, block_k)`` of the grid for ``kernel`` ("fwd" or
    "bwd"), from what the kernel sees (``Dv``: the values' width where
    it is not the keys').

    The rule: the largest lane-aligned block up to 1,024 that divides
    ``T`` and whose grid step fits `_VMEM_BUDGET`, the same for queries
    and keys.  A grid step costs some 0.35 us whatever it holds and its
    copies hide behind the step before, so the grid is as coarse as
    VMEM allows; inside a step the kernels walk `_sub_tiles` up to the
    diagonal, so a coarse block spends no work above it.  A step of
    several heads (``G`` > 1: under a mask ``keep``) may take twice the
    budget and asks for its own limit: inside one budget eight heads of
    128 get blocks of 256 (16.9 ms a call at 16,128 positions against
    13.97 at 512: PR 41's builder, PERF.md section 6)."""
    if T % _LANE:
        # interpret-mode small/odd shapes; flash_attention refuses them
        # on TPU
        return T, T
    fits = [n for n in _aligned_divisors(T, _MAX_BLOCK)
            if _vmem_bytes(T, D, dtype, kernel, n, n, Dv, keep, G)[0]
            <= _VMEM_BUDGET * (1 if G == 1 else 2)]
    return (fits or [_LANE])[0], (fits or [_LANE])[0]


# sublane copies of the logsumexp row persisted to HBM between fwd and
# bwd: (B·H, 8, T), one f32 tile row — the minimum that is not padded
_LSE_ROWS = 8


def _lse_to_rows(lse):
    """kernel working layout (bq, 128), lane-replicated → the stored
    (8, bq) block with the sequence on lanes."""
    return lse.T[:_LSE_ROWS]


def _bcast_lanes(x, n):
    """lane-replicated (bq, k) -> (bq, n); every lane of x is identical."""
    k = x.shape[1]
    if n == k:
        return x
    if n < k:
        return x[:, :n]
    if n % k == 0:
        return jnp.tile(x, (1, n // k))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    """An MXU product of the operands as they are given (bfloat16 inputs
    go in as bfloat16, float32 as float32), accumulated in float32."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _walk(causal, q0, sub_q, k0, sub_k, n, tile, carry, window=None,
          always=False):
    """Run ``tile(c, carry, masked)`` over those of a block's ``n`` key
    sub-tiles (``sub_k`` positions each, from ``k0``) that the ``sub_q``
    queries from ``q0`` can see: unmasked while a sub-tile lies wholly
    at or below the first query, masked while it holds any key at or
    below the last one, and none above the diagonal.  With ``window``
    (an int; causal) a query sees its own position and the ``window -
    1`` before it: none of the sub-tiles that lie wholly behind the
    first query's band, masked those that hold a key behind the last
    query's.  ``always``: one loop over every sub-tile up to the
    diagonal, all masked (the caller's mask is not the diagonal's: a
    selection)."""
    loop = jax.lax.fori_loop
    if not causal:
        return loop(0, n, lambda c, x: tile(c, x, False), carry)
    div = jax.lax.div
    whole = jnp.clip(div(jnp.maximum(q0 + 1 - k0, 0), sub_k), 0, n)
    some = jnp.clip(div(jnp.maximum(q0 + sub_q - k0 + sub_k - 1, 0),
                        sub_k), 0, n)
    if always:
        return loop(0, some, lambda c, x: tile(c, x, True), carry)
    if window is None:
        carry = loop(0, whole, lambda c, x: tile(c, x, False), carry)
        return loop(whole, some, lambda c, x: tile(c, x, True), carry)
    # the first sub-tile with a key at or past the first query's band,
    # and the first whose keys all lie inside the last query's
    begin = jnp.clip(div(jnp.maximum(q0 - (window - 1) - k0, 0), sub_k),
                     0, some)
    inside = jnp.clip(div(jnp.maximum(q0 + sub_q - window - k0 + sub_k - 1,
                                      0), sub_k), begin, some)
    whole = jnp.clip(whole, inside, some)
    carry = loop(begin, inside, lambda c, x: tile(c, x, True), carry)
    carry = loop(inside, whole, lambda c, x: tile(c, x, False), carry)
    return loop(whole, some, lambda c, x: tile(c, x, True), carry)


# -- forward -------------------------------------------------------------------

def _fwd_tiles(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, tiles, q_first,
               k_first, at, *, scale, causal, sub_q, sub_k, window=None,
               keep_ref=None):
    """One key block's part of a query block's running softmax: q_ref
    (1, block_q, D) from position ``q_first``, its first ``tiles``
    sub-tiles; the key block ``at`` of k_ref (., block_k, D) and v_ref
    (., block_k, Dv), resident, from position ``k_first``; maximum and
    sum (block_q, 128) and the accumulator (block_q, Dv) in scratch.
    ``keep_ref`` (., block_q, block_k) int8 or None: window ``at`` of a
    selection mask, the query block's rows by the key block's keys; a
    query then sees the keys its row marks and no other (the mask holds
    causality), in every sub-tile the walk visits.  Several heads a
    step (the rows kernel under a mask): q_ref (1, G, block_q, D), the
    scratch G times as tall, head after head; a sub-tile of keys,
    values and mask is then worked for the G heads in turn."""
    from jax.experimental import pallas as pl

    Dv = acc_scr.shape[1]
    G = 1 if len(q_ref.shape) == 3 else q_ref.shape[1]
    block_q = m_scr.shape[0] // G

    def q_tile(a, carry):
        rows = pl.ds(pl.multiple_of(a * sub_q, sub_q), sub_q)
        q0 = q_first + a * sub_q
        # one head's queries stay loaded over its key sub-tiles
        q = q_ref[0, rows, :] if G == 1 else None

        def tile(c, carry, masked):
            keys = pl.ds(pl.multiple_of(c * sub_k, sub_k), sub_k)
            # the mask's sub-tile is read and compared once for the
            # heads that share it
            kept = None if keep_ref is None else \
                keep_ref[at, rows, keys] != 0
            for g in range(G):
                mine = rows if g == 0 else pl.ds(pl.multiple_of(
                    g * block_q + a * sub_q, sub_q), sub_q)
                s = _dot(q if G == 1 else q_ref[0, g, rows, :],
                         k_ref[at, keys, :], _NT) * scale  # (sub_q, sub_k)
                if kept is not None:
                    # a row need mark no key of the first sub-tiles it
                    # visits, nor its own position: like a row whose
                    # band begins later (below), it is wiped by the
                    # first key it does see, and every live row marks
                    # one
                    s = jnp.where(kept, s, _NEG)
                elif masked:
                    # query q0 + i sees key k0 + j iff i - j >= k0 - q0;
                    # a row of a visited sub-tile always holds a visible
                    # key or has seen key 0 before, so exp(_NEG - m) is
                    # 0.0
                    i_j = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                           - jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                      1))
                    seen = i_j >= k_first + c * sub_k - q0
                    if window is not None:
                        # and no further behind than the band.  A row
                        # whose band begins past this sub-tile sees
                        # nothing yet: its sums take exp(0) of every
                        # key here and are wiped (exp(_NEG - m) is 0.0)
                        # by the first key it does see, at the latest
                        # its own
                        seen = seen & (i_j < k_first + c * sub_k - q0
                                       + window)
                    s = jnp.where(seen, s, _NEG)
                m_prev = m_scr[mine, :]
                m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
                p = jnp.exp(s - _bcast_lanes(m_next, sub_k))
                alpha = jnp.exp(m_prev - m_next)
                l_scr[mine, :] = (alpha * l_scr[mine, :]
                                  + jnp.sum(p, axis=1)[:, None])
                m_scr[mine, :] = m_next
                v = v_ref[at, keys, :]
                acc_scr[mine, :] = (acc_scr[mine, :]
                                    * _bcast_lanes(alpha, Dv)
                                    + _dot(p.astype(v.dtype), v, _NN))
            return carry

        return _walk(causal, q0, sub_q, k_first, sub_k,
                     k_ref.shape[1] // sub_k, tile, carry, window,
                     always=keep_ref is not None)

    jax.lax.fori_loop(0, tiles, q_tile, 0)


def _fwd_open(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)


def _fwd_close(o_ref, lse_ref, m_scr, l_scr, acc_scr, q_first, n):
    """The query block's output (and logsumexp rows, where asked for)
    from its finished sums; rows at and past the length ``n`` (None:
    all live) zero."""
    l = l_scr[...]
    lsafe = jnp.where(l == 0.0, 1.0, l)
    o = acc_scr[...] / _bcast_lanes(lsafe, acc_scr.shape[1])
    if n is not None:
        at = q_first + jax.lax.broadcasted_iota(jnp.int32, o.shape, 0)
        o = jnp.where(at < n, o, 0.0)
    o_ref[0] = o.astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0] = _lse_to_rows(m_scr[...] + jnp.log(lsafe))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, sub_q, sub_k, nk):
    """The forward with its blocks brought by the grid (training's): a
    step is query block ``qi`` of a head over key block ``kj``; the
    sums are opened on a head's first key block and closed on its last,
    where the logsumexp rows are written for the backward."""
    from jax.experimental import pallas as pl

    scr = (m_scr, l_scr, acc_scr)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    pl.when(kj == 0)(lambda: _fwd_open(*scr))
    _fwd_tiles(q_ref, k_ref, v_ref, *scr, block_q // sub_q, qi * block_q,
               kj * block_k, 0, scale=scale, causal=causal, sub_q=sub_q,
               sub_k=sub_k)
    pl.when(kj == nk - 1)(lambda: _fwd_close(o_ref, lse_ref, *scr,
                                             qi * block_q, None))


def _fwd_rows_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
                     m_scr, l_scr, acc_scr, slot_ref, *, scale, block_q,
                     block_k, sub_q, sub_k, heads, window=None, group=1,
                     keep_hbm=None, keep_buf=None):
    """The causal forward with its key blocks brought by the kernel (a
    serving prefill's): a step is query block ``qi`` of a head over ALL
    the key blocks it can see, to its diagonal, and none where the block
    lies past the row's length (``len_ref``, scalar prefetch; ``heads``
    grid rows a batch row): it stores zeros.  k and v stay in HBM; a
    key block's (block_k, D) and (block_k, Dv) come into one of two
    buffers by asynchronous copies while the block before is worked,
    and every step leaves the next walking step's first block in
    flight, so no copy waits for a grid step.  Scratch besides the
    buffers: the copies' semaphores (k or v, buffer), the running sums,
    and which buffer the step's first block is in.

    ``window`` (an int or None): a query sees its own position and the
    ``window - 1`` before it, so a step's walk begins at the first key
    block that holds a key of its first query's band (``first_of``)
    and no block wholly behind the band is copied or multiplied.
    ``group``: query heads a key head; grid row ``h`` reads key head
    ``h // group`` of k_hbm and v_hbm, which then hold ``1 / group`` as
    many heads as q (grouped-query attention, nothing repeated in
    HBM).  ``keep_hbm`` (batch rows, T, T) int8 in HBM, or None: a
    selection mask, ``[r, t, s] != 0`` where query ``t`` of batch row
    ``r`` sees key ``s``.  Its (block_q, block_k) window travels with
    each key block, into ``keep_buf`` (2, block_q, block_k) under a
    third pair of semaphores, on the same schedule.  Under a mask a
    grid row is a key head and the step works all its ``G`` query
    heads over one copy of keys, values and mask: q_ref (1, G,
    block_q, D), o_ref (1, G, block_q, Dv), ``heads`` the key heads a
    batch row, ``group`` 1 (PERF.md section 6, PR 43: 13.9-14.0 ms a
    call at 16,128 positions against 17.5 with a head a step)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scr = (m_scr, l_scr, acc_scr)
    b, qi = pl.program_id(0), pl.program_id(1)
    nh, nq = pl.num_programs(0), pl.num_programs(1)
    n = len_ref[b // heads]
    # the block's query sub-tiles that hold a live position
    tiles = jnp.clip(pl.cdiv(n - qi * block_q, sub_q), 0, block_q // sub_q)

    def first_of(i):
        """The first key block that query block ``i`` walks."""
        return jnp.maximum(i * block_q - (window - 1), 0) // block_k

    def blocks_of(head, i):
        """Key blocks that query block ``i`` of ``head`` walks."""
        live = i * block_q < len_ref[head // heads]
        to_diagonal = (i * block_q + block_q - 1) // block_k + 1
        if window is not None:
            to_diagonal = to_diagonal - first_of(i)
        return jnp.where(live, to_diagonal, 0)

    def copies(head, i, j, slot):
        """Key block ``j`` of ``head`` (and its window of the mask for
        query block ``i``) into buffer ``slot``."""
        at = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        masks = ()
        if keep_hbm is not None:
            rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            masks = (pltpu.make_async_copy(
                keep_hbm.at[head // heads, rows, at], keep_buf.at[slot],
                sem.at[2, slot]),)
        if group != 1:
            head = head // group
        return (pltpu.make_async_copy(k_hbm.at[head, at, :], k_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[head, at, :], v_buf.at[slot],
                                      sem.at[1, slot])) + masks

    blocks = blocks_of(b, qi)
    # the walk's first block: 0 with no window, as a Python int
    first = 0 if window is None else first_of(qi)

    @pl.when((b == 0) & (qi == 0))
    def _first():
        slot_ref[0] = 0

        @pl.when(blocks > 0)
        def _():
            for c in copies(b, qi, 0, 0):
                c.start()

    base = slot_ref[0]
    wrap = qi + 1 == nq
    nb, ni = jnp.where(wrap, b + 1, b), jnp.where(wrap, 0, qi + 1)

    def hand_on(slot):
        """The next step's first block into ``slot``, if it walks any."""
        @pl.when((nb < nh) & (blocks_of(jnp.minimum(nb, nh - 1), ni) > 0))
        def _():
            for c in copies(nb, ni, 0 if window is None else first_of(ni),
                            slot):
                c.start()

    _fwd_open(*scr)

    def block(j, carry):
        slot = (base + j) % 2
        at = j if window is None else first + j

        # the next block sets out before this one is waited for: the
        # step's own, or behind its last the next step's first
        @pl.when(j + 1 < blocks)
        def _ahead():
            for c in copies(b, qi, at + 1, 1 - slot):
                c.start()

        pl.when(j + 1 == blocks)(lambda: hand_on(1 - slot))
        for c in copies(b, qi, at, slot):
            c.wait()
        _fwd_tiles(q_ref, k_buf, v_buf, *scr, tiles, qi * block_q,
                   at * block_k, slot, scale=scale, causal=True,
                   sub_q=sub_q, sub_k=sub_k, window=window,
                   keep_ref=keep_buf)
        return carry

    jax.lax.fori_loop(0, blocks, block, 0)
    pl.when(blocks == 0)(lambda: hand_on(base))
    slot_ref[0] = (base + blocks) % 2
    if len(q_ref.shape) == 3:
        _fwd_close(o_ref, None, *scr, qi * block_q, n)
    else:
        # several heads a step: o_ref (1, G, block_q, Dv), the sums head
        # after head
        for g in range(q_ref.shape[1]):
            rows = pl.ds(g * block_q, block_q)
            _fwd_close(o_ref.at[0, pl.ds(g, 1)], None,
                       *(r.at[rows] for r in scr), qi * block_q, n)


def _fwd_rows_kept_kernel(len_ref, q_ref, k_hbm, v_hbm, keep_hbm, o_ref,
                          keep_buf, *scratch, **static):
    """`_fwd_rows_kernel` under a selection mask: the mask the fourth
    operand, its two windows the first scratch."""
    _fwd_rows_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, *scratch,
                     keep_hbm=keep_hbm, keep_buf=keep_buf, **static)


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct that carries the varying-mesh-axes set when the
    kernel runs inside a check_vma=True shard_map (ring attention)."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _blocks(T, D, dtype, kernel, block_q, block_k, Dv=None, keep=False,
            G=1):
    """The grid's blocks for ``kernel``: the caller's where it names
    them, `_block_sizes`' otherwise; they must divide ``T``."""
    dbq, dbk = _block_sizes(T, D, dtype, kernel, Dv, keep, G)
    bq, bk = int(block_q or dbq), int(block_k or dbk)
    if T % bq or T % bk:
        raise ValueError(
            f"flash_attention: block sizes ({bq}, {bk}) must divide "
            f"sequence length {T} (a non-dividing block would silently "
            f"leave tail blocks unwritten)")
    return bq, bk


def _compiler_params(T, D, dtype, kernel, block_q, block_k, semantics,
                     Dv=None, keep=False, G=1):
    from jax.experimental.pallas import tpu as pltpu

    need = sum(_vmem_bytes(T, D, dtype, kernel, block_q, block_k, Dv, keep,
                           G))
    limit = {} if need <= _VMEM_DEFAULT else {
        "vmem_limit_bytes": need + need // 4}
    return pltpu.CompilerParams(dimension_semantics=semantics, **limit)


def _flash_call(q, k, v, causal, scale, block_q=None, block_k=None,
                vma=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    block_q, block_k = _blocks(T, D, q.dtype, "fwd", block_q, block_k)
    sub_q, sub_k = _sub_tiles(block_q, block_k)
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, T, D)
    vr = v.reshape(B * H, T, D)
    nq, nk = T // block_q, T // block_k
    interpret = _use_interpret()
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, sub_q=sub_q, sub_k=sub_k, nk=nk)
    kw = {} if interpret else {
        "compiler_params": _compiler_params(
            T, D, q.dtype, "fwd", block_q, block_k,
            ("parallel", "parallel", "arbitrary"))}

    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, _LSE_ROWS, block_q),
                         lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            _sds((B * H, T, D), q.dtype, vma),
            _sds((B * H, _LSE_ROWS, T), jnp.float32, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        **kw,
    )(qr, kr, vr)
    return out.reshape(B, H, T, D), lse


def _flash_rows_call(q, k, v, scale, lengths, block_q=None, block_k=None,
                     window=None, keep=None):
    """`_fwd_rows_kernel` over q (B, H, T, D), k (B, Hk, T, D), v (B,
    Hk, T, Dv) (``Hk`` divides ``H``: ``H / Hk`` query heads read a key
    head) and ``lengths`` (B,) int32 within [0, T]: grid (B·H, query
    blocks), both axes in order (a step hands the next its first
    block).  ``window``: static, None for all earlier positions.
    ``keep``: (B, T, T) int8 or None, the selection mask; with it the
    grid is (B·Hk, query blocks), a key head's query heads one step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    Hk, Dv = k.shape[1], v.shape[-1]
    kept = keep is not None
    # under a mask a step works the G query heads of a key head over one
    # copy of its keys, values and mask
    G = H // Hk if kept else 1
    block_q, block_k = _blocks(T, D, q.dtype, "fwd", block_q, block_k, Dv,
                               kept, G)
    sub_q, sub_k = _sub_tiles(block_q, block_k)
    interpret = _use_interpret()
    kw = {} if interpret else {
        "compiler_params": _compiler_params(
            T, D, q.dtype, "fwd", block_q, block_k,
            ("arbitrary", "arbitrary"), Dv, kept, G)}
    # with no window, no mask and a key head a query head, the kernel
    # the parent built: none is an operand, and none adds an equation
    more = {} if window is None else {"window": int(window)}
    if Hk != H and G == 1:
        more["group"] = H // Hk
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # grid rows a batch row, and a step's heads: an axis of q and of the
    # output where they are several
    R = H // G
    several = (G,) if G > 1 else ()

    def q_at(b, i, lens):
        # no further than the row's last live block: a step that stores
        # zeros keeps the query block that is resident
        return (b,) + (0,) * len(several) + (jnp.minimum(i, jnp.maximum(
            pl.cdiv(lens[b // R], block_q), 1) - 1), 0)

    operands = [lengths, q.reshape((B * R,) + several + (T, D)),
                k.reshape(B * Hk, T, D), v.reshape(B * Hk, T, Dv)]
    in_specs = [pl.BlockSpec((1,) + several + (block_q, D), q_at), hbm, hbm]
    scratch = [pltpu.VMEM((2, block_k, D), k.dtype),
               pltpu.VMEM((2, block_k, Dv), v.dtype),
               pltpu.SemaphoreType.DMA((3 if kept else 2, 2)),
               pltpu.VMEM((G * block_q, _LANE), jnp.float32),
               pltpu.VMEM((G * block_q, _LANE), jnp.float32),
               pltpu.VMEM((G * block_q, Dv), jnp.float32),
               pltpu.SMEM((1,), jnp.int32)]
    kernel = _fwd_rows_kernel
    if kept:
        # the mask the last operand, its two windows the first scratch
        kernel = _fwd_rows_kept_kernel
        operands.append(keep)
        in_specs.append(hbm)
        scratch.insert(0, pltpu.VMEM((2, block_q, block_k), jnp.int8))
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, block_q=block_q,
                          block_k=block_k, sub_q=sub_q, sub_k=sub_k,
                          heads=R, **more),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * R, T // block_q),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1,) + several + (block_q, Dv),
                lambda b, i, lens: (b,) + (0,) * len(several) + (i, 0)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B * R,) + several + (T, Dv),
                                       q.dtype),
        interpret=interpret,
        **kw,
    )(*operands)
    return out.reshape(B, H, T, Dv)


# -- backward (FlashAttention-2, one call) -------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *, scale, causal,
                block_q, block_k, sub_q, sub_k, nq, nk):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when((ki == 0) & (qj == 0))
    def _init_dq():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(qj == 0)
    def _init_dkv():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    # scores are held transposed, keys on sublanes and queries on lanes:
    # the stored logsumexp and delta rows broadcast down the sublanes as
    # they are, and of the five products only dq's takes a transposed
    # operand.  The query sub-tiles are unrolled (their slices of the
    # rows are along lanes, which a dynamic index cannot cut).
    for a in range(block_q // sub_q):
        rows = slice(a * sub_q, (a + 1) * sub_q)
        q = q_ref[0, rows, :]
        g = g_ref[0, rows, :]
        lse = lse_ref[0, 0:1, rows]                       # (1, sub_q)
        delta = delta_ref[0, 0:1, rows]
        q0 = qj * block_q + a * sub_q

        def tile(c, dq, masked):
            keys = pl.ds(pl.multiple_of(c * sub_k, sub_k), sub_k)
            k = k_ref[0, keys, :]
            st = _dot(k, q, _NT) * scale                  # (sub_k, sub_q)
            if masked:
                i_j = (jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
                       - jax.lax.broadcasted_iota(jnp.int32, st.shape, 0))
                st = jnp.where(
                    i_j >= ki * block_k + c * sub_k - q0, st, _NEG)
            pt = jnp.exp(st - lse)
            # ds without its factor ``scale``, which dq and dk take once
            # in float32 as they are stored
            dst = (pt * (_dot(v_ref[0, keys, :], g, _NT) - delta)).astype(
                q.dtype)
            dv_scr[keys, :] += _dot(pt.astype(g.dtype), g, _NN)
            dk_scr[keys, :] += _dot(dst, q, _NN)
            return dq + _dot(dst, k, _TN)                 # (sub_q, D)

        dq = _walk(causal, q0, sub_q, ki * block_k, sub_k,
                   block_k // sub_k, tile,
                   jnp.zeros((sub_q, dq_scr.shape[1]), jnp.float32))
        dq_scr[pl.ds(pl.multiple_of(q0, sub_q), sub_q), :] += dq

    @pl.when(qj == nq - 1)
    def _store_dkv():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((ki == nk - 1) & (qj == nq - 1))
    def _store_dq():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_call(q, k, v, out, lse, g, causal, scale, block_q=None,
                    block_k=None, vma=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    block_q, block_k = _blocks(T, D, q.dtype, "bwd", block_q, block_k)
    sub_q, sub_k = _sub_tiles(block_q, block_k)
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, T, D)
    vr = v.reshape(B * H, T, D)
    gr = g.reshape(B * H, T, D)
    # D_i = rowsum(dO * O), once, in float32, laid out as the logsumexp
    # is: the sequence on lanes
    delta = jnp.sum(gr.astype(jnp.float32)
                    * out.reshape(B * H, T, D).astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], lse.shape)
    nq, nk = T // block_q, T // block_k
    interpret = _use_interpret()
    kw = {} if interpret else {
        "compiler_params": _compiler_params(
            T, D, q.dtype, "bwd", block_q, block_k,
            ("parallel", "arbitrary", "arbitrary"))}

    qspec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, j, 0))
    rspec = pl.BlockSpec((1, _LSE_ROWS, block_q),
                         lambda b, i, j: (b, 0, j))
    kspec = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0))
    # key blocks outer, query blocks inner: dk and dv of a key block
    # accumulate in scratch over the inner axis; dq of the whole head
    # stays in VMEM (float32 scratch) until the head's last step
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sub_q=sub_q,
                          sub_k=sub_k, nq=nq, nk=nk),
        grid=(B * H, nk, nq),
        in_specs=[qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=[pl.BlockSpec((1, T, D), lambda b, i, j: (b, 0, 0)),
                   kspec, kspec],
        out_shape=[
            _sds((B * H, T, D), q.dtype, vma),
            _sds((B * H, T, D), k.dtype, vma),
            _sds((B * H, T, D), v.dtype, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((T, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        **kw,
    )(qr, kr, vr, gr, lse, delta)

    return (dq.reshape(B, H, T, D), dk.reshape(B, H, T, D),
            dv.reshape(B, H, T, D))


# -- custom vjp ----------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, scale, block_q, block_k, vma=()):
    out, _ = _flash_call(q, k, v, causal, scale, block_q, block_k,
                         vma=vma)
    return out


def _dense_ref(q, k, v, causal, scale):
    """Dense oracle for tests (and the doc of what the kernel
    computes)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        T = s.shape[-1]
        mask = jnp.tril(jnp.ones((s.shape[-2], T), bool), k=T - s.shape[-2])
        s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, vma=()):
    out, lse = _flash_call(q, k, v, causal, scale, block_q, block_k,
                           vma=vma)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, vma, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_call(q, k, v, out, lse, g, causal, scale, block_q,
                           block_k, vma=vma)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, vma=()):
    """Blockwise fused attention; q,k,v: (B, H, T, D).

    ``block_q``/``block_k`` override the tile sizes (tests use small
    blocks to exercise multi-block streaming at modest T).  ``vma``:
    varying-mesh-axes set when calling from inside a check_vma=True
    shard_map region (ring/ulysses)."""
    T = q.shape[2]
    if v.shape[-1] != q.shape[-1]:
        raise ValueError(
            "flash_attention: the backward kernel has one width for keys "
            f"and values, got {q.shape[-1]} and {v.shape[-1]} "
            "(flash_attention_forward takes a value width of its own)")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if T % _LANE != 0 and not _use_interpret():
        raise ValueError(
            f"flash_attention: sequence length {T} is not "
            f"{_LANE}-aligned, which the TPU kernel's tiles need; pad "
            "the sequence or use impl='dense'")
    _blocks(T, q.shape[-1], q.dtype, "fwd", block_q, block_k)  # they divide
    return _flash_core(q, k, v, bool(causal), float(scale), block_q,
                       block_k, tuple(vma))


def lane_tiles(n):
    """``n`` up to a whole number of lane tiles (128): the width at
    which `flash_attention_forward` takes queries and keys as they are,
    and the length it works a shorter block at."""
    return n + -n % _LANE


def flash_attention_forward(q, k, v, lengths=None, *, scale, block_q=None,
                            block_k=None, window=None, keep=None):
    """Causal attention, the forward alone, for a caller that takes no
    gradient (a serving prefill): no ``custom_vjp``, no logsumexp
    written, and no derivative (JAX has none for the call).  q (B, H,
    T, D); k (B, Hk, T, D); v (B, Hk, T, Dv), ``Dv`` need not be ``D``;
    returns (B, H, T, Dv) in q's type.  ``Hk`` is ``H`` or a divisor of
    it: query head h then reads key head ``h // (H / Hk)`` where it
    lies, and nothing is repeated in HBM (Command A+: 128 over 8).
    ``scale`` is the caller's to give: the entry cannot tell a head's
    width from zeros it arrives padded with.

    ``window`` (a static int, or None for every earlier position):
    query i sees keys ``i - window + 1 .. i``.  The walk of a query
    block over its key blocks starts at the first block that holds a
    visible key, so no grid step, copy or product is spent behind the
    band either; ``window`` need not be a multiple of a block.  With
    None the call builds the kernel it built before the operand
    existed (tests/test_pallas_attention.py holds its grid and
    operands).

    ``keep`` (B, T, T) int8, or None: a selection mask, ``[b, t, s] !=
    0`` where query ``t`` of row ``b`` attends to key ``s``, and to no
    other (Keye-VL-2.0's learned selection, as
    `indexed_attention.select_prefill` writes it).  The mask holds
    causality: it marks no key past its query, and for every query
    below the row's length at least one key.  It stays in HBM; a step
    brings the window of it that belongs to each key block it walks
    beside that block, and the walk stays dense to the diagonal: grid,
    steps and products are those of the call without a mask.  With
    None the call builds the kernel it built before the operand
    existed, as with ``window``.

    ``lengths`` (B,) int32, a traced operand (None: all ``T``): row b
    holds ``lengths[b]`` real positions from 0.  Its queries at and past
    the length come out zero, and no grid step, copy or product is
    spent on them or above the diagonal (`_fwd_rows_kernel`).

    The kernel's tiles and copies are whole lane tiles, here and on the
    TPU alike (`lane_tiles`).  **Any ``T``**: a block whose length is no
    multiple of 128 (a serving bucket of 8-64 positions) is padded with
    positions past every row's length, which cost a step that stores
    zeros at most, and the result is cut back to ``T``; ``block_q`` and
    ``block_k`` then divide the padded length.  A width ``D`` or ``Dv``
    that is no multiple of 128 is padded with zeros (Mosaic refuses the
    copy of a narrower row), one more pass over the operand: a caller
    that can make q and k that wide to begin with spares it (HBM pads
    their rows to whole tiles anyway), and the scores are the same."""
    B, T, Dv = q.shape[0], q.shape[2], v.shape[-1]
    if q.shape[1] % k.shape[1] or k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"flash_attention_forward: {k.shape[1]} key heads do not "
            f"divide {q.shape[1]} query heads (q {q.shape}, k {k.shape}, "
            f"v {v.shape})")
    if window is not None and int(window) < 1:
        raise ValueError("flash_attention_forward: a window holds the "
                         f"query's own position at least, got {window}")
    if keep is not None and (keep.shape != (B, T, T)
                             or keep.dtype != jnp.int8):
        raise ValueError(
            f"flash_attention_forward: keep is int8 {(B, T, T)}, a row of "
            f"keys for every query, got {keep.dtype} {keep.shape}")
    if keep is not None and window is not None:
        raise ValueError("flash_attention_forward: a mask holds its own "
                         "band; keep and window are not combined")
    lengths = (jnp.full((B,), T, jnp.int32) if lengths is None
               else jnp.clip(lengths.astype(jnp.int32), 0, T))

    def whole(x, first=2):
        """x's positions and width up to whole lane tiles."""
        more = [(0, 0)] * first + [(0, lane_tiles(n) - n)
                                   for n in x.shape[first:]]
        return jnp.pad(x, more) if any(m for _, m in more) else x

    return _flash_rows_call(whole(q), whole(k), whole(v), float(scale),
                            lengths, block_q, block_k, window,
                            None if keep is None else whole(keep, 1)
                            )[:, :, :T, :Dv]

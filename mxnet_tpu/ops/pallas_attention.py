"""Flash attention as a Pallas TPU kernel — forward AND backward.

Reference parity target: the fused MHA kernels the reference gets from
contrib/transformer.cu + cuDNN; here the TPU version is a blockwise
online-softmax kernel (Flash-Attention-2) so neither the (Tq × Tk) score
matrix nor the whole K/V sequence is ever resident:

- grid (batch·heads, q blocks, kv blocks): K and V stream through VMEM
  one (block_k, D) tile per grid step — per-step VMEM is bounded by the
  block sizes and INDEPENDENT of sequence length (long-context safe);
- the score block Q·Kᵀ runs on the MXU with f32 accumulation;
- m/l/o accumulators live in VMEM scratch, carried across the kv grid
  dimension ("arbitrary" semantics); outputs store on the last kv step;
- m/l are kept lane-replicated (block_q, 128) in VMEM so the
  online-softmax update is pure elementwise VPU work — the same layout
  trick the production TPU kernels use; the logsumexp persisted to HBM
  for the backward is stored TRANSPOSED, (B·H, 8, T): the sequence on
  the lane axis, 8 sublane copies.  HBM tiles are (8, 128), so this
  costs 8·T floats per head, where a (T, 8) layout is padded to
  (T, 128) — 16× (measured on the v5e: 1.05 GB of padding for BERT-base
  at b32/T512, which alone pushed that step past 16 GB);
- causal q/kv block pairs above the diagonal skip all compute (pl.when);
- backward is the FlashAttention-2 recipe: recompute p = exp(s − L) per
  tile; dq accumulates over the kv grid, dk/dv over the q grid; D_i =
  rowsum(dO ∘ O) is computed in-kernel from the O/dO tiles (never
  materialized in HBM).

On the CPU (tests, the virtual mesh) the kernels run in interpret mode,
keeping one code path.  On TPU they compile through Mosaic, which needs
128-aligned tiles: a sequence length not divisible by 128 raises, and
nothing is substituted for the kernel the caller named.
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


def _block_sizes(T):
    if T % _LANE == 0:
        # bq capped at 256: the dq backward's f32 working set at bq=512
        # (dq scratch + (bq,bk) intermediates + double-buffered operand
        # blocks) blows the ~16MB scoped-VMEM budget at BERT shapes
        # (measured: b32·h12·T512·D64 fails to compile at 512, fits at
        # 256)
        bq = 256 if T % 256 == 0 else _LANE
        return min(bq, T), _LANE
    # interpret-mode small/odd shapes; flash_attention refuses them on TPU
    return T, T


# sublane copies of the logsumexp row persisted to HBM between fwd and
# bwd: (B·H, 8, T), one f32 tile row — the minimum that is not padded
_LSE_ROWS = 8


def _lse_to_rows(lse):
    """kernel working layout (bq, 128), lane-replicated → the stored
    (8, bq) block with the sequence on lanes."""
    return lse.T[:_LSE_ROWS]


def _lse_from_rows(rows, n):
    """stored (8, bq) block → (bq, n), lane-replicated."""
    bq = rows.shape[1]
    return _bcast_lanes(jnp.broadcast_to(rows[:1], (_LANE, bq)).T, n)


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


# -- forward -------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale, causal, block_q, block_k, nk):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _run():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_curr = jnp.max(s, axis=1)[:, None]          # (bq, 1)
        m_next = jnp.maximum(m_prev, m_curr)          # (bq, 128)
        p = jnp.exp(s - _bcast_lanes(m_next, s.shape[1]))
        p = jnp.where(s <= _NEG / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_scr[...] = m_next
        v = v_ref[0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        D = acc_scr.shape[1]
        acc_scr[...] = acc_scr[...] * _bcast_lanes(alpha, D) + pv

    if causal:
        pl.when(kj * block_k <= (qi + 1) * block_q - 1)(_run)
    else:
        _run()

    @pl.when(kj == nk - 1)
    def _store():
        l = l_scr[...]
        lsafe = jnp.where(l == 0.0, 1.0, l)
        D = acc_scr.shape[1]
        o_ref[0] = (acc_scr[...] / _bcast_lanes(lsafe, D)).astype(
            o_ref.dtype)
        lse_ref[0] = _lse_to_rows(m_scr[...] + jnp.log(lsafe))


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct that carries the varying-mesh-axes set when the
    kernel runs inside a check_vma=True shard_map (ring attention)."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _flash_call(q, k, v, causal, scale, block_q, block_k, vma=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, T, D)
    vr = v.reshape(B * H, T, D)
    nq, nk = T // block_q, T // block_k
    interpret = _use_interpret()
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, nk=nk)
    kw = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}
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


# -- backward (FlashAttention-2) -----------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, dq_ref,
               acc_scr, delta_scr, *, scale, causal, block_q, block_k, nk):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        delta_scr[...] = jnp.sum(g * o, axis=1)[:, None] * jnp.ones(
            (1, _LANE), jnp.float32)

    def _run():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        bk = s.shape[1]
        p = jnp.exp(s - _lse_from_rows(lse_ref[0], bk))
        p = jnp.where(s <= _NEG / 2, 0.0, p)
        v = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(                      # dO · Vᵀ
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _bcast_lanes(delta_scr[...], bk)) * scale
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(kj * block_k <= (qi + 1) * block_q - 1)(_run)
    else:
        _run()

    @pl.when(kj == nk - 1)
    def _store():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale, causal, block_q,
                block_k, nq):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when(qj == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def _run():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            qpos = qj * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        bk = s.shape[1]
        p = jnp.exp(s - _lse_from_rows(lse_ref[0], bk))
        p = jnp.where(s <= _NEG / 2, 0.0, p)
        delta = jnp.sum(g * o, axis=1)[:, None]        # (bq, 1)
        v = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dv_scr[...] += jax.lax.dot_general(            # Pᵀ · dO
            p.astype(g_ref.dtype), g_ref[0],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(            # dSᵀ · Q
            ds.astype(q_ref.dtype), q_ref[0],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when((qj + 1) * block_q - 1 >= ki * block_k)(_run)
    else:
        _run()

    @pl.when(qj == nq - 1)
    def _store():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_call(q, k, v, out, lse, g, causal, scale, block_q,
                    block_k, vma=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, T, D)
    vr = v.reshape(B * H, T, D)
    gr = g.reshape(B * H, T, D)
    outr = out.reshape(B * H, T, D)
    nq, nk = T // block_q, T // block_k
    interpret = _use_interpret()
    kw = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}

    qspec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    lspec = pl.BlockSpec((1, _LSE_ROWS, block_q),
                         lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk),
        grid=(B * H, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, qspec, lspec],
        out_specs=qspec,
        out_shape=_sds((B * H, T, D), q.dtype, vma),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        interpret=interpret,
        **kw,
    )(qr, kr, vr, gr, outr, lse)

    # dkv grid: kv block is the revisited (outer) axis, q streams inner
    qspec2 = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, j, 0))
    kspec2 = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0))
    lspec2 = pl.BlockSpec((1, _LSE_ROWS, block_q),
                          lambda b, i, j: (b, 0, j))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq),
        grid=(B * H, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, qspec2, lspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            _sds((B * H, T, D), k.dtype, vma),
            _sds((B * H, T, D), v.dtype, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        **kw,
    )(qr, kr, vr, gr, outr, lse)

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
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if T % _LANE != 0 and not _use_interpret():
        raise ValueError(
            f"flash_attention: sequence length {T} is not "
            f"{_LANE}-aligned, which the TPU kernel's tiles need; pad "
            "the sequence or use impl='dense'")
    dbq, dbk = _block_sizes(T)
    bq, bk = int(block_q or dbq), int(block_k or dbk)
    if T % bq or T % bk:
        raise ValueError(
            f"flash_attention: block sizes ({bq}, {bk}) must divide "
            f"sequence length {T} (a non-dividing block would silently "
            f"leave tail blocks unwritten)")
    return _flash_core(q, k, v, bool(causal), float(scale), bq, bk,
                       tuple(vma))

"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

NEW, TPU-first (SURVEY.md §5.7: absent in the 2018-era reference, required
by the long-context BERT/NMT configs).  Two strategies over the mesh ``sp``
axis:

- **Ring attention** (Liu et al. 2023): Q stays local; K/V blocks rotate
  around the ring via ``ppermute`` while a flash-style online-softmax
  accumulator folds each block in.  Peak memory is O(T/p) per chip and the
  KV transfer overlaps the local block matmul on ICI.
- **Ulysses** (DeepSpeed-Ulysses): ``all_to_all`` reshards sequence ↔ heads
  so each chip runs FULL-sequence attention for T/p of the heads — cheaper
  collectives when head count ≥ ring size.

Both are differentiable by construction (shard_map transposes) and run on
the virtual CPU mesh for tests.
"""

from __future__ import annotations

import functools

import jax

from ..base import MXNetError
from .collectives import pvary as _pvary
from .mesh import SP, default_mesh

_NEG_INF = -1e30


def _vma_of(x):
    """The mesh axes `x` varies over inside shard_map (empty outside a
    manual region)."""
    return tuple(jax.typeof(x).vma)


def _place(mesh, spec, *arrays):
    """Eagerly-called shard_map needs concrete inputs laid on the mesh;
    tracers get a device_put-as-resharding too — under eager autodiff
    (NDArray autograd → jax.vjp) the primal may be COMMITTED to a single
    context device (e.g. initialized parameters) and the implicit jit
    around shard_map rejects committed off-mesh args; the device_put
    reshards the primal onto the mesh inside the trace.  Returns the
    placed arrays plus an `eager` flag so the caller can un-commit its
    output (eager callers mix results with single-device arrays)."""
    import jax
    from jax.sharding import NamedSharding

    from ..ndarray.register import in_eager_op_trace

    sh = NamedSharding(mesh, spec)
    out = []
    eager = in_eager_op_trace()
    for a in arrays:
        if not isinstance(a, jax.core.Tracer):
            eager = True
        out.append(jax.device_put(a, sh))
    return tuple(out), eager


def _uncommit(x, eager):
    """Bring an eager result back to the default device so it composes
    with ordinary single-device arrays (debug/eager path only — under a
    real enclosing jit the sharding stays)."""
    import jax

    if not eager:
        return x
    if isinstance(x, jax.core.Tracer):
        # eager-autograd trace: reshard inside the trace
        return jax.device_put(x, jax.devices()[0])
    import numpy as _host_np

    return jax.device_put(_host_np.asarray(x), jax.devices()[0])


def _online_block(o, l, m, s, v):
    """Fold one score block into the flash accumulator.

    o: (B,H,Tq,D) weighted sum; l: (B,H,Tq) denom; m: (B,H,Tq) running max;
    s: (B,H,Tq,Tk) scores; v: (B,H,Tk,D).
    """
    import jax.numpy as jnp

    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (all -inf): exp(-inf - -inf) would be NaN
    m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
    correction = jnp.exp(jnp.where(m <= _NEG_INF / 2, _NEG_INF,
                                   m - m_safe))
    correction = jnp.where(m <= _NEG_INF / 2, 0.0, correction)
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v)
    return o_new, l_new, m_new


def _local_scores(q, k, scale, causal, q_off, k_off):
    import jax.numpy as jnp

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        qpos = q_off + jnp.arange(Tq)
        kpos = k_off + jnp.arange(Tk)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    return s


# -- flash-ring: Pallas blockwise kernel per ring step --------------------------
#
# Each ring step runs the streaming flash kernel (ops/pallas_attention) on
# the local (q, rotating-KV-block) pair and merges the block's NORMALIZED
# output + logsumexp into the running accumulator with the numerically
# stable logaddexp combine — per-step HBM traffic is O(Tq/p · D), never an
# O(Tq/p × Tk/p) score tensor (VERDICT r3 Weak #2).  Backward is a second
# ring pass through the FlashAttention-2 Pallas backward kernels, each
# block recomputing p = exp(s − lse_global); dk/dv accumulators travel
# around the ring with their K/V block and arrive home after p hops.


def _ring_block_fwd(q, k, v, j, i, causal, scale, bq, bk):
    """One KV block's flash forward → (out_blk, lse_blk (B,H,Tq) f32).

    Causal at BLOCK granularity: block j<i is fully visible (plain
    kernel), j==i is the diagonal (standard in-block causal, offsets
    equal), j>i is fully masked (skipped: zero output, -inf lse)."""
    import jax.numpy as jnp
    from jax import lax

    from ..ops.pallas_attention import _flash_call

    B, H, Tq, D = q.shape
    vma = _vma_of(q)

    def _call(causal_flag):
        out, lse8 = _flash_call(q, k, v, causal_flag, scale, bq, bk,
                                vma=vma)
        return out, lse8[:, 0, :].reshape(B, H, Tq)

    if not causal:
        return _call(False)

    def full(_):
        return _call(False)

    def diag(_):
        return _call(True)

    def skip(_):
        return (_pvary(jnp.zeros(q.shape, q.dtype), vma),
                _pvary(jnp.full((B, H, Tq), _NEG_INF, jnp.float32), vma))

    idx = jnp.where(j > i, 2, jnp.where(j == i, 1, 0))
    return lax.switch(idx, [full, diag, skip], None)


def _ring_block_bwd(q, k, v, out, lse8, g, j, i, causal, scale, bq, bk):
    """One KV block's flash backward with the GLOBAL lse → (dq, dk, dv)."""
    import jax.numpy as jnp
    from jax import lax

    from ..ops.pallas_attention import _flash_bwd_call

    vma = _vma_of(q)

    def _call(causal_flag):
        return _flash_bwd_call(q, k, v, out, lse8, g, causal_flag, scale,
                               bq, bk, vma=vma)

    if not causal:
        return _call(False)

    def full(_):
        return _call(False)

    def diag(_):
        return _call(True)

    def skip(_):
        return (_pvary(jnp.zeros(q.shape, q.dtype), vma),
                _pvary(jnp.zeros(k.shape, k.dtype), vma),
                _pvary(jnp.zeros(v.shape, v.dtype), vma))

    idx = jnp.where(j > i, 2, jnp.where(j == i, 1, 0))
    return lax.switch(idx, [full, diag, skip], None)


def _ring_flash_fwd_core(q, k, v, axis, p, causal, scale, bq, bk):
    import jax.numpy as jnp
    from jax import lax

    i = lax.axis_index(axis)
    B, H, Tq, D = q.shape
    vma = _vma_of(q) or axis
    o = _pvary(jnp.zeros((B, H, Tq, D), jnp.float32), vma)
    lse = _pvary(jnp.full((B, H, Tq), _NEG_INF, jnp.float32), vma)
    perm = [(r, (r + 1) % p) for r in range(p)]

    def body(step, carry):
        o, lse, k_c, v_c = carry
        j = (i - step) % p
        o_blk, lse_blk = _ring_block_fwd(q, k_c, v_c, j, i, causal,
                                         scale, bq, bk)
        lse_new = jnp.logaddexp(lse, lse_blk)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + o_blk.astype(jnp.float32)
             * jnp.exp(lse_blk - lse_new)[..., None])
        k_c = lax.ppermute(k_c, axis, perm)
        v_c = lax.ppermute(v_c, axis, perm)
        return o, lse_new, k_c, v_c

    o, lse, _, _ = lax.fori_loop(0, p, body, (o, lse, k, v))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q, k, v, axis, p, causal, scale, bq, bk):
    out, _ = _ring_flash_fwd_core(q, k, v, axis, p, causal, scale, bq, bk)
    return out


def _ring_flash_vjp_fwd(q, k, v, axis, p, causal, scale, bq, bk):
    out, lse = _ring_flash_fwd_core(q, k, v, axis, p, causal, scale, bq,
                                    bk)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis, p, causal, scale, bq, bk, res, g):
    import jax.numpy as jnp
    from jax import lax

    from ..ops.pallas_attention import _LSE_ROWS

    q, k, v, out, lse = res
    i = lax.axis_index(axis)
    B, H, Tq, D = q.shape
    lse8 = jnp.tile(lse.reshape(B * H, 1, Tq), (1, _LSE_ROWS, 1))
    vma = _vma_of(q) or axis
    dq = _pvary(jnp.zeros(q.shape, jnp.float32), vma)
    dk_acc = _pvary(jnp.zeros(k.shape, jnp.float32), vma)
    dv_acc = _pvary(jnp.zeros(v.shape, jnp.float32), vma)
    perm = [(r, (r + 1) % p) for r in range(p)]

    def body(step, carry):
        dq, dk_acc, dv_acc, k_c, v_c = carry
        j = (i - step) % p
        dq_b, dk_b, dv_b = _ring_block_bwd(q, k_c, v_c, out, lse8, g, j,
                                           i, causal, scale, bq, bk)
        dq = dq + dq_b.astype(jnp.float32)
        dk_acc = dk_acc + dk_b.astype(jnp.float32)
        dv_acc = dv_acc + dv_b.astype(jnp.float32)
        k_c = lax.ppermute(k_c, axis, perm)
        v_c = lax.ppermute(v_c, axis, perm)
        dk_acc = lax.ppermute(dk_acc, axis, perm)
        dv_acc = lax.ppermute(dv_acc, axis, perm)
        return dq, dk_acc, dv_acc, k_c, v_c

    dq, dk_acc, dv_acc, _, _ = lax.fori_loop(
        0, p, body, (dq, dk_acc, dv_acc, k, v))
    return (dq.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype))


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_attention(q, k, v, mesh=None, axis=SP, causal=False, scale=None,
                   impl=None, block_q=None, block_k=None):
    """Attention with the sequence dim sharded on `axis`.

    q,k,v: GLOBAL arrays (B, H, T, D) laid out with T sharded on `axis`.
    Returns the attention output with the same sharding.

    ``impl``: None (auto: Pallas flash blocks when the local sequence is
    lane-aligned or off-TPU, else the dense-XLA online-softmax path),
    ``"flash"`` or ``"dense"`` to force.  ``block_q``/``block_k``
    override the flash tile sizes (tests use small tiles to prove the
    streaming property at modest T).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..ops.pallas_attention import _LANE, _blocks, _use_interpret

    mesh = mesh or default_mesh()
    if mesh is None:
        raise MXNetError("ring_attention needs a mesh (pass mesh= or "
                         "parallel.set_default_mesh)")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    nshards = mesh.shape.get(axis, 1)
    # compose with data parallelism: batch dim stays dp-sharded inside the
    # manual region when the mesh has a dp axis
    batch_ax = "dp" if "dp" in mesh.shape else None
    spec = PartitionSpec(batch_ax, None, axis, None)
    (q, k, v), eager = _place(mesh, spec, q, k, v)

    def local_dense(q, k, v):
        p = nshards
        i = lax.axis_index(axis)
        B, H, Tq, D = q.shape
        o = _pvary(jnp.zeros_like(q, dtype=jnp.float32), axis)
        l = _pvary(jnp.zeros((B, H, Tq), jnp.float32), axis)
        m = _pvary(jnp.full((B, H, Tq), _NEG_INF, jnp.float32), axis)
        Tk = k.shape[2]
        perm = [(r, (r + 1) % p) for r in range(p)]

        def body(step, carry):
            o, l, m, k, v = carry
            j = (i - step) % p          # which global KV block we hold now
            s = _local_scores(q.astype(jnp.float32),
                              k.astype(jnp.float32), scale, causal,
                              i * Tq, j * Tk)
            o, l, m = _online_block(o, l, m, s, v.astype(jnp.float32))
            k = lax.ppermute(k, axis, perm)
            v = lax.ppermute(v, axis, perm)
            return o, l, m, k, v

        o, l, m, k, v = lax.fori_loop(0, p, body, (o, l, m, k, v))
        l = jnp.where(l == 0.0, 1.0, l)
        return (o / l[..., None]).astype(q.dtype)

    if impl not in (None, "flash", "dense"):
        raise MXNetError(
            f"ring_attention: unknown impl {impl!r} (None, 'flash' or "
            "'dense')")
    Tloc = q.shape[2] // nshards
    flash_ok = _use_interpret() or Tloc % _LANE == 0
    if impl == "flash" and not flash_ok:
        raise MXNetError(
            f"ring_attention impl='flash': local sequence {Tloc} not "
            f"{_LANE}-aligned on TPU")
    use_flash = impl != "dense" and flash_ok
    if use_flash:
        # the kernels choose their own blocks from the local shape
        # where the caller names none; a named one must divide it
        try:
            _blocks(Tloc, q.shape[-1], q.dtype, "fwd", block_q, block_k)
        except ValueError as e:
            raise MXNetError(f"ring_attention (local sequence): {e}") \
                from None

    def local_flash(q, k, v):
        return _ring_flash(q, k, v, axis, nshards, bool(causal),
                           float(scale), block_q, block_k)

    # check_vma off for INTERPRET-mode flash only: interpret pallas_call
    # inside a vma-checked manual region hits a jax-internal
    # dynamic_slice vma mismatch (the error message itself prescribes
    # check_vma=False).  On real TPU the Mosaic lowering takes the vma
    # plumbed through _flash_call's out_shapes, so the check stays on.
    fn = jax.shard_map(local_flash if use_flash else local_dense,
                       mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec,
                       check_vma=not (use_flash and _use_interpret()))
    return _uncommit(fn(q, k, v), eager)


def ulysses_attention(q, k, v, mesh=None, axis=SP, causal=False,
                      scale=None):
    """All-to-all head↔sequence resharding attention (DeepSpeed-Ulysses).

    q,k,v: (B, H, T, D) with T sharded on `axis`; H must be divisible by
    the axis size.
    """
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec

    import jax

    mesh = mesh or default_mesh()
    if mesh is None:
        raise MXNetError("ulysses_attention needs a mesh")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    nshards = mesh.shape.get(axis, 1)
    if q.shape[1] % nshards != 0:
        raise MXNetError(
            f"ulysses: num_heads {q.shape[1]} not divisible by sp size "
            f"{nshards}")
    batch_ax = "dp" if "dp" in mesh.shape else None
    spec = PartitionSpec(batch_ax, None, axis, None)
    (q, k, v), eager = _place(mesh, spec, q, k, v)

    from ..ops.pallas_attention import (_LANE, _use_interpret,
                                        flash_attention)

    T_full = q.shape[2]
    use_flash = _use_interpret() or T_full % _LANE == 0

    def local(q, k, v):
        # (B, H, T/p, D) → (B, H/p, T, D): gather sequence, scatter heads
        def seq2head(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        def head2seq(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        qf, kf, vf = seq2head(q), seq2head(k), seq2head(v)
        if use_flash:
            # full-sequence attention for T/p of the heads through the
            # streaming flash kernel (custom-vjp, so Ulysses stays
            # differentiable) — the (T × T) score matrix is never
            # resident, same long-context property as the ring path
            of = flash_attention(qf, kf, vf, causal=causal, scale=scale,
                                 vma=_vma_of(qf))
        else:
            s = jnp.einsum("bhqd,bhkd->bhqk", qf.astype(jnp.float32),
                           kf.astype(jnp.float32)) * scale
            if causal:
                T = s.shape[-1]
                mask = jnp.tril(jnp.ones((T, T), bool))
                s = jnp.where(mask[None, None], s, _NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            of = jnp.einsum("bhqk,bhkd->bhqd", p,
                            vf.astype(jnp.float32)).astype(q.dtype)
        return head2seq(of)

    # check_vma off only for interpret-mode flash (same jax-internal
    # limitation as the ring path); on TPU the vma plumbs through
    # flash_attention's out_shapes and the check stays on
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec,
                       check_vma=not (use_flash and _use_interpret()))
    return _uncommit(fn(q, k, v), eager)

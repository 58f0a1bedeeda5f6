"""The Mamba-2 ops of ops/ssm.py (`mamba2_scan_rows`,
`mamba2_update_rows`), on the CPU: the chunked matrix-form kernel and
the one-position kernel, interpreted at small sizes that keep every
ratio (several heads, one group of B and C, N != P), against the plain
``lax.scan`` oracle in float32, itself against the recurrence written
out; ragged lengths (inside a chunk, a whole chunk, a whole block),
``live`` subsets and the state returned."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax.numpy as jnp                                     # noqa: E402

from mxnet_tpu.ops import ssm                               # noqa: E402

H, P, N = 4, 8, 16


def _operands(R, S, seed=0, heads=H):
    """x, dt, A, B, C, D of a layer whose heads' decay rates span four
    orders of magnitude (drawn on the host: a draw on the device
    compiles once a shape)."""
    rng = np.random.RandomState(seed)
    normal = lambda *shape: rng.normal(size=shape).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (
        normal(R, S, heads, P),
        np.logaddexp(0.0, normal(R, S, heads) - 1.0),
        -np.exp(rng.uniform(-5.0, 4.0, heads)).astype(np.float32),
        normal(R, S, N), normal(R, S, N), normal(heads)))


def _by_hand(x, dt, A, B, C, D, n):
    """Row 0's recurrence over its first ``n`` positions, a position and
    a head at a time in NumPy float64."""
    x, dt, A, B, C, D = (np.asarray(a, np.float64)
                         for a in (x, dt, A, B, C, D))
    S = np.zeros((x.shape[2], P, N))
    ys = []
    for t in range(n):
        for h in range(x.shape[2]):
            S[h] = np.exp(dt[0, t, h] * A[h]) * S[h] \
                + dt[0, t, h] * np.outer(x[0, t, h], B[0, t])
        ys.append(S @ C[0, t] + D[:, None] * x[0, t])
    return np.stack(ys), S.reshape(-1, N)


def test_the_oracle_is_the_recurrence_written_out():
    ops = _operands(1, 12, seed=3)
    y, h = ssm._mamba2_scan_plain(*ops, jnp.array([9], jnp.int32))
    want_y, want_h = _by_hand(*ops, 9)
    np.testing.assert_allclose(y[0, :9], want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h[0], want_h, rtol=2e-5, atol=2e-5)
    assert h.shape == (1, H * P, N)


# chunks of 8 positions, two heads a block: rows of length 1, 2 and 3,
# one inside a chunk, one that ends on a chunk's edge, one of a whole
# chunk and a full one; blocks of one chunk, of several, and of no whole
# number of chunks (padded by the call)
@pytest.mark.parametrize("S,lengths,heads", [
    (32, (1, 2, 3, 13, 16, 8, 32), 2),
    (20, (3, 20, 17, 8), 4),
], ids=str)
def test_the_scan_kernel_equals_the_oracle(S, lengths, heads):
    R = len(lengths)
    ops = _operands(R, S, seed=S)
    n = jnp.array(lengths, jnp.int32)
    y0, s0 = ssm._mamba2_scan_plain(*ops, n)
    y1, s1 = ssm._mamba2_scan_kernel_call(*ops, n, interpret=True, chunk=8,
                                          heads=heads)
    # float32 on both sides, the sums in another order (a chunk's
    # products against a position at a time): states that reach 12 and
    # outputs that reach 50 agree to 1e-5 of their size
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=2e-5)
    assert bool(jnp.all(jnp.isfinite(y1)))
    for r, k in enumerate(lengths):
        np.testing.assert_allclose(y1[r, :k], y0[r, :k], rtol=1e-5,
                                   atol=5e-5)


def test_the_scan_kernels_products_take_their_operands_narrow():
    """``operands=bfloat16``: x dt, the masked C B^T, the state and B go
    into the products with 8 bits of mantissa and are summed in
    float32: the outputs move by under a hundredth of their size, not
    by nothing."""
    ops = _operands(2, 16, seed=5)
    n = jnp.array([16, 11], jnp.int32)
    y0, s0 = ssm._mamba2_scan_plain(*ops, n)
    y1, s1 = ssm._mamba2_scan_kernel_call(
        *ops, n, operands=jnp.bfloat16, interpret=True, chunk=8, heads=2)
    worst = float(jnp.abs(y1[0] - y0[0]).max())
    assert 1e-4 < worst < 0.02 * float(jnp.abs(y0).max()), worst
    assert float(jnp.abs(s1 - s0).max()) < 0.02 * float(jnp.abs(s0).max())


def test_a_scan_over_padding_would_leave_another_state():
    """The length is not an optimisation: run to the block's end, the
    state is another."""
    ops = _operands(2, 24, seed=1)
    short = ssm._mamba2_scan_plain(*ops, jnp.array([9, 24], jnp.int32))[1]
    whole = ssm._mamba2_scan_plain(*ops, jnp.array([24, 24], jnp.int32))[1]
    assert float(jnp.abs(short[0] - whole[0]).max()) > 0.1
    np.testing.assert_array_equal(short[1], whole[1])


@pytest.mark.parametrize("live", [None, (True, False, True, False),
                                  (False,) * 4, (True,) * 4], ids=str)
@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_one_position_moves_the_live_rows_states_and_no_other(path, live):
    L, R = 3, 4
    x, dt, A, B, C, D = _operands(R, 1, seed=7)
    state = jnp.asarray(np.random.RandomState(8).normal(
        size=(L, R, H * P, N)), jnp.float32)
    mask = None if live is None else jnp.array(live)
    # tiles of 8 channels: four a row
    update = ssm._mamba2_update_plain if path == "plain" else \
        lambda *a: ssm._mamba2_update_kernel_call(*a, interpret=True, rows=8)
    y, out = update(state, 1, x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D,
                    mask)
    assert y.shape == (R, H, P) and out.shape == state.shape
    # the other layers, and the rows that are not live, bit for bit
    np.testing.assert_array_equal(out[0], state[0])
    np.testing.assert_array_equal(out[2], state[2])
    for r in range(R):
        if live is not None and not live[r]:
            np.testing.assert_array_equal(out[1, r], state[1, r])
            assert float(jnp.abs(y[r]).max()) == 0.0
            continue
        S = np.asarray(state[1, r], np.float64).reshape(H, P, N)
        a = np.exp(np.asarray(dt[r, 0], np.float64) * np.asarray(A))
        S = a[:, None, None] * S + np.asarray(dt[r, 0])[:, None, None] \
            * np.asarray(x[r, 0])[:, :, None] * np.asarray(B[r, 0])
        np.testing.assert_allclose(out[1, r], S.reshape(-1, N), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(
            y[r], S @ np.asarray(C[r, 0]) + np.asarray(D)[:, None]
            * np.asarray(x[r, 0]), rtol=1e-5, atol=2e-5)


def test_one_position_after_a_block_is_the_block_one_longer():
    """The state a prefilled block leaves is the one the update starts
    from: a block of 11 then one position is the block of 12, through
    the plain paths and through the kernels."""
    ops = _operands(2, 12, seed=11)
    x, dt, A, B, C, D = ops
    y, h = ssm._mamba2_scan_plain(*ops, jnp.array([12, 12], jnp.int32))
    for scan, update in (
            (ssm._mamba2_scan_plain, ssm._mamba2_update_plain),
            (lambda *a: ssm._mamba2_scan_kernel_call(
                *a, interpret=True, chunk=4, heads=2),
             lambda *a: ssm._mamba2_update_kernel_call(
                 *a, interpret=True, rows=16))):
        _, h11 = scan(*ops, jnp.array([11, 11], jnp.int32))
        y12, out = update(h11[None], 0, x[:, 11], dt[:, 11], A, B[:, 11],
                          C[:, 11], D, None)
        np.testing.assert_allclose(out[0], h, rtol=1e-5, atol=2e-5)
        np.testing.assert_allclose(y12, y[:, 11], rtol=1e-5, atol=5e-5)


def test_the_entry_points_tally_rows_by_path_and_take_the_plain_path_here():
    import collections

    ops = _operands(3, 8, seed=2)
    tally = collections.Counter()
    ssm.mamba2_scan_rows(*ops, jnp.array([8, 3, 1], jnp.int32), tally=tally)
    x, dt, A, B, C, D = ops
    ssm.mamba2_update_rows(jnp.zeros((2, 3, H * P, N)), 1, x[:, 0], dt[:, 0],
                           A, B[:, 0], C[:, 0], D,
                           live=jnp.array([True, False, True]), tally=tally)
    assert dict(tally) == {"plain": 6}
    # what the kernels need of the sizes: Granite 4.0-H's fit, a head of
    # 4 channels or 16 states do not
    assert ssm._mamba2_scan_fits(128, 64, 128)
    assert ssm._mamba2_update_fits(8192, 128)
    assert not ssm._mamba2_scan_fits(4, 4, 128)
    assert not ssm._mamba2_scan_fits(H, P, N)
    assert not ssm._mamba2_update_fits(H * P, N)
    assert ssm.mamba2_chunk() == 128

"""The state-space ops (ops/ssm.py), on the CPU: the scan kernel and the
one-position kernel, interpreted, against the plain ``lax.scan`` oracle
in float32, with ragged lengths, a block continued from a carried state
and ``live``; and both convolutions against a convolution written out
position by position."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from mxnet_tpu.ops import ssm                               # noqa: E402

N = 16


def _operands(R, S, E, seed=0):
    """c, dt, A, B, C, D of a layer whose decay rates span four orders
    of magnitude."""
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (R, S, E)),
            jax.nn.softplus(jax.random.normal(ks[1], (R, S, E)) - 1.0),
            -jnp.exp(jax.random.uniform(ks[2], (N, E), minval=-5.0,
                                        maxval=4.0)),
            jax.random.normal(ks[3], (R, S, N)),
            jax.random.normal(ks[4], (R, S, N)),
            jax.random.normal(ks[5], (E,)))


def _by_hand(c, dt, A, B, C, D, n, h=None):
    """Row 0's recurrence over its first ``n`` positions, a position at
    a time in NumPy float64."""
    c, dt, A, B, C, D = (np.asarray(x, np.float64)
                         for x in (c, dt, A, B, C, D))
    h = np.zeros(A.shape) if h is None else np.asarray(h, np.float64)
    ys = []
    for t in range(n):
        h = np.exp(dt[0, t] * A) * h + (dt[0, t] * c[0, t]) * B[0, t][:, None]
        ys.append((h * C[0, t][:, None]).sum(0) + D * c[0, t])
    return np.stack(ys), h


def test_the_oracle_is_the_recurrence_written_out():
    ops = _operands(1, 12, 128, seed=3)
    y, h = ssm._scan_plain(*ops, jnp.array([9], jnp.int32))
    want_y, want_h = _by_hand(*ops, 9)
    np.testing.assert_allclose(y[0, :9], want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h[0], want_h, rtol=2e-5, atol=2e-5)


# rows of length 1, 2 and 3, one inside a chunk, one that ends on a
# chunk's edge and a full one; blocks of one chunk, of several, and of
# no whole number of chunks (padded by the call)
@pytest.mark.parametrize("S,E,lengths,carried", [
    (256, 128, (1, 2, 3, 131, 128, 256), False),
    (256, 128, (1, 3, 131, 256), True),
    (64, 128, (1, 17, 64), False),
    (200, 1024, (3, 200), True),
], ids=str)
def test_the_scan_kernel_equals_the_oracle(S, E, lengths, carried):
    R = len(lengths)
    ops = _operands(R, S, E, seed=S + E)
    n = jnp.array(lengths, jnp.int32)
    h0 = jax.random.normal(jax.random.key(9), (R, N, E)) if carried else None
    y0, s0 = ssm._scan_plain(*ops, n, h0)
    y1, s1 = ssm._scan_kernel_call(*ops, n, h0, interpret=True)
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-5)
    assert bool(jnp.all(jnp.isfinite(y1)))
    for r, k in enumerate(lengths):
        np.testing.assert_allclose(y1[r, :k], y0[r, :k], rtol=1e-5,
                                   atol=2e-5)


def test_a_scan_over_padding_would_leave_another_state():
    """The length is not an optimisation: run to the block's end, the
    state is another."""
    ops = _operands(2, 64, 128, seed=1)
    short = ssm._scan_plain(*ops, jnp.array([20, 64], jnp.int32))[1]
    whole = ssm._scan_plain(*ops, jnp.array([64, 64], jnp.int32))[1]
    assert float(jnp.abs(short[0] - whole[0]).max()) > 0.1
    np.testing.assert_array_equal(short[1], whole[1])


def test_a_block_continued_from_its_carried_state_is_the_whole_block():
    ops = _operands(2, 128, 128, seed=2)
    c, dt, A, B, C, D = ops
    n = jnp.array([128, 128], jnp.int32)
    y, h = ssm._scan_plain(*ops, n)
    half = jnp.array([64, 64], jnp.int32)
    for scan in (ssm._scan_plain,
                 lambda *a: ssm._scan_kernel_call(*a, interpret=True)):
        y1, h1 = scan(c[:, :64], dt[:, :64], A, B[:, :64], C[:, :64], D,
                      half, None)
        y2, h2 = scan(c[:, 64:], dt[:, 64:], A, B[:, 64:], C[:, 64:], D,
                      half, h1)
        np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y,
                                   rtol=1e-5, atol=2e-5)
        np.testing.assert_allclose(h2, h, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("live", [None, (True, False, True, False),
                                  (False,) * 4, (True,) * 4], ids=str)
@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_one_position_moves_the_live_rows_states_and_no_other(path, live):
    L, R, E = 3, 4, 256
    c, dt, A, B, C, D = _operands(R, 1, E, seed=7)
    state = jax.random.normal(jax.random.key(8), (L, R, N, E))
    mask = None if live is None else jnp.array(live)
    update = ssm._update_plain if path == "plain" else \
        lambda *a: ssm._update_kernel_call(*a, interpret=True)
    y, out = update(state, 1, c[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D,
                    mask)
    want_y, want_h = ssm._scan_plain(c, dt, A, B, C, D,
                                     jnp.ones((R,), jnp.int32), state[1])
    np.testing.assert_array_equal(out[0], state[0])
    np.testing.assert_array_equal(out[2], state[2])
    for r in range(R):
        if live is None or live[r]:
            np.testing.assert_allclose(out[1, r], want_h[r], rtol=1e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(y[r], want_y[r, 0], rtol=1e-5,
                                       atol=2e-5)
        else:       # bit for bit what it was
            np.testing.assert_array_equal(out[1, r], state[1, r])
            assert not np.asarray(y[r]).any()


def _conv_by_hand(a, w, b, n):
    a, w, b = (np.asarray(x, np.float64) for x in (a, w, b))
    k = w.shape[0]
    out = np.zeros((n, a.shape[-1]))
    for t in range(n):
        acc = b.copy()
        for j in range(k):
            if t - k + 1 + j >= 0:
                acc += w[j] * a[t - k + 1 + j]
        out[t] = acc / (1.0 + np.exp(-acc))
    return out


@pytest.mark.parametrize("lengths", [(1, 2, 3, 24), (24, 7, 4, 1)], ids=str)
def test_the_block_convolution_and_the_tail_it_leaves(lengths):
    R, S, E, k = 4, 24, 128, 4
    ks = jax.random.split(jax.random.key(4), 3)
    a = jax.random.normal(ks[0], (R, S, E))
    w, b = jax.random.normal(ks[1], (k, E)), jax.random.normal(ks[2], (E,))
    c, tail = ssm.causal_conv_rows(a, w, b, jnp.array(lengths, jnp.int32))
    assert c.shape == (R, S, E) and tail.shape == (R, 3 * E)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(c[r, :n], _conv_by_hand(a[r], w, b, n),
                                   rtol=1e-5, atol=1e-5)
        # the row's last three real inputs, oldest first, zeros where
        # the row is shorter
        want = np.zeros((3, E), np.float32)
        for j in range(3):
            if n - 3 + j >= 0:
                want[j] = a[r, n - 3 + j]
        np.testing.assert_array_equal(tail[r].reshape(3, E), want)


@pytest.mark.parametrize("live", [None, (True, False, True)], ids=str)
def test_one_position_of_the_convolution_from_the_tail(live):
    """A block of n positions, then position n from the tail, is the
    block of n + 1; a row that is not live keeps its tail bit for bit."""
    R, S, E, k, L = 3, 16, 128, 4, 2
    ks = jax.random.split(jax.random.key(5), 3)
    a = jax.random.normal(ks[0], (R, S, E))
    w, b = jax.random.normal(ks[1], (k, E)), jax.random.normal(ks[2], (E,))
    n = jnp.array([1, 5, 15], jnp.int32)
    c_more, tail_more = ssm.causal_conv_rows(a, w, b, n + 1)
    _, tail = ssm.causal_conv_rows(a, w, b, n)
    tails = jnp.stack([jnp.full_like(tail, 7.0), tail])
    new = a[jnp.arange(R), n]
    mask = None if live is None else jnp.array(live)
    c, out = ssm.conv_step(tails, 1, new, w, b, mask)
    np.testing.assert_array_equal(out[0], tails[0])
    for r in range(R):
        np.testing.assert_allclose(c[r], c_more[r, int(n[r])], rtol=1e-5,
                                   atol=1e-5)
        if live is None or live[r]:
            np.testing.assert_array_equal(out[1, r], tail_more[r])
        else:
            np.testing.assert_array_equal(out[1, r], tail[r])


def test_the_tally_says_which_path_ran():
    import collections

    tally = collections.Counter()
    ops = _operands(2, 8, 128)
    ssm.selective_scan_rows(*ops, jnp.array([8, 3], jnp.int32), tally=tally)
    c, dt, A, B, C, D = ops
    ssm.state_update_rows(jnp.zeros((1, 2, N, 128)), 0, c[:, 0], dt[:, 0],
                          A, B[:, 0], C[:, 0], D,
                          live=jnp.array([True, False]), tally=tally)
    # on the CPU neither kernel runs
    assert tally == {"plain": 4}
    assert ssm._kernel_fits(512, 5120) and ssm._kernel_fits(704, 5120)
    assert ssm._kernel_fits(8, 256) and not ssm._kernel_fits(12, 256)
    assert ssm.scan_chunk(512) == 128 and ssm.scan_chunk(32) == 32

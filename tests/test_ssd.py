"""``ops/ssd.py``: the chunked scan against the recurrence written out token
by token, right-padded rows, the hand-over from the scan to the one-token
step, and the convolution's tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.ops import ssd
from progen_tpu.ops.lowering import record_lowerings

H, D, N, CHUNK = 3, 4, 5, 8

# one compiled program a shape (eagerly the chunked scan is dispatched op by
# op); ``test_both_lowerings_say_which_they_are`` reads what a trace notes
# and keeps the eager calls
_scan = jax.jit(ssd.ssd_scan, static_argnums=6)
_step = jax.jit(ssd.ssd_step)


def _inputs(rows, p, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (rows, p, H, D))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, p, H)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=-1.0, maxval=2.0))
    b = jax.random.normal(ks[3], (rows, p, N))
    c = jax.random.normal(ks[4], (rows, p, N))
    return x, dt, a, b, c


def _sequential(x, dt, a, b, c, length):
    """One row, token by token, straight from the equations."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    state = np.zeros((H, D, N))
    ys = np.zeros((x.shape[0], H, D))
    for t in range(length):
        keep = np.exp(dt[t] * a)
        state = (state * keep[:, None, None]
                 + (dt[t][:, None] * x[t])[:, :, None] * b[t][None, None, :])
        ys[t] = state @ c[t]
    return ys, state


@pytest.mark.parametrize("p", [5, 8, 9, 16, 21, 32])
def test_chunked_scan_is_the_sequential_recurrence(p):
    """Below a chunk, exactly one, one token past it, whole chunks, and a
    length that is no multiple of the chunk."""
    x, dt, a, b, c = _inputs(2, p, seed=p)
    lengths = jnp.array([p, p], jnp.int32)
    y, state = _scan(x, dt, a, b, c, lengths, CHUNK)
    assert y.shape == (2, p, H, D) and state.shape == (2, H, D, N)
    assert y.dtype == state.dtype == jnp.float32
    for i in range(2):
        want_y, want_state = _sequential(x[i], dt[i], a, b[i], c[i], p)
        np.testing.assert_allclose(y[i], want_y, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(state[i], want_state, atol=2e-5,
                                   rtol=1e-5)


@pytest.mark.parametrize("bucket", [24, 32, 64])
def test_right_padded_rows_hand_over_the_state_at_their_true_length(bucket):
    """Rows of 0, 1, 7, 8, 9 and 23 real tokens in one call, padded to a
    bucket: each row's state and its outputs at real positions are its
    own, whatever the bucket holds after them; the empty row hands over
    zeros."""
    lengths = np.array([0, 1, 7, 8, 9, 23], np.int32)
    x, dt, a, b, c = _inputs(len(lengths), bucket, seed=3)
    y, state = _scan(x, dt, a, b, c, jnp.asarray(lengths), CHUNK)
    assert np.isfinite(np.asarray(y)).all()
    for i, n in enumerate(lengths):
        want_y, want_state = _sequential(x[i], dt[i], a, b[i], c[i], n)
        np.testing.assert_allclose(y[i, :n], want_y[:n], atol=2e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(state[i], want_state, atol=2e-5,
                                   rtol=1e-5)
    assert not np.asarray(state[0]).any()
    # what stands in the padding does not matter
    junk = jnp.where(jnp.arange(bucket)[None, :, None, None]
                     >= lengths[:, None, None, None], 1e3, x)
    _, again = _scan(junk, dt, a, b, c, jnp.asarray(lengths), CHUNK)
    np.testing.assert_array_equal(again, state)


@pytest.mark.parametrize("n,k", [(5, 4), (8, 1), (13, 11)])
def test_a_scan_of_n_then_k_steps_is_a_scan_of_n_plus_k(n, k):
    x, dt, a, b, c = _inputs(2, n + k, seed=n)
    full = jnp.array([n + k] * 2, jnp.int32)
    want_y, want_state = _scan(x, dt, a, b, c, full, CHUNK)
    _, state = _scan(x, dt, a, b, c, jnp.array([n] * 2, jnp.int32), CHUNK)
    for t in range(n, n + k):
        y, state = _step(state, x[:, t], dt[:, t], a, b[:, t], c[:, t])
        np.testing.assert_allclose(y, want_y[:, t], atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=1e-5)


def test_bfloat16_operands_keep_a_float32_carry():
    x, dt, a, b, c = _inputs(2, 19, seed=7)
    lengths = jnp.array([19, 11], jnp.int32)
    want_y, want_state = _scan(x, dt, a, b, c, lengths, CHUNK)
    lo = jnp.bfloat16
    y, state = _scan(x.astype(lo), dt, a, b.astype(lo), c.astype(lo),
                     lengths, CHUNK)
    assert y.dtype == state.dtype == jnp.float32
    # a product of bfloat16 operands: a few parts in a thousand of the
    # values' spread
    assert 0 < np.abs(np.asarray(state - want_state)).max() < 0.05 * float(
        jnp.abs(want_state).max())
    assert np.abs(np.asarray(y - want_y))[1, :11].max() < 0.05 * float(
        jnp.abs(want_y).max())
    step_y, stepped = _step(state, x[:, 0].astype(lo), dt[:, 0], a,
                            b[:, 0].astype(lo), c[:, 0].astype(lo))
    assert step_y.dtype == stepped.dtype == jnp.float32


def test_an_idle_slots_state_stays_finite_for_a_thousand_steps():
    """A row that is not live steps on the same token for ever: every decay
    is at most 1, so the carry converges and never overflows."""
    x, dt, a, b, c = _inputs(2, 1, seed=5)

    def body(state, _):
        _, state = ssd.ssd_step(state, 50.0 * x[:, 0], dt[:, 0], a, b[:, 0],
                                c[:, 0])
        return state, None

    state, _ = jax.lax.scan(body, jnp.zeros((2, H, D, N)), None, length=1000)
    assert np.isfinite(np.asarray(state)).all()


def test_both_lowerings_say_which_they_are():
    x, dt, a, b, c = _inputs(1, 8)
    with record_lowerings() as chosen:
        _, state = ssd.ssd_scan(x, dt, a, b, c, jnp.array([8]), CHUNK)
        ssd.ssd_step(state, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0])
    assert chosen == {"ssd_prefill": {"xla"}, "ssd_step": {"xla"}}
    assert ssd.scanned_slots(2, 24, CHUNK) == 48
    assert ssd.scanned_slots(2, 20, CHUNK) == 48     # a partial chunk
    assert ssd.scanned_slots(3, 4, CHUNK) == 12      # shorter than a chunk


# -------------------------------------------------------------- convolution

C, K = 6, 4


def _conv_inputs(rows, p, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (rows, p, C)),
            jax.random.normal(ks[1], (C, K)), jax.random.normal(ks[2], (C,)))


def test_causal_conv_reads_zeros_before_the_first_token():
    u, w, bias = _conv_inputs(2, 9)
    got = np.asarray(ssd.causal_conv(u, w, bias))
    un, wn = np.asarray(u), np.asarray(w)
    for t in range(9):
        want = np.asarray(bias).copy()
        for j in range(K):
            if t - (K - 1) + j >= 0:
                want = want + wn[:, j] * un[:, t - (K - 1) + j]
        np.testing.assert_allclose(got[:, t], want, atol=1e-5)


@pytest.mark.parametrize("lengths", [(0, 1, 2), (3, 4, 9)])
def test_conv_tail_is_the_last_three_real_inputs(lengths):
    """Zeros where the row is shorter than three tokens, and nothing of the
    padding."""
    u, _, _ = _conv_inputs(3, 9, seed=1)
    tail = np.asarray(ssd.conv_tail(u, jnp.asarray(lengths), K))
    assert tail.shape == (3, K - 1, C)
    for i, n in enumerate(lengths):
        real = np.asarray(u[i, :n])
        want = np.concatenate([np.zeros((max(0, 3 - n), C)), real[-3:]]
                              if n else [np.zeros((3, C))])
        np.testing.assert_array_equal(tail[i], want)


def test_conv_steps_continue_the_prefills_convolution():
    u, w, bias = _conv_inputs(3, 12, seed=2)
    lengths = jnp.array([1, 2, 7])
    want = ssd.causal_conv(u, w, bias)
    tail = ssd.conv_tail(u, lengths, K)
    for j in range(4):
        new = u[jnp.arange(3), lengths + j]
        out, tail = ssd.conv_step(tail, new, w, bias)
        np.testing.assert_allclose(
            out, want[jnp.arange(3), lengths + j], atol=1e-5)


# ------------------------------------------------- B and C in groups

GH, G = 8, 4        # eight heads over four B/C groups: two heads a group


def _grouped_inputs(rows, p, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (rows, p, GH, D))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, p, GH)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (GH,), minval=-1.0, maxval=2.0))
    b = jax.random.normal(ks[3], (rows, p, G, N))
    c = jax.random.normal(ks[4], (rows, p, G, N))
    return x, dt, a, b, c


def _sequential_grouped(x, dt, a, b, c, length):
    """One row, token by token: head ``h`` reads group ``h // (GH / G)``."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    state = np.zeros((GH, D, N))
    ys = np.zeros((x.shape[0], GH, D))
    for t in range(length):
        for h in range(GH):
            g = h // (GH // G)
            state[h] = (state[h] * np.exp(dt[t, h] * a[h])
                        + dt[t, h] * np.outer(x[t, h], b[t, g]))
            ys[t, h] = state[h] @ c[t, g]
    return ys, state


@pytest.mark.parametrize("lengths,bucket", [
    ((5, 8), 8), ((9, 16), 16), ((0, 21), 24), ((1, 23), 32)],
    ids=["under-a-chunk", "whole-chunks", "an-empty-row", "ragged"])
def test_grouped_scan_step_and_recurrence_agree(lengths, bucket):
    """``G`` = 4: the chunked scan, the scan to ``n - 3`` then three
    one-token steps, and the recurrence written out head by head give one
    state and one output, right-padded rows included."""
    x, dt, a, b, c = _grouped_inputs(2, bucket, seed=bucket)
    n = jnp.asarray(lengths, jnp.int32)
    y, state = _scan(x, dt, a, b, c, n, CHUNK)
    assert y.shape == (2, bucket, GH, D) and state.shape == (2, GH, D, N)
    for i, length in enumerate(lengths):
        want_y, want_state = _sequential_grouped(x[i], dt[i], a, b[i], c[i],
                                                 length)
        np.testing.assert_allclose(y[i, :length], want_y[:length],
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(state[i], want_state, atol=2e-5,
                                   rtol=1e-5)
    back = 3
    if min(lengths) < back:
        return
    _, stepped = _scan(x, dt, a, b, c, n - back, CHUNK)
    rows = jnp.arange(2)
    for j in range(back):
        t = n - back + j
        y_t, stepped = _step(stepped, x[rows, t], dt[rows, t], a,
                             b[rows, t], c[rows, t])
        np.testing.assert_allclose(y_t, y[rows, t], atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(stepped, state, atol=2e-5, rtol=1e-5)


def test_one_group_with_and_without_its_axis_is_one_result():
    """``b, c (..., N)`` is ``(..., 1, N)``: the form without the axis
    (Granite's, whose program text is pinned) computes what the grouped
    form computes at ``G`` = 1."""
    x, dt, a, b, c = _inputs(2, 19, seed=5)
    lengths = jnp.array([19, 11], jnp.int32)
    y, state = _scan(x, dt, a, b, c, lengths, CHUNK)
    y1, state1 = _scan(x, dt, a, b[:, :, None], c[:, :, None], lengths,
                       CHUNK)
    np.testing.assert_allclose(y1, y, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(state1, state, atol=1e-6, rtol=1e-6)
    s, s1 = _step(state, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0]), \
        _step(state, x[:, 0], dt[:, 0], a, b[:, 0, None], c[:, 0, None])
    np.testing.assert_array_equal(s[0], s1[0])
    np.testing.assert_array_equal(s[1], s1[1])

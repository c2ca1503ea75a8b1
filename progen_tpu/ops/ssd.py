"""The Mamba-2 recurrence (state-space duality) twice: a chunked scan over
right-padded rows for prefill and a one-token update of every slot's carry
for decode, with the depthwise causal convolution that feeds both.

Per head ``h`` of group ``g = h // (H / G)`` (``x_t (D,)``, ``B_t,g, C_t,g
(N,)`` shared by the ``H / G`` heads of a group; ``dt_t > 0`` the step,
``a_h < 0``), everything in float32::

    S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t (x) B_t,g       # (D, N), S_{-1} = 0
    y_t = S_t C_t,g

(the caller adds the skip ``D_h x_t``, the gate and the norm).  ``b`` and
``c`` come as ``(..., G, N)``, or as ``(..., N)`` where there is ONE group
(Granite's ``mamba_n_groups`` 1: no axis of one is made, so that model's
programs are the ops they always were; Nemotron-H has eight).

:func:`ssd_scan` — ``x (R, P, H, D)``, ``dt (R, P, H)``, ``b, c (R, P, [G,]
N)`` over rows of ``lengths (R,)`` real leading tokens: ``(y (R, P, H, D)
float32, S (R, H, D, N) float32)``, the carry AT EACH ROW'S TRUE LENGTH.
``dt`` is zeroed at and past ``lengths`` (decay 1, no input), so padding
leaves the carry alone whatever the bucket, and a row of length 0 hands
over zeros; ``y`` at a pad position is finite and nothing reads it.  The
sequence is cut into chunks of ``min(chunk, P)`` tokens (``P`` padded up to
a whole number of them, again with ``dt = 0``).  With ``cum`` the cumulative
sum of ``dt a`` inside a chunk — a sum of non-positive numbers, kept in log
space and float32, so every exponent taken is of a non-positive number —
there are FOUR products a chunk:

1. ``G = C B^T`` ``(q, q)`` a group, shared by the group's heads;
2. ``y_diag = (G * exp(cum_i - cum_j) [i >= j]) (dt x)`` per head: what the
   chunk's own tokens hand each other;
3. ``states = B^T (exp(cum_last - cum_j) dt x)`` per head ``(D, N)``: what
   the chunk adds to the carry;
4. ``y_off = exp(cum_i) C S_before`` per head: what the carry before the
   chunk hands its tokens,

and between chunks ``S <- exp(cum_last) S + states``, a sequential
``lax.scan`` over the few chunks of a row.  The products' operands are in
``x``'s dtype (bfloat16 as served, the decay factors rounded with them),
accumulated in float32; ``dt``, every ``exp`` and the carry are float32.

:func:`ssd_step` — one token a slot, ``state (S, H, D, N)`` float32 read and
written once: ``(y (S, H, D) float32, state)``.  No matrix unit: the update
and the read-out are float32 elementwise passes over the carry, so the
carry is never rounded.

Both are plain XLA and say so under ``"ssd_prefill"`` / ``"ssd_step"``
(``ops/lowering.py``), where a kernel would say ``"pallas"``.

:func:`causal_conv` / :func:`conv_tail` / :func:`conv_step` — the depthwise
convolution of width ``K`` over ``u (R, P, C)`` with zeros before a row's
first token, the last ``K - 1`` REAL inputs of each row (zeros where the
row is shorter) as the decode's tail, and the one-token form over ``(tail,
u_t)``.  ``K`` multiply-adds a channel, summed and returned in float32 (the
caller rounds it once); ``bias`` None for a convolution without one
(``models/lfm2.py``'s short convolution: three taps, no bias, no
activation; the Mamba-2 block's, ``models/state.py``, has four taps, a
bias and a ``silu``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from progen_tpu.ops.lowering import note

F32 = jnp.float32


def scanned_slots(rows: int, n: int, chunk: int) -> int:
    """Token slots :func:`ssd_scan` computes for ``rows`` rows padded to
    ``n``: whole chunks, padding included."""
    q = min(chunk, n)
    return rows * -(-n // q) * q


def ssd_scan(x, dt, a, b, c, lengths, chunk: int):
    note("ssd_prefill", "xla")
    r, p, h, d = x.shape
    n = b.shape[-1]
    dtype = x.dtype
    # a group axis: the heads are (group, head of the group) below and the
    # products carry both letters; none: one letter, as ever
    groups = b.shape[2:-1]
    hs, gs = ("ge", "g") if groups else ("h", "")
    heads = groups + (h // groups[0],) if groups else (h,)
    real = jnp.arange(p)[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], dt.astype(F32), 0.0)
    q = min(chunk, p)
    pad = -p % q
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    nc = (p + pad) // q
    x = x.reshape(r, nc, q, h, d)
    dt = dt.reshape(r, nc, q, h)
    b, c = (v.reshape((r, nc, q) + groups + (n,)) for v in (b, c))
    cum = jnp.cumsum(dt * a.astype(F32), axis=2)           # (r, nc, q, h) <= 0
    xdt = x.astype(F32) * dt[..., None]

    def by_group(v, axis):
        """``v``'s head axis as the products' head letters."""
        return v.reshape(v.shape[:axis] + heads + v.shape[axis + 1:])

    # inside a chunk: token i takes from j <= i what has decayed since
    lower = jnp.tril(jnp.ones((q, q), bool))
    by_head = cum.swapaxes(2, 3)                             # (r, nc, h, q)
    seg = by_head[..., :, None] - by_head[..., None, :]     # cum_i - cum_j
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    g = jnp.einsum(f"rci{gs}n,rcj{gs}n->rc{gs}ij", c, b,
                   preferred_element_type=F32)
    y = jnp.einsum(f"rc{hs}ij,rcj{hs}d->rci{hs}d",
                   (jnp.expand_dims(g, -3) * by_group(decay, 2)).astype(
                       dtype),
                   by_group(xdt, 3).astype(dtype),
                   preferred_element_type=F32)

    # what each chunk adds to the carry, and the carry before each chunk
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)
    states = jnp.einsum(f"rcj{gs}n,rcj{hs}d->rc{hs}dn", b,
                        by_group(xdt * to_end[..., None], 3).astype(dtype),
                        preferred_element_type=F32)
    states = states.reshape(r, nc, h, d, n)
    whole = jnp.exp(cum[:, :, -1, :])                        # (r, nc, h)

    def carry_on(s, chunk_of):
        add, keep = chunk_of
        return s * keep[..., None, None] + add, s

    final, before = jax.lax.scan(
        carry_on, jnp.zeros((r, h, d, n), F32),
        (states.swapaxes(0, 1), whole.swapaxes(0, 1)))
    y_off = jnp.einsum(f"rci{gs}n,cr{hs}dn->rci{hs}d", c,
                       by_group(before, 2).astype(dtype),
                       preferred_element_type=F32)
    y = y.reshape(r, nc, q, h, d) + y_off.reshape(
        r, nc, q, h, d) * jnp.exp(cum)[..., None]
    return y.reshape(r, nc * q, h, d)[:, :p], final


def _per_head(v, heads: int):
    """``b`` or ``c`` of one token a slot, ``(S, [G,] N)``, against the
    carry ``(S, H, D, N)``: a group's row under each of its heads."""
    if v.ndim == 3:
        v = jnp.repeat(v, heads // v.shape[1], axis=1)[:, :, None, :]
    else:
        v = v[:, None, None, :]
    return v.astype(F32)


def ssd_step(state, x, dt, a, b, c):
    note("ssd_step", "xla")
    dt = dt.astype(F32)
    heads = state.shape[1]
    keep = jnp.exp(dt * a.astype(F32))                       # (S, H)
    add = (x.astype(F32) * dt[..., None])[..., None] * _per_head(b, heads)
    state = state * keep[..., None, None] + add
    y = jnp.sum(state * _per_head(c, heads), axis=-1)
    return y, state


# -------------------------------------------------------------- convolution


def _taps(window, w, bias):
    """``window (..., K, C)`` against ``w (C, K)``: the taps summed in
    float32."""
    out = jnp.sum(window.astype(F32) * w.astype(F32).T, axis=-2)
    return out if bias is None else out + bias.astype(F32)


def causal_conv(u, w, bias):
    k = w.shape[1]
    p = u.shape[1]
    front = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    out = 0.0 if bias is None else bias.astype(F32)
    for j in range(k):          # tap j reads the input k - 1 - j tokens back
        out = out + front[:, j:j + p].astype(F32) * w[:, j].astype(F32)
    return out


def conv_tail(u, lengths, k: int):
    at = lengths[:, None] - (k - 1) + jnp.arange(k - 1)[None, :]   # (R, K-1)
    rows = jnp.take_along_axis(u, jnp.clip(at, 0, u.shape[1] - 1)[..., None],
                               axis=1)
    return jnp.where((at >= 0)[..., None], rows, jnp.zeros((), u.dtype))


def conv_step(tail, u, w, bias):
    window = jnp.concatenate([tail, u[:, None].astype(tail.dtype)], axis=1)
    return _taps(window, w, bias), window[:, 1:]

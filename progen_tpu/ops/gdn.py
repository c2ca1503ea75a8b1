"""The gated delta rule twice: a chunked form over right-padded rows for
prefill and a one-token update of every slot's carry for decode.  The
depthwise convolution that feeds both is ``ops/ssd.py``'s.

Per value head ``h``, which reads key head ``h // (Hv / Hk)`` (``q_t, k_t
(Dk,)`` unit vectors, ``q`` times ``Dk^-1/2`` besides; ``v_t (Dv,)``;
``beta_t`` in (0, 1) the write strength, ``g_t <= 0`` the log of the decay
``alpha_t = exp(g_t)``), everything in float32::

    S_t = alpha_t S_{t-1} + k_t (x) beta_t (v_t - alpha_t S_{t-1}^T k_t)
    o_t = S_t^T q_t                                    # S (Dk, Dv), S_{-1} = 0

The state ERASES before it writes: what the decayed state already holds
under ``k_t`` is taken out of ``v_t`` first, so a key written twice holds its
last value and not the sum (``ops/ssd.py``'s recurrence only decays and
adds).

:func:`gdn_scan` — ``q, k (R, P, Hk, Dk)``, ``v (R, P, Hv, Dv)``, ``g, beta
(R, P, Hv)`` over rows of ``lengths (R,)`` real leading tokens: ``(o (R, P,
Hv, Dv)`` in ``v``'s dtype (accumulated in float32, rounded once as it leaves
its chunk: 0.5 GB less at two rows of 16,384), ``S (R, Hv, Dk, Dv)
float32)``, the carry AT EACH ROW'S TRUE LENGTH.  ``g`` and ``beta`` are zeroed at and past ``lengths`` (decay 1,
nothing written), so padding leaves the carry alone whatever the bucket and
a row of length 0 hands over zeros; ``o`` at a pad position is finite and
nothing reads it.  The sequence is cut into chunks of ``C = min(chunk, P)``
tokens (``P`` padded up to a whole number of them, again with ``g = beta =
0``).  With ``gam_i`` the cumulative sum of ``g`` inside a chunk — a sum of
non-positive numbers, kept in log space, so every exponent taken is of a
non-positive number — and ``K, Q, V`` a head's rows of the chunk (the WY /
UT form)::

    A  = -strict_lower[(beta_i K_i . K_j) exp(gam_i - gam_j)]
    T  = (I - A)^-1                                  # unit lower triangular
    U  = T (beta V),   W = T (beta K exp(gam))
    V' = U - W S
    O  = (Q exp(gam)) S + lower[Q K^T exp(gam_i - gam_j)] V'
    S <- exp(gam_C) S + (K exp(gam_C - gam))^T V'

``K K^T`` and ``Q K^T`` are computed once a KEY head and shared by its value
heads.  ``T`` is made by forward substitution (:func:`unit_lower_inverse`);
``T``, the two products that apply it and every ``exp`` are float32
(``Precision.HIGHEST``: the chip's default would round ``T`` to bfloat16 on
the way into the matrix unit).  Everything up to ``U`` and ``W``
is computed for a SEGMENT of ``SEGMENT`` chunks at once (2,048 tokens a row:
all 256 chunks of a 16,384 bucket side by side are 3 GB of float32
triangles); the three lines that read ``S`` are a sequential ``lax.scan``
over the chunks of a segment inside one over the segments of a row, their
operands in ``v``'s dtype (bfloat16 as served), accumulated in float32, the
carry float32.  ``P`` is padded up to whole segments (of ``min(SEGMENT,
chunks of P)`` chunks: a bucket of ``512 * 2^k`` tokens needs none).

:func:`gdn_step` — one token a slot, ``state (S, Hv, Dk, Dv)`` float32 read
and written once: ``(o (S, Hv, Dv) float32, state)``.  No matrix unit: the
decay, the erase, the write and the read-out are float32 elementwise passes
over the carry, so the carry is never rounded.

Both are plain XLA and say so under ``"gdn_prefill"`` / ``"gdn_step"``
(``ops/lowering.py``), where a kernel would say ``"pallas"``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from progen_tpu.ops.lowering import note

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
SEGMENT = 32        # chunks whose products are computed side by side


def _cut(p: int, chunk: int):
    """``(tokens a chunk, chunks a segment, segments)`` of rows padded to
    ``p``."""
    c = min(chunk, p)
    seg = min(SEGMENT, -(-p // c))
    return c, seg, -(-p // (c * seg))


def scanned_slots(rows: int, p: int, chunk: int) -> int:
    """Token slots :func:`gdn_scan` computes for ``rows`` rows padded to
    ``p``: whole segments of whole chunks, padding included."""
    c, seg, n = _cut(p, chunk)
    return rows * n * seg * c


def unit_lower_inverse(a):
    """``(I - a)^-1`` of strictly lower triangular ``a (..., C, C)``
    float32, by forward substitution.  The series ``(I + a)(I + a^2)(I +
    a^4)...`` is exact for a nilpotent ``a`` on paper and 1.7 times faster
    on the chip, but it forms powers whose entries reach ``C(62, k) |a|^k``
    before they cancel: a chunk of one repeated token (equal keys, ``beta``
    0.5) comes back 176 off in float32 where no entry of the inverse passes
    1 (PERF.md section 6, PR 63; ``tests/test_qwen3_next_model.py``)."""
    eye = jnp.eye(a.shape[-1], dtype=F32)
    return jax.scipy.linalg.solve_triangular(
        eye - a, jnp.broadcast_to(eye, a.shape), lower=True,
        unit_diagonal=True)


def _segment(s, xs):
    """One segment's chunks ``(seg, R, Hk, ...)`` over the carry ``s (R,
    Hk, E, Dk, Dv)`` before it: ``(the carry after it, o (seg, R, Hk, E, C,
    Dv)`` in ``v``'s dtype``)``."""
    q, k, v, g, beta = xs
    dtype, c = v.dtype, k.shape[-2]
    gam = jnp.cumsum(g, axis=-1)                            # <= 0
    lower = jnp.tril(jnp.ones((c, c), bool))
    apart = gam[..., :, None] - gam[..., None, :]           # gam_i - gam_j
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, apart, 0.0)), 0.0)
    kk = jnp.einsum("nrgid,nrgjd->nrgij", k, k, preferred_element_type=F32)
    qk = jnp.einsum("nrgid,nrgjd->nrgij", q, k, preferred_element_type=F32)
    a = -jnp.where(jnp.tril(lower, -1),
                   beta[..., :, None] * kk[:, :, :, None] * decay, 0.0)
    t = unit_lower_inverse(a)                           # (seg, r, hk, e, c, c)
    kf = k.astype(F32)[:, :, :, None]                   # (seg, r, hk, 1, c, dk)
    u = jnp.matmul(t, beta[..., None] * v.astype(F32), precision=HIGHEST)
    w = jnp.matmul(t, (beta * jnp.exp(gam))[..., None] * kf,
                   precision=HIGHEST).astype(dtype)
    within = (qk[:, :, :, None] * decay).astype(dtype)      # lower: decay's
    q_in = (q.astype(F32)[:, :, :, None]
            * jnp.exp(gam)[..., None]).astype(dtype)
    k_out = (kf * jnp.exp(gam[..., -1:] - gam)[..., None]).astype(dtype)
    whole = jnp.exp(gam[..., -1])                       # (seg, r, hk, e)

    def chunk_of(s, xs):
        u, w, within, q_in, k_out, whole = xs
        sd = s.astype(dtype)
        fresh = u - jnp.matmul(w, sd, preferred_element_type=F32)
        o = (jnp.matmul(q_in, sd, preferred_element_type=F32)
             + jnp.matmul(within, fresh.astype(dtype),
                          preferred_element_type=F32))
        s = s * whole[..., None, None] + jnp.einsum(
            "rgeik,rgeiv->rgekv", k_out, fresh.astype(dtype),
            preferred_element_type=F32)
        return s, o.astype(dtype)

    return jax.lax.scan(chunk_of, s, (u, w, within, q_in, k_out, whole))


def gdn_scan(q, k, v, g, beta, lengths, chunk: int):
    note("gdn_prefill", "xla")
    r, p, hk, dk = k.shape
    hv, dv = v.shape[2:]
    e = hv // hk                    # value heads a key head
    real = (jnp.arange(p)[None, :] < lengths[:, None])[..., None]
    g = jnp.where(real, g.astype(F32), 0.0)
    beta = jnp.where(real, beta.astype(F32), 0.0)
    c, seg, n = _cut(p, chunk)
    pad = n * seg * c - p
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    # segments and their chunks lead, a head's rows of a chunk are the last
    # two axes
    q, k = (x.reshape(r, n, seg, c, hk, dk).transpose(1, 2, 0, 4, 3, 5)
            for x in (q, k))                        # (n, seg, r, hk, c, dk)
    v = v.reshape(r, n, seg, c, hk, e, dv).transpose(1, 2, 0, 4, 5, 3, 6)
    g, beta = (x.reshape(r, n, seg, c, hk, e).transpose(1, 2, 0, 4, 5, 3)
               for x in (g, beta))                  # (n, seg, r, hk, e, c)
    final, o = jax.lax.scan(_segment, jnp.zeros((r, hk, e, dk, dv), F32),
                            (q, k, v, g, beta))
    o = o.transpose(2, 0, 1, 5, 3, 4, 6).reshape(r, n * seg * c, hv, dv)
    return o[:, :p], final.reshape(r, hv, dk, dv)


def gdn_step(state, q, k, v, g, beta):
    note("gdn_step", "xla")
    e = state.shape[1] // k.shape[1]
    q, k = (jnp.repeat(x.astype(F32), e, axis=1)[..., None] for x in (q, k))
    state = state * jnp.exp(g.astype(F32))[..., None, None]
    held = jnp.sum(state * k, axis=-2)                      # S^T k: (S, Hv, Dv)
    write = beta.astype(F32)[..., None] * (v.astype(F32) - held)
    state = state + k * write[..., None, :]
    return jnp.sum(state * q, axis=-2), state

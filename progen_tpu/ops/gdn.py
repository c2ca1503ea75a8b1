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
a row of length 0 hands over zeros; ``o`` at a pad position is finite
(zero in a chunk the kernel leaves alone) and nothing reads it.  The
sequence is cut into chunks of ``C = min(chunk, P)``
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
heads.  ``T``, the two products that apply it and every ``exp`` are float32
(``Precision.HIGHEST``: the chip's default would round ``T`` to bfloat16 on
the way into the matrix unit); the three lines that read ``S`` take their
operands in ``v``'s dtype (bfloat16 as served: ``W``, the in-chunk scores,
``Q exp(gam)``, ``K exp(gam_C - gam)``, ``V'`` and ``S`` itself as an
operand), accumulate in float32 and keep the carry float32.  Two lowerings
of that one contract, chosen by :func:`scan_lowering` from the backend, the
mesh in scope and the shapes, never from a knob, and noted under
``"gdn_prefill"`` (``ops/lowering.py``):

**The kernel** ``gdn_prefill_fwd`` (``"pallas"``: a TPU, no mesh in scope,
``Dk`` and ``Dv`` whole lane tiles, ``C`` whole blocks of ``SOLVED`` = 16
rows, whole key heads a value head; forward only).  Its grid is (row, group
of heads, block of chunks), the last axis sequential: a grid step holds
``STEP_TOKENS`` = 512 tokens (8 chunks of 64, an inner loop) of
``STEP_HEADS`` = 4 value heads (two key heads and both value heads of
each), their carries ``(Dk, Dv)`` float32 in a VMEM scratch from the row's
first chunk to its last and written to HBM once, at the end.  ``q``, ``k``,
``v`` and ``o`` are read and written IN PLACE as ``(1, tokens, heads *
width)`` blocks of the ``(R, P, heads * width)`` arrays the block has (no
transposed copy in or out); ``g`` and ``beta`` alone are handed over a
chunk at a time with the tokens on the lanes (``(R, groups, chunks, 2
heads, C)`` float32, 4 MB at two rows of 16,384), and the kernel makes the
cumulative ``gam`` along lanes and along sublanes with three small float32
products against a triangle of ones (no transpose).  Everything of a chunk
stays in VMEM: ``gam``, the decay mask, ``K K^T``, ``Q K^T``, ``A``, ``T``,
``U``, ``W``, ``V'``, ``O``, the carry's update — no float32 triangle is a
buffer of the admission any more.  ``T`` is made by blocks
(:func:`blocked_lower_inverse`): the diagonal 16 x 16 blocks by forward
substitution a row at a time on the vector unit (15 dependent steps, the
four blocks of a chunk and the heads of the step independent chains — the
triangles of all four heads are inverted as ONE batch of operations and a
block's column ``j`` comes from one 0 / 1 product, which keeps the kernel's
trace a quarter as long: every admission bucket traces and lowers it once
a process, cached or not), the blocks under the
diagonal by float32 products ``T21 = T22 A21 T11`` at 32 and at 64 rows; no
power of ``A`` is formed.  The kernel keeps float32 what
the XLA form keeps and rounds only where it rounds.  **It stops at a row's
length**: ``lengths`` is a prefetched scalar; a chunk whose first token is
at or past ``lengths[r]`` computes nothing, leaves the carry alone and
writes ZEROS to its rows of ``o``, and the index maps of a block wholly
past the length point at the row's last live block, so nothing is fetched
for it.  A chunk that straddles the length is computed whole with ``g =
beta = 0`` past it.  On a v5e, bfloat16, 16 / 32 heads of 128, chunks of
64: 20.3 ms a layer at 2 x 16,384 tokens with both rows full where the XLA
form takes 63.1, and 9.0 at lengths 9,000 and 3,000 (PERF.md section 6,
PR 64).

**The XLA form** (``"xla"``: the CPU, a mesh, any other shape) computes
EVERY chunk of the bucket.  ``T`` is made by forward substitution
(:func:`unit_lower_inverse`, ``solve_triangular``).  Everything up to ``U``
and ``W`` is computed for a SEGMENT of ``SEGMENT`` chunks at once (2,048
tokens a row: all 256 chunks of a 16,384 bucket side by side are 3 GB of
float32 triangles); the three lines that read ``S`` are a sequential
``lax.scan`` over the chunks of a segment inside one over the segments of a
row.  ``P`` is padded up to whole segments (of ``min(SEGMENT, chunks of
P)`` chunks: a bucket of ``512 * 2^k`` tokens needs none).

:func:`computed_slots` says how many token slots a call computes under
either (the counter ``gdn.scan_slots``: ``models/state.py``).

:func:`gdn_step` — one token a slot, ``state (S, Hv, Dk, Dv)`` float32 read
and written once: ``(o (S, Hv, Dv) float32, state)``.  No matrix unit: the
decay, the erase, the write and the read-out are float32 elementwise passes
over the carry, so the carry is never rounded.  Plain XLA, noted as
``"gdn_step"``.

:func:`kda_scan` / :func:`kda_step` — the same rule with a decay a key
CHANNEL (``g (R, P, H, Dk)``, as many key heads as value heads; the formulas
are in :func:`kda_scan`'s docstring).  The decay sits inside the sum over
``Dk``, so a chunk's two products are made by blocks of 16 rows
(:func:`decayed_products`), every exponent non-positive.  The chunked form
has two lowerings of that one contract too, chosen by
:func:`kda_scan_lowering` and noted under ``"kda_prefill"``: **the kernel**
``kda_prefill_fwd`` (``"pallas"``: a TPU, no mesh in scope, ``Dk`` and ``Dv``
whole lane tiles, ``C`` whole blocks of ``SOLVED`` rows, the products' blocks
``SOLVED`` rows) in ``gdn_prefill_fwd``'s mould — the same grid, carries,
in-place blocks of ``q``, ``k``, ``v``, ``o`` and the stop at a row's length
—, with ``g`` streamed as ``k`` is (a ``(1, tokens, heads * Dk)`` float32
block: no chunk-major copy of anything, ``beta`` alone with the tokens on the
lanes) and EVERY operation over the grid step's four heads at once: ``gam``
by one float32 product with a triangle of ones; the diagonal blocks' direct
``16 x 16 x Dk`` sums on the vector unit (:func:`_diagonal_blocks`), sent to
their columns by one 0 / 1 product; the blocks under the diagonal against
the last row of their column block, all column blocks in ONE product
(:func:`_blocks_below`); ``T`` by :func:`blocked_lower_inverse`; then the XLA
form's lines with ``gam`` a ``(C, Dk)`` array a head, float32 where it is
float32 and rounded only where it rounds.  On a v5e, bfloat16, 32 heads of
128, chunks of 64: 15.0 ms a layer at one full row of 16,384 tokens where
the XLA form takes 54.3 (the inverse 5.0 of them, the diagonal sums 3.7), 6.5
at a row of 6,000 in that bucket (PERF.md section 6, PR 66).  **The XLA
form** (:func:`xla_kda_scan`: the CPU, a mesh, any other shape):
:func:`xla_gdn_scan`'s segments and scans, every chunk of the bucket.
:func:`kda_step` is :func:`gdn_step`'s passes with the decay a ``(S, H, Dk)``
array, noted as ``"kda_step"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from progen_tpu.ops.lowering import mesh_in_scope as _mesh_in_scope
from progen_tpu.ops.lowering import note
from progen_tpu.ops.lowering import on_tpu as _on_tpu

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
SEGMENT = 32        # XLA form: chunks whose products are computed side by side
# the kernel: tokens a grid step (whole chunks), value heads a grid step (whole
# key heads), the triangle's diagonal blocks solved by substitution
STEP_TOKENS, STEP_HEADS, SOLVED = 512, 4, 16


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


def scan_lowering(p: int, hk: int, hv: int, dk: int, dv: int,
                  chunk: int) -> str:
    """``"pallas"`` or ``"xla"``: what :func:`gdn_scan` takes for rows
    padded to ``p`` tokens of ``hk`` key heads ``dk`` wide feeding ``hv``
    value heads ``dv`` wide in chunks of ``chunk``, traced here and now: the
    kernel on a TPU with no mesh in scope, both widths on the lane tile, the
    chunk ``min(chunk, p)`` whole blocks of ``SOLVED`` rows and whole key
    heads a value head."""
    kernel = (_on_tpu() and not _mesh_in_scope() and dk % 128 == 0
              and dv % 128 == 0 and min(chunk, p) % SOLVED == 0
              and hv % hk == 0)
    return "pallas" if kernel else "xla"


def computed_slots(lengths, p: int, chunk: int, lowering: str):
    """Token slots :func:`gdn_scan` computes over rows of ``lengths (R,)``
    padded to ``p`` under ``lowering``: the kernel's whole chunks up to each
    row's length, a float32 scalar; the XLA form's every chunk of every
    row, whatever the ``lengths`` (they are not looked at: a number of the
    shapes)."""
    if lowering == "xla":
        return scanned_slots(lengths.shape[0], p, chunk)
    c = min(chunk, p)
    return (jnp.sum(-(-lengths // c)) * c).astype(F32)


def _real_only(g, beta, lengths):
    """``g`` and ``beta (R, P, Hv)`` in float32, zero at and past each
    row's length: decay 1, nothing written."""
    real = (jnp.arange(g.shape[1])[None, :] < lengths[:, None])[..., None]
    return (jnp.where(real, g.astype(F32), 0.0),
            jnp.where(real, beta.astype(F32), 0.0))


def gdn_scan(q, k, v, g, beta, lengths, chunk: int):
    r, p, hk, dk = k.shape
    lowering = scan_lowering(p, hk, v.shape[2], dk, v.shape[3], chunk)
    note("gdn_prefill", lowering)
    if lowering == "pallas":
        return pallas_gdn_scan(q, k, v, g, beta, lengths, chunk)
    return xla_gdn_scan(q, k, v, g, beta, lengths, chunk)


def xla_gdn_scan(q, k, v, g, beta, lengths, chunk: int):
    """The XLA form of :func:`gdn_scan`: every chunk of the bucket, a
    segment's triangles side by side."""
    r, p, hk, dk = k.shape
    hv, dv = v.shape[2:]
    e = hv // hk                    # value heads a key head
    g, beta = _real_only(g, beta, lengths)
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


# ------------------------------------------------------------- the kernel

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=F32)


def _iota(c: int):
    """``(row, column)`` numbers of a ``(c, c)`` tile."""
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def blocked_lower_inverse(a):
    """``(I - a)^-1`` of strictly lower triangular ``a (..., C, C)`` float32
    as the kernel makes it, ``C`` whole blocks of ``SOLVED`` rows (the
    kernel hands over the triangles of all value heads of a grid step at
    once: one chain of operations, every head in it).  The diagonal blocks
    by forward substitution, a row at a time: with ``T = I`` to begin with,
    step ``j`` adds ``a[:, j] (x) T[j, :]`` to the rows under ``j``, whose
    row ``j`` is final by then — ``SOLVED - 1`` dependent steps on the
    vector unit over ``(..., C / SOLVED, SOLVED, C)``, every diagonal block
    and every head an independent chain.  The blocks under the diagonal by
    products, a level at a time: with ``D`` the inverse of the diagonal
    blocks of size ``s`` and ``L`` what ``a`` holds inside the blocks of ``2
    s`` and outside those of ``s``, the inverse at ``2 s`` is ``D + D L D``
    (``T21 = T22 A21 T11``), float32 on the matrix unit.  No power of ``a``
    is formed, so no entry on the way is larger than the inverse's own."""
    c = a.shape[-1]
    row, col = _iota(c)
    diagonal = jnp.where(row // SOLVED == col // SOLVED, a, 0.0)
    # column j of EVERY diagonal block at once: one product with the 0 / 1
    # matrix that sends lane l to lane l mod SOLVED (a row of a diagonal
    # block has nothing outside the block, so the sum has one term)
    fold = (row - row // SOLVED * SOLVED == col)[:, :SOLVED].astype(F32)
    blocks = a.shape[:-2] + (c // SOLVED, SOLVED)
    columns = _matmul(diagonal, fold).reshape(blocks + (SOLVED,))
    t = jnp.broadcast_to(
        (row == col).astype(F32).reshape(c // SOLVED, SOLVED, c),
        blocks + (c,))
    for j in range(SOLVED - 1):
        t = t + columns[..., j:j + 1] * t[..., j:j + 1, :]
    t = t.reshape(a.shape)
    size = SOLVED
    while size < c:
        under = jnp.where((row // (2 * size) == col // (2 * size))
                          & (row // size != col // size), a, 0.0)
        t = t + _matmul(_matmul(t, under), t)
        size *= 2
    return t


def _matmul(a, b):
    """``a @ b`` over leading batch axes, float32 on the matrix unit."""
    return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=F32)


def _scan_kernel(len_ref, q_ref, k_ref, v_ref, gb_ref, o_ref, carry_ref,
                 s_ref, *, c, nb, e):
    """One grid step: ``nb`` chunks of ``c`` tokens of one row, the key
    heads of one group (``e`` value heads each).  ``gb_ref (1, 1, nb, 2
    heads, c)``: a chunk's ``g`` a value head, then its ``beta``, tokens on
    the lanes.  ``s_ref (heads, Dk, Dv)`` float32 carries the state from the
    row's first chunk to its last."""
    from jax.experimental import pallas as pl

    ri, bi = pl.program_id(0), pl.program_id(2)
    length = len_ref[ri]
    heads, dk, dv = s_ref.shape
    dtype = v_ref.dtype
    row, col = _iota(c)
    lower, strict = row >= col, row > col
    summed, eye = lower.astype(F32), (row == col).astype(F32)

    @pl.when(bi == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, F32)

    def computed(ci, rows):
        gb = gb_ref[0, 0, ci]                               # (2 heads, c)
        # the cumulative decay with the tokens along the lanes and along
        # the sublanes, and beta along the sublanes: three small products
        # in place of a transpose (the matrix unit adds a float32's pieces
        # in an order of its own: a sum of non-positive numbers is held
        # at 0 from above)
        gam_rows = jnp.minimum(
            _dot(gb[:heads], summed, _NT, HIGHEST), 0.0)        # (heads, c)
        gam_cols = jnp.minimum(
            _dot(summed, gb[:heads], _NT, HIGHEST), 0.0)        # (c, heads)
        beta_cols = _dot(eye, gb[heads:], _NT, HIGHEST)

        def triangle(h, kk):
            """``(decay mask, A)`` of value head ``h``, ``(c, c)`` each."""
            gam, beta = gam_cols[:, h:h + 1], beta_cols[:, h:h + 1]
            apart = jnp.minimum(gam - gam_rows[h:h + 1], 0.0)
            decay = jnp.where(lower, jnp.exp(apart), 0.0)
            return decay, jnp.where(strict, -(beta * kk * decay), 0.0)

        def value_head(h, t, decay, qk, kf, qf):
            gam, beta = gam_cols[:, h:h + 1], beta_cols[:, h:h + 1]
            grown = jnp.exp(gam)
            values = v_ref[0, rows, h * dv:(h + 1) * dv].astype(F32)
            u = _dot(t, beta * values, _NN, HIGHEST)
            w = _dot(t, (beta * grown) * kf, _NN, HIGHEST).astype(dtype)
            within = (qk * decay).astype(dtype)
            q_in = (qf * grown).astype(dtype)
            end = gam[c - 1:c]
            k_out = (kf * jnp.exp(jnp.minimum(end - gam, 0.0))).astype(dtype)
            s = s_ref[h]
            sd = s.astype(dtype)
            fresh = (u - _dot(w, sd, _NN)).astype(dtype)
            o = _dot(q_in, sd, _NN) + _dot(within, fresh, _NN)
            o_ref[0, rows, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
            # (1, 1) to the lanes first: the chip broadcasts one way a time
            whole = jnp.exp(jnp.broadcast_to(end, (1, dv)))
            s_ref[h] = s * whole + _dot(k_out, fresh, _TN)

        # a key head's rows and products, then every value head's triangle
        # inverted in ONE chain of operations, then the heads one by one
        keys = []
        for n in range(heads // e):
            kb = k_ref[0, rows, n * dk:(n + 1) * dk]
            qb = q_ref[0, rows, n * dk:(n + 1) * dk]
            keys.append((_dot(kb, kb, _NT), _dot(qb, kb, _NT),
                         kb.astype(F32), qb.astype(F32)))
        masks = [triangle(h, keys[h // e][0]) for h in range(heads)]
        ts = blocked_lower_inverse(jnp.stack([a for _, a in masks]))
        for h in range(heads):
            value_head(h, ts[h], masks[h][0], *keys[h // e][1:])

    def chunk(ci, done):
        rows = pl.ds(pl.multiple_of(ci * c, c), c)
        live = (bi * nb + ci) * c < length

        @pl.when(live)
        def _():
            computed(ci, rows)

        @pl.when(jnp.logical_not(live))
        def _():
            o_ref[0, rows, :] = jnp.zeros((c, o_ref.shape[2]), o_ref.dtype)

        return done

    jax.lax.fori_loop(0, nb, chunk, 0)

    @pl.when(bi == pl.num_programs(2) - 1)
    def _():
        carry_ref[0] = s_ref[...]


def key_heads_a_step(hk: int, e: int) -> int:
    """Key heads a grid step: ``STEP_HEADS`` value heads' worth where that
    divides ``hk`` (the widths are lane tiles, so any count is a block)."""
    n = max(1, STEP_HEADS // e)
    while hk % n:
        n -= 1
    return n


def pallas_gdn_scan(q, k, v, g, beta, lengths, chunk: int, *,
                    interpret=None):
    """The kernel lowering of :func:`gdn_scan`.  ``q``, ``k``, ``v`` and
    ``o`` are read and written in place as ``(R, P, heads * width)``; ``g``
    and ``beta``, zeroed at and past ``lengths``, are handed over a chunk at
    a time with the tokens on the lanes (4 MB at two rows of 16,384).
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU."""
    r, p, hk, dk = k.shape
    hv, dv = v.shape[2:]
    e = hv // hk
    c = min(chunk, p)
    kh = key_heads_a_step(hk, e)
    nb = max(1, min(STEP_TOKENS // c, -(-p // c)))
    padded = -(-p // (nb * c)) * nb * c
    gb = jnp.stack(_real_only(g, beta, lengths), axis=2)
    q, k, v = (x.reshape(r, p, -1) for x in (q, k, v))
    if padded != p:
        q, k, v, gb = (
            jnp.pad(x, ((0, 0), (0, padded - p)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, gb))
    gb = gb.reshape(r, padded // c, c, 2, hk // kh, kh * e).transpose(
        0, 4, 1, 3, 5, 2).reshape(r, hk // kh, padded // c, 2 * kh * e, c)
    o, carry = _scan_call(
        q, k, v, gb, lengths.astype(jnp.int32), c=c, nb=nb, kh=kh, e=e,
        dk=dk, interpret=not _on_tpu() if interpret is None else interpret)
    return o[:, :p].reshape(r, p, hv, dv), carry


# jitted as ``gqa._flash_call`` is: a model's delta layers share ONE traced
# and lowered kernel a bucket
@functools.partial(jax.jit, static_argnames=("c", "nb", "kh", "e", "dk",
                                             "interpret"))
def _scan_call(q, k, v, gb, lengths, *, c, nb, kh, e, dk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, padded = q.shape[:2]
    hk = q.shape[2] // dk
    heads, dv, tb = kh * e, v.shape[2] // (hk * e), nb * c

    def fetched(ri, bi, len_ref):
        # a block wholly past the row's length is neither computed nor
        # fetched: its steps point at the row's last live block
        return jnp.minimum(bi, jnp.maximum(-(-len_ref[ri] // tb) - 1, 0))

    def tokens(ri, gi, bi, len_ref):
        return ri, fetched(ri, bi, len_ref), gi

    def gates(ri, gi, bi, len_ref):
        return ri, gi, fetched(ri, bi, len_ref), 0, 0

    return pl.pallas_call(
        functools.partial(_scan_kernel, c=c, nb=nb, e=e),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r, hk // kh, padded // tb),
            in_specs=[pl.BlockSpec((1, tb, kh * dk), tokens),
                      pl.BlockSpec((1, tb, kh * dk), tokens),
                      pl.BlockSpec((1, tb, heads * dv), tokens),
                      pl.BlockSpec((1, 1, nb, 2 * heads, c), gates)],
            out_specs=[
                pl.BlockSpec((1, tb, heads * dv),
                             lambda ri, gi, bi, len_ref: (ri, bi, gi)),
                pl.BlockSpec((1, heads, dk, dv),
                             lambda ri, gi, bi, len_ref: (ri, gi, 0, 0))],
            scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((r, hk * e, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_prefill_fwd",
    )(lengths, q, k, v, gb)


def gdn_step(state, q, k, v, g, beta):
    note("gdn_step", "xla")
    e = state.shape[1] // k.shape[1]
    q, k = (jnp.repeat(x.astype(F32), e, axis=1)[..., None] for x in (q, k))
    state = state * jnp.exp(g.astype(F32))[..., None, None]
    held = jnp.sum(state * k, axis=-2)                      # S^T k: (S, Hv, Dv)
    write = beta.astype(F32)[..., None] * (v.astype(F32) - held)
    state = state + k * write[..., None, :]
    return jnp.sum(state * q, axis=-2), state


# ------------------------------------------------- a decay a channel (KDA)

BLOCK = 16      # rows of a block of :func:`kda_scan`'s decayed products


def _diagonal_products(qb, kb, gb):
    """The decayed products INSIDE the diagonal blocks ``(..., nb, B, Dk)``
    float32: ``(sum_d K_id K_jd exp(gam_id - gam_jd), the same under Q_id)``
    over ``i >= j`` of a block, ``(..., nb, B, B)`` each, zero above the
    diagonal.  The ``B x B x Dk`` sum taken directly on the vector unit:
    every exponent is of a non-positive number (``PERF.md`` section 6, PR
    65, has it timed beside a reference row inside the block on the matrix
    unit, whose positive exponents reach ``B`` times the gate's bound)."""
    b = kb.shape[-2]
    inside = jnp.tril(jnp.ones((b, b), bool))[..., None]
    apart = gb[..., :, None, :] - gb[..., None, :, :]       # gam_i - gam_j
    decay = jnp.where(inside, jnp.exp(jnp.where(inside, apart, 0.0)), 0.0)
    cols = kb[..., None, :, :] * decay
    return (jnp.sum(kb[..., :, None, :] * cols, axis=-1),
            jnp.sum(qb[..., :, None, :] * cols, axis=-1))


def decayed_products(q, k, gam, block: int, dtype):
    """``(sum_d K_id K_jd exp(gam_id - gam_jd), sum_d Q_id K_jd exp(gam_id -
    gam_jd))`` over ``i >= j`` of a chunk, ``(..., C, C)`` float32 each and
    zero above the diagonal, from ``q, k, gam (..., C, Dk)`` float32 with
    ``gam`` the cumulative log decay a channel (non-increasing along ``C``).
    The decay sits INSIDE the sum over ``d``, so no ``C x C`` mask comes out
    of it, and ``exp(-gam_j)`` alone overflows: the chunk is cut into blocks
    of ``block`` rows.  A block of rows ``I`` over a block of columns ``J <
    I``, ``r`` the last row of ``J``: ``(K_I exp(gam_I - gam_r)) (K_J
    exp(gam_r - gam_J))^T`` — both exponents non-positive, the factors
    rounded to ``dtype`` like every operand of a served product, float32
    accumulation.  The diagonal blocks: :func:`_diagonal_products`."""
    c, dk = k.shape[-2:]
    if c % block:
        block = c
    nb, lead = c // block, k.shape[:-2]
    cut = lead + (nb, block, dk)
    qb, kb, gb = q.reshape(cut), k.reshape(cut), gam.reshape(cut)
    kk, qk = _diagonal_products(qb, kb, gb)
    same = jnp.eye(nb, dtype=F32)[:, None, :, None]         # (I, 1, J, 1)
    kk, qk = ((x[..., None, :] * same).reshape(lead + (c, c))
              for x in (kk, qk))
    if nb == 1:
        return kk, qk
    ref = gb[..., -1, :]                                    # (..., nb, Dk)
    cols = (kb * jnp.exp(ref[..., None, :] - gb)).astype(dtype)
    rows = jnp.exp(jnp.minimum(
        gam[..., None, :, :] - ref[..., :, None, :], 0.0))  # (..., J, C, Dk)
    under = (jnp.arange(c)[:, None] // block
             > jnp.arange(c)[None, :] // block)

    def below(x):
        scaled = (x[..., None, :, :] * rows).astype(dtype)
        out = jnp.einsum("...jid,...jbd->...ijb", scaled, cols,
                         preferred_element_type=F32)
        return jnp.where(under, out.reshape(lead + (c, c)), 0.0)

    return kk + below(k), qk + below(q)


def _kda_segment(block, s, xs):
    """One segment's chunks ``(seg, R, H, C, ...)`` over the carry ``s (R,
    H, Dk, Dv)`` before it: ``(the carry after it, o (seg, R, H, C, Dv)`` in
    ``v``'s dtype``)``; :func:`_segment` with a decay a channel."""
    q, k, v, g, beta = xs
    dtype, c = v.dtype, k.shape[-2]
    gam = jnp.cumsum(g, axis=-2)                            # <= 0
    qf, kf = q.astype(F32), k.astype(F32)
    kk, qk = decayed_products(qf, kf, gam, block, dtype)
    a = -jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                   beta[..., None] * kk, 0.0)
    t = unit_lower_inverse(a)                               # (seg, r, h, c, c)
    grown, end = jnp.exp(gam), gam[..., -1:, :]
    u = jnp.matmul(t, beta[..., None] * v.astype(F32), precision=HIGHEST)
    w = jnp.matmul(t, beta[..., None] * (kf * grown),
                   precision=HIGHEST).astype(dtype)
    within = qk.astype(dtype)                               # lower: its own
    q_in = (qf * grown).astype(dtype)
    k_out = (kf * jnp.exp(end - gam)).astype(dtype)
    whole = jnp.exp(end[..., 0, :])                         # (seg, r, h, dk)

    def chunk_of(s, xs):
        u, w, within, q_in, k_out, whole = xs
        sd = s.astype(dtype)
        fresh = u - jnp.matmul(w, sd, preferred_element_type=F32)
        o = (jnp.matmul(q_in, sd, preferred_element_type=F32)
             + jnp.matmul(within, fresh.astype(dtype),
                          preferred_element_type=F32))
        s = s * whole[..., None] + jnp.einsum(
            "rhik,rhiv->rhkv", k_out, fresh.astype(dtype),
            preferred_element_type=F32)
        return s, o.astype(dtype)

    return jax.lax.scan(chunk_of, s, (u, w, within, q_in, k_out, whole))


def kda_scan_lowering(p: int, dk: int, dv: int, chunk: int,
                      block: int) -> str:
    """``"pallas"`` or ``"xla"``: what :func:`kda_scan` takes for rows padded
    to ``p`` tokens, keys ``dk`` and values ``dv`` wide, in chunks of
    ``chunk`` cut into blocks of ``block`` rows, traced here and now: the
    kernel on a TPU with no mesh in scope, both widths on the lane tile, the
    chunk ``min(chunk, p)`` whole blocks of ``SOLVED`` rows and the products'
    blocks the inverse's own (``block == SOLVED``).  Any count of heads will
    do (:func:`key_heads_a_step`), so it is not asked."""
    kernel = (_on_tpu() and not _mesh_in_scope() and dk % 128 == 0
              and dv % 128 == 0 and min(chunk, p) % SOLVED == 0
              and block == SOLVED)
    return "pallas" if kernel else "xla"


def kda_scan(q, k, v, g, beta, lengths, chunk: int, block: int = BLOCK):
    """:func:`gdn_scan` with a decay a CHANNEL (Kimi Delta Attention):
    ``q, k (R, P, H, Dk)``, ``v (R, P, H, Dv)``, ``g (R, P, H, Dk)`` the log
    of the decay ``alpha_t = exp(g_t)`` a key channel, ``beta (R, P, H)``::

        S_t = diag(alpha_t) S_{t-1} + k_t (x) beta_t (v_t - (diag(alpha_t)
              S_{t-1})^T k_t),   o_t = S_t^T q_t

    and in chunks of ``C``, ``gam`` the cumulative sum of ``g`` inside a
    chunk, a vector over ``Dk``::

        A  = -strict_lower[beta_i sum_d K_id K_jd exp(gam_id - gam_jd)]
        T  = (I - A)^-1,   U = T (beta V),   W = T (beta K exp(gam))
        V' = U - W S
        O  = (Q exp(gam)) S + lower[sum_d Q_id K_jd exp(gam_id - gam_jd)] V'
        S <- diag(exp(gam_C)) S + (K exp(gam_C - gam))^T V'

    Results, padding (``g = beta = 0`` at and past ``lengths``; the carry at
    each row's TRUE length) and precision as :func:`gdn_scan`; the two
    decayed products by blocks of ``block`` rows (:func:`decayed_products`),
    so that every exponent taken is of a non-positive number whatever the
    gate's bound.  Two lowerings of that one contract, chosen by
    :func:`kda_scan_lowering` and noted as ``"kda_prefill"``: the kernel
    ``kda_prefill_fwd`` (:func:`pallas_kda_scan`) and the XLA form
    (:func:`xla_kda_scan`)."""
    r, p, h, dk = k.shape
    lowering = kda_scan_lowering(p, dk, v.shape[3], chunk, block)
    note("kda_prefill", lowering)
    if lowering == "pallas":
        return pallas_kda_scan(q, k, v, g, beta, lengths, chunk)
    return xla_kda_scan(q, k, v, g, beta, lengths, chunk, block)


def xla_kda_scan(q, k, v, g, beta, lengths, chunk: int, block: int = BLOCK):
    """The XLA form of :func:`kda_scan`: every chunk of the bucket, a
    segment's triangles side by side (:func:`xla_gdn_scan`'s segments)."""
    r, p, h, dk = k.shape
    dv = v.shape[3]
    real = jnp.arange(p)[None, :] < lengths[:, None]
    g = jnp.where(real[..., None, None], g.astype(F32), 0.0)
    beta = jnp.where(real[..., None], beta.astype(F32), 0.0)
    c, seg, n = _cut(p, chunk)
    pad = n * seg * c - p
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    # segments and their chunks lead, a head's rows of a chunk are the last
    # two axes
    q, k, v, g = (x.reshape(r, n, seg, c, h, -1).transpose(1, 2, 0, 4, 3, 5)
                  for x in (q, k, v, g))            # (n, seg, r, h, c, d)
    beta = beta.reshape(r, n, seg, c, h).transpose(1, 2, 0, 4, 3)
    final, o = jax.lax.scan(functools.partial(_kda_segment, block),
                            jnp.zeros((r, h, dk, dv), F32),
                            (q, k, v, g, beta))
    o = o.transpose(2, 0, 1, 4, 3, 5).reshape(r, n * seg * c, h, dv)
    return o[:, :p], final


def _diagonal_blocks(q, k, gam):
    """:func:`_diagonal_products` as the kernel makes them, of every block
    of ``q, k, gam (heads, c, dk)`` float32: ``(heads, blocks, 16, 16)``
    twice, ``i`` then ``j``.  The mask comes from an iota (the chip's
    compiler takes no boolean constant) and the exponent is held at 0 from
    above (``gam`` is a product's sum)."""
    heads, c, dk = k.shape
    cut = (heads, c // SOLVED, SOLVED, dk)
    q, k, gam = q.reshape(cut), k.reshape(cut), gam.reshape(cut)
    pair = (SOLVED, SOLVED, dk)
    inside = (jax.lax.broadcasted_iota(jnp.int32, pair, 0)
              >= jax.lax.broadcasted_iota(jnp.int32, pair, 1))
    apart = gam[:, :, :, None] - gam[:, :, None]            # gam_i - gam_j
    cols = k[:, :, None] * jnp.where(
        inside, jnp.exp(jnp.minimum(apart, 0.0)), 0.0)
    return (jnp.sum(k[:, :, :, None] * cols, axis=-1),
            jnp.sum(q[:, :, :, None] * cols, axis=-1))


def _blocks_below(q, k, gam, ends, dtype):
    """The decayed products of the blocks UNDER the diagonal, ``k`` over
    ``q`` ``(heads, 2 c, c)`` float32 (what lies in and above the diagonal
    blocks is not theirs: the caller masks it).  ``ends[J] (heads, 1, dk)``
    is ``gam`` at the last row of block ``J``, the reference of its columns:
    rows ``(x exp(gam - ends[J]))``, columns ``(k exp(ends[J] - gam_J))``,
    both exponents non-positive (held there: ``gam`` is a product's sum),
    both factors rounded to ``dtype``.  ONE product a chunk: block ``J``'s
    row factors side by side on the lanes, its columns' factors zero
    outside its own rows."""
    heads, c, dk = k.shape
    own = jnp.concatenate([jnp.broadcast_to(e, (heads, SOLVED, dk))
                           for e in ends], 1)
    cols = (k * jnp.exp(jnp.minimum(own - gam, 0.0))).astype(dtype)
    block = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) // SOLVED
    down = [jnp.exp(jnp.minimum(gam - e, 0.0)) for e in ends[:-1]]
    rows = jnp.concatenate(
        [jnp.concatenate([(x * d).astype(dtype) for d in down], 2)
         for x in (k, q)], 1)                           # (heads, 2 c, J dk)
    mine = jnp.concatenate(
        [jnp.where(block == j, cols, jnp.zeros_like(cols))
         for j in range(len(down))], 2)                 # (heads, c, J dk)
    return jnp.einsum("hid,hjd->hij", rows, mine,
                      preferred_element_type=F32)


def _kda_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, carry_ref,
                s_ref, *, c, nb):
    """One grid step: ``nb`` chunks of ``c`` tokens of one row, one group of
    heads, every operation over the group's heads at once.  ``g_ref (1,
    tokens, heads * Dk)`` float32 as ``k_ref`` is; ``b_ref (1, 1, nb, heads,
    c)``: a chunk's ``beta`` a head, tokens on the lanes.  ``s_ref (heads,
    Dk, Dv)`` float32 carries the state from the row's first chunk to its
    last."""
    from jax.experimental import pallas as pl

    ri, bi = pl.program_id(0), pl.program_id(2)
    length = len_ref[ri]
    heads, dk, dv = s_ref.shape
    dtype = v_ref.dtype
    row, col = _iota(c)
    strict, summed = row > col, (row >= col).astype(F32)
    same, under = row // SOLVED == col // SOLVED, row // SOLVED > col // SOLVED
    # sends column l of a block's 16 to the lanes l, 16 + l, 32 + l, ...
    spread = (col - col // SOLVED * SOLVED == row)[:SOLVED].astype(F32)
    token = row[:, :1]

    @pl.when(bi == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, F32)

    def a_head(x, width):
        """``(heads, rows, width)`` of ``x (rows, heads * width)``."""
        return jnp.stack([x[:, h * width:(h + 1) * width]
                          for h in range(heads)])

    def columns(x):
        """``(heads, rows, 1)`` of ``x (heads, rows)``, a head's numbers
        along the sublanes: a product with the identity in place of a
        transpose."""
        i, j = _iota(x.shape[1])
        return a_head(_dot((i == j).astype(F32), x, _NT, HIGHEST), 1)

    def computed(ci, rows):
        real = (bi * nb + ci) * c + token < length
        # the cumulative decay of every head at once: a float32 product with
        # a triangle of ones, held at 0 from above (the matrix unit adds a
        # float32's pieces in an order of its own)
        g = jnp.where(real, g_ref[0, rows, :], 0.0)
        gam = a_head(jnp.minimum(_dot(summed, g, _NN, HIGHEST), 0.0), dk)
        kf = a_head(k_ref[0, rows, :], dk).astype(F32)
        qf = a_head(q_ref[0, rows, :], dk).astype(F32)
        beta = columns(b_ref[0, 0, ci])
        # the decayed products by blocks of 16 rows: the diagonal blocks'
        # direct sums sent to their columns by a 0 / 1 product, the blocks
        # under them against the last row of their column block
        kk, qk = _diagonal_blocks(qf, kf, gam)
        both = _matmul(jnp.concatenate([kk.reshape(heads, c, SOLVED),
                                        qk.reshape(heads, c, SOLVED)], 1),
                       spread)                          # (heads, 2 c, c)
        kk, qk = (jnp.where(same, x, 0.0) for x in (both[:, :c], both[:, c:]))
        ends = [gam[:, (j + 1) * SOLVED - 1:(j + 1) * SOLVED]
                for j in range(c // SOLVED)]            # (heads, 1, dk) each
        if len(ends) > 1:
            below = _blocks_below(qf, kf, gam, ends, dtype)
            kk, qk = (x + jnp.where(under, y, 0.0)
                      for x, y in ((kk, below[:, :c]), (qk, below[:, c:])))
        t = blocked_lower_inverse(jnp.where(strict, -(beta * kk), 0.0))
        # ``value_head``'s lines with ``gam`` a (c, dk) array a head
        grown, end = jnp.exp(gam), ends[-1]
        values = a_head(v_ref[0, rows, :], dv).astype(F32)
        u = _matmul(t, beta * values)
        w = _matmul(t, beta * (kf * grown)).astype(dtype)
        q_in = (qf * grown).astype(dtype)
        k_out = (kf * jnp.exp(jnp.minimum(end - gam, 0.0))).astype(dtype)
        s = s_ref[...]
        sd = s.astype(dtype)
        fresh = (u - jnp.matmul(w, sd, preferred_element_type=F32)).astype(
            dtype)
        o = (jnp.matmul(q_in, sd, preferred_element_type=F32)
             + jnp.matmul(qk.astype(dtype), fresh,
                          preferred_element_type=F32))
        o_ref[0, rows, :] = jnp.concatenate(
            [o[h] for h in range(heads)], 1).astype(o_ref.dtype)
        whole = jnp.exp(columns(jnp.concatenate(
            [end[h] for h in range(heads)], 0)))        # (heads, dk, 1)
        s_ref[...] = s * whole + jnp.einsum(
            "hck,hcv->hkv", k_out, fresh, preferred_element_type=F32)

    def chunk(ci, done):
        rows = pl.ds(pl.multiple_of(ci * c, c), c)
        live = (bi * nb + ci) * c < length

        @pl.when(live)
        def _():
            computed(ci, rows)

        @pl.when(jnp.logical_not(live))
        def _():
            o_ref[0, rows, :] = jnp.zeros((c, o_ref.shape[2]), o_ref.dtype)

        return done

    jax.lax.fori_loop(0, nb, chunk, 0)

    @pl.when(bi == pl.num_programs(2) - 1)
    def _():
        carry_ref[0] = s_ref[...]


def pallas_kda_scan(q, k, v, g, beta, lengths, chunk: int, *,
                    interpret=None):
    """The kernel lowering of :func:`kda_scan` (blocks of ``SOLVED`` rows).
    ``q``, ``k``, ``v``, ``o`` and ``g`` are read and written in place as
    ``(R, P, heads * width)``, ``g`` float32 and zeroed past ``lengths`` in
    the kernel; ``beta`` alone, zeroed at and past ``lengths``, is handed
    over a chunk at a time with the tokens on the lanes.  ``interpret=None``
    auto-selects the Pallas interpreter off-TPU."""
    r, p, h, dk = k.shape
    dv = v.shape[3]
    c = min(chunk, p)
    kh = key_heads_a_step(h, 1)
    nb = max(1, min(STEP_TOKENS // c, -(-p // c)))
    padded = -(-p // (nb * c)) * nb * c
    real = jnp.arange(p)[None, :] < lengths[:, None]
    beta = jnp.where(real[..., None], beta.astype(F32), 0.0)
    q, k, v, g = (x.reshape(r, p, -1) for x in (q, k, v, g.astype(F32)))
    if padded != p:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, padded - p), (0, 0)))
                            for x in (q, k, v, g, beta))
    beta = beta.reshape(r, padded // c, c, h // kh, kh).transpose(
        0, 3, 1, 4, 2)                      # (r, groups, chunks, heads, c)
    o, carry = _kda_call(
        q, k, v, g, beta, lengths.astype(jnp.int32), c=c, nb=nb, kh=kh,
        dk=dk, interpret=not _on_tpu() if interpret is None else interpret)
    return o[:, :p].reshape(r, p, h, dv), carry


# jitted as ``_scan_call`` is: a model's delta layers share ONE traced and
# lowered kernel a bucket
@functools.partial(jax.jit, static_argnames=("c", "nb", "kh", "dk",
                                             "interpret"))
def _kda_call(q, k, v, g, beta, lengths, *, c, nb, kh, dk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, padded = q.shape[:2]
    h = q.shape[2] // dk
    dv, tb = v.shape[2] // h, nb * c

    def fetched(ri, bi, len_ref):
        # a block wholly past the row's length is neither computed nor
        # fetched: its steps point at the row's last live block
        return jnp.minimum(bi, jnp.maximum(-(-len_ref[ri] // tb) - 1, 0))

    def tokens(ri, gi, bi, len_ref):
        return ri, fetched(ri, bi, len_ref), gi

    def gates(ri, gi, bi, len_ref):
        return ri, gi, fetched(ri, bi, len_ref), 0, 0

    return pl.pallas_call(
        functools.partial(_kda_kernel, c=c, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r, h // kh, padded // tb),
            in_specs=[pl.BlockSpec((1, tb, kh * dk), tokens),
                      pl.BlockSpec((1, tb, kh * dk), tokens),
                      pl.BlockSpec((1, tb, kh * dv), tokens),
                      pl.BlockSpec((1, tb, kh * dk), tokens),
                      pl.BlockSpec((1, 1, nb, kh, c), gates)],
            out_specs=[
                pl.BlockSpec((1, tb, kh * dv),
                             lambda ri, gi, bi, len_ref: (ri, bi, gi)),
                pl.BlockSpec((1, kh, dk, dv),
                             lambda ri, gi, bi, len_ref: (ri, gi, 0, 0))],
            scratch_shapes=[pltpu.VMEM((kh, dk, dv), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((r, h, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_prefill_fwd",
    )(lengths, q, k, v, g, beta)


def kda_step(state, q, k, v, g, beta):
    """:func:`gdn_step` with a decay a channel: ``g (S, H, Dk)``; float32
    elementwise passes over the carry, noted as ``"kda_step"``."""
    note("kda_step", "xla")
    q, k = (x.astype(F32)[..., None] for x in (q, k))
    state = state * jnp.exp(g.astype(F32))[..., None]
    held = jnp.sum(state * k, axis=-2)                      # S^T k: (S, H, Dv)
    write = beta.astype(F32)[..., None] * (v.astype(F32) - held)
    state = state + k * write[..., None, :]
    return jnp.sum(state * q, axis=-2), state

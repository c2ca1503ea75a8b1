"""The causal attention core of a latent-attention (MLA) prefill: queries
and keys of ``nope + rope`` numbers a head (128 + 64 as LongCat publishes
them), values of another width (128), every head, every real position.

:func:`prefill_attention` takes the expanded per-head operands and returns
the per-head outputs laid out as the output projection reads them,
``(R, P, H * v)``.  ``lengths (R,)`` says how many leading positions of each
row are real; the contract is the output AT REAL POSITIONS (a real query
sees real keys only, because attention is causal), while a pad position's
output is finite and otherwise unspecified — nothing downstream of a
prefill reads it.  An optional ``keep (R, P, P)`` — one byte a pair, the same
for every head — thins the causal pairs: ``(t, s)`` is attended iff ``s <=
t`` and ``keep[r, t, s]``.  LongCat's and DeepSeek-V2's admissions
(``models/latent.py``) call without one and trace the program they traced
before the operand existed; dots3's full layers (``ops/dsa.py``) bring the
indexer's selection as one.  Two lowerings keep that contract, chosen from
what the code can observe and never from a knob (as ``ops/row_write.py``):

* **Pallas kernel** ``mla_prefill_fwd`` — on a TPU backend, no mesh in
  scope, 2- or 4-byte floats, ``nope`` and ``v`` multiples of 128 (the lane
  tile: the output block is one head's columns of ``(R, P, H * v)``),
  ``rope`` a multiple of 64, and ``P`` a multiple of 512 (the smallest
  query tile; LongCat's prefill buckets are 512 * 2^k).  A flash kernel:
  grid ``(R, H, P / bq, P / bk)``, the key axis innermost; a ``(bq, bk)``
  score tile ``q_nope k_nope^T + q_rope k_r^T`` is accumulated in float32
  from the compute-dtype operands and scaled in float32, lives in VMEM
  only, and updates a float32 running max, running sum and output
  accumulator; probabilities are cast to the compute dtype for the value
  product alone, and the one division by the sum comes at the end.  ``k_r``
  is shared by the heads through its index map, so neither ``[k_nope |
  k_r]`` nor ``[q_nope | q_rope]`` is ever built.  ``lengths`` is
  scalar-prefetched: a key tile wholly above the diagonal or wholly past
  the row's length is not visited (its index map points at the last tile
  that is, so it is not fetched either), only tiles the diagonal crosses
  pay for the iota mask, and a query tile that starts at or past the row's
  length is not computed — its output is written as zeros.  A row of
  length 0 costs no attention.  ``keep`` is one more operand in ``(bq,
  bk)`` int8 tiles on the keys' clamped index map (an unvisited tile's mask
  is not fetched either) and one more select on every visited tile.  Under
  it a row may keep no key of its first tiles, nor itself, so the running
  maximum starts at a finite floor (``MASKED_FLOOR``) in place of ``-inf``:
  ``exp(-inf - floor)`` is 0 where ``exp(-inf - -inf)`` would be NaN, and
  the first kept key's ``alpha`` is 0 as before.  Every row of a visited
  query tile has to keep a key it can see by its last tile (the one
  division): dots3's keeps ``min(t + 1, top_k)`` of them.
* **blocked XLA** — everywhere else (the CPU of tier-1, the tests' tiny
  widths, any trace under a mesh): blocks of ``QUERY_BLOCK`` query rows
  against the keys they can see, float32 softmax.  It computes the pad
  positions too.

Which one a traced call took is noted under ``"mla_prefill"``
(``ops/lowering.py``; ``ServingEngine.status()["mla_prefill"]``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from progen_tpu.ops.lowering import mesh_in_scope as _mesh_in_scope
from progen_tpu.ops.lowering import note
from progen_tpu.ops.lowering import on_tpu as _on_tpu

F32 = jnp.float32
QUERY_BLOCK = 256     # blocked XLA form: query rows per score block
# the kernel's query and key tile on a v5e (PERF.md section 6, PR 31, has
# the nine pairs measured), halved down to ``MIN_TILE`` until it divides P
TILE, MIN_TILE = 1024, 512
# the running maximum's start under a keep mask (``_flash_kernel``)
MASKED_FLOOR = -1e30


def blocked_prefill_attention(q_nope, q_rope, k_nope, k_r, v, keep=None):
    """The XLA form: every position computed, the score tensor ``(R, H,
    QUERY_BLOCK, <= P)`` float32 and the work the causal half."""
    r, n, heads, _ = q_nope.shape
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, None], (r, heads) + k_r.shape[1:])],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
    scale = 1.0 / math.sqrt(q.shape[-1])
    outs = []
    for s in range(0, n, QUERY_BLOCK):
        e = min(s + QUERY_BLOCK, n)
        logits = jnp.einsum("rhqd,rhkd->rhqk", q[:, :, s:e], k[:, :, :e],
                            preferred_element_type=F32) * scale
        causal = jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None]
        if keep is not None:
            causal = (causal & (keep[:, s:e, :e] != 0))[:, None]
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        outs.append(jnp.einsum(
            "rhqk,rhkd->rqhd", probs.astype(v.dtype), v[:, :, :e],
            preferred_element_type=F32).astype(v.dtype))
    return jnp.concatenate(outs, axis=1).reshape(r, n, -1)


def _dot_t(a, b):  # a @ b^T, float32 accumulate
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _flash_kernel(len_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, *refs,
                  scale, bq, bk, masked):
    """``refs``: ``(o, m, l, acc)``, after ``keep (1, bq, bk)`` where the
    call is ``masked``."""
    from jax.experimental import pallas as pl

    keep_ref = refs[0] if masked else None
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    # under a mask a row may keep no key of the tiles seen so far: a FINITE
    # floor under the running maximum keeps ``exp(-inf - m)`` at 0 (not
    # NaN) until its first kept key comes, whose ``alpha`` is then 0 too
    floor = -jnp.inf if keep_ref is None else MASKED_FLOOR
    length = len_ref[pl.program_id(0)]
    ki = pl.program_id(3)
    q0, k0 = pl.program_id(2) * bq, ki * bk
    live = q0 < length          # the query tile holds a real position

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, floor, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def tile(on_diagonal):
        s = (_dot_t(qn_ref[0, 0], kn_ref[0, 0])
             + _dot_t(qr_ref[0, 0], kr_ref[0])) * scale
        if on_diagonal:
            rows = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, -jnp.inf)
        if keep_ref is not None:
            s = jnp.where(keep_ref[0] != 0, s, -jnp.inf)
        # without a mask key tile 0 is always visited first and shows every
        # row its key 0, so ``m_next`` is finite from the first tile on
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=F32)
        m_ref[...] = m_next

    # a key tile is visited if a real query of this tile can see a real
    # key of it; it needs the mask only where the diagonal crosses it
    seen = live & (k0 < length) & (k0 < q0 + bq)
    crossed = k0 + bk - 1 > q0
    pl.when(seen & crossed)(functools.partial(tile, True))
    pl.when(seen & jnp.logical_not(crossed))(functools.partial(tile, False))

    last = ki == pl.num_programs(3) - 1

    @pl.when(last & live)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    @pl.when(last & jnp.logical_not(live))
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def fitted_tile(n: int) -> int:
    """The largest of ``TILE``, ``TILE / 2``, ... down to ``MIN_TILE`` that
    divides ``n``."""
    tile = TILE
    while tile > MIN_TILE and n % tile:
        tile //= 2
    return tile


def pallas_prefill_attention(q_nope, q_rope, k_nope, k_r, v, lengths,
                             keep=None, *, block_q=None, block_k=None,
                             interpret=None):
    """The kernel lowering.  ``q_nope (R, H, P, nope)``, ``q_rope (R, H, P,
    rope)``, ``k_nope (R, H, P, nope)``, ``k_r (R, P, rope)``, ``v (R, H,
    P, vd)``, ``lengths (R,)``, ``keep (R, P, P)`` int8 or ``None`` ->
    ``(R, P, H * vd)``.  ``interpret=None`` auto-selects the Pallas
    interpreter off-TPU; ``block_q`` / ``block_k`` default to
    :func:`fitted_tile`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    r, heads, n, nope = q_nope.shape
    rope, vd = q_rope.shape[-1], v.shape[-1]
    bq = block_q or fitted_tile(n)
    bk = block_k or fitted_tile(n)
    if n % bq or n % bk:
        raise ValueError(f"tiles ({bq}, {bk}) do not divide P = {n}")

    def last_key_tile(ri, qi, len_ref):
        # the last tile a real query of tile ``qi`` sees: under the
        # diagonal and under the row's length
        return jnp.minimum((qi * bq + bq - 1) // bk,
                           jnp.maximum(len_ref[ri] - 1, 0) // bk)

    def q_map(ri, hi, qi, ki, len_ref):
        # a tile past the length is not computed: keep the last real one
        return ri, hi, jnp.minimum(
            qi, jnp.maximum(len_ref[ri] - 1, 0) // bq), 0

    def kv_map(ri, hi, qi, ki, len_ref):
        return ri, hi, jnp.minimum(ki, last_key_tile(ri, qi, len_ref)), 0

    def kr_map(ri, hi, qi, ki, len_ref):
        return ri, jnp.minimum(ki, last_key_tile(ri, qi, len_ref)), 0

    def keep_map(ri, hi, qi, ki, len_ref):
        # the queries' and the keys' clamps: an unvisited tile is not fetched
        return (ri, q_map(ri, hi, qi, ki, len_ref)[2],
                kr_map(ri, hi, qi, ki, len_ref)[1])

    masks = [] if keep is None else [(keep, pl.BlockSpec((1, bq, bk),
                                                         keep_map))]
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=1.0 / math.sqrt(nope + rope),
                          bq=bq, bk=bk, masked=bool(masks)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r, heads, n // bq, n // bk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, nope), q_map),
                pl.BlockSpec((1, 1, bq, rope), q_map),
                pl.BlockSpec((1, 1, bk, nope), kv_map),
                pl.BlockSpec((1, bk, rope), kr_map),
                pl.BlockSpec((1, 1, bk, vd), kv_map),
                *[spec for _, spec in masks],
            ],
            out_specs=pl.BlockSpec(
                (1, bq, vd), lambda ri, hi, qi, ki, len_ref: (ri, qi, hi)),
            scratch_shapes=[pltpu.VMEM((bq, 1), F32),
                            pltpu.VMEM((bq, 1), F32),
                            pltpu.VMEM((bq, vd), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((r, n, heads * vd), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="mla_prefill_fwd",
    )(lengths.astype(jnp.int32), q_nope, q_rope, k_nope, k_r, v,
      *[mask for mask, _ in masks])


def prefill_lowering(n: int, nope: int, rope: int, vd: int, dtype) -> str:
    """``"pallas"`` or ``"xla"`` for ``n`` positions of heads ``nope + rope``
    wide beside values ``vd`` wide, as the module docstring says."""
    kernel = (_on_tpu() and not _mesh_in_scope()
              and jnp.dtype(dtype).itemsize in (2, 4)
              and jnp.issubdtype(dtype, jnp.floating)
              and nope % 128 == 0 and vd % 128 == 0 and rope % 64 == 0
              and n % MIN_TILE == 0)
    return "pallas" if kernel else "xla"


def pairs_visited(lengths, n: int):
    """Query-key pairs the kernel computes for each row of ``lengths (R,)``
    padded to ``n``, one head, ``(R,)`` float32: the tiles its grid visits
    (a live query tile's key tiles at or under the diagonal and under the
    row's length) times their size."""
    bq = bk = fitted_tile(n)
    q0 = bq * jnp.arange(n // bq)
    length = jnp.asarray(lengths)[..., None]
    last = jnp.minimum((q0 + bq - 1) // bk, (length - 1) // bk)
    tiles = jnp.sum(jnp.where(q0 < length, last + 1, 0), axis=-1)
    return tiles.astype(F32) * (bq * bk)


def prefill_attention(q_nope, q_rope, k_nope, k_r, v, lengths=None,
                      keep=None):
    """Causal attention of ``q_nope (R, P, H, nope)``, ``q_rope (R, P, H,
    rope)`` over ``k_nope (R, H, P, nope)``, ``k_r (R, P, rope)`` (one per
    position, shared by the heads) and ``v (R, H, P, vd)``, scaled by
    ``1 / sqrt(nope + rope)``: ``(R, P, H * vd)``, exact at the first
    ``lengths (R,)`` positions of each row (default: all ``P``).  With
    ``keep (R, P, P)`` (one byte a pair, the same for every head) the pair
    ``(t, s)`` is attended iff ``s <= t`` and ``keep[r, t, s]``; every row
    keeps at least one key it can see.  The lowering is chosen as the
    module docstring says."""
    n = q_nope.shape[1]
    lowering = prefill_lowering(n, q_nope.shape[-1], q_rope.shape[-1],
                                v.shape[-1], v.dtype)
    note("mla_prefill", lowering)
    if lowering == "xla":
        return blocked_prefill_attention(q_nope, q_rope, k_nope, k_r, v,
                                         keep)
    if lengths is None:
        lengths = jnp.full((q_nope.shape[0],), n, jnp.int32)
    return pallas_prefill_attention(
        q_nope.transpose(0, 2, 1, 3), q_rope.transpose(0, 2, 1, 3), k_nope,
        k_r, v, lengths, keep)

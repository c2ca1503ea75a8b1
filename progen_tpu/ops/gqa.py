"""Grouped-query attention cores: ``H`` query heads in groups of ``H / KV``
that share one of ``KV`` key/value heads (query head ``h`` reads key/value
head ``h // (H / KV)``), under one of the TWO MASKS a prefill takes — the
causal mask with an optional per-token sliding window, or (``block = B``,
no window) the BLOCK mask of a family that generates by diffusion over
blocks: position ``i`` sees ``j`` iff ``j // B <= i // B``, causal across
blocks of ``B`` and open inside one — and the two cores of a decode step,
each a kernel and an XLA form: one query a slot, or a block of ``B``
queries that see each other.

:func:`prefill_attention` — ``q (R, P, H, d)`` against ``k, v (R, KV, P,
d)``, ``lengths (R,)`` leading positions of each row real: position ``i``
sees ``j`` iff ``j <= i``, under a ``window`` ``i - j < window``, and ``j <
length``.  It returns the heads' outputs laid out as the output projection
reads them, ``(R, P, H * d)`` (``H * dv`` where the values are another width, below).
The contract is the output AT REAL
POSITIONS (a real query sees real keys only, because attention is causal);
a pad position's output is finite and otherwise unspecified — nothing
downstream of a prefill reads it.  Two lowerings keep that contract, chosen
from what the code can observe and never from a knob (as
``ops/mla_prefill.py`` and ``ops/row_write.py``):

* **Pallas kernel** ``gqa_prefill_fwd`` — on a TPU backend, no mesh in
  scope, 2- or 4-byte floats, the values' width ``dv`` (``d`` unless the
  family says otherwise) a multiple of 128 (the lane tile: a block of ``q``
  and of the output is one head's columns of ``(R, P, H * d)`` and ``(R, P,
  H * dv)``, so neither is ever transposed) beside keys of at least 128
  (padded to the next multiple where they are none: "Two widths and a sink"
  below), ``P`` a multiple of ``MIN_TILE`` (Trinity's prefill buckets are
  512 * 2^k) and ``window`` None or a multiple of the key tile.  So
  Trinity's head width of 128 takes the kernel, MiMo's full layers (keys
  192 beside values of 128) take it over keys padded to 256, and Granite
  4.0-H's 64 (``models/granite_hybrid.py``) the blocked XLA form below, on
  a TPU too.  One flash kernel for both kinds of block,
  ``window`` a static parameter that changes which tiles are visited and
  nothing else.  Grid ``(R, H, P / bq, key steps)``, the key axis
  innermost and RELATIVE: step ``ki`` of query tile ``qi`` is key tile
  ``first + ki``, where :func:`key_tiles` gives the ``first`` and ``last``
  key tile a real query of the tile sees — nothing above the diagonal,
  nothing past the row's length, nothing wholly left of the window — so
  under a window the axis is as long as the window's span of tiles and not
  ``P / bk``.  Steps past ``last`` are neither computed nor fetched (their
  index map stays on ``last``); a query tile that starts at or past the
  row's length is not computed and reads as zeros; a row of length 0 costs
  no attention.  ``k, v`` stay ``(R, KV, P, d)``: the index map sends query
  head ``h`` to key/value head ``h // (H / KV)``, no key is repeated.  A
  ``(bq, bk)`` score tile ``q k^T`` is accumulated in float32 from the
  compute-dtype operands and scaled in float32, lives in VMEM only, and
  updates a float32 running maximum, sum and output accumulator;
  probabilities are cast to the compute dtype for the value product alone,
  and the ONE division by the sum comes at the end.  Only tiles that the
  diagonal or the window's left edge crosses pay for the iota mask.  **The
  first VISITED tile initialises** the running maximum, sum and
  accumulator (step ``ki == 0``), and the mask is a large FINITE negative,
  not ``-inf``: the first visited tile under a window shows its key to the
  tile's first rows only, and a row that has seen no key yet carries a
  maximum of ``MASKED`` and weights that the first key it does see scales
  by ``exp(MASKED - m) == 0`` exactly.
* **blocked XLA** (:func:`blocked_prefill_attention`) — everywhere else
  (the CPU of tier-1, the tests' tiny widths, any trace under a mesh), and
  the kernel's test oracle: blocks of ``QUERY_BLOCK`` query rows against
  the keys they can see, float32 softmax (divided by its sum after the
  value product); no ``(P, P)`` tensor exists.  Under a window every block
  has ONE shape — its own rows and the ``window`` before them, the keys
  padded in front so that the first blocks have it too — and the blocks are
  a ``lax.map`` over one body; without one a block's keys grow with it, so
  ``FULL_GROUP`` consecutive blocks share the keys of the last of them (a
  map over one body a group, a tenth more keys than the causal half) and
  the groups are unrolled.  It takes no notice of ``lengths``: pad
  positions and empty rows are computed in full.

Which one a traced call took is noted under ``"gqa_prefill"``
(``ops/lowering.py``; ``ServingEngine.status()["gqa_prefill"]``), and
:func:`pairs_visited` counts the query-key pairs it computes beside the
pairs the mask allows (:func:`pairs_allowed`; the counters
``attn.prefill_pairs_visited`` / ``attn.prefill_pairs_allowed``) from the
same :func:`key_tiles` the kernel's grid follows.

:func:`decode_attention` — one query a slot, ``q (S, H, d)``, against the
first ``counts (S,)`` rows of ``k, v (S, KV, T, d)`` in whatever order they
lie: a ring of the last ``T`` tokens and a cache that grows with the
request differ only in where the caller wrote the row and in ``counts``
(keys carry their own rotary phase, and a softmax does not care for the
order of its terms).  ``counts`` is at least 1 everywhere (a decode step
has just written the row it stands on).  ``(S, H * d)`` in ``q``'s dtype.
Two lowerings keep that contract, chosen as the prefill's are:

* **Pallas kernel** ``gqa_decode_fwd`` — on a TPU backend, no mesh in scope,
  no sink, ``q``, ``k`` and ``v`` of one 2- or 4-byte float type, ``T`` a
  multiple of ``MIN_TILE`` and the head widths either ONE, a multiple of
  128, or TWO (values ``dv`` wide beside keys of ``d``), both whole half
  lane tiles with the keys' at least a tile: Trinity's rings and grown
  caches, LFM2's packed cache rows and MiMo's full layers (keys 192 beside
  values of 128); Granite 4.0-H's 64-wide heads keep the XLA form on a TPU
  too.  Grid ``(S, T / bk)``, slots ``"parallel"``, the key axis innermost
  and ``"arbitrary"``; ALL ``KV`` heads ride in one block ``(1, KV, bk, d)``
  of ``k`` and ``(1, KV, bk, dv)`` of ``v`` — a block's last dimension is
  the array's, so a 192-wide key tile goes in whole: one and a half lane
  tiles, which the chip holds as two (below) —, and the body walks them
  unrolled — a grid axis over the heads costs a fixed price ``KV`` times a
  slot and was 1.4 times slower at Trinity's grown caches (PERF.md section
  6, PR 47).  Query head ``h`` reads key/value head ``h // G``: the query
  goes in as ``(S, KV, G, d)``, a head's ``G`` rows one ``(G, d)`` operand
  (8 rows of bfloat16 are half a sublane tile; the chip's compiler takes
  them as they are, and padding them to 16 bought nothing).  A ``(G, bk)``
  score tile is accumulated in float32 from the compute-dtype operands and
  scaled in float32, lives in VMEM only, and updates a float32 running
  maximum, running sum and ``(G, dv)`` accumulator a head; probabilities are
  cast to the compute dtype for the value product alone, and the one
  division by the sum comes at the end.  ``counts`` is scalar-prefetched: a
  key tile with ``k0 >= counts[s]`` is neither visited nor fetched, and only
  the tile the count crosses pays for the iota mask.  The steps past a
  slot's last tile point their index map at the NEXT slot's first tile
  (always visited), so its fetch starts as soon as the slot's last tile is
  in and not at the slot's last grid step, where nothing would overlap it: a
  slot that ends early otherwise costs one exposed tile fetch (0.68 -> 0.56
  ms a call at Trinity's grown caches).  It aliases nothing and writes only
  the output, so a cache that is a scan's carry, and the output of
  ``row_write`` one line above, is read where it lies.
* **XLA** (:func:`xla_decode_attention`) — everywhere else (the CPU of
  tier-1, the tests' tiny widths, any trace under a mesh, mixed dtypes):
  scores ``(S, H, T)`` in float32, a masked softmax, the value product;
  the whole cache is read.

Which one a traced call took is noted under ``"gqa_decode"``
(``ServingEngine.status()["gqa_decode"]``; :func:`decode_lowering` says it
without tracing), and :func:`rows_visited` says how many cache rows that
lowering reads (the counters ``attn.window_rows_read`` /
``attn.full_rows_read``): whole key tiles up to each slot's count under
the kernel, ``S * T`` under the XLA form.

**The block mask** changes one comparison in each prefill lowering and
nothing else: a query block, and a tile, start on a block's edge (``B``
divides ``QUERY_BLOCK`` and, a power of two, the tiles), so the keys a block
of query rows can see end where the causal mask's do, the visit rule
(:func:`key_tiles`) stands, and only the tiles the diagonal crosses pay for
the mask — there ``gap >= (row % B) - (B - 1)`` in place of ``gap >= 0``.
``lengths`` are whole blocks (a real query then sees real keys only) and
``P`` is a multiple of ``B``.  With ``block`` 1 both lowerings trace the
program they traced before the mask existed.

:func:`block_decode_attention` — ``B`` queries a slot, ``q (S, B, H, d)``,
against the first ``counts (S,)`` rows of the cache AND the block's own B
keys and values, handed in beside the cache and not written to it: one
softmax over both, the cache never concatenated.  The same call takes TWO
blocks a slot (``2B`` tokens, ``lead (S,)``): the block in front, whose keys
are not in the cache yet, beside the block after it, which sees the front
block's keys where ``lead`` (and finds them among the cache's rows where
not); the queries may be the second block's alone.  ``counts`` may be 0.
Two lowerings keep that contract, chosen by the one-query core's rule and
nothing else (:func:`block_decode_lowering`):

* **Pallas kernel** ``gqa_block_decode_fwd`` — ``gqa_decode_fwd``'s grid,
  tile and index maps with ``G * m`` query rows a key/value head (all ``m``
  queries of the call in ONE pass: a tile's scores live in VMEM, so the
  float32 score tensor that makes the XLA form split its queries does not
  exist) and the forward's own ``n`` keys as one small tile at the slot's
  FIRST grid step, under the two-block mask as an iota comparison with
  ``lead`` scalar-prefetched beside ``counts``.  A query's own block is
  always there, so the running maximum is finite before any cache tile
  and a slot with nothing committed visits none; an idle step points at
  the next slot's first tile whether that slot will visit it or not.  The
  two kernels share the cache tile's update (``_cache_tile``).  SDAR's
  cell: 64 x 2,560 rows, 64 and 32 query rows a head.
* **XLA** (:func:`xla_block_decode_attention`) — everywhere else: two
  score tensors under one running maximum, a pass over the WHOLE cache a
  block of queries (PERF.md section 6, PR 48, has why two passes of
  ``G * B`` rows beat one of twice as many there).

Which one a traced call took is noted under ``"gqa_block_decode"``, and
:func:`rows_visited` counts its rows as the one-query core's (a count of 0
is no tile).

**Two widths and a sink** (``models/mimo_v2.py``; PR 54, PR 55, PR 62).  The
prefill core and the one-query decode core take values of ANOTHER WIDTH than
the keys — ``k (..., T, d)`` beside ``v (..., T, dv)``, the output ``H *
dv`` columns — and an optional learned ``sink (H,)``: one more term of every
query's softmax, a float a head, which takes mass and has no value — ``m =
max(max_j s_ij, sink_h)``, ``p_ij = exp(s_ij - m) / (sum_j exp(s_ij - m) +
exp(sink_h - m))``.  The XLA forms take both (the blocked form adds the term
after the block's maximum, the decode form appends one column to the scores
and drops it before the value product).  Of the kernels, **the one-query
decode kernel takes two widths** (PR 55: MiMo's full layers, which have no
sink and no window — 96 % of the bytes that family's decode cores read): the
key block, the query block and the contraction are ``d`` wide, the value
block, the accumulator and the output ``dv``, and nothing else of the kernel
knows.  The whole 192-wide block ``(1, KV, bk, 192)`` lowers; the chip keeps
such a row in two lane tiles (256 columns of HBM inside the program), so a
key tile's fetch costs what a 256-wide one would.  Measured level with two
products over columns ``[0:128]`` and ``[128:192]`` of the same tile, and
0.045 ms a call behind keys PADDED to 256 in the cache, which would cost a
fifth more cache memory (PERF.md section 6, PR 55, has the table).  **The
prefill kernel takes two widths too** (PR 62: MiMo's full layers, 64 query
heads over 4 key/value heads, four fifths of what that family's admissions
spent in attention): the value block, the accumulator, the output block and
the output are ``dv`` wide — whole lane tiles, since an output block is one
head's columns of ``(R, P, H * dv)`` — and the query and key blocks ``d``.
A query block is one head's columns of ``(R, P, H * d)`` as well, and 192
columns are no lane multiple: where ``d`` is none, q's heads and k's are
PADDED WITH ZERO COLUMNS to the next (256) on the way into the kernel
(:func:`pallas_prefill_attention`) — zero columns add nothing to ``q . k``,
so the scores are the same numbers, the scale stays the caller's ``192 **
-0.5``, and the matrix unit, which contracts 128 columns a pass, takes the
two passes 192 would have cost it.  The cache keeps the published 192-wide
rows: the padded copies (537 MB of q at 1 x 16,384, 34 MB of k) live for
one layer's core.  Timed alone at 1 x 16,384 against a query transposed to
``(R, H, P, 192)`` (a block whose last dimension is the array's own) and
against two heads a 384-column block: 50.7 | 49.0 | 45.5 ms with what each
prepares, the blocked form 160.5 (PERF.md section 6, PR 62, has the table
and why the form that adds nothing to the kernel was taken).  **What still
keeps the XLA forms:** a sink (both cores), a ring shorter than ``MIN_TILE``
rows (MiMo's 128: its sliding layers' decode core), a window that is no
multiple of the key tile (MiMo's 128, dots3's 513 over joined heads of 256
beside values of 128), values that are no whole lane tiles, and keys under
one.  A window SHORTER than ``QUERY_BLOCK`` costs the blocked form a block
of ``QUERY_BLOCK + window`` keys for every ``QUERY_BLOCK`` rows, most of it
masked; :func:`pairs_visited` counts it as it is.  Without a sink and with
``dv == d`` every function traces the program it traced before the
arguments existed (``tests/test_program_identity.py`` for the XLA forms;
``tests/test_pallas_gqa_decode.py`` and ``tests/test_pallas_gqa_prefill.py``
hold the kernels' jaxpr text at one width).  The block mask and the block
form of the decode step take neither.

**A keep mask** (``models/glm_dsa.py``; PR 60).  The prefill core takes an
optional ``keep (R, P, P)`` — one byte a pair, the same for every head: ``(t,
s)`` is attended iff the causal rule allows it AND ``keep[r, t, s]``; every
real row keeps at least one key it can see.  It is how GLM-5.2's layers
attend under the indexer's selection (``ops/dsa.py:prefill_keep``), over
latent attention's heads JOINED to one width (``[nope | rope]`` 256 beside
values of 256: lane multiples both, where ``ops/mla_prefill.py``'s kernel
wants a ``nope`` of 128s).  The blocked form ands it into a block's mask; the
kernel takes it as one more operand in ``(bq, bk)`` int8 tiles on the keys'
clamped index map (an unvisited tile's mask is not fetched) and one more
select on every visited tile — a row that keeps no key of its first tiles
carries ``MASKED`` and is wiped by the first key it does keep, as under a
window.  Measured against ``mla_prefill_fwd`` taught a 192-wide ``nope``:
65.8 against 82.2 ms a layer at 1 x 16,384, 18.9 against 23.8 at 1 x 8,192
(PERF.md section 6, PR 60): ONE contraction over 256 columns is two passes of
the 128-wide matrix unit where 192 and 64 apart are three.  No window, no
block mask, no sink and one width beside it; without it every function
traces the program it traced before the operand existed.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.ops.lowering import mesh_in_scope as _mesh_in_scope
from progen_tpu.ops.lowering import note
from progen_tpu.ops.lowering import on_tpu as _on_tpu

F32 = jnp.float32
QUERY_BLOCK = 256     # blocked XLA form: query rows per score block
FULL_GROUP = 4        # blocked XLA form, no window: blocks sharing a key span
# the kernel's query and key tile on a v5e (PERF.md section 6, PR 35, has
# the pairs measured), halved down to ``MIN_TILE`` until it divides P
TILE, MIN_TILE = 1024, 512
# the decode kernel's key tile for long caches (``fitted_decode_tile``)
DECODE_TILE = 1024
MASKED = -0.7 * float(jnp.finfo(F32).max)   # a masked score: finite


# ------------------------------------------------------ the blocked XLA form


def _score_block(q, k, v, first_row, first_key, scale, window, block=1,
                 sink=None, keep=None):
    """``q (R, KV, G, bq, d)`` at rows ``first_row + arange(bq)`` against
    ``k (R, KV, t, d)``, ``v (R, KV, t, dv)`` at positions ``first_key +
    arange(t)`` (a position below 0 is padding): ``(R, KV, G, bq, dv)``.
    ``sink (KV, G, 1, 1)`` float32: one more term of every row's softmax,
    which takes mass and has no value.  ``keep (R, bq, t)``: the pairs of
    the block that may be attended at all."""
    logits = jnp.einsum("rkgqd,rktd->rkgqt", q, k,
                        preferred_element_type=F32) * scale
    at = first_key + jnp.arange(k.shape[2])[None, :]
    if block == 1:
        gap = first_row + jnp.arange(q.shape[3])[:, None] - at
        seen = (gap >= 0) & (at >= 0)
        if window is not None:
            seen = seen & (gap < window)
    else:       # causal across blocks of ``block``, every key inside one
        rows = first_row + jnp.arange(q.shape[3])[:, None]
        seen = at // block <= rows // block
    if keep is not None:
        seen = seen & (keep != 0)[:, None, None]
    # float32 scores, maximum and sum; the unnormalised probabilities are
    # cast for the value product and the ONE division by the sum comes
    # after it, on (bq, d) numbers and not (bq, t) — the repo's kernels'
    # convention, and here what keeps a second float32 score tensor out of
    # memory: 4 x 8192 x 32 heads without a window take 86 ms this way and
    # 1,098 ms through ``jax.nn.softmax`` (PERF.md section 6, PR 34).
    # Every row sees its own key, so the maximum is finite
    logits = jnp.where(seen, logits, -jnp.inf)
    top = jnp.max(logits, axis=-1, keepdims=True)
    if sink is not None:        # the extra term, after the block's maximum
        top = jnp.maximum(top, sink)
    p = jnp.exp(logits - top)
    out = jnp.einsum("rkgqt,rktd->rkgqd", p.astype(v.dtype), v,
                     preferred_element_type=F32)
    total = jnp.sum(p, axis=-1)
    if sink is not None:
        total = total + jnp.exp(sink - top)[..., 0]
    return (out / total[..., None]).astype(q.dtype)


def _rows(x, start, size):
    return jax.lax.dynamic_slice_in_dim(x, start, size, axis=x.ndim - 2)


def _blocked_bodies(n: int, window) -> list:
    """``(first block, blocks, keys each sees)`` for every traced body of
    the blocked form over ``n`` positions: one under a window, one a
    group of ``FULL_GROUP`` blocks without."""
    bq = min(QUERY_BLOCK, n)
    blocks = -(-n // bq)
    if window is not None:
        # the furthest any block looks back: the window, or all there is
        return [(0, blocks, min(window, (blocks - 1) * bq) + bq)]
    return [(first, min(FULL_GROUP, blocks - first),
             min(first + FULL_GROUP, blocks) * bq)
            for first in range(0, blocks, FULL_GROUP)]


def blocked_prefill_attention(q, k, v, scale, window=None, block=1,
                              sink=None, keep=None):
    """The XLA form: every position of every row computed, the score
    tensor ``(R, KV, G, QUERY_BLOCK, keys)`` float32.  ``block``: the block
    mask's length (a query block holds whole ones, so the keys a block of
    query rows can see end where the causal mask's do).  ``v``'s heads may
    be another width than ``q``'s and ``k``'s (the output is ``H * dv``
    wide); ``sink (H,)`` adds its term to every row's softmax; ``keep (R, P,
    P)`` thins the causal pairs (no window beside it)."""
    r, n, heads, d = q.shape
    dv = v.shape[-1]
    if keep is not None and (window is not None or block != 1):
        raise ValueError("a keep mask thins the causal mask alone")
    if block != 1 and (window is not None or QUERY_BLOCK % block
                       or n % block):
        raise ValueError(
            f"a block mask of {block} takes no window ({window}) and "
            f"divides the query block of {QUERY_BLOCK} and the {n} "
            "positions (a last block cut short would see the padding)")
    kv = k.shape[1]
    if sink is not None:
        sink = sink.astype(F32).reshape(kv, heads // kv, 1, 1)
    bq = min(QUERY_BLOCK, n)
    blocks = -(-n // bq)
    pad = blocks * bq - n
    q = q.reshape(r, n, kv, heads // kv, d).transpose(0, 2, 3, 1, 4)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, pad), (0, 0)))
    k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (k, v))
    if keep is not None:    # a padded row keeps the padded key it stands on
        keep = jnp.pad(keep, ((0, 0), (0, pad), (0, pad)), constant_values=1)
    bodies = _blocked_bodies(n, window)
    if window is not None:
        back = bodies[0][2] - bq
        k, v = (jnp.pad(a, ((0, 0), (0, 0), (back, 0), (0, 0)))
                for a in (k, v))

        def body(i):        # padded row ``s`` holds position ``s - back``
            s = i * bq
            return _score_block(_rows(q, s, bq), _rows(k, s, back + bq),
                                _rows(v, s, back + bq), s, s - back, scale,
                                window, sink=sink)

        out = jax.lax.map(body, jnp.arange(blocks))
    else:
        outs = []
        for first, count, span in bodies:
            keys, values = k[:, :, :span], v[:, :, :span]

            def body(i, keys=keys, values=values, span=span):
                kept = None if keep is None else jax.lax.dynamic_slice_in_dim(
                    keep[:, :, :span], i * bq, bq, axis=1)
                return _score_block(_rows(q, i * bq, bq), keys, values,
                                    i * bq, 0, scale, None, block, sink,
                                    kept)

            outs.append(jax.lax.map(body, jnp.arange(first, first + count)))
        out = jnp.concatenate(outs, axis=0)
    # (blocks, R, KV, G, bq, dv) -> (R, P, H * dv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(r, blocks * bq, heads * dv)
    return out[:, :n]


# --------------------------------------------------------------- the kernel


def key_tiles(qi, length, bq: int, bk: int, window, xp=jnp):
    """``(live, first, last)`` for query tile ``qi`` (rows ``qi * bq ..``)
    of a row of ``length`` real positions: whether the tile holds a real
    query, and the first and last key tile (of ``bk`` keys; inclusive) in
    which a real query of the tile sees a key — ``last`` lies under the
    diagonal and under the length, ``first`` holds the leftmost key of the
    tile's first row's window.  Where ``live``, ``first <= last`` and every
    tile between holds a seen pair.  The one visit rule: the kernel's grid
    and index maps, and :func:`pairs_visited`, all call this (``xp``:
    ``numpy`` for static arguments inside a trace)."""
    q0 = qi * bq
    last = xp.minimum(q0 + bq - 1, xp.maximum(length - 1, 0)) // bk
    first = xp.zeros_like(last) if window is None else (
        xp.maximum(q0 - window + 1, 0) // bk)
    return q0 < length, first, last


def key_steps(n: int, bq: int, bk: int, window) -> int:
    """The length of the kernel's key axis: the most key tiles any query
    tile of ``n`` positions visits."""
    return max(int(last - first + 1) for _, first, last in (
        key_tiles(qi, n, bq, bk, window, np) for qi in range(n // bq)))


def _dot_t(a, b):  # a @ b^T, float32 accumulate
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, *refs, scale, bq, bk, window,
                  block=1, masked=False):
    """``refs``: ``(o, m, l, acc)``, after ``keep (1, bq, bk)`` where the
    call is ``masked``."""
    from jax.experimental import pallas as pl

    keep_ref = refs[0] if masked else None
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    length = len_ref[pl.program_id(0)]
    qi, ki = pl.program_id(2), pl.program_id(3)
    live, first, last = key_tiles(qi, length, bq, bk, window)
    kt = first + ki
    q0, k0 = qi * bq, kt * bk

    @pl.when(ki == 0)       # the first visited tile, where there is one
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def tile(under_diagonal, inside_window):
        s = _dot_t(q_ref[0], k_ref[0, 0]) * scale
        if not (under_diagonal and inside_window):
            gap = (q0 - k0) + (
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            if block == 1:
                seen = under_diagonal or gap >= 0
            else:
                # ``key // block <= row // block``: a row sees the keys up
                # to the end of its own block (tiles start on a block's
                # edge, ``block`` a power of two)
                ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                         & (block - 1)) - (block - 1)
                seen = under_diagonal or gap >= ahead
            if not inside_window:
                seen = seen & (gap < window)
            s = jnp.where(seen, s, MASKED)
        if keep_ref is not None:
            s = jnp.where(keep_ref[0] != 0, s, MASKED)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=F32)
        m_ref[...] = m_next

    # a tile needs a mask only where the diagonal or the window's left
    # edge crosses it: some (row, key) of it with key > row, or with
    # row - key >= window
    visited = live & (kt <= last)
    under_diagonal = k0 + bk - 1 <= q0
    inside_window = True if window is None else q0 + bq - 1 - k0 < window
    for under, inside in itertools.product(
            (True, False), (True,) if window is None else (True, False)):
        pl.when(visited
                & (under_diagonal if under else ~under_diagonal)
                & (inside_window if inside else ~inside_window)
                )(functools.partial(tile, under, inside))

    done = ki == pl.num_programs(3) - 1

    @pl.when(done & live)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    @pl.when(done & ~live)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def _halved_to_fit(tile: int, n: int) -> int:
    """``tile``, ``tile / 2``, ... down to ``MIN_TILE``: the first that
    divides ``n``."""
    while tile > MIN_TILE and n % tile:
        tile //= 2
    return tile


def fitted_tile(n: int) -> int:
    """The largest of ``TILE``, ``TILE / 2``, ... down to ``MIN_TILE`` that
    divides ``n``."""
    return _halved_to_fit(TILE, n)


def pallas_prefill_attention(q, k, v, lengths, scale, window=None, *,
                             block=1, keep=None, block_q=None, block_k=None,
                             interpret=None):
    """The kernel lowering.  ``q (R, P, H * d)``, ``k (R, KV, P, d)``, ``v
    (R, KV, P, dv)``, ``lengths (R,)`` -> ``(R, P, H * dv)``.  A ``d`` that
    is no multiple of 128 is PADDED to the next with zero columns, q's heads
    and k's alike, on the way in (module docstring, "Two widths and a
    sink"); ``scale`` stays the caller's.  ``interpret=None`` auto-selects
    the Pallas interpreter off-TPU; ``block_q`` / ``block_k`` default to
    :func:`fitted_tile``; ``block`` is the block mask's length (a power of
    two that divides both tiles, no window beside it; ``lengths`` whole
    blocks); ``keep (R, P, P)`` int8 thins the causal pairs (no window and
    no block mask beside it)."""
    r, _, n, d = k.shape
    if d % 128:     # zero columns add nothing to ``q . k``
        columns = ((0, 0),) * 3 + ((0, -d % 128),)
        q = jnp.pad(q.reshape(r, n, -1, d), columns).reshape(r, n, -1)
        k = jnp.pad(k, columns)
    bq = block_q or fitted_tile(n)
    bk = block_k or fitted_tile(n)
    if n % bq or n % bk:
        raise ValueError(f"tiles ({bq}, {bk}) do not divide P = {n}")
    if block != 1 and (window is not None or block & (block - 1)
                       or bq % block or bk % block):
        raise ValueError(
            f"a block mask of {block} takes no window ({window}) and is a "
            f"power of two that divides the tiles ({bq}, {bk})")
    if keep is not None and (window is not None or block != 1):
        raise ValueError("a keep mask thins the causal mask alone")
    if interpret is None:
        interpret = not _on_tpu()
    return _flash_call(q, k, v, lengths.astype(jnp.int32), scale=scale,
                       window=window, bq=bq, bk=bk, interpret=interpret,
                       block=block, keep=keep)


# jitted so that the blocks of one kind in a model share ONE traced and
# lowered kernel: nine calls of an 8192-token admission lower in 0.1 s
# instead of 0.6-1.6 s, and the program holds two Mosaic kernels, not nine
@functools.partial(jax.jit, static_argnames=("scale", "window", "bq", "bk",
                                             "interpret", "block"))
def _flash_call(q, k, v, lengths, *, scale, window, bq, bk, interpret,
                block=1, keep=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, kv, n, d = k.shape
    dv = v.shape[-1]
    heads = q.shape[-1] // d
    group = heads // kv

    def q_map(ri, hi, qi, ki, len_ref):
        # a tile past the length is not computed: keep the last real one
        return ri, jnp.minimum(
            qi, jnp.maximum(len_ref[ri] - 1, 0) // bq), hi

    def kv_map(ri, hi, qi, ki, len_ref):
        # steps past the last visited tile stay on it, and a query tile
        # past the length stays on the row's last: nothing is fetched
        live, first, last = key_tiles(qi, len_ref[ri], bq, bk, window)
        return ri, hi // group, jnp.minimum(
            jnp.where(live, first + ki, last), last), 0

    def keep_map(ri, hi, qi, ki, len_ref):
        # the queries' and the keys' clamps: an unvisited tile is not fetched
        return (ri, q_map(ri, hi, qi, ki, len_ref)[1],
                kv_map(ri, hi, qi, ki, len_ref)[2])

    masks = [] if keep is None else [(keep, pl.BlockSpec((1, bq, bk),
                                                         keep_map))]
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk,
                          window=window, block=block, masked=bool(masks)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r, heads, n // bq, key_steps(n, bq, bk, window)),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, 1, bk, d), kv_map),
                pl.BlockSpec((1, 1, bk, dv), kv_map),
                *[spec for _, spec in masks],
            ],
            out_specs=pl.BlockSpec(
                (1, bq, dv), lambda ri, hi, qi, ki, len_ref: (ri, qi, hi)),
            scratch_shapes=[pltpu.VMEM((bq, 1), F32),
                            pltpu.VMEM((bq, 1), F32),
                            pltpu.VMEM((bq, dv), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((r, n, heads * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="gqa_prefill_fwd",
    )(lengths, q, k, v, *[mask for mask, _ in masks])


def _kernel_takes(dtype) -> bool:
    """What both kernels ask before any shape: a TPU backend, no mesh in
    scope, a 2- or 4-byte float type."""
    return (_on_tpu() and not _mesh_in_scope()
            and jnp.issubdtype(dtype, jnp.floating)
            and jnp.dtype(dtype).itemsize in (2, 4))


def prefill_lowering(n: int, d: int, dtype, window, block: int = 1, *,
                     dv: int | None = None, sink: bool = False,
                     keep: bool = False) -> str:
    """``"pallas"`` or ``"xla"``: what :func:`prefill_attention` takes for
    ``n`` positions of heads ``d`` wide (values ``dv`` wide: default ``d``)
    in ``dtype``, with a ``sink`` or a ``keep`` mask or without, traced here
    and now (the module docstring has the rule)."""
    # values of whole lane tiles (an output block is one head's columns of
    # ``(R, P, H * dv)``) beside keys of at least one, which are padded to
    # the next where they are no multiple (MiMo's 192 beside 128)
    dv = d if dv is None else dv
    kernel = (_kernel_takes(dtype) and not sink
              and not (keep and (window is not None or block != 1))
              and dv % 128 == 0 and d >= 128 and n % MIN_TILE == 0
              and (window is None or window % fitted_tile(n) == 0)
              and (block == 1 or (window is None and not block & (block - 1)
                                  and MIN_TILE % block == 0)))
    return "pallas" if kernel else "xla"


def prefill_attention(q, k, v, scale, window=None, lengths=None, block=1,
                      sink=None, keep=None):
    """``(R, P, H * dv)`` in ``q``'s dtype, exact at the first ``lengths
    (R,)`` positions of each row (default: all ``P``).  ``block``: 1 for
    the causal mask, else the block mask's length (``lengths`` then whole
    blocks).  ``sink (H,)``: a learned term of every row's softmax beside
    its keys.  ``keep (R, P, P)``: one byte a pair, the same for every head;
    ``(t, s)`` is attended iff ``s <= t`` and ``keep[r, t, s]``.  The
    lowering is chosen as the module docstring says."""
    r, n, heads, d = q.shape
    lowering = prefill_lowering(n, d, q.dtype, window, block,
                                dv=v.shape[-1], sink=sink is not None,
                                keep=keep is not None)
    note("gqa_prefill", lowering)
    if lowering == "xla":
        return blocked_prefill_attention(q, k, v, scale, window, block, sink,
                                         keep)
    if lengths is None:
        lengths = jnp.full((r,), n, jnp.int32)
    # the causal call is the one it was (callers wrap it with that signature)
    extra = {} if block == 1 else {"block": block}
    if keep is not None:
        extra["keep"] = keep
    return pallas_prefill_attention(q.reshape(r, n, heads * d), k, v,
                                    lengths, scale, window, **extra)


def pairs_allowed(lengths, window=None, block: int = 1):
    """Query-key pairs the mask allows at the real positions of rows of
    ``lengths (R,)``, one head, as a float32 scalar: position ``i <
    length`` sees ``min(i + 1, window)`` keys, or under a block mask the
    ``(i // block + 1) * block`` up to its block's end (``lengths`` whole
    blocks)."""
    n = lengths.astype(F32)
    if block != 1:
        return jnp.sum(n * (n + block) / 2)
    pairs = n * (n + 1) / 2
    if window is not None:
        past = jnp.maximum(n - window, 0)   # positions with a full window
        pairs = pairs - past * (past + 1) / 2
    return jnp.sum(pairs)


def tiles_visited(lengths, n: int, bq: int, bk: int, window):
    """Key tiles the kernel computes for rows of ``lengths (R,)`` padded
    to ``n``, one head: :func:`key_tiles` over every query tile."""
    live, first, last = key_tiles(jnp.arange(n // bq)[None, :],
                                  lengths[:, None], bq, bk, window)
    return jnp.sum(jnp.where(live, last - first + 1, 0))


def pairs_visited(lengths, n: int, window, lowering: str):
    """Query-key pairs :func:`prefill_attention` computes for rows of
    ``lengths (R,)`` padded to ``n``, one head, under ``lowering``, as a
    float32 scalar: the blocked form's every block of every row at its
    full key span, pads and empty rows included; the kernel's visited
    tiles (the rule its grid follows) times ``bq * bk``."""
    if lowering == "xla":
        bq = min(QUERY_BLOCK, n)
        pairs = sum(count * bq * span
                    for _, count, span in _blocked_bodies(n, window))
        return jnp.asarray(lengths.shape[0] * pairs, F32)
    tile = fitted_tile(n)
    return tiles_visited(lengths, n, tile, tile, window).astype(F32) * (
        tile * tile)


# ------------------------------------------------------------------- decode


def xla_decode_attention(q, k, v, counts, scale, sink=None):
    """The XLA form: scores over the whole static cache in float32.
    ``(S, H * dv)`` in ``q``'s dtype.  ``sink (H,)``: one more column of
    the softmax, dropped before the value product."""
    s, heads, d = q.shape
    kv, t = k.shape[1], k.shape[2]
    q = q.reshape(s, kv, heads // kv, d)
    logits = jnp.einsum("skgd,sktd->skgt", q, k.astype(q.dtype),
                        preferred_element_type=F32) * scale
    seen = jnp.arange(t)[None, :] < counts[:, None]
    logits = jnp.where(seen[:, None, None], logits, -jnp.inf)
    if sink is None:
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        column = jnp.broadcast_to(
            sink.astype(F32).reshape(kv, heads // kv, 1), (s, kv, heads // kv, 1))
        probs = jax.nn.softmax(
            jnp.concatenate([logits, column], axis=-1), axis=-1)[..., :t]
    out = jnp.einsum("skgt,sktd->skgd", probs.astype(q.dtype),
                     v.astype(q.dtype), preferred_element_type=F32)
    return out.astype(q.dtype).reshape(s, heads * v.shape[-1])


def _cache_tile(count, k0, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
                scale, bk):
    """One grid step of a decode kernel over a slot's cache: the key tile
    at rows ``k0 .. k0 + bk`` under the online softmax, for every
    key/value head (``q_ref (1, KV, rows, d)``: a head's query rows one
    operand)."""
    from jax.experimental import pallas as pl

    def head(h, crossed):
        s = _dot_t(q_ref[0, h], k_ref[0, h]) * scale           # (rows, bk)
        if crossed:
            cols = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < count, s, -jnp.inf)
        # the running maximum is finite before any cache tile past the
        # first: key tile 0 holds the slot's row 0, or the block kernel's
        # own keys came first
        m_prev = m_ref[h]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, h], preferred_element_type=F32)
        m_ref[h] = m_next

    def tile(crossed):
        for h in range(k_ref.shape[1]):     # the key/value heads, unrolled
            head(h, crossed)

    # a key tile is visited if it holds a row the slot has; it needs the
    # mask only where the count ends inside it
    seen = k0 < count
    crossed = k0 + bk > count
    pl.when(seen & crossed)(functools.partial(tile, True))
    pl.when(seen & jnp.logical_not(crossed))(functools.partial(tile, False))


def _decode_kernel(cnt_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale, bk):
    from jax.experimental import pallas as pl

    count = cnt_ref[pl.program_id(0)]
    ki = pl.program_id(1)
    k0 = ki * bk

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    # key tile 0 is always visited first and holds the slot's row 0
    # (counts >= 1), so the maximum is finite from the first tile on
    _cache_tile(count, k0, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                scale=scale, bk=bk)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def fitted_decode_tile(t: int) -> int:
    """The decode kernel's key tile for caches of ``t`` rows.  A slot
    reads half a tile past its count on average and a visited grid step
    costs about 0.5 us beside its bytes, so long caches take
    ``DECODE_TILE`` (fewer steps: 0.56 against 0.64 ms a call at 64 x
    9,216) and caches under four of them ``MIN_TILE`` (fewer rows: 0.54
    against 0.61 ms at 128 x 3,072; PERF.md section 6, PR 47, has the
    table); halved down to ``MIN_TILE`` until it divides ``t``."""
    return _halved_to_fit(
        DECODE_TILE if t >= 4 * DECODE_TILE else MIN_TILE, t)


def _decode_options(t: int, block_k, interpret) -> dict:
    """``bk`` and ``interpret`` of a decode kernel over caches of ``t``
    rows: ``block_k`` defaults to :func:`fitted_decode_tile`,
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU."""
    bk = block_k or fitted_decode_tile(t)
    if t % bk:
        raise ValueError(f"tile {bk} does not divide T = {t}")
    return {"bk": bk,
            "interpret": not _on_tpu() if interpret is None else interpret}


def pallas_decode_attention(q, k, v, counts, scale, *, block_k=None,
                            interpret=None):
    """The kernel lowering of :func:`decode_attention`
    (:func:`_decode_options` has the two options)."""
    return _decode_call(q, k, v, counts.astype(jnp.int32), scale=scale,
                        **_decode_options(k.shape[2], block_k, interpret))


# jitted as ``_flash_call`` is: a model's rings, and its grown caches, share
# ONE traced and lowered kernel each, and the chunk program holds two Mosaic
# kernels, not one a layer
@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def _decode_call(q, k, v, counts, *, scale, bk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, heads, d = q.shape
    kv, t, dv = k.shape[1], k.shape[2], v.shape[3]
    group = heads // kv

    def slot_map(si, ki, cnt_ref):
        return si, 0, 0, 0

    def cache_map(si, ki, cnt_ref):
        # a tile past the count is neither visited nor fetched: the steps
        # past a slot's last tile point at the NEXT slot's first tile
        # (always visited: counts >= 1), so that its fetch starts at once
        # and not at the slot's last step with nothing to overlap it; the
        # last slot's stay on its last tile
        last = jnp.maximum(cnt_ref[si] - 1, 0) // bk
        done, more = ki > last, si + 1 < s
        return (jnp.where(done & more, si + 1, si), 0,
                jnp.where(done, jnp.where(more, 0, last), ki), 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, t // bk),
            in_specs=[pl.BlockSpec((1, kv, group, d), slot_map),
                      pl.BlockSpec((1, kv, bk, d), cache_map),
                      pl.BlockSpec((1, kv, bk, dv), cache_map)],
            out_specs=pl.BlockSpec((1, kv, group, dv), slot_map),
            scratch_shapes=[pltpu.VMEM((kv, group, 1), F32),
                            pltpu.VMEM((kv, group, 1), F32),
                            pltpu.VMEM((kv, group, dv), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((s, kv, group, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gqa_decode_fwd",
    )(counts, q.reshape(s, kv, group, d), k, v)
    return out.reshape(s, heads * dv)


def decode_lowering(q_dtype, k, v, sink: bool = False) -> str:
    """``"pallas"`` or ``"xla"``: what :func:`decode_attention` takes for
    a query of ``q_dtype`` over caches ``k`` and ``v`` (arrays or their
    shapes), with a ``sink`` or without, traced here and now (the module
    docstring has the rule)."""
    dtype = jnp.dtype(q_dtype)
    d, dv = k.shape[3], v.shape[3]
    # one width on the lane tile, or two that are whole half lane tiles
    # with the keys' at least one tile (MiMo's 192 beside 128)
    widths = d % 128 == 0 if d == dv else (
        d % 64 == 0 and dv % 64 == 0 and d >= 128)
    kernel = (_kernel_takes(dtype) and not sink and widths
              and dtype == jnp.dtype(k.dtype) == jnp.dtype(v.dtype)
              and k.shape[2] % MIN_TILE == 0)
    return "pallas" if kernel else "xla"


def decode_attention(q, k, v, counts, scale, sink=None):
    """``(S, H * dv)`` in ``q``'s dtype: ``q (S, H, d)`` over the first
    ``counts (S,)`` (each at least 1) rows of ``k (S, KV, T, d)`` and ``v
    (S, KV, T, dv)``, with the learned term ``sink (H,)`` in the softmax
    where given.  The lowering is chosen as the module docstring says."""
    lowering = decode_lowering(q.dtype, k, v, sink is not None)
    note("gqa_decode", lowering)
    if lowering == "xla":
        return xla_decode_attention(q, k, v, counts, scale, sink)
    return pallas_decode_attention(q, k, v, counts, scale)


def rows_visited(k, counts, lowering: str):
    """Cache rows :func:`decode_attention` reads of ``k`` (and as many of
    ``v``) in one call under ``lowering``, as a float32 scalar: the
    kernel's whole key tiles up to each slot's count; the XLA form's every
    row of every slot, whatever the ``counts`` (they are not looked at)."""
    if lowering == "xla":
        return jnp.asarray(k.shape[0] * k.shape[2], F32)
    tile = fitted_decode_tile(k.shape[2])
    return (jnp.sum(-(-counts // tile)) * tile).astype(F32)


# ------------------------------------------------------- a block of queries


def block_decode_lowering(q_dtype, k, v) -> str:
    """``"pallas"`` or ``"xla"``: what :func:`block_decode_attention` takes
    for queries of ``q_dtype`` over caches ``k`` and ``v`` (arrays or their
    shapes), traced here and now: the one-query core's rule — the two
    kernels differ in the query rows a head, not in what they ask of the
    backend, the mesh, the dtypes or the cache's shape."""
    return decode_lowering(q_dtype, k, v)


def block_decode_attention(q, k, v, k_new, v_new, counts, scale, lead=None):
    """``n`` tokens a slot against the first ``counts (S,)`` rows of ``k, v
    (S, KV, T, d)`` AND the forward's own ``k_new, v_new (S, KV, n, d)``:
    one softmax over both.  ``q (S, m, H, d)`` are the queries of the LAST
    ``m <= n`` of them.  ``lead`` None: the ``n`` rows are ONE block, every
    key of which every query of it sees (bidirectional inside the block).
    ``lead (S,)``: they are TWO blocks of ``n / 2``, causal across and open
    inside — the front block's queries see their own block's keys, the
    second block's see their own and, where ``lead``, the front block's
    (where not, the front block is a slot's filler: the caller counts its
    rows among the cache's).  ``counts`` may be 0 (a first block with
    nothing committed before it): a query's own block is always there.
    Nothing is written: whether a block's keys enter the cache is the
    caller's (``ops/row_write.py:write_row_blocks``).  ``(S, m, H * d)`` in
    ``q``'s dtype.  The lowering is chosen as the module docstring says and
    noted as ``"gqa_block_decode"`` (``ops/lowering.py``)."""
    lowering = block_decode_lowering(q.dtype, k, v)
    note("gqa_block_decode", lowering)
    if lowering == "xla":
        return xla_block_decode_attention(q, k, v, k_new, v_new, counts,
                                          scale, lead)
    return pallas_block_decode_attention(q, k, v, k_new, v_new, counts,
                                         scale, lead)


def _block_decode_kernel(cnt_ref, lead_ref, q_ref, k_ref, v_ref, kn_ref,
                         vn_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, bk,
                         second_from):
    """:func:`_decode_kernel` with ``G * m`` query rows a key/value head —
    token-major, row ``j * G + g`` the ``j``-th query's — and the forward's
    own ``n`` keys as one small tile BEFORE the cache's: a query sees its
    own block there, so the running maximum is finite from the first step
    on and a slot with nothing committed visits no cache tile at all.
    ``second_from``: the first query row of the second of two blocks (None:
    the ``n`` keys are one block, and there is no mask among them)."""
    from jax.experimental import pallas as pl

    si, ki = pl.program_id(0), pl.program_id(1)
    count = cnt_ref[si]

    @pl.when(ki == 0)
    def _():
        rows, n = q_ref.shape[2], kn_ref.shape[2]
        seen = None
        if second_from is not None:
            # two blocks of ``b``, causal across and open inside: the
            # front block's queries see columns under ``b``; the second
            # block's their own and, where the slot leads, the front's
            b = n // 2
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
            second = row >= second_from
            first = jnp.where(second & (lead_ref[si] == 0), b, 0)
            seen = (col >= first) & (col < jnp.where(second, n, b))

        def head(h):    # the softmax's first tile: it sets what later add to
            s = _dot_t(q_ref[0, h], kn_ref[0, h]) * scale      # (rows, n)
            if seen is not None:
                s = jnp.where(seen, s, -jnp.inf)
            top = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - top)
            m_ref[h] = top
            l_ref[h] = jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = jnp.dot(p.astype(vn_ref.dtype), vn_ref[0, h],
                                 preferred_element_type=F32)

        for h in range(k_ref.shape[1]):     # the key/value heads, unrolled
            head(h)

    _cache_tile(count, ki * bk, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                scale=scale, bk=bk)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def pallas_block_decode_attention(q, k, v, k_new, v_new, counts, scale,
                                  lead=None, *, block_k=None,
                                  interpret=None):
    """The kernel lowering of :func:`block_decode_attention`
    (:func:`_decode_options` has the two options)."""
    two_blocks = lead is not None
    lead = (lead.astype(jnp.int32) if two_blocks
            else jnp.zeros(counts.shape, jnp.int32))
    return _block_decode_call(
        q, k, v, k_new.astype(k.dtype), v_new.astype(v.dtype),
        counts.astype(jnp.int32), lead, scale=scale, two_blocks=two_blocks,
        **_decode_options(k.shape[2], block_k, interpret))


# jitted as ``_decode_call`` is: the layers of one query width share ONE
# traced and lowered kernel (SDAR's chunk program holds two, for 2B and B
# query rows, not one a layer)
@functools.partial(jax.jit, static_argnames=("scale", "bk", "two_blocks",
                                             "interpret"))
def _block_decode_call(q, k, v, k_new, v_new, counts, lead, *, scale, bk,
                       two_blocks, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, m, heads, d = q.shape
    kv, t = k.shape[1], k.shape[2]
    group, n = heads // kv, k_new.shape[2]
    rows = m * group

    def slot_map(si, ki, cnt_ref, lead_ref):
        return si, 0, 0, 0

    def cache_map(si, ki, cnt_ref, lead_ref):
        # ``_decode_call.cache_map``'s rule with a count of 0 meaning "no
        # tile visited": the steps past a slot's tiles point at the NEXT
        # slot's first tile, the last slot's stay on its last
        tiles = (cnt_ref[si] + bk - 1) // bk
        done, more = ki >= tiles, si + 1 < s
        return (jnp.where(done & more, si + 1, si), 0,
                jnp.where(done, jnp.where(more, 0, jnp.maximum(tiles - 1, 0)),
                          ki), 0)

    out = pl.pallas_call(
        functools.partial(
            _block_decode_kernel, scale=scale, bk=bk,
            # query ``j`` of ``m`` is row ``n - m + j`` of the forward
            second_from=(n // 2 - (n - m)) * group if two_blocks else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, t // bk),
            in_specs=[pl.BlockSpec((1, kv, rows, d), slot_map),
                      pl.BlockSpec((1, kv, bk, d), cache_map),
                      pl.BlockSpec((1, kv, bk, d), cache_map),
                      pl.BlockSpec((1, kv, n, d), slot_map),
                      pl.BlockSpec((1, kv, n, d), slot_map)],
            out_specs=pl.BlockSpec((1, kv, rows, d), slot_map),
            scratch_shapes=[pltpu.VMEM((kv, rows, 1), F32),
                            pltpu.VMEM((kv, rows, 1), F32),
                            pltpu.VMEM((kv, rows, d), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((s, kv, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gqa_block_decode_fwd",
    )(counts, lead,
      q.reshape(s, m, kv, group, d).transpose(0, 2, 1, 3, 4).reshape(
          s, kv, rows, d), k, v, k_new, v_new)
    return out.reshape(s, kv, m, group, d).transpose(0, 2, 1, 3, 4).reshape(
        s, m, heads * d)


def xla_block_decode_attention(q, k, v, k_new, v_new, counts, scale,
                               lead=None):
    """The XLA form of :func:`block_decode_attention`, as
    :func:`xla_decode_attention`: the whole cache is read
    (:func:`rows_visited` under ``"xla"``), scores ``(S, KV, G * b, T)`` in
    float32."""
    n = k_new.shape[2]
    seen = jnp.arange(k.shape[2])[None, :] < counts[:, None]
    if lead is None:
        return _block_core(q, k, v, k_new, v_new, seen, None, scale)
    m, b = q.shape[1], n // 2
    second = jnp.arange(n) >= b                     # by row of the forward
    asks = second[n - m:, None]
    among = (asks == second[None, :]) | (
        lead[:, None, None] & asks & ~second[None, :])      # (S, m, n)
    # a block of queries a pass over the cache: the float32 scores of
    # ``G * b`` query rows a key/value head stay where the cache's read
    # bounds the pass, and those of twice as many do not (PERF.md section
    # 6, PR 48: 0.45 ms a layer against 1.18 at 64 x 2,560 rows)
    return jnp.concatenate([
        _block_core(q[:, i:i + b], k, v, k_new, v_new, seen,
                    among[:, i:i + b], scale)
        for i in range(0, m, b)], axis=1)


def _block_core(q, k, v, k_new, v_new, seen, among, scale):
    """:func:`block_decode_attention` of the queries ``q (S, m, H, d)``
    over the cache's rows ``seen (S, T)`` and those of the forward's own
    keys that ``among (S, m, n)`` allows (None: all)."""
    s, m, heads, d = q.shape
    kv = k.shape[1]
    group = heads // kv
    q = q.reshape(s, m, kv, group, d).transpose(0, 2, 3, 1, 4).reshape(
        s, kv, group * m, d)
    past = jnp.einsum("skqd,sktd->skqt", q, k.astype(q.dtype),
                      preferred_element_type=F32) * scale
    past = jnp.where(seen[:, None, None], past, -jnp.inf)
    own = jnp.einsum("skqd,skbd->skqb", q, k_new.astype(q.dtype),
                     preferred_element_type=F32) * scale
    if among is not None:
        # (S, m queries, n keys) -> the query axis as ``q`` has it, G * m
        own = jnp.where(jnp.tile(among, (1, group, 1))[:, None], own,
                        -jnp.inf)
    # a query's scores of its own block are finite, so the maximum is
    top = jnp.maximum(jnp.max(past, axis=-1), jnp.max(own, axis=-1))[..., None]
    p_past, p_own = jnp.exp(past - top), jnp.exp(own - top)
    total = jnp.sum(p_past, axis=-1) + jnp.sum(p_own, axis=-1)
    out = (jnp.einsum("skqt,sktd->skqd", p_past.astype(q.dtype),
                      v.astype(q.dtype), preferred_element_type=F32)
           + jnp.einsum("skqb,skbd->skqd", p_own.astype(q.dtype),
                        v_new.astype(q.dtype), preferred_element_type=F32))
    out = (out / total[..., None]).astype(q.dtype)
    return out.reshape(s, kv, group, m, d).transpose(0, 3, 1, 2, 4).reshape(
        s, m, heads * d)

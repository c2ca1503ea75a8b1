"""The attention core of one ABSORBED latent-attention (MLA) decode step:
every head's query, already carried into the latent space, against the
slot's cache of latent rows — which are keys and, in their first ``rank``
numbers, values at once.

:func:`decode_attention` takes ``q_cat (S, H, latent)`` (``[q_lat |
q_rope]`` a head), ``cache (S, T, latent)`` (``[c_kv | rope(k_r)]`` a
token), ``lengths (S,)`` and returns ``o_lat (S, H, rank)``: for each slot
the softmax over its first ``lengths[s]`` cache rows of ``q_cat . row *
scale``, times those rows' first ``rank`` numbers.  ``lengths`` is at least
1 everywhere (a decode step has just written the row it stands on).  Two
lowerings keep that contract, chosen from what the code can observe and
never from a knob (as ``ops/mla_prefill.py`` and ``ops/row_write.py``):

* **Pallas kernel** ``mla_decode_fwd`` — on a TPU backend, no mesh in
  scope, query and cache of one 2- or 4-byte float type, ``rank`` a multiple
  of 128 (the lane tile: the value product reads ``tile[:, :rank]``), ``T``
  a multiple of ``MIN_TILE``.  Grid ``(S, T / bk)``, the key axis
  innermost; the cache tile ``(bk, latent)`` is loaded ONCE and serves both
  products, for all heads (H is the MXU's row axis).  A ``(H, bk)`` score
  tile is accumulated in float32 from the compute-dtype operands and scaled
  in float32, lives in VMEM only, and updates a float32 running max,
  running sum and ``(H, rank)`` accumulator; probabilities are cast to the
  compute dtype for the value product alone, and the one division by the
  sum comes at the end.  ``lengths`` is scalar-prefetched: a key tile
  wholly past the slot's length is not visited and its index map points at
  the last tile that is, so it is not fetched either; only the tile the
  length crosses pays for the iota mask.  It aliases nothing and writes
  only ``o_lat``, so a cache that is a loop carry is read where it lies.
* **XLA** — everywhere else (the CPU of tier-1, the tests' tiny widths, any
  trace under a mesh): the score tensor ``(S, H, T)`` in float32, a masked
  softmax, the value product; the whole cache is read twice.

Which one a traced call took is noted under ``"mla_decode"``
(``ops/lowering.py``; ``ServingEngine.status()["mla_decode"]``), and
:func:`rows_visited` says how many cache rows that lowering reads (the
counter ``mla.cache_rows_read``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from progen_tpu.ops.lowering import mesh_in_scope as _mesh_in_scope
from progen_tpu.ops.lowering import note
from progen_tpu.ops.lowering import on_tpu as _on_tpu

F32 = jnp.float32
# the kernel's key tile on a v5e (PERF.md section 6, PR 33, has the tiles
# measured at both cells' shapes), halved down to ``MIN_TILE`` until it
# divides T
TILE, MIN_TILE = 512, 128


def xla_decode_attention(q_cat, cache, lengths, rank, scale):
    """The XLA form: scores over the whole static cache in float32."""
    logits = jnp.einsum("shl,stl->sht", q_cat, cache,
                        preferred_element_type=F32) * scale
    seen = jnp.arange(cache.shape[1])[None, :] < lengths[:, None]
    probs = jax.nn.softmax(
        jnp.where(seen[:, None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("sht,stl->shl", probs.astype(q_cat.dtype),
                      cache[..., :rank],
                      preferred_element_type=F32).astype(q_cat.dtype)


def _decode_kernel(len_ref, q_ref, c_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale, bk, rank):
    from jax.experimental import pallas as pl

    length = len_ref[pl.program_id(0)]
    ki = pl.program_id(1)
    k0 = ki * bk

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def tile(crossed):
        rows = c_ref[0]                         # (bk, latent), read once
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=F32) * scale
        if crossed:
            cols = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < length, s, -jnp.inf)
        # key tile 0 is always visited first and holds the slot's row 0
        # (lengths >= 1), so ``m_next`` is finite from the first tile on
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank], preferred_element_type=F32)
        m_ref[...] = m_next

    # a key tile is visited if it holds a row the slot has; it needs the
    # mask only where the length ends inside it
    seen = k0 < length
    crossed = k0 + bk > length
    pl.when(seen & crossed)(functools.partial(tile, True))
    pl.when(seen & jnp.logical_not(crossed))(functools.partial(tile, False))

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def fitted_tile(max_len: int) -> int:
    """The largest of ``TILE``, ``TILE / 2``, ... down to ``MIN_TILE`` that
    divides ``max_len``."""
    tile = TILE
    while tile > MIN_TILE and max_len % tile:
        tile //= 2
    return tile


def pallas_decode_attention(q_cat, cache, lengths, rank, scale, *,
                            block_k=None, interpret=None):
    """The kernel lowering; ``interpret=None`` auto-selects the Pallas
    interpreter off-TPU; ``block_k`` defaults to :func:`fitted_tile`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    slots, heads, latent = q_cat.shape
    max_len = cache.shape[1]
    bk = block_k or fitted_tile(max_len)
    if max_len % bk:
        raise ValueError(f"tile {bk} does not divide T = {max_len}")

    def slot_map(si, ki, len_ref):
        return si, 0, 0

    def cache_map(si, ki, len_ref):
        # a tile past the length is not visited: keep the last one that is
        return si, jnp.minimum(ki, jnp.maximum(len_ref[si] - 1, 0) // bk), 0

    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, max_len // bk),
            in_specs=[pl.BlockSpec((1, heads, latent), slot_map),
                      pl.BlockSpec((1, bk, latent), cache_map)],
            out_specs=pl.BlockSpec((1, heads, rank), slot_map),
            scratch_shapes=[pltpu.VMEM((heads, 1), F32),
                            pltpu.VMEM((heads, 1), F32),
                            pltpu.VMEM((heads, rank), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, heads, rank), q_cat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode_fwd",
    )(lengths.astype(jnp.int32), q_cat, cache)


def _key_tile(q_dtype, cache, rank):
    """The kernel's key tile for a query of ``q_dtype`` over ``cache`` (an
    array or its shape), ``None`` where the XLA form runs."""
    dtype = jnp.dtype(q_dtype)
    kernel = (_on_tpu() and not _mesh_in_scope()
              and dtype == jnp.dtype(cache.dtype)
              and jnp.issubdtype(dtype, jnp.floating)
              and dtype.itemsize in (2, 4)
              and rank % 128 == 0 and cache.shape[1] % MIN_TILE == 0)
    return fitted_tile(cache.shape[1]) if kernel else None


def decode_attention(q_cat, cache, lengths, rank, scale):
    """``o_lat (S, H, rank)`` of ``q_cat (S, H, latent)`` over the first
    ``lengths (S,)`` (each at least 1) rows of ``cache (S, T, latent)``,
    scores scaled by ``scale``.  The lowering is chosen as the module
    docstring says."""
    tile = _key_tile(q_cat.dtype, cache, rank)
    note("mla_decode", "xla" if tile is None else "pallas")
    if tile is None:
        return xla_decode_attention(q_cat, cache.astype(q_cat.dtype),
                                    lengths, rank, scale)
    return pallas_decode_attention(q_cat, cache, lengths, rank, scale,
                                   block_k=tile)


def rows_visited(q_dtype, cache, lengths, rank):
    """Cache rows the lowering :func:`decode_attention` takes for a query of
    ``q_dtype`` over ``cache`` reads in one call, as a float32 scalar: whole
    key tiles up to each slot's length under the kernel, the whole cache
    otherwise."""
    tile = _key_tile(q_dtype, cache, rank)
    if tile is None:
        return jnp.asarray(cache.shape[0] * cache.shape[1], F32)
    return (jnp.sum(-(-lengths // tile)) * tile).astype(F32)

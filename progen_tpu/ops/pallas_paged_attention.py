"""Ragged paged decode kernel: per-row page-table walk over pooled state.

This is the decode-side companion of the paged serving subsystem
(``decode/paging.py``).  In this architecture the attention k/v cache is
already an O(2·window) ring per slot — the per-token state that actually
scales with request length (the thing a "paged KV cache" must page) is
the **SGU gate cache**: the spatial gating unit attends over ALL previous
token rows through the learned causal ``(n, n)`` weight, exactly the
all-past-tokens contraction that Ragged Paged Attention (PAPERS.md)
pages.  So the pooled resource here is gate rows and the ragged kernel
computes, for batch row ``b`` at position ``pos_b``::

    mixed[b] = sum_{i <= pos_b} W[pos_b, i] * pool[table[b, i // ps], i % ps]
               + bias[pos_b]

where ``pool`` is the global page pool ``(num_pages, page_size, d)`` and
``table`` is the per-row page table ``(B, pages_per_row)``.  Each batch
row walks ONLY its own pages: the grid is ``(B, pages_per_row)``, the
page axis is innermost (consecutive visits to the same output row, the
accumulation contract from ``pallas_sgu.py``), and pages past the row's
position are skipped entirely (``@pl.when`` — a short request touches
``pos // ps + 1`` pages, not the table width).  Discipline:

* the per-page partial products accumulate in an f32 VMEM scratch;
* ``pos`` and the page table ride in as SCALAR-PREFETCH operands
  (``pltpu.PrefetchScalarGridSpec``): the index map that chooses the pool
  page (``table_ref[b, p]``) is an integer lookup into prefetched SMEM —
  no gather materialization of the pool, no float work on the scalar
  core;
* every block is one the TPU compiler takes: the last two dimensions of a
  block are multiples of (8, 128) or the whole of the array's, so every
  size-1 axis sits in a LEADING block dimension.  The weight row of each
  batch row is gathered, causally masked and (for int8 weights)
  dequantized by XLA before the kernel — ``B`` rows of ``n`` f32, a few
  KB against the pool's megabytes — and handed over as
  ``(B, pages_per_row, 1, page_size)``; the bias is added after it.  The
  kernel keeps what is large: the page walk and the contraction;
* the contraction is a VPU multiply + sublane reduce (``(ps, 1)`` weight
  column against the ``(ps, d)`` page), not an M=1 MXU matmul;
* unowned table entries point at the all-zeros ``NULL_PAGE`` so reading
  them is harmless, and the causal mask zeroes columns past ``pos`` so
  stale rows in reused pages contribute exact ±0.

The XLA fallback (``impl="xla"``) is a gather + the SAME masked einsum
the dense decode path uses, sliced to the dense row count — on CPU it is
bitwise identical to the fixed-slot engine's contraction, which is what
the engine-parity tier-1 tests pin.  ``interpret=None`` auto-selects the
Pallas interpreter off-TPU, mirroring ``pallas_sgu.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from progen_tpu.decode.paging import DUMP_PAGE, NULL_PAGE
from progen_tpu.ops.quant import quantize_rows


def _column(row):
    """``(1, k)`` row -> ``(k, 1)`` column without a transpose: broadcast
    down the sublanes, keep the diagonal, reduce along the lanes (exact:
    every sum has one non-zero term)."""
    k = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (k, k), 1))
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (k, k)), 0.0),
                   axis=1, keepdims=True)


def _mix_kernel(pos_ref, table_ref, w_ref, pool_ref, *rest, page_size,
                pages_per_row, scaled):
    """One (batch row, page) step.  ``w_ref`` is this page's slice of the
    row's masked f32 weights; with ``scaled`` the int8 page is dequantized
    here by folding its per-row scales (``pscale_ref``, indexed like the
    page) into the weights, so nothing 8-bit of the pool ever round-trips
    HBM at higher precision."""
    if scaled:
        pscale_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages strictly past the row's position hold no live rows: skip the
    # fetch-multiply entirely (ragged walk — work scales with pos, not
    # with the table width)
    @pl.when(p <= pos_ref[b] // page_size)
    def _accumulate():
        w = w_ref[0, 0]  # (1, page_size)
        if scaled:
            w = w * pscale_ref[0]
        acc_ref[...] += jnp.sum(
            _column(w) * pool_ref[0].astype(jnp.float32),
            axis=0, keepdims=True)

    @pl.when(p == pages_per_row - 1)
    def _epilogue():
        o_ref[0] = acc_ref[...]


def _pallas_mix(weights, biases, pool, table, pos, w_scale=None,
                pool_scale=None, *, interpret):
    """Launch the ragged page walk; ``w_scale`` (per weight row) and
    ``pool_scale`` (per pool row) mark the int8 side(s).  Full precision
    passes no scale operand at all: the bit-identity contract of the
    default path must not depend on all-ones multiplies optimizing
    away."""
    batch, pages_per_row = table.shape
    num_pages, page_size, d = pool.shape
    n = weights.shape[0]
    span = pages_per_row * page_size
    pos = pos.astype(jnp.int32)
    w_rows = weights[pos].astype(jnp.float32)  # (B, n)
    if w_scale is not None:
        w_rows = w_rows * w_scale.astype(jnp.float32)[pos][:, None]
    w_rows = jnp.where(jnp.arange(n)[None, :] <= pos[:, None], w_rows, 0.0)
    if span > n:
        # the last page may run past the (n, n) weight square: its
        # columns get zero weights, and their pool rows are real page
        # rows, so the product is exact zero, not garbage
        w_rows = jnp.pad(w_rows, ((0, 0), (0, span - n)))
    w_rows = w_rows[:, :span].reshape(batch, pages_per_row, 1, page_size)

    in_specs = [
        pl.BlockSpec((1, 1, 1, page_size),
                     lambda b, p, pos_ref, table_ref: (b, p, 0, 0)),
        # the pool page this row's table names for block p (integer-only
        # index map: a scalar-prefetch ref indexed by grid coordinates)
        pl.BlockSpec((1, page_size, d),
                     lambda b, p, pos_ref, table_ref: (table_ref[b, p], 0, 0)),
    ]
    operands = [w_rows, pool]
    if pool_scale is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, page_size),
                         lambda b, p, pos_ref, table_ref:
                         (table_ref[b, p], 0, 0)))
        operands.append(pool_scale.astype(jnp.float32).reshape(
            num_pages, 1, page_size))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, pages_per_row),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, d),
                               lambda b, p, pos_ref, table_ref: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
    )
    kernel = functools.partial(_mix_kernel, page_size=page_size,
                               pages_per_row=pages_per_row,
                               scaled=pool_scale is not None)
    mixed = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, 1, d), jnp.float32),
        interpret=interpret,
        name="paged_gate_mix",
    )(pos, table.astype(jnp.int32), *operands)
    # biases come in as (n, 1) column vectors (ops/sgu.py layout)
    return mixed[:, 0] + biases.astype(jnp.float32).reshape(n, 1)[pos]


def _xla_mix(weights, biases, pool, table, pos, *, n_rows,
             w_scale=None, pool_scale=None):
    """Gather fallback, bit-matched to the dense decode contraction.

    Gathers each row's pages, slices to exactly ``n_rows`` (the dense
    engine's cache length) and runs the IDENTICAL masked f32 einsum the
    dense ``SGUDecode`` uses — stale rows in reused pages meet exact-zero
    causal weights, so the sums are bitwise those of the dense engine.
    Under quantization the int8 weight rows / pool rows dequantize in f32
    right after the gather (``w_scale`` per weight row, ``pool_scale``
    per pool row), so the contraction itself is unchanged.
    """
    batch, pages_per_row = table.shape
    _, page_size, d = pool.shape
    rows = pool[table].reshape(batch, pages_per_row * page_size, d)
    rows = rows[:, :n_rows].astype(jnp.float32)
    if pool_scale is not None:
        ps = pool_scale[table].reshape(batch, pages_per_row * page_size)
        rows = rows * ps[:, :n_rows, None]
    w_rows = weights.astype(jnp.float32)[pos][:, :n_rows]
    if w_scale is not None:
        w_rows = w_rows * w_scale.astype(jnp.float32)[pos][:, None]
    causal = jnp.arange(n_rows)[None, :] <= pos[:, None]
    w_rows = w_rows * causal.astype(jnp.float32)
    mixed = jnp.einsum("bnd,bn->bd", rows, w_rows,
                       preferred_element_type=jnp.float32)
    bias_m = biases.astype(jnp.float32)[pos]  # (B, 1), dense layout
    return mixed + bias_m


def paged_gate_mix(weights, biases, pool, table, pos, *, n_rows,
                   impl="xla", interpret=None, w_scale=None,
                   pool_scale=None):
    """Ragged paged spatial-gate contraction.

    Args:
      weights: ``(n, n)`` learned causal spatial weights (f32, or int8
        when ``w_scale`` is given).
      biases: ``(n, 1)`` spatial biases.
      pool: ``(num_pages, page_size, d)`` global gate-row pool (compute
        dtype, or int8 when ``pool_scale`` is given).
      table: ``(B, pages_per_row)`` int32 page table (NULL_PAGE for
        unowned entries).
      pos: ``(B,)`` int32 current positions.
      n_rows: dense cache length the XLA path slices to (the fixed-slot
        engine's ``decode_len``) — keeps the fallback bit-identical to
        the dense contraction.
      impl: ``"xla"`` (gather fallback) or ``"pallas"`` (ragged kernel).
      interpret: force/disable the Pallas interpreter; None auto-selects
        it off-TPU.
      w_scale: optional ``(n,)`` f32 per-row scale for int8 weights.
      pool_scale: optional ``(num_pages, page_size)`` f32 per-row scale
        pool for int8 gate pages.

    Returns:
      ``(B, d)`` f32 ``mixed + bias`` (caller casts to the compute dtype
      and applies the gate multiply, matching dense ``SGUDecode``).
    """
    if impl == "xla":
        return _xla_mix(weights, biases, pool, table, pos, n_rows=n_rows,
                        w_scale=w_scale, pool_scale=pool_scale)
    if impl != "pallas":
        raise ValueError(f"unknown paged gate impl: {impl!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _pallas_mix(weights, biases, pool, table, pos, w_scale,
                       pool_scale, interpret=interpret)


def write_gate_row(pool, table, pos, gate, write_ok, scale=None):
    """Scatter each live row's freshly computed gate into its page.

    Rows with ``write_ok=False`` (done / inactive / paused) and rows
    whose table entry is still NULL are redirected to the write-sink
    ``DUMP_PAGE`` — the scatter stays dense and unpredicated, and the
    zero page plus read-only shared pages are never clobbered.

    With ``scale`` (the ``(num_pages, page_size)`` f32 scale pool of an
    int8 gate pool) the row is quantized per-row on scatter — the int8
    code and its f32 scale land through the SAME redirected target — and
    the call returns ``(pool, scale)`` instead of ``pool``.
    """
    page_size = pool.shape[1]
    tgt = jnp.take_along_axis(table, (pos // page_size)[:, None],
                              axis=1)[:, 0]
    tgt = jnp.where(write_ok & (tgt != NULL_PAGE), tgt, DUMP_PAGE)
    if scale is None:
        return pool.at[tgt, pos % page_size].set(gate)
    q, s = quantize_rows(gate)
    return (pool.at[tgt, pos % page_size].set(q),
            scale.at[tgt, pos % page_size].set(s))

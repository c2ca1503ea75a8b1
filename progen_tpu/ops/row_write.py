"""One new row per slot into a cache whose slots sit at different rows.

A batched decode step writes ``update[b]`` into ``cache[b]`` at row
``idx[b]`` — the k/v rings, the SGU gate cache, LongCat's latent cache, the
engine's token buffer.  Written as a ``vmap`` of
``dynamic_update_index_in_dim`` (or ``cache.at[arange, idx].set``) this is
ONE ``scatter`` with a batching dimension, and the TPU compiler expands a
scatter into a serial loop over its ``B`` indices: five tiny device
operations per row, 3–4 µs an iteration, 27 scatters x 64 rows a generated
token at ProGen-small (PERF.md section 6, PR 29).

:func:`write_rows` keeps that contract and picks the lowering from what it
can observe, never from a knob:

* **Pallas kernel** (``row_write``) — on a TPU backend, no mesh in scope,
  the written axis second to last in the cache and a multiple of the
  dtype's sublane tile ``T`` (16 for bf16, 8 for f32/s32).  The cache is
  aliased to the output (``input_output_aliases``), so nothing but the
  touched tiles moves: the ``(B,)`` indices are scalar-prefetched, the grid
  runs over slots, and per slot only the aligned ``(…, T, d)`` tile holding
  ``idx[b]`` is brought in, one row of it replaced by an iota select, and
  written back.  Caches of one shape (a layer's k and v) share one call.
* **iota select** — a 2-D buffer (``(B, L)`` scalars, the token buffer):
  one elementwise pass over a buffer of kilobytes, on every backend.
* **the scatter** — everywhere else: the CPU (tier-1 must not run 27
  interpreted kernels a decode step), an axis that is no multiple of ``T``
  (an odd ``decode_len`` from the sampler), and any trace under a mesh
  (mesh serving shards the slot axis; the kernel is NOT wrapped in
  ``shard_map``, the sharded path keeps the scatter XLA can partition).

All three write the same bytes to the same places; out-of-range indices
follow the scatter's rule (a negative index counts from the end, then
clips).  :func:`record_paths` lets a caller that traces a program collect
which lowering its writes took (``ServingEngine.status()["row_write"]``).

:func:`write_row_blocks` is the same contract for a BLOCK of ``n`` rows a
slot that a per-slot flag may withhold (a block-diffusion step writes a
block's keys only when it commits it): the kernel ``row_block_write``
brings in the one aligned tile that holds the block (``n`` divides the
tile), replaces the block's rows where the flag is set and writes the tile
back; elsewhere a slice update a slot.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from progen_tpu.ops.lowering import mesh_in_scope as _mesh_in_scope
from progen_tpu.ops.lowering import note, record_lowerings
from progen_tpu.ops.lowering import on_tpu as _on_tpu


@contextlib.contextmanager
def record_paths():
    """Collect the lowerings (``"pallas"`` / ``"scatter"``) that the
    :func:`write_rows` calls traced inside the block chose for caches of
    three or more dimensions."""
    with record_lowerings() as chosen:
        yield chosen.setdefault("row_write", set())


def sublane_tile(dtype) -> int:
    """Rows of the chip's native tile for ``dtype``: 8 x 128 words of 32
    bits, narrower types packed along the sublanes."""
    return 32 // jnp.dtype(dtype).itemsize


def _kernel_takes(cache, axis) -> bool:
    return (cache.ndim >= 3 and axis + 1 == cache.ndim - 2
            and jnp.dtype(cache.dtype).itemsize in (2, 4)
            and cache.shape[-2] % sublane_tile(cache.dtype) == 0)


def _scatter_rows(cache, update, idx, axis):
    return jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_index_in_dim(c, u, i, axis)
    )(cache, update, idx)


def _wrap(idx, size):
    """The scatter's index rule: negative counts from the end, then clip."""
    idx = idx.astype(jnp.int32)
    return jnp.clip(jnp.where(idx < 0, idx + size, idx), 0, size - 1)


def _row_kernel(idx_ref, *refs, tile):
    from jax.experimental import pallas as pl

    n = len(refs) // 3
    row = idx_ref[pl.program_id(0)] % tile
    for c_ref, u_ref, o_ref in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        rows = jax.lax.broadcasted_iota(jnp.int32, c_ref.shape, 2)
        o_ref[...] = jnp.where(rows == row, u_ref[...], c_ref[...])


def pallas_write_rows(caches, updates, idx, *, interpret=None):
    """The kernel lowering: ``caches`` a tuple of equal-shaped
    ``(B, …, R, d)`` arrays, ``updates`` their ``(B, …, d)`` rows, the
    written axis second to last and ``R`` a multiple of the sublane tile.
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU."""
    # imported here: a process that never takes the kernel (the CPU, a mesh)
    # does not pay the second that importing Pallas costs
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    shape, dtype = caches[0].shape, caches[0].dtype
    b, (r, d) = shape[0], shape[-2:]
    tile = 32 // dtype.itemsize  # sublane_tile, spelled so graftcheck sees a host int
    # leading dims fold into one (the last two, which the chip tiles, stay)
    caches = [c.reshape(b, -1, r, d) for c in caches]
    updates = [u.reshape(b, -1, 1, d) for u in updates]
    h = caches[0].shape[1]
    tile_spec = pl.BlockSpec(
        (1, h, tile, d), lambda i, idx_ref: (i, 0, idx_ref[i] // tile, 0))
    row_spec = pl.BlockSpec((1, h, 1, d), lambda i, idx_ref: (i, 0, 0, 0))
    n = len(caches)
    out = pl.pallas_call(
        functools.partial(_row_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[tile_spec] * n + [row_spec] * n,
            out_specs=[tile_spec] * n,
        ),
        out_shape=[jax.ShapeDtypeStruct(c.shape, dtype) for c in caches],
        # operand 0 is the prefetched index vector
        input_output_aliases={1 + i: i for i in range(n)},
        interpret=interpret,
        name="row_write",
    )(_wrap(idx, r), *caches, *updates)
    return tuple(o.reshape(shape) for o in out)


def write_rows(cache, update, idx, axis):
    """Write ``update[b]`` into ``cache[b]`` at row ``idx[b]`` along
    ``axis`` of the per-slot view (``axis + 1`` of ``cache``); ``idx`` is
    ``(B,)``.  ``cache`` and ``update`` may be tuples of equal-shaped
    arrays written at the same rows (a layer's k and v): a tuple comes
    back.  The lowering is chosen as the module docstring says."""
    many = isinstance(cache, (tuple, list))
    caches = tuple(cache) if many else (cache,)
    updates = tuple(update) if many else (update,)
    first = caches[0]
    if first.ndim == 2:
        hit = (jnp.arange(first.shape[1])[None, :]
               == _wrap(idx, first.shape[1])[:, None])
        out = tuple(jnp.where(hit, u[:, None], c)
                    for c, u in zip(caches, updates))
        return out if many else out[0]
    kernel = _on_tpu() and not _mesh_in_scope() and _kernel_takes(first, axis)
    note("row_write", "pallas" if kernel else "scatter")
    if kernel:
        out = pallas_write_rows(caches, updates, idx)
    else:
        out = tuple(_scatter_rows(c, u, idx, axis)
                    for c, u in zip(caches, updates))
    return out if many else out[0]


# ------------------------------------------------- a block of rows, or none


def _block_kernel(idx_ref, n_ref, *refs, tile):
    from jax.experimental import pallas as pl

    n = len(refs) // 3
    i = pl.program_id(0)
    first = idx_ref[i] % tile
    for c_ref, u_ref, o_ref in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        rows = jax.lax.broadcasted_iota(jnp.int32, c_ref.shape, 2)
        hit = (rows >= first) & (rows < first + n_ref[i])
        o_ref[...] = jnp.where(hit, u_ref[...], c_ref[...])


def pallas_write_row_blocks(caches, updates, start, write, *, interpret=None):
    """The kernel lowering of :func:`write_row_blocks`: as
    :func:`pallas_write_rows`, the aligned tile that holds the block brought
    in, the block's rows replaced where ``write`` and the tile written back
    (unchanged where not).  The update arrives repeated to a whole tile
    (``n`` divides the tile and ``start`` is a multiple of ``n``, so copy
    ``j`` of the block lies where the block would), and the select needs no
    shuffle inside the kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    shape, dtype = caches[0].shape, caches[0].dtype
    b, (r, d) = shape[0], shape[-2:]
    n = updates[0].shape[-2]
    tile = 32 // dtype.itemsize  # sublane_tile, a host int for graftcheck
    caches = [c.reshape(b, -1, r, d) for c in caches]
    updates = [jnp.tile(u.reshape(b, -1, n, d), (1, 1, tile // n, 1))
               for u in updates]
    h = caches[0].shape[1]
    tile_spec = pl.BlockSpec(
        (1, h, tile, d),
        lambda i, idx_ref, n_ref: (i, 0, idx_ref[i] // tile, 0))
    block_spec = pl.BlockSpec((1, h, tile, d),
                              lambda i, idx_ref, n_ref: (i, 0, 0, 0))
    m = len(caches)
    out = pl.pallas_call(
        functools.partial(_block_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[tile_spec] * m + [block_spec] * m,
            out_specs=[tile_spec] * m,
        ),
        out_shape=[jax.ShapeDtypeStruct(c.shape, dtype) for c in caches],
        # operands 0 and 1 are the prefetched starts and counts
        input_output_aliases={2 + i: i for i in range(m)},
        interpret=interpret,
        name="row_block_write",
    )(_wrap(start, r), jnp.where(write, n, 0).astype(jnp.int32),
      *caches, *updates)
    return tuple(o.reshape(shape) for o in out)


def write_row_blocks(caches, updates, start, write):
    """Write the ``n`` rows ``updates[b] (…, n, d)`` into ``caches[b] (…,
    R, d)`` at rows ``start[b] .. start[b] + n - 1`` where ``write[b]``, and
    NOTHING where not: a block-diffusion step's commit (``models/kv.py``),
    which most rows of most steps withhold.  ``caches`` / ``updates`` are
    tuples of equal-shaped arrays written at the same rows (a layer's k and
    v); ``start (B,)`` is a multiple of ``n``, ``start + n <= R``.  The
    lowering is chosen as :func:`write_rows` chooses (noted under
    ``"row_write"`` too): the kernel ``row_block_write`` on a TPU with no
    mesh in scope where ``n`` divides the dtype's sublane tile and the tile
    divides ``R``; elsewhere a slice update a slot, the withheld rows
    re-written with what they held."""
    first = caches[0]
    n = updates[0].shape[-2]
    kernel = (_on_tpu() and not _mesh_in_scope()
              and _kernel_takes(first, first.ndim - 3)
              and sublane_tile(first.dtype) % n == 0)
    note("row_write", "pallas" if kernel else "scatter")
    if kernel:
        return pallas_write_row_blocks(tuple(caches), tuple(updates), start,
                                       write)
    axis = first.ndim - 3           # of the per-slot view

    def one(c, u, i, w):
        old = jax.lax.dynamic_slice_in_dim(c, i, n, axis)
        return jax.lax.dynamic_update_slice_in_dim(
            c, jnp.where(w, u, old), i, axis)

    return tuple(jax.vmap(one)(c, u, start.astype(jnp.int32), write)
                 for c, u in zip(caches, updates))

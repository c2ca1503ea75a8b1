"""Blocked lower-triangular Pallas SGU kernel — fused forward AND backward.

The SGU token-mixing matmul (``ops/sgu.py``) is a LEARNED causal ``(n, n)``
weight against the gate half of the gMLP hidden: ``mixed[m] = sum_{k<=m}
W[m, k] * gate[k] + bias[m]``, followed by the elementwise gate multiply
``out = res * mixed`` (``models/progen.py`` SGU).  The XLA path computes
the masked matmul DENSE — 2x the causal FLOPs plus an ``(n, n)`` mask (or
tril) materialization — and round-trips the ``(B, n, d)`` ``mixed`` tensor
through HBM between the matmul and the multiply.

These kernels recover both:

* **block skipping** — the ``(n, n)`` weights are tiled into square
  ``block x block`` tiles and the grid enumerates ONLY the lower-triangle
  tiles (``R(R+1)/2`` of ``R^2``), pairing row ``i`` with row ``R-1-i`` so
  the triangle flattens into an exactly rectangular ``(R/2, R+1)`` grid
  with integer-only index maps (no sqrt on the scalar core).  The tril
  mask is applied only INSIDE diagonal tiles; strictly-upper tiles are
  never fetched or multiplied, so the executed matmul FLOPs are
  ``(R+1)/(2R)`` of dense (0.5625x at n=1024, 0.531x at n=2048 with the
  128-wide tiles the chip requires — see :func:`sgu_block_flops`);
* **epilogue fusion** — the ``+ bias`` and the final ``res * mixed``
  multiply run in VMEM on the f32 accumulator before the single output
  write, so ``mixed`` never reaches HBM.

Backward (hand-written custom VJP, mirroring ``pallas_attention.py``'s
flash-style structure):

* ``d_res = dout * mixed`` — ``mixed`` is NOT saved by the forward; it is
  recomputed blockwise by the SAME forward kernel with ``dout`` standing
  in for ``res`` (``dout * (W_tril @ gate + b)``), so the only extra
  residual the VJP keeps is the gate input itself;
* ``d_gate = W_tril^T @ (dout * res)`` — a transposed triangle sweep
  (output column tile j consumes row tiles i >= j), same pairing trick;
* ``d_W = tril(sum_b (dout * res) @ gate^T)`` — triangle tiles only, batch
  as the innermost (accumulating) grid dimension; the strict upper
  triangle is hard-zeroed (matching the reference parameterization where
  masked weights get exactly-zero grads);
* ``d_bias`` — a plain XLA fused multiply+reduce (never materializes
  ``dout * res``).

All matmuls accumulate in f32 scratch; inputs/outputs stay in the compute
dtype.  ``interpret=None`` auto-selects the Pallas interpreter off-TPU so
the CPU test tier exercises the real kernel logic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Square (block, block) weight tiles of 128: the one size the chip takes.
# The TPU compiler wants the last two dimensions of every block divisible
# by (8, 128), so a 64-wide weight tile is refused outright, and the v5e
# MXU is 128 deep, so narrower tiles would half-fill it anyway.  The
# block-granular causal hull is (R+1)/2R of dense: 0.5625x at n=1024
# (R=8), 0.531x at n=2048 (R=16).  Sequences shorter than one pair of
# tiles pad up to it (``_prep``), under the interpreter as on the chip,
# so the CPU tests run the tiling the chip runs.
DEFAULT_BLOCK = 128


def _dot(a, b):  # a @ b, f32 accumulate
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tl(a, b):  # a^T @ b, f32 accumulate
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tr(a, b):  # a @ b^T, f32 accumulate
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _tile_tril(block: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return rows >= cols


# -- triangle -> rectangle grid flattening ------------------------------------
#
# Lower-triangle tile rows have lengths 1..R.  Pairing row p (length p+1)
# with row R-1-p (length R-p) gives constant length R+1, so the grid is
# exactly (R/2, R+1) with R even (the wrappers pad to even R).  Row-major
# within a pair keeps each output tile's visits CONSECUTIVE — the Pallas
# revisiting/accumulation contract.


def _fwd_ij(p, c, nbr):
    """Grid step (p, c) -> weight-tile (i, j): pair p covers row i=p for
    c in [0, p] (j=c) then row i=nbr-1-p for c in [p+1, nbr] (j=c-p-1).
    In both segments j ascends to the DIAGONAL tile last."""
    in_a = c <= p
    i = jnp.where(in_a, p, nbr - 1 - p)
    j = jnp.where(in_a, c, c - p - 1)
    return i, j


def _dgate_ji(p, c, nbr):
    """Transposed sweep for d_gate: output COLUMN tile j consumes row
    tiles i >= j.  Column lengths are R-j, so pair column j=p (length
    nbr-p, c in [0, nbr-1-p], i=p+c) with column j=nbr-1-p (length p+1,
    c in [nbr-p, nbr], i=c-1).  Each segment STARTS at the diagonal."""
    in_a = c <= nbr - 1 - p
    j = jnp.where(in_a, p, nbr - 1 - p)
    i = jnp.where(in_a, p + c, c - 1)
    return i, j


# -- kernels ------------------------------------------------------------------


def _fwd_kernel(w_ref, g_ref, res_ref, b_ref, o_ref, acc_ref, *, nbr):
    p = pl.program_id(1)
    c = pl.program_id(2)
    first = jnp.logical_or(c == 0, c == p + 1)
    diag = jnp.logical_or(c == p, c == nbr)  # j == i: segment's LAST step

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]  # (block, block) tile at (i, j)
    w = jnp.where(jnp.logical_and(diag, ~_tile_tril(w.shape[0])), 0, w)
    acc_ref[...] += _dot(w, g_ref[0])

    @pl.when(diag)
    def _():
        # epilogue matches the XLA path bit-for-bit in spirit: f32 mixed
        # (+bias) cast to the compute dtype, THEN multiplied by res
        mixed = acc_ref[...] + b_ref[...].astype(jnp.float32)
        o_ref[0] = res_ref[0] * mixed.astype(o_ref.dtype)


def _dgate_kernel(w_ref, do_ref, res_ref, dg_ref, acc_ref, *, nbr):
    p = pl.program_id(1)
    c = pl.program_id(2)
    diag = jnp.logical_or(c == 0, c == nbr - p)  # segment's FIRST step
    last = jnp.logical_or(c == nbr - 1 - p, c == nbr)

    @pl.when(diag)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    w = jnp.where(jnp.logical_and(diag, ~_tile_tril(w.shape[0])), 0, w)
    dmix = do_ref[0] * res_ref[0]
    acc_ref[...] += _dot_tl(w, dmix)  # W^T @ dmix: (block_j, d)

    @pl.when(last)
    def _():
        dg_ref[0] = acc_ref[...].astype(dg_ref.dtype)


def _dw_kernel(do_ref, res_ref, g_ref, dw_ref, acc_ref, *, nbatch):
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dmix = do_ref[0] * res_ref[0]  # (block_i, d)
    acc_ref[...] += _dot_tr(dmix, g_ref[0])  # dmix @ gate^T: (block_i, block_j)

    @pl.when(b == nbatch - 1)
    def _():
        # no in-tile mask: the wrapper tril's the whole (n, n) grad, which
        # also zeroes the never-visited strictly-upper tiles exactly
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


# -- padded launch helpers ----------------------------------------------------


def _prep(res, gate, weights, biases, block: int):
    """Flatten batch, pad n up to an EVEN number of tiles (zero rows/cols
    are exact: zero gate/res rows contribute and produce nothing)."""
    n = weights.shape[0]
    d = gate.shape[-1]
    lead = gate.shape[:-2]
    bsz = 1
    for s in lead:
        bsz *= s
    nbr = -(-n // block)
    nbr += nbr % 2  # pairing needs an even tile count
    npad = nbr * block - n
    g = gate.reshape(bsz, n, d)
    r = res.reshape(bsz, n, d)
    if npad:
        g = jnp.pad(g, ((0, 0), (0, npad), (0, 0)))
        r = jnp.pad(r, ((0, 0), (0, npad), (0, 0)))
        weights = jnp.pad(weights, ((0, npad), (0, npad)))
        biases = jnp.pad(biases, ((0, npad), (0, 0)))
    return g, r, weights, biases, bsz, nbr, lead


def _forward(res, gate, weights, biases, block: int, interpret: bool):
    n, d = weights.shape[0], gate.shape[-1]
    g, r, w, b, bsz, nbr, lead = _prep(res, gate, weights, biases, block)

    def wmap(bb, p, c):
        return _fwd_ij(p, c, nbr)

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, nbr=nbr),
        grid=(bsz, nbr // 2, nbr + 1),
        in_specs=[
            pl.BlockSpec((block, block), wmap),
            pl.BlockSpec((1, block, d),
                         lambda bb, p, c: (bb, _fwd_ij(p, c, nbr)[1], 0)),
            pl.BlockSpec((1, block, d),
                         lambda bb, p, c: (bb, _fwd_ij(p, c, nbr)[0], 0)),
            pl.BlockSpec((block, 1),
                         lambda bb, p, c: (_fwd_ij(p, c, nbr)[0], 0)),
        ],
        out_specs=pl.BlockSpec((1, block, d),
                               lambda bb, p, c: (bb, _fwd_ij(p, c, nbr)[0], 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, nbr * block, d), gate.dtype),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        interpret=interpret,
        name="sgu_fwd",
    )(w, g, r, b)
    return out[:, :n].reshape(*lead, n, d)


def _backward_dgate(weights, dout, res, block: int, interpret: bool):
    n, d = weights.shape[0], dout.shape[-1]
    do, r, w, _b, bsz, nbr, lead = _prep(
        dout, res, weights, jnp.zeros((n, 1), weights.dtype), block)
    # _prep maps (res=dout, gate=res) -> (g=res? no: g is the FIRST tensor)
    # — name them explicitly to avoid confusion:
    do_p, res_p = do, r

    def wmap(bb, p, c):
        return _dgate_ji(p, c, nbr)

    dg = pl.pallas_call(
        functools.partial(_dgate_kernel, nbr=nbr),
        grid=(bsz, nbr // 2, nbr + 1),
        in_specs=[
            pl.BlockSpec((block, block), wmap),
            pl.BlockSpec((1, block, d),
                         lambda bb, p, c: (bb, _dgate_ji(p, c, nbr)[0], 0)),
            pl.BlockSpec((1, block, d),
                         lambda bb, p, c: (bb, _dgate_ji(p, c, nbr)[0], 0)),
        ],
        out_specs=pl.BlockSpec((1, block, d),
                               lambda bb, p, c: (bb, _dgate_ji(p, c, nbr)[1], 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, nbr * block, d), dout.dtype),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        interpret=interpret,
        name="sgu_dgate",
    )(w, do_p, res_p)
    return dg[:, :n].reshape(*lead, n, d)


def _backward_dw(dout, res, gate, weights_dtype, n: int, block: int,
                 interpret: bool):
    d = dout.shape[-1]
    do, r, _w, _b, bsz, nbr, _lead = _prep(
        dout, res, jnp.zeros((n, n), weights_dtype),
        jnp.zeros((n, 1), weights_dtype), block)
    g = gate.reshape(bsz, n, d)
    if nbr * block != n:
        g = jnp.pad(g, ((0, 0), (0, nbr * block - n), (0, 0)))

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, nbatch=bsz),
        grid=(nbr // 2, nbr + 1, bsz),  # batch INNERMOST: accumulating dim
        in_specs=[
            pl.BlockSpec((1, block, d),
                         lambda p, c, bb: (bb, _fwd_ij(p, c, nbr)[0], 0)),
            pl.BlockSpec((1, block, d),
                         lambda p, c, bb: (bb, _fwd_ij(p, c, nbr)[0], 0)),
            pl.BlockSpec((1, block, d),
                         lambda p, c, bb: (bb, _fwd_ij(p, c, nbr)[1], 0)),
        ],
        out_specs=pl.BlockSpec((block, block),
                               lambda p, c, bb: _fwd_ij(p, c, nbr)),
        out_shape=jax.ShapeDtypeStruct((nbr * block, nbr * block),
                                       weights_dtype),
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)],
        interpret=interpret,
        name="sgu_dw",
    )(do, r, g)
    # hard-zero the masked parameterization's dead region: tril also
    # clears the strictly-upper tiles the grid never visited
    return jnp.tril(dw[:n, :n])


# -- custom VJP ---------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _sgu_fused(res, gate, weights, biases, block, interpret, reduce_axes):
    return _forward(res, gate, weights, biases, block, interpret)


def _sgu_fwd(res, gate, weights, biases, block, interpret, reduce_axes):
    out = _forward(res, gate, weights, biases, block, interpret)
    return out, (res, gate, weights, biases)


def _sgu_bwd(block, interpret, reduce_axes, saved, dout):
    res, gate, weights, biases = saved
    n = weights.shape[0]
    lead_axes = tuple(range(dout.ndim - 2))
    # d_res = dout * mixed — mixed recomputed by the forward kernel with
    # dout standing in for res (nothing beyond the inputs was saved)
    d_res = _forward(dout, gate, weights, biases, block, interpret)
    d_gate = _backward_dgate(weights, dout, res, block, interpret)
    d_w = _backward_dw(dout, res, gate, weights.dtype, n, block, interpret)
    # bias broadcast over batch and d: fused XLA multiply+reduce, f32
    d_b = jnp.sum(
        (dout * res).astype(jnp.float32), axis=lead_axes + (dout.ndim - 1,)
    ).reshape(n, 1).astype(biases.dtype)
    if reduce_axes:
        # full-manual shard_map: weights/biases enter replicated, so their
        # cotangents must be summed over the data-parallel and d-sharded
        # mesh axes explicitly (parallel/context.py passes the axis names)
        d_w = jax.lax.psum(d_w, reduce_axes)
        d_b = jax.lax.psum(d_b, reduce_axes)
    return d_res, d_gate, d_w, d_b


_sgu_fused.defvjp(_sgu_fwd, _sgu_bwd)


# -- public API ---------------------------------------------------------------


def pallas_spatial_gate(res, gate, weights, biases, *,
                        block_size: int | None = None,
                        interpret: bool | None = None,
                        reduce_axes: tuple = ()):
    """Fused blocked-causal SGU: ``res * (tril(weights) @ gate + biases)``.

    ``res``/``gate``: ``(..., n, d)`` (the two halves of the gMLP hidden,
    gate already LayerNormed); ``weights``: ``(n, n)``; ``biases``:
    ``(n, 1)``.  Drop-in for the XLA ``x * spatial_gate(gate, w, b)``
    composition in ``models/progen.py``.

    ``interpret=None`` auto-selects the Pallas interpreter off-TPU.
    ``reduce_axes`` is for the full-manual shard_map wrapper
    (``parallel/context.py``): mesh axis names whose devices hold
    replicated weights/biases — their grads are psummed in the VJP.
    """
    n = weights.shape[0]
    if weights.shape != (n, n):
        raise ValueError(f"weights must be square, got {weights.shape}")
    if gate.shape[-2] != n or res.shape != gate.shape:
        raise ValueError(
            f"res/gate {res.shape}/{gate.shape} must be (..., {n}, d) "
            f"matching weights {weights.shape}"
        )
    if biases.shape != (n, 1):
        raise ValueError(f"biases must be ({n}, 1), got {biases.shape}")
    interp = jax.default_backend() != "tpu" if interpret is None else interpret
    block = DEFAULT_BLOCK if block_size is None else block_size
    return _sgu_fused(res, gate, weights, biases, block, interp,
                      tuple(reduce_axes))


def sgu_block_flops(n: int, d: int, block_size: int | None = None) -> dict:
    """Static FLOP accounting for one forward spatial matmul at seq ``n``,
    width ``d``: blocks executed x per-block FLOPs vs the dense einsum.
    The acceptance gate (tests/test_pallas_sgu.py) pins the ratio of the
    default 128-wide tiles: exactly ``(R+1)/2R`` — 0.5625 at n=1024,
    ``<= 0.55`` from n=2048 up."""
    block = DEFAULT_BLOCK if block_size is None else block_size
    nbr = -(-n // block)
    nbr += nbr % 2
    blocks_executed = nbr * (nbr + 1) // 2
    blocks_dense = nbr * nbr
    flops_per_block = 2 * block * block * d
    return {
        "block": block,
        "blocks_executed": blocks_executed,
        "blocks_dense": blocks_dense,
        "flops_executed": blocks_executed * flops_per_block,
        "flops_dense": 2 * n * n * d,
        "ratio": blocks_executed * flops_per_block / (2 * n * n * d),
    }

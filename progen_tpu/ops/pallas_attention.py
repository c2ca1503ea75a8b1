"""Pallas TPU kernels for windowed local attention — fused forward AND
backward.

Why a kernel when XLA already fuses well here: the XLA path
(``ops/local_attention.py``) materializes the ``[previous ‖ own]`` key/value
concat — every k/v window is written to and re-read from HBM twice
(``concat_previous_window``).  These kernels instead map each grid step
onto the SAME k/v arrays through shifted BlockSpec index maps, so each
window streams from HBM once and the mask + f32 softmax + matmuls run
fused in VMEM on MXU-shaped blocks (wsz x d, d in {64, 128}).

Layout: all kernels take EXTENDED key/value sequences ``(B, H, L+wsz, D)``
whose first window is the "previous window" of query window 0:

* single device: a ZERO window — which reproduces the reference's phantom
  zero-pad semantics (``progen.py:90-95``: zero logits in the softmax
  denominator, zero values) with no special-casing in the kernel;
* context parallel: the left neighbour's last window delivered by
  ``ppermute`` (``parallel/context.py``), zeros on the leftmost shard — the
  same phantom semantics fall out at the sequence edge.

Query window j then attends k_ext windows ``j`` (previous) and ``j+1``
(own).

The backward is flash-style: the forward saves the per-row logsumexp; the
backward recomputes probabilities blockwise in VMEM and runs two kernels —
dq over query windows, and dk/dv over key windows (key window i receives
grads from query windows i-1, which see it as "own", and i, which see it
as "previous").  No (L, 2wsz) probability tensor ever reaches HBM, unlike
the old rematerialize-through-XLA backward which re-paid the concat.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from progen_tpu.ops.local_attention import ATTN_MASK_VALUE


def _causal_own_mask(wsz: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (wsz, wsz), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (wsz, wsz), 1)
    return rows >= cols


def _dot_t(a, b):  # a @ b^T, f32 accumulate
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot(a, b):  # a @ b, f32 accumulate
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


# -- forward ------------------------------------------------------------------


def _fwd_kernel(q_ref, kp_ref, ko_ref, vp_ref, vo_ref, o_ref, lse_ref,
                *, scale: float):
    q = q_ref[0]          # (wsz, d)
    k_prev = kp_ref[0]    # k_ext window j   (= previous window of query j)
    k_own = ko_ref[0]     # k_ext window j+1 (= own window of query j)
    v_prev = vp_ref[0]
    v_own = vo_ref[0]
    wsz = q.shape[0]

    s_prev = _dot_t(q, k_prev) * scale
    s_own = _dot_t(q, k_own) * scale
    s_own = jnp.where(_causal_own_mask(wsz), s_own, ATTN_MASK_VALUE)

    m = jnp.maximum(
        jnp.max(s_prev, axis=-1, keepdims=True),
        jnp.max(s_own, axis=-1, keepdims=True),
    )
    p_prev = jnp.exp(s_prev - m)
    p_own = jnp.exp(s_own - m)
    denom = jnp.sum(p_prev, -1, keepdims=True) + jnp.sum(p_own, -1, keepdims=True)

    acc = _dot(p_prev.astype(v_prev.dtype), v_prev)
    acc = acc + _dot(p_own.astype(v_own.dtype), v_own)
    o_ref[0] = (acc / denom).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(denom)   # (wsz, 1)


def _forward_ext(q, k_ext, v_ext, window_size: int, scale: float,
                 interpret: bool):
    b, h, n, d = q.shape
    wsz = window_size
    w = n // wsz
    bh = b * h
    qf = q.reshape(bh, n, d)
    kf = k_ext.reshape(bh, n + wsz, d)
    vf = v_ext.reshape(bh, n + wsz, d)

    block = (1, wsz, d)
    q_spec = pl.BlockSpec(block, lambda bh_, j: (bh_, j, 0))
    prev = pl.BlockSpec(block, lambda bh_, j: (bh_, j, 0))
    own = pl.BlockSpec(block, lambda bh_, j: (bh_, j + 1, 0))
    # per-row scalars live as (bh, n, 1): Mosaic wants the last two block
    # dims divisible by (8, 128) OR equal to the array dims — (wsz, 1) is.
    lse_spec = pl.BlockSpec((1, wsz, 1), lambda bh_, j: (bh_, j, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(bh, w),
        in_specs=[q_spec, prev, own, prev, own],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, d), q.dtype),
            jax.ShapeDtypeStruct((bh, n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="local_attn_fwd",
    )(qf, kf, kf, vf, vf)
    return out.reshape(b, h, n, d), lse.reshape(b, h, n)


# -- backward -----------------------------------------------------------------


def _dq_kernel(q_ref, kp_ref, ko_ref, vp_ref, vo_ref, do_ref, lse_ref,
               dd_ref, dq_ref, *, scale: float):
    q = q_ref[0]
    k_prev, k_own = kp_ref[0], ko_ref[0]
    v_prev, v_own = vp_ref[0], vo_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]    # (wsz, 1)
    dd = dd_ref[0]      # D = rowsum(do * o), (wsz, 1)
    wsz = q.shape[0]

    s_prev = _dot_t(q, k_prev) * scale
    s_own = _dot_t(q, k_own) * scale
    s_own = jnp.where(_causal_own_mask(wsz), s_own, ATTN_MASK_VALUE)
    p_prev = jnp.exp(s_prev - lse)
    p_own = jnp.exp(s_own - lse)

    dp_prev = _dot_t(do, v_prev)
    dp_own = _dot_t(do, v_own)
    ds_prev = p_prev * (dp_prev - dd)
    ds_own = p_own * (dp_own - dd)

    dq = (_dot(ds_prev.astype(k_prev.dtype), k_prev)
          + _dot(ds_own.astype(k_own.dtype), k_own)) * scale
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, qo_ref, qp_ref, doo_ref, dop_ref, lseo_ref,
                lsep_ref, ddo_ref, ddp_ref, dk_ref, dv_ref,
                *, scale: float, num_windows: int):
    # Key-extended window i: "own" user is query window i-1 (valid i >= 1),
    # "prev" user is query window i (valid i <= w-1, w = num query windows).
    i = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    q_own, q_prev = qo_ref[0], qp_ref[0]      # query windows i-1, i (clamped)
    do_own, do_prev = doo_ref[0], dop_ref[0]
    lse_own = lseo_ref[0]     # (wsz, 1)
    lse_prev = lsep_ref[0]
    dd_own = ddo_ref[0]
    dd_prev = ddp_ref[0]
    wsz = k.shape[0]

    own_valid = i >= 1
    prev_valid = i <= num_windows - 1

    # own-window user: causal mask applies
    s_o = _dot_t(q_own, k) * scale
    s_o = jnp.where(_causal_own_mask(wsz), s_o, ATTN_MASK_VALUE)
    p_o = jnp.exp(s_o - lse_own)
    p_o = jnp.where(own_valid, p_o, 0.0)
    dp_o = _dot_t(do_own, v)
    ds_o = p_o * (dp_o - dd_own)

    # previous-window user: fully visible, no mask
    s_p = _dot_t(q_prev, k) * scale
    p_p = jnp.exp(s_p - lse_prev)
    p_p = jnp.where(prev_valid, p_p, 0.0)
    dp_p = _dot_t(do_prev, v)
    ds_p = p_p * (dp_p - dd_prev)

    dv = (_dot(p_o.astype(do_own.dtype).T, do_own)
          + _dot(p_p.astype(do_prev.dtype).T, do_prev))
    dk = (_dot(ds_o.astype(q_own.dtype).T, q_own)
          + _dot(ds_p.astype(q_prev.dtype).T, q_prev)) * scale
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _backward_ext(q, k_ext, v_ext, o, lse, do, window_size: int,
                  scale: float, interpret: bool):
    b, h, n, d = q.shape
    wsz = window_size
    w = n // wsz
    bh = b * h
    qf = q.reshape(bh, n, d)
    kf = k_ext.reshape(bh, n + wsz, d)
    vf = v_ext.reshape(bh, n + wsz, d)
    dof = do.reshape(bh, n, d)
    lsef = lse.reshape(bh, n, 1)
    # D_i = sum_j dO_ij * O_ij — cheap XLA elementwise+reduce, f32
    ddf = jnp.sum(
        dof.astype(jnp.float32) * o.reshape(bh, n, d).astype(jnp.float32),
        -1, keepdims=True,
    )

    block = (1, wsz, d)
    row = pl.BlockSpec((1, wsz, 1), lambda bh_, j: (bh_, j, 0))
    q_spec = pl.BlockSpec(block, lambda bh_, j: (bh_, j, 0))
    prev = pl.BlockSpec(block, lambda bh_, j: (bh_, j, 0))
    own = pl.BlockSpec(block, lambda bh_, j: (bh_, j + 1, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(bh, w),
        in_specs=[q_spec, prev, own, prev, own, q_spec, row, row],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, n, d), q.dtype),
        interpret=interpret,
        name="local_attn_dq",
    )(qf, kf, kf, vf, vf, dof, lsef, ddf)

    # grid over the w+1 EXTENDED key windows
    kv_spec = pl.BlockSpec(block, lambda bh_, i: (bh_, i, 0))
    q_own_spec = pl.BlockSpec(
        block, lambda bh_, i: (bh_, jnp.maximum(i - 1, 0), 0))
    q_prev_spec = pl.BlockSpec(
        block, lambda bh_, i: (bh_, jnp.minimum(i, w - 1), 0))
    row_own = pl.BlockSpec(
        (1, wsz, 1), lambda bh_, i: (bh_, jnp.maximum(i - 1, 0), 0))
    row_prev = pl.BlockSpec(
        (1, wsz, 1), lambda bh_, i: (bh_, jnp.minimum(i, w - 1), 0))
    dk_ext, dv_ext = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, num_windows=w),
        grid=(bh, w + 1),
        in_specs=[kv_spec, kv_spec, q_own_spec, q_prev_spec, q_own_spec,
                  q_prev_spec, row_own, row_prev, row_own, row_prev],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n + wsz, d), k_ext.dtype),
            jax.ShapeDtypeStruct((bh, n + wsz, d), v_ext.dtype),
        ],
        interpret=interpret,
        name="local_attn_dkv",
    )(kf, vf, qf, qf, dof, dof, lsef, lsef, ddf, ddf)

    return (
        dq.reshape(b, h, n, d),
        dk_ext.reshape(b, h, n + wsz, d),
        dv_ext.reshape(b, h, n + wsz, d),
    )


# -- public API ---------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def pallas_local_attention_ext(q, k_ext, v_ext, window_size: int,
                               scale: float, interpret: bool):
    """Windowed attention over ``q (B, H, L, D)`` against EXTENDED
    ``k_ext/v_ext (B, H, L+wsz, D)`` whose first window is query window 0's
    previous window (zeros, or a context-parallel halo)."""
    out, _ = _forward_ext(q, k_ext, v_ext, window_size, scale, interpret)
    return out


def _ext_fwd(q, k_ext, v_ext, window_size, scale, interpret):
    out, lse = _forward_ext(q, k_ext, v_ext, window_size, scale, interpret)
    return out, (q, k_ext, v_ext, out, lse)


def _ext_bwd(window_size, scale, interpret, res, do):
    q, k_ext, v_ext, out, lse = res
    return _backward_ext(q, k_ext, v_ext, out, lse, do, window_size, scale,
                         interpret)


pallas_local_attention_ext.defvjp(_ext_fwd, _ext_bwd)


def pallas_local_attention(q, k, v, window_size: int,
                           scale: float | None = None,
                           interpret: bool | None = None):
    """Drop-in for :func:`~progen_tpu.ops.local_attention.local_attention`
    on ``(B, H, L, Dh)`` tensors.  Prepends the phantom zero window to k/v
    and runs the extended kernels.  ``interpret=None`` auto-selects the
    Pallas interpreter off-TPU (tests on CPU)."""
    b, h, n, d = q.shape
    if n % window_size != 0:
        raise ValueError(
            f"sequence length {n} must be divisible by window {window_size}"
        )
    scale_v = d ** -0.5 if scale is None else scale
    interp = jax.default_backend() != "tpu" if interpret is None else interpret
    pad = [(0, 0), (0, 0), (window_size, 0), (0, 0)]
    k_ext = jnp.pad(k, pad)
    v_ext = jnp.pad(v, pad)
    return pallas_local_attention_ext(q, k_ext, v_ext, window_size, scale_v,
                                      interp)

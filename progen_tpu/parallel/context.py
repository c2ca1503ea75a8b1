"""Context (sequence) parallelism: explicit halo exchange over the mesh's
``seq`` axis.

The model's two sequence-mixing structures (SURVEY.md §5.7) and their CP
communication patterns:

* **Local windowed attention** (``ops/local_attention.py``): each query
  window needs only ``[previous window ‖ own window]`` keys, so a sequence
  shard needs exactly ONE window of halo from its left neighbour — a
  single ``ppermute`` hop per layer, O(B·H·window·D) bytes over ICI,
  instead of the generic all-to-all GSPMD falls back to.  Device 0's
  missing left neighbour is the reference's phantom zero-pad window
  (``progen.py:90-95``), which ``ppermute`` provides for free: slots with
  no source are filled with zeros.
* **SGU/gMLP spatial matmul** (``ops/sgu.py``): output row m mixes ALL
  gate rows n <= m, so the gate tensor is all-gathered along ``seq``
  (O(B·L·D/shards) per device per layer — the standard sequence-parallel
  dense-mixing cost) while the learned ``(L, L)`` weights stay row-sharded;
  causal masking uses GLOBAL row indices derived from the shard index.

Both functions are drop-in equivalents of their single-device ops — the
tests assert exact agreement — and run under PARTIAL-MANUAL ``shard_map``:
only the ``seq`` mesh axis is manual (``axis_names={seq}``), so batch/fsdp/
tensor shardings on the same tensors keep flowing through GSPMD and the
ops compose with the dp/fsdp/tp rule sets.  They are called from inside
the model forward (``progen_tpu/models/progen.py``) whenever the model is
built with a mesh whose ``seq`` axis is >1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _left_halo(t, axis_name: str):
    """Send each shard's LAST window right; receive the left neighbour's
    (zeros at the leftmost shard).  ``t``: (..., W_local, wsz, D) ->
    (..., 1, wsz, D) halo window."""
    n = jax.lax.axis_size(axis_name)
    last = t[..., -1:, :, :]
    if n == 1:
        return jnp.zeros_like(last)
    return jax.lax.ppermute(
        last, axis_name, perm=[(i, i + 1) for i in range(n - 1)]
    )


def _haloed_windows(k_loc, v_loc, window_size: int, seq_axis: str):
    """Shared per-shard halo assembly for both CP attention paths.

    Reshapes the local k/v ``(B, H, L_loc, D)`` into windows, fetches the
    left neighbour's last window, and returns ``(kw, vw, k_halo, v_halo)``
    with ``kw/vw (B, H, W_loc, wsz, D)`` and halos ``(B, H, 1, wsz, D)``.
    """
    b, h, n_loc, d = k_loc.shape
    wsz = window_size
    if n_loc % wsz != 0:
        raise ValueError(
            f"local sequence {n_loc} must be divisible by window {wsz}; "
            "choose a seq-axis size that keeps whole windows per shard"
        )
    w_loc = n_loc // wsz
    kw = k_loc.reshape(b, h, w_loc, wsz, d)
    vw = v_loc.reshape(b, h, w_loc, wsz, d)
    return kw, vw, _left_halo(kw, seq_axis), _left_halo(vw, seq_axis)


def cp_local_attention(
    q, k, v, *, mesh: Mesh, window_size: int, scale: float | None = None,
    seq_axis: str = "seq",
):
    """Sequence-sharded windowed attention: ``(B, H, L, D)`` global tensors,
    L sharded over ``mesh[seq_axis]``; one ppermute halo per call.

    Requires ``L_local % window_size == 0`` (shard boundaries align to
    windows — the natural layout for this model).
    """
    from progen_tpu.ops.local_attention import local_attention

    def inner(q_loc, k_loc, v_loc):
        wsz = window_size
        kw, vw, k_halo, v_halo = _haloed_windows(k_loc, v_loc, wsz, seq_axis)
        # previous window of window j: [halo, own windows 0..W-2][j]
        k_prev = jnp.concatenate([k_halo, kw[..., :-1, :, :]], axis=-3)
        v_prev = jnp.concatenate([v_halo, vw[..., :-1, :, :]], axis=-3)
        k2 = jnp.concatenate([k_prev, kw], axis=-2)  # (b,h,W,2wsz,d)
        v2 = jnp.concatenate([v_prev, vw], axis=-2)

        return local_attention(q_loc, k2, v2, window_size=wsz, scale=scale)

    spec = P(None, None, seq_axis, None)
    return jax.shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset({seq_axis}), check_vma=True,
    )(q, k, v)


def sharded_pallas_local_attention(
    q, k, v, *, mesh: Mesh, window_size: int, scale: float | None = None,
    seq_axis: str = "seq", batch_axes=("data", "fsdp"), head_axis: str = "tensor",
):
    """The Pallas windowed-attention kernel under a sharded mesh.

    ``pl.pallas_call`` has no GSPMD partitioning rule, so the kernel must
    see per-device arrays: this wrapper runs it inside a FULL-manual
    shard_map — batch over ``batch_axes``, heads over ``head_axis``,
    sequence over ``seq_axis``.  The halo exchange happens on the way in:
    each shard receives its left neighbour's last k/v window by
    ``ppermute`` (zeros on the leftmost shard — the reference's phantom
    window) and hands the kernel EXTENDED k/v, so one code path covers
    every mesh from single-chip (all axes size 1) to dp x tp x sp.

    Requires exact divisibility: ``B % prod(batch_axes)``,
    ``H % head_axis``, ``L/seq_axis % window_size`` — the model's standard
    shapes satisfy all three.
    """
    from progen_tpu.ops.pallas_attention import pallas_local_attention_ext

    d = q.shape[-1]
    scale_v = d ** -0.5 if scale is None else scale
    interp = mesh.devices.flat[0].platform != "tpu"

    def inner(q_loc, k_loc, v_loc):
        b, h, n_loc, dd = q_loc.shape
        wsz = window_size
        kw, vw, k_halo, v_halo = _haloed_windows(k_loc, v_loc, wsz, seq_axis)
        k_ext = jnp.concatenate([k_halo, kw], axis=-3).reshape(
            b, h, n_loc + wsz, dd)
        v_ext = jnp.concatenate([v_halo, vw], axis=-3).reshape(
            b, h, n_loc + wsz, dd)
        return pallas_local_attention_ext(q_loc, k_ext, v_ext, wsz, scale_v,
                                          interp)

    spec = P(batch_axes, head_axis, seq_axis, None)
    # check_vma=False: pallas_call's out_shape carries no varying-mesh-axes
    # metadata, which the vma checker requires; this shard_map is full-manual
    # so there is nothing for the checker to catch anyway.
    return jax.shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def sharded_pallas_spatial_gate(
    res, gate, weights, biases, *, mesh: Mesh, seq_axis: str = "seq",
    batch_axes=("data", "fsdp"), d_axis: str = "tensor",
):
    """The blocked-causal Pallas SGU kernel under a sharded mesh.

    Like :func:`sharded_pallas_local_attention`, ``pl.pallas_call`` has no
    GSPMD partitioning rule, so the kernel runs inside a FULL-manual
    shard_map: batch over ``batch_axes``, the hidden ``d`` over ``d_axis``,
    weights/biases REPLICATED (every device runs the full ``(n, n)``
    triangle against its batch/d slice — the spatial matmul contracts over
    sequence, so the seq axis cannot shard it; fsdp's row-sharding of the
    stored params is re-gathered by ZeRO-3 before apply anyway).

    Sequence parallelism is NOT supported here: ``cp_spatial_gate`` owns
    the op when the mesh's seq axis is >1 (the model falls back to it) —
    this wrapper raises rather than silently mis-sharding.

    Weight/bias gradients: shard_map's transpose inserts the psum over all
    mesh axes for replicated (``P()``) inputs itself — verified empirically
    for this jax version, including with a custom_vjp inside — so the
    kernel's ``reduce_axes`` stays empty (an explicit psum would double
    count).
    """
    from progen_tpu.ops.pallas_sgu import pallas_spatial_gate

    if mesh.shape[seq_axis] != 1:
        raise ValueError(
            f"pallas SGU cannot run under sequence parallelism (mesh "
            f"{seq_axis!r} axis has size {mesh.shape[seq_axis]}); use "
            "sgu_impl='xla' so cp_spatial_gate owns the op"
        )
    interp = mesh.devices.flat[0].platform != "tpu"

    def inner(res_loc, gate_loc, w, b):
        return pallas_spatial_gate(res_loc, gate_loc, w, b, interpret=interp)

    spec = P(batch_axes, None, d_axis)
    # check_vma=False for the same reason as sharded_pallas_local_attention:
    # pallas_call outputs carry no varying-mesh-axes metadata.
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(spec, spec, P(), P()),
        out_specs=spec,
        check_vma=False,
    )(res, gate, weights, biases)


def cp_spatial_gate(
    gate, weights, biases, *, mesh: Mesh, seq_axis: str = "seq"
):
    """Sequence-sharded SGU mixing: ``gate (B, L, D)`` sharded on L,
    ``weights (L, L)``/``biases (L, 1)`` row-sharded; all-gather the gate,
    keep rows local, mask causally by GLOBAL row index."""
    n_total = weights.shape[0]
    # XLA's CPU backend crashes ("Invalid binary instruction opcode copy" in
    # AllReducePromotion) when promoting the bf16 reduce-scatter that is the
    # backward of a bf16 all_gather; gather in f32 there. TPU keeps the
    # narrow dtype on the wire.
    on_cpu = mesh.devices.flat[0].platform == "cpu"

    def inner(gate_loc, w_loc, b_loc):
        n_loc = w_loc.shape[0]
        idx = jax.lax.axis_index(seq_axis)
        # gather full gate along the sequence: (B, L, D)
        if on_cpu and gate_loc.dtype == jnp.bfloat16:
            gate_full = jax.lax.all_gather(
                gate_loc.astype(jnp.float32), seq_axis, axis=1, tiled=True
            ).astype(gate_loc.dtype)
        else:
            gate_full = jax.lax.all_gather(
                gate_loc, seq_axis, axis=1, tiled=True
            )
        rows = idx * n_loc + jnp.arange(n_loc)          # global row ids
        mask = (jnp.arange(n_total)[None, :] <= rows[:, None]).astype(w_loc.dtype)
        w = w_loc * mask
        mixed = jnp.einsum("bnd,mn->bmd", gate_full, w,
                           preferred_element_type=jnp.float32)
        return (mixed + b_loc).astype(gate_loc.dtype)

    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(None, seq_axis, None), P(seq_axis, None), P(seq_axis, None)),
        out_specs=P(None, seq_axis, None),
        axis_names=frozenset({seq_axis}),
        check_vma=True,
    )(gate, weights, biases)

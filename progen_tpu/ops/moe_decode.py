"""The held experts' product for a DECODE step's handful of tokens: every
touched expert's gate, up and down matrices streamed from HBM once, back
to back, through one software pipeline.

:func:`pallas_expert_terms` takes ``u (T, h)``, a list ``eid (held,)`` of
the experts to visit (the first ``n_real`` entries are real), each listed
expert's per-token routing weights ``wt (held, T)`` (float32, zero where the
token is not the expert's) and the stacked ``wg``/``wu (held, h, inner)``,
``wd (held, inner, h)``; it returns ``y (T, h)`` float32,

    y = sum over the first n_real i of
        wt[i][:, None] * ((silu(u wg[eid[i]]) * (u wu[eid[i]])) wd[eid[i]])

over the rows whose weight is not zero.  With ``T`` at most the MXU's 128
rows a weight tile takes no longer to use than to load, so ALL tokens go
through every listed expert and the routing weight (zero for the others)
selects: no sort, no gather and no scatter-add around the kernel, and the
time is the touched experts' bytes over the rate they stream at.

The kernel ``moe_decode_fwd``: grid ``(held, inner / ik)``, one ITEM an
expert of the list, one step an ``ik``-wide slice of its inner width: the
tiles ``wg[:, cols]``, ``wu[:, cols]`` ``(h, ik)`` and ``wd[cols] (ik, h)``
arrive together, ``silu(u wg) * (u wu)`` for those columns is formed in
float32 from compute-dtype operands and cast once, and its product with
the ``wd`` tile (float32) is weighted in float32 and added to the resident
float32 output — the down product's sum over inner tiles and the sum over
experts are one accumulator.  ``eid`` and ``n_real`` are scalar-prefetched
and the index maps read them, so while an expert's last tiles are used the
next expert's first are in flight (the pipeline does not drain between
experts); an item past ``n_real`` points at the blocks the last real item
left, so nothing is fetched for it, and does no work.  (Tiles cut along
``h`` instead — contiguous runs, a float32 ``(T, inner)`` accumulator, two
phases an expert — stream no faster on a v5e: PERF.md section 6, PR 37.)

Which lowering a call of ``models/experts.py:held_experts`` takes is
decided by :func:`fitted_tile` from what the code can observe and never
from a knob (as ``ops/mla_decode.py`` and its siblings), and noted under
``"moe_experts"`` (``ops/lowering.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from progen_tpu.ops.lowering import mesh_in_scope as _mesh_in_scope
from progen_tpu.ops.lowering import on_tpu as _on_tpu

F32 = jnp.float32
# the most tokens a call may carry and still be bound by the weights'
# stream: the MXU's rows (a weight tile is loaded in about the time 128
# rows take to pass it)
MAX_TOKENS = 128
LANE = 128              # widths and tiles are whole lane tiles
# bytes of one step's three tiles on a v5e (PERF.md section 6, PR 37, has
# the tiles measured at the three cells' widths): the inner tile is the
# largest whole number of lane tiles that divides the inner width under it
STEP_BYTES = 12 << 20
ROW_GROUP = 16          # token rows are padded to whole bfloat16 sublane tiles


def xla_expert_terms(u, eid, n_real, wt, wg, wu, wd):
    """The contract in plain XLA (the tests' oracle): a loop over the
    listed experts, float32 accumulation."""
    def item(i, y):
        e = eid[i]
        gate = jnp.dot(u, wg[e].astype(u.dtype), preferred_element_type=F32)
        up = jnp.dot(u, wu[e].astype(u.dtype), preferred_element_type=F32)
        out = jnp.dot((jax.nn.silu(gate) * up).astype(u.dtype),
                      wd[e].astype(u.dtype), preferred_element_type=F32)
        w = wt[i][:, None]
        return y + jnp.where(w != 0, out * w, 0.0)

    return jax.lax.fori_loop(0, n_real, item, jnp.zeros(u.shape, F32))


def _kernel(eid_ref, n_ref, u_ref, wt_ref, wg_ref, wu_ref, wd_ref, y_ref):
    from jax.experimental import pallas as pl

    i, s = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (s == 0))
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, F32)

    @pl.when(i < n_ref[0])
    def _():
        x = u_ref[...]                                       # (T, h)
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=F32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=F32)
        act = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        out = jnp.dot(act, wd_ref[...], preferred_element_type=F32)
        w = wt_ref[...]                                      # (T, 1)
        y_ref[...] += jnp.where(w != 0, out * w, 0.0)


def inner_tile(h: int, inner: int, itemsize: int) -> int:
    """The largest multiple of ``LANE`` that divides ``inner`` and keeps a
    step's three tiles under ``STEP_BYTES`` (``LANE`` where none does)."""
    best = LANE
    for ik in range(LANE, inner + 1, LANE):
        if inner % ik == 0 and 3 * h * ik * itemsize <= STEP_BYTES:
            best = ik
    return best


def pallas_expert_terms(u, eid, n_real, wt, wg, wu, wd, *, tile=None,
                        interpret=None):
    """The kernel lowering; ``interpret=None`` auto-selects the Pallas
    interpreter off-TPU; ``tile`` (of the inner width) defaults to
    :func:`inner_tile`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    t, h = u.shape
    items, _, inner = wg.shape
    itemsize = u.dtype.itemsize
    ik = tile or inner_tile(h, inner, itemsize)
    if inner % ik:
        raise ValueError(f"tile {ik} does not divide inner = {inner}")
    steps = inner // ik
    rows = -(-t // ROW_GROUP) * ROW_GROUP       # whole sublane tiles
    u = jnp.pad(u, ((0, rows - t), (0, 0)))
    wt = jnp.pad(wt.astype(F32), ((0, 0), (0, rows - t)))[..., None]
    n_real = jnp.asarray(n_real, jnp.int32).reshape(1)
    last = jnp.maximum(n_real[0] - 1, 0)
    eid = eid.astype(jnp.int32)
    eid = jnp.where(jnp.arange(items) < n_real[0], eid, eid[last])

    def fixed(i, s, eid_ref, n_ref):
        return 0, 0

    def weight_map(i, s, eid_ref, n_ref):
        return jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0)), 0, 0

    def tile_of(i, s, n_ref):
        # past the list: the last tile, which the last real item left
        return jnp.where(i < n_ref[0], s, steps - 1)

    def gate_up_map(i, s, eid_ref, n_ref):
        return eid_ref[i], 0, tile_of(i, s, n_ref)

    def down_map(i, s, eid_ref, n_ref):
        return eid_ref[i], tile_of(i, s, n_ref), 0

    vmem = (2 * 3 * h * ik * itemsize               # the streamed tiles
            + rows * h * (itemsize + 3 * 4)         # u, y twice, a product
            + 3 * rows * ik * 4 + 2 * rows * LANE * 4)
    y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(items, steps),
            in_specs=[
                pl.BlockSpec((rows, h), fixed, pipeline_mode=pl.Buffered(1)),
                pl.BlockSpec((None, rows, 1), weight_map),
                pl.BlockSpec((None, h, ik), gate_up_map),
                pl.BlockSpec((None, h, ik), gate_up_map),
                pl.BlockSpec((None, ik, h), down_map),
            ],
            out_specs=pl.BlockSpec((rows, h), fixed),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, h), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name="moe_decode_fwd",
    )(eid, n_real, u, wt, wg, wu, wd)
    return y[:t]


def fitted_tile(u, experts):
    """The kernel's inner tile for ``u (T, h)`` over the stacked
    ``experts`` (arrays or their shapes), ``None`` where the XLA form runs:
    the kernel on a TPU backend with no mesh in scope, one 2- or 4-byte
    float type for tokens and weights, ``h`` and the inner width multiples
    of ``LANE`` and at most ``MAX_TOKENS`` tokens."""
    dtype = jnp.dtype(u.dtype)
    t, h = u.shape
    inner = experts["wg"].shape[-1]
    kernel = (_on_tpu() and not _mesh_in_scope()
              and all(jnp.dtype(experts[k].dtype) == dtype
                      for k in ("wg", "wu", "wd"))
              and jnp.issubdtype(dtype, jnp.floating)
              and dtype.itemsize in (2, 4)
              and h % LANE == 0 and inner % LANE == 0 and t <= MAX_TOKENS)
    return inner_tile(h, inner, dtype.itemsize) if kernel else None

"""The held experts' product as Pallas kernels, three regimes of one
contract.  Where the WEIGHTS are the work every touched expert's matrices —
gate, up and down, or up and down alone (the two expert forms, below) — are
streamed from HBM once, back to back, through one software pipeline: for a
decode step's handful of tokens (``moe_decode_fwd``) and for a block
step's few hundred (``moe_grouped_fwd``).  Where the ROWS are the work — an
admission's thousands of tokens — an expert's matrices stay while row tiles
of the rows as sorted by expert stream past them (``moe_sorted_fwd``).

:func:`pallas_expert_terms` takes ``u (T, h)``, a list ``eid (held,)`` of
the experts to visit (the first ``n_real`` entries are real), each listed
expert's per-token routing weights ``wt (held, T)`` (float32, zero where the
token is not the expert's) and the stacked ``wg``/``wu (held, h, inner)``,
``wd (held, inner, h)``; it returns ``y (T, h)`` float32,

    y = sum over the first n_real i of
        wt[i][:, None] * ((silu(u wg[eid[i]]) * (u wu[eid[i]])) wd[eid[i]])

over the rows whose weight is not zero.  **Two expert forms**, told apart
by what the caller holds and never by a knob: three matrices, the gated
SwiGLU above; or two (``wg`` None: ``models/nemotron_h.py``'s latent
experts), ``relu(u wu[e])^2 wd[e]`` — no gate, the activation squared.
Everything below is the same for both but the step's tiles, two where
there is no gate.  A gated expert's layer may state a ``limit`` (a static of
the three calls; ``models/bailing_hybrid.py``'s last layers): its two
products are then clipped before the activation (:func:`clipped`), and a
call without one is the program it was.  With ``T`` at most the MXU's 128
rows a weight tile takes no longer to use than to load, so ALL tokens go
through every listed expert and the routing weight (zero for the others)
selects: no sort, no gather and no scatter-add around the kernel, and the
time is the touched experts' bytes over the rate they stream at.

Past 128 tokens that trick stops paying (256 rows through 117 experts are
1.4 ms of the MXU's peak beside 1.35 ms of stream, and a tile is then used
for twice the time it loads in), while an expert's OWN rows are still few
(a block-diffusion step: 256 tokens, 16-20 rows an expert).
:func:`pallas_grouped_terms` takes the rows GROUPED by expert instead —
``xs (items * row_tile, h)``, every run of ``row_tile`` rows one ITEM, all
of one expert ``eid[i]``, an expert with more rows than a tile in
consecutive items, each row with its routing weight (zero for a tile's
padding) — and returns each real item's rows through its expert, weighted,
in the same grouped order; the caller (``models/experts.py:_grouped``)
gathers the rows in and sums each token's ``k`` rows back by a gather.

Past 1,024 tokens the padded grouping is what costs (its list is sized for
``T k / 32 + held`` items whatever share of the router the chip holds, and
every item's rows are gathered into a tile of their own).
:func:`pallas_sorted_add` takes the rows as SORTED by expert and not
padded — ``xs (N, h)``, expert ``e``'s rows at ``[lo[e], hi[e])``, back to
back — in row tiles of 128 read by the kernel's own ``BlockSpec``: the work
list (:func:`sorted_work_list`) names ``(expert, row tile)`` items, an
expert's tiles side by side, a tile that straddles two or three experts
once an expert with the others' rows left alone; it is as long as the
tiles that hold a row and the experts that share one (``N / 128 + held - 1``
at most, read from a prefetched count: a grid step past the last real tile
fetches and multiplies nothing), so the work follows the LIVE rows while
the shapes follow the window.  The caller (``models/experts.py:_sorted``)
gathers the rows in; the terms reach their tokens INSIDE the kernel: ``y
(T, h)`` float32 stays in HBM, aliased to the result and laid out ``(T, h /
128, 128)`` so that a token's row is one run of it (row ``t`` of a ``(T,
h)`` array is a sublane of ``h / 128`` tiles, which no DMA may slice), the
window's token numbers are prefetched beside the work list, and at its
last step an item fetches its live rows' rows of ``y`` by row DMAs, adds
its float32 terms and writes them back before the next item starts.
(XLA's scatter-add of the same terms is a serial loop over the rows whose
rate follows the width in no simple way — us a row on a v5e: 0.08 at 1,024
wide, 0.22 at 2,048, 0.32 at 4,096, 3.8 at 5,120, 0.86 at 6,144 — and at
dots3's 5,120 it was five sixths of the layer: 65.0 ms a layer alone at a
16,384-token admission, 18.2 with the scatter in column slabs of 1,024,
12.2 here, and faster or level at every cell's width; PERF.md section 6,
PR 59.)  A step holds an expert's whole
inner width wherever three (two) tiles of it fit ``SORTED_STEP_BYTES``
(Trinity's 12.6 MB, LFM2's 22, Nemotron-3's 11, SDAR's 9.4), so consecutive
items of one expert re-use its matrices and only the rows move; DeepSeek-V2's
47 MB and LongCat's 75 take two and four steps an item.

All three kernels: grid ``(items, inner / ik)``, one step an ``ik``-wide
slice of the item's expert: the tiles ``wg[:, cols]``, ``wu[:, cols]`` ``(h,
ik)`` and ``wd[cols] (ik, h)`` arrive together, ``silu(x wg) * (x wu)`` for
those columns is formed in float32 from compute-dtype operands and cast
once, and its product with the ``wd`` tile (float32) is weighted in float32
and added to a float32 output block — the whole resident ``y`` under
``moe_decode_fwd`` (the down product's sum over inner tiles and the sum over
experts are one accumulator), the item's own ``(row_tile, h)`` block under
``moe_grouped_fwd``, a ``(row_tile, h)`` scratch under ``moe_sorted_fwd``
(an item's terms, added to its tokens' rows of ``y`` at its last step).
The work list is scalar-prefetched and the index maps read it, so while an
expert's last tiles are used the next expert's first are in flight (the
pipeline does not drain between
experts); consecutive items of one expert name the same weight blocks, so
where a step holds the whole inner width (SDAR's 768) its second row tile
costs MXU time and no second stream; an item past ``n_real`` points at the
blocks the last real item left, so nothing is fetched or written for it,
and does no work.  (Tiles cut along ``h`` instead — contiguous runs, a
float32 ``(T, inner)`` accumulator, two phases an expert — stream no faster
on a v5e: PERF.md section 6, PR 37.  The tile of an expert's rows formed
INSIDE the kernel by a one-hot selection on the MXU, ``u`` and ``y``
resident: PERF.md section 6, PR 42.)

Which lowering a call of ``models/experts.py:held_experts`` takes is
decided by :func:`fitted_tile` from what the code can observe and never
from a knob (as ``ops/mla_decode.py`` and its siblings), and noted under
``"moe_experts"`` (``ops/lowering.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

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
# bytes of one step's tiles (three, or two without a gate) on a v5e (PERF.md
# section 6, PR 37, has the tiles measured at the three cells' widths): the
# inner tile is the largest whole number of lane tiles that divides the
# inner width under it
STEP_BYTES = 12 << 20
ROW_GROUP = 16          # token rows are padded to whole bfloat16 sublane tiles
# above MAX_TOKENS an expert gets its OWN rows, in tiles of this many.  One
# layer of a block step's shapes alone on a v5e (256 tokens, 128 experts of
# 2048 x 768, top-8, 15 rows an expert and the fullest 2.8 times the mean,
# about what the cell reads), ms a call: 1.88 at 16, 1.83 at 32, 1.92 at 64
# beside 4.68 for the XLA form; at 1,024 tokens 2.92, 2.53, 2.58 beside
# 5.74 (at 128, under a synthetic skew of 7 times the mean: 2.57 and 3.27
# where 32 reads 1.85 and 2.42) — a taller tile is more padding to gather,
# a shorter one more items.  The cell end to end, tokens a second: 2,821 at
# 16, 2,833 at 32, 2,756 at 64 (PERF.md section 6, PR 42)
ROW_TILE = 32
# the most tokens a call may carry and take the GROUPED kernel; above it the
# sorted one runs.  One layer alone, XLA form | grouped kernel: 512 tokens
# 4.97 | 1.98 ms, 1,024 5.74 | 2.53, 2,048 (under the synthetic skew) 7.34 |
# 3.99 — so the edge is not where the grouped kernel stops winning but the
# largest admission shape measured END TO END with it, 1,024 tokens: against
# an edge of 512 a cell that holds the layer whole gains 0.6 % of its tokens
# a second (4 rows x 256: 64 rows an expert) and a cell that holds 1/48 of
# the router reads the same within its noise (2 rows x 512: 16 rows an
# expert, and 672 MiB of temporaries under that program's peak).  Its list
# is sized for T k / ROW_TILE + held items whatever share of the router the
# chip holds and every item's rows are gathered into a tile of their own
# (PERF.md section 6, PR 42), which is why it does not take the thousands;
# whether the sorted kernel should take the hundreds too is ROADMAP S11 (c)
MAX_GROUPED_TOKENS = 1024
# above MAX_GROUPED_TOKENS (an admission's thousands of tokens) the rows are
# MXU work: an expert's matrices stay while row tiles of the SORTED rows
# stream.  One layer alone on a v5e at the cells' admission runs, a third of
# the slots live, ms a call — the XLA form | the same with a quarter of its
# window | megablox.gmm three times in that window | this kernel (PERF.md
# section 6, PR 53): Trinity 4 x 8192 17.07 | 9.09 | 9.66 | 6.27; LFM2 8 x
# 1024 9.85 | 7.49 | 7.26 | 4.36; Nemotron-3 4 x 1024 12.28 | 9.32 | 6.13 |
# 3.34; DeepSeek-V2 4 x 512 11.03 | 9.76 | 7.78 | 7.23; LongCat 2 x 2048
# 5.99 | 5.65 | 4.49 | 3.19; SDAR 4 x 512 6.92 | 5.95 | 5.12 | 2.93.
# The row tile: 64 | 128 | 256 read 6.30 | 6.27 | 6.21 on Trinity (2,048
# rows an expert if every slot were live) and 4.42 | 4.36 | 4.39 on LFM2;
# 32 | 64 | 128 read 3.65 | 3.41 | 3.38 on Nemotron-3 (176), 8.04 | 6.99 |
# 6.45 on DeepSeek-V2 (77: its expert takes two steps, so an item more is
# a stream more) and 4.72 | 3.76 | 3.23 on LongCat (64) — the MXU's 128
# rows are the fastest or tied everywhere, so the tile is a constant.  The
# step's bytes: twice STEP_BYTES, so that LFM2's expert (22 MB) is one step
# and its second row tile moves rows only
SORTED_STEP_BYTES = 24 << 20
SORTED_ROW_TILE = 128


def clipped(gate, up, limit: float):
    """A SwiGLU's two products under a ``limit``, BEFORE the activation:
    the gate's held from above, the other on both sides."""
    return jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)


def activation(gate, up, limit: float = 0.0):
    """An expert's hidden row from its float32 products: ``silu(gate) *
    up`` (the two :func:`clipped` first where the layer has a ``limit``),
    or ``relu(up)^2`` where the expert has no gate."""
    if gate is None:
        return jnp.square(jax.nn.relu(up))
    if limit:
        gate, up = clipped(gate, up, limit)
    return jax.nn.silu(gate) * up


def xla_expert_terms(u, eid, n_real, wt, wg, wu, wd, limit: float = 0.0):
    """The contract in plain XLA (the tests' oracle): a loop over the
    listed experts, float32 accumulation; ``wg`` None for experts of two
    matrices."""
    def item(i, y):
        e = eid[i]
        gate = None if wg is None else jnp.dot(
            u, wg[e].astype(u.dtype), preferred_element_type=F32)
        up = jnp.dot(u, wu[e].astype(u.dtype), preferred_element_type=F32)
        out = jnp.dot(activation(gate, up, limit).astype(u.dtype),
                      wd[e].astype(u.dtype), preferred_element_type=F32)
        w = wt[i][:, None]
        return y + jnp.where(w != 0, out * w, 0.0)

    return jax.lax.fori_loop(0, n_real, item, jnp.zeros(u.shape, F32))


def _item_product(x_ref, wt_ref, w_refs, limit: float = 0.0):
    """One step of either kernel: the rows ``x (R, h)`` through an inner
    slice of one expert — ``w_refs`` its tiles, ``(gate, up, down)`` or
    ``(up, down)`` — and their routing weights ``(R, 1)``; what the step
    adds is ``where(w != 0, out * w, 0)``: a row whose weight is zero adds
    nothing, whatever it holds.  Under a ``limit`` (a static of the call)
    the gated form's two products are :func:`clipped` first."""
    x = x_ref[...]
    *wg_ref, wu_ref, wd_ref = w_refs
    if wg_ref:
        gate = jnp.dot(x, wg_ref[0][...], preferred_element_type=F32)
    up = jnp.dot(x, wu_ref[...], preferred_element_type=F32)
    if wg_ref and limit:
        gate, up = clipped(gate, up, limit)
    act = (gate * jax.nn.sigmoid(gate) * up if wg_ref
           else jnp.square(jnp.maximum(up, 0.0)))
    out = jnp.dot(act.astype(x.dtype), wd_ref[...],
                  preferred_element_type=F32)
    return out, wt_ref[...]


def _kernel(eid_ref, n_ref, u_ref, wt_ref, *refs, limit: float = 0.0):
    from jax.experimental import pallas as pl

    *w_refs, y_ref = refs

    i, s = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (s == 0))
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, F32)

    @pl.when(i < n_ref[0])
    def _():
        out, w = _item_product(u_ref, wt_ref, w_refs, limit)
        y_ref[...] += jnp.where(w != 0, out * w, 0.0)


def _grouped_kernel(eid_ref, n_ref, x_ref, wt_ref, *refs,
                    limit: float = 0.0):
    from jax.experimental import pallas as pl

    *w_refs, o_ref = refs
    i, s = pl.program_id(0), pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _():
        out, w = _item_product(x_ref, wt_ref, w_refs, limit)
        term = jnp.where(w != 0, out * w, 0.0)

        @pl.when(s == 0)
        def _():
            o_ref[...] = term

        @pl.when(s != 0)
        def _():
            o_ref[...] += term


def _under(kernel, limit: float):
    """``kernel`` with the clip's ``limit`` as a static; the kernel itself
    where there is none: a caller that passes no limit traces what it
    traced."""
    return functools.partial(kernel, limit=float(limit)) if limit else kernel


def inner_tile(h: int, inner: int, itemsize: int, matrices: int = 3,
               step_bytes: int | None = None) -> int:
    """The largest multiple of ``LANE`` that divides ``inner`` and keeps a
    step's tiles — one of each of the expert's ``matrices`` — under
    ``step_bytes`` (default ``STEP_BYTES``; ``LANE`` where none does).
    Nemotron-H's latent experts (``h`` 1024, inner 2688 = 21 x 128, two
    matrices, bfloat16): the whole
    2688, 11.0 MB a step, one step an expert; counted as three tiles the
    divisors under the budget would end at 896."""
    best = LANE
    for ik in range(LANE, inner + 1, LANE):
        if inner % ik == 0 and matrices * h * ik * itemsize <= (
                step_bytes or STEP_BYTES):
            best = ik
    return best


def _work_list(eid, n_real):
    """``(eid, n_real (1,))`` as the kernels prefetch them: past the real
    items the list repeats the last real one, so that the index maps name
    the blocks already held."""
    n_real = jnp.asarray(n_real, jnp.int32).reshape(1)
    last = jnp.maximum(n_real[0] - 1, 0)
    eid = eid.astype(jnp.int32)
    return jnp.where(jnp.arange(eid.shape[0]) < n_real[0], eid,
                     eid[last]), n_real


def _item_map(i, s, eid_ref, n_ref):
    """An item's own block of a per-item operand; past the list, the last
    real item's."""
    return jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))


def _tile_map(i, s, eid_ref, tile_ref, *lists):
    """The row tile an item of ``moe_sorted_fwd`` reads (the list repeats
    its last real item past the end)."""
    return tile_ref[i], 0


def _weight_specs(h, ik, steps, gated: bool):
    """Block specs of the streamed tiles: ``wg`` (where ``gated``), ``wu``
    and ``wd``."""
    from jax.experimental import pallas as pl

    def tile_of(i, s, n_ref):
        # past the list: the last tile, which the last real item left
        return jnp.where(i < n_ref[0], s, steps - 1)

    # the prefetched lists: the items' experts first, their count last
    def gate_up_map(i, s, eid_ref, *lists):
        return eid_ref[i], 0, tile_of(i, s, lists[-1])

    def down_map(i, s, eid_ref, *lists):
        return eid_ref[i], tile_of(i, s, lists[-1]), 0

    return [pl.BlockSpec((None, h, ik), gate_up_map)] * (1 + gated) + [
        pl.BlockSpec((None, ik, h), down_map)]


def _inner_steps(h, inner, itemsize, tile, matrices):
    ik = tile or inner_tile(h, inner, itemsize, matrices)
    if inner % ik:
        raise ValueError(f"tile {ik} does not divide inner = {inner}")
    return ik, inner // ik


def pallas_expert_terms(u, eid, n_real, wt, wg, wu, wd, *, tile=None,
                        limit: float = 0.0, interpret=None):
    """The kernel lowering for at most ``MAX_TOKENS`` tokens; ``wg`` None
    for experts of two matrices; ``interpret=None`` auto-selects the Pallas
    interpreter off-TPU; ``tile`` (of the inner width) defaults to
    :func:`inner_tile`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    t, h = u.shape
    items, _, inner = wu.shape
    itemsize = u.dtype.itemsize
    weights = [w for w in (wg, wu, wd) if w is not None]
    ik, steps = _inner_steps(h, inner, itemsize, tile, len(weights))
    rows = -(-t // ROW_GROUP) * ROW_GROUP       # whole sublane tiles
    u = jnp.pad(u, ((0, rows - t), (0, 0)))
    wt = jnp.pad(wt.astype(F32), ((0, 0), (0, rows - t)))[..., None]
    eid, n_real = _work_list(eid, n_real)

    def fixed(i, s, eid_ref, n_ref):
        return 0, 0

    def weight_map(i, s, eid_ref, n_ref):
        return _item_map(i, s, eid_ref, n_ref), 0, 0

    vmem = (2 * len(weights) * h * ik * itemsize    # the streamed tiles
            + rows * h * (itemsize + 3 * 4)         # u, y twice, a product
            + 3 * rows * ik * 4 + 2 * rows * LANE * 4)
    y = pl.pallas_call(
        _under(_kernel, limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(items, steps),
            in_specs=[
                pl.BlockSpec((rows, h), fixed, pipeline_mode=pl.Buffered(1)),
                pl.BlockSpec((None, rows, 1), weight_map),
                *_weight_specs(h, ik, steps, wg is not None),
            ],
            out_specs=pl.BlockSpec((rows, h), fixed),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, h), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name="moe_decode_fwd",
    )(eid, n_real, u, wt, *weights)
    return y[:t]


def pallas_grouped_terms(xs, eid, n_real, wt, wg, wu, wd, *, row_tile,
                         tile=None, limit: float = 0.0, interpret=None):
    """The kernel lowering for rows GROUPED by expert: ``xs (items *
    row_tile, h)``, item ``i`` the rows ``[i * row_tile, (i + 1) *
    row_tile)`` and all of them expert ``eid[i]``'s, ``wt (items *
    row_tile,)`` each row's routing weight (zero for a tile's padding); the
    first ``n_real`` items are real, and consecutive items of one expert
    keep its weight blocks.  Returns ``(items * row_tile, h)`` float32:
    each real item's rows through its expert, weighted (zero where the
    weight is); rows past the real items are NOT WRITTEN.  ``wg`` None for
    experts of two matrices."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    n, h = xs.shape
    items, inner = eid.shape[0], wu.shape[-1]
    if n != items * row_tile:
        raise ValueError(f"{n} rows are not {items} tiles of {row_tile}")
    itemsize = xs.dtype.itemsize
    weights = [w for w in (wg, wu, wd) if w is not None]
    ik, steps = _inner_steps(h, inner, itemsize, tile, len(weights))
    eid, n_real = _work_list(eid, n_real)

    def rows_map(i, s, eid_ref, n_ref):
        return _item_map(i, s, eid_ref, n_ref), 0

    vmem = (2 * len(weights) * h * ik * itemsize    # the streamed tiles
            + 2 * row_tile * h * (itemsize + 2 * 4)  # rows in, terms out
            + 3 * row_tile * ik * 4 + 2 * row_tile * LANE * 4)
    return pl.pallas_call(
        _under(_grouped_kernel, limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(items, steps),
            in_specs=[
                pl.BlockSpec((row_tile, h), rows_map),
                pl.BlockSpec((row_tile, 1), rows_map),
                *_weight_specs(h, ik, steps, wg is not None),
            ],
            out_specs=pl.BlockSpec((row_tile, h), rows_map),
        ),
        out_shape=jax.ShapeDtypeStruct((n, h), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name="moe_grouped_fwd",
    )(eid, n_real, xs, wt.astype(F32)[:, None], *weights)


def tiles_an_expert(lo, hi, row_tile: int):
    """How many row tiles of rows SORTED by expert hold a row of each: the
    tiles ``[lo, hi)`` reaches into, none where it is empty."""
    return jnp.where(hi > lo, (hi - 1) // row_tile - lo // row_tile + 1, 0)


def sorted_work_list(lo, hi, tiles: int, row_tile: int):
    """``moe_sorted_fwd``'s work list over rows SORTED by expert, expert
    ``e``'s at ``[lo[e], hi[e])`` of ``tiles * row_tile`` rows (ascending,
    back to back): ``(eid, tile, n)`` — item ``i < n`` is expert ``eid[i]``
    over row tile ``tile[i]``, an expert's tiles side by side in ascending
    order of expert, so a tile is revisited only by consecutive items; past
    ``n`` the list repeats its last real item.  An expert with rows takes
    the tiles it owns and at most one it shares with those before it: at
    most ``tiles + held - 1`` items whatever the rows hold."""
    held = lo.shape[0]
    ntile = tiles_an_expert(lo, hi, row_tile)
    tile_end = jnp.cumsum(ntile)
    n = tile_end[-1]
    item = jnp.minimum(jnp.arange(tiles + held - 1), jnp.maximum(n - 1, 0))
    eid = jnp.minimum(jnp.searchsorted(tile_end, item, side="right"),
                      held - 1)
    tile = (lo // row_tile - (tile_end - ntile))[eid] + item
    return (eid.astype(jnp.int32),
            jnp.clip(tile, 0, tiles - 1).astype(jnp.int32),
            n.astype(jnp.int32).reshape(1))


def _sorted_kernel(eid_ref, tile_ref, lo_ref, hi_ref, tok_ref, n_ref, x_ref,
                   wt_ref, *refs, limit: float = 0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # ``y`` comes in and goes out as one HBM array (aliased)
    *w_refs, _, y_ref, acc_ref, rows_ref, sem = refs
    i, s = pl.program_id(0), pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _():
        out, w = _item_product(x_ref, wt_ref, w_refs, limit)

        @pl.when(s == 0)
        def _():
            acc_ref[...] = out * w

        @pl.when(s != 0)
        def _():
            acc_ref[...] += out * w

        @pl.when(s == pl.num_programs(1) - 1)
        def _():
            # the item's own rows of the tile — a tile that straddles
            # experts is visited once an expert —: one expert's, so their
            # tokens are distinct, and what lies past the last real row is
            # nobody's
            rt, e = x_ref.shape[0], eid_ref[i]
            base = tile_ref[i] * rt
            r0 = jnp.maximum(lo_ref[e] - base, 0)
            r1 = jnp.minimum(hi_ref[e] - base, rt)

            def row_copy(r, fetch):
                at = y_ref.at[tok_ref[base + r]], rows_ref.at[r]
                return pltpu.make_async_copy(*(at if fetch else at[::-1]),
                                             sem)

            def each(do):
                jax.lax.fori_loop(r0, r1, lambda r, _: do(r), None)

            # every read in flight together, every write waited for before
            # the next item starts.  (The reads started at the item's FIRST
            # step, to fly while it multiplies, made the kernel slower —
            # 9.0 -> 10.0 ms a layer at dots3's 16,384 bucket, 4.65 -> 5.2
            # at MiMo's: they queue ahead of the next weight tiles; PERF.md
            # section 6, PR 59.)
            each(lambda r: row_copy(r, True).start())
            each(lambda r: row_copy(r, True).wait())
            rows_ref[...] += acc_ref[...].reshape(rows_ref.shape)
            each(lambda r: row_copy(r, False).start())
            each(lambda r: row_copy(r, False).wait())


def pallas_sorted_add(y, xs, tok, wt, lo, hi, wg, wu, wd, *, row_tile,
                      tile=None, limit: float = 0.0, interpret=None):
    """The kernel lowering for rows SORTED by expert and not padded: ``xs
    (N, h)``, ``N`` a multiple of ``row_tile``, expert ``e``'s rows at
    ``[lo[e], hi[e])`` (``lo``, ``hi (held,)``, ascending and back to back;
    an expert without rows has ``lo == hi``), ``wt (N,)`` each row's
    routing weight, ``tok (N,)`` the token each row is of.  Returns ``y (T,
    h / LANE, LANE)`` float32 — token ``t``'s row as ``h / LANE`` lane
    tiles, so that a row is one run of HBM: ``y.reshape(T, h)`` is the
    layer's — with each row ``r`` of some ``[lo[e], hi[e])`` through its
    expert, weighted, ADDED to row ``tok[r]``.  ``y`` stays in HBM and is
    the result (aliased): at an item's last step its own live rows of ``y``
    are fetched by row DMAs, all in flight together, the item's terms are
    added and the rows written back, all waited for before the next item
    starts.  An item is one expert's rows of one tile, so its tokens are
    distinct, and items run in order: a token that two experts share is
    added to twice in sequence, in ascending order of expert.  A row
    outside every ``[lo, hi)`` moves nothing, whatever ``xs``, ``wt`` and
    ``tok`` hold there, a token no live row names keeps its bits, and the
    work ends with the last expert's last tile: the grid steps past it
    fetch nothing and multiply nothing.  ``wg`` None for experts of two
    matrices."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    n, h = xs.shape
    inner = wu.shape[-1]
    if n % row_tile:
        raise ValueError(f"{n} rows are not whole tiles of {row_tile}")
    itemsize = xs.dtype.itemsize
    weights = [w for w in (wg, wu, wd) if w is not None]
    ik, steps = _inner_steps(h, inner, itemsize, tile, len(weights))
    lo, hi = lo.astype(jnp.int32), hi.astype(jnp.int32)
    eid, tiles, n_real = sorted_work_list(lo, hi, n // row_tile, row_tile)

    vmem = (2 * len(weights) * h * ik * itemsize    # the streamed tiles
            + 2 * row_tile * h * itemsize           # rows in
            + 4 * row_tile * h * 4      # the terms, y's rows, a product, a sum
            + 3 * row_tile * ik * 4 + 2 * row_tile * LANE * 4)
    # the lists the index maps read (the count last), the blocked operands
    # and ``y``, which the kernel adds to where it lies
    operands = (eid, tiles, lo, hi, tok.astype(jnp.int32), n_real, xs,
                wt.astype(F32)[:, None], *weights, y)
    return pl.pallas_call(
        _under(_sorted_kernel, limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(eid.shape[0], steps),
            in_specs=[
                pl.BlockSpec((row_tile, h), _tile_map),
                pl.BlockSpec((row_tile, 1), _tile_map),
                *_weight_specs(h, ik, steps, wg is not None),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((row_tile, h), F32),
                            pltpu.VMEM((row_tile, h // LANE, LANE), F32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(y.shape, F32),
        input_output_aliases={len(operands) - 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name="moe_sorted_fwd",
    )(*operands)


class Tiles(NamedTuple):
    """What :func:`fitted_tile` fits a kernel call with: the ``inner`` tile
    of a step and the ``rows`` of a row tile — ``None`` where every token
    goes through every touched expert (``moe_decode_fwd``) —, and whether
    the row tiles are cut from the rows as SORTED (``moe_sorted_fwd``) or
    from an expert's own rows padded to whole tiles (``moe_grouped_fwd``)."""
    inner: int
    rows: int | None
    sorted: bool = False

    @property
    def lowering(self) -> str:
        if self.rows is None:
            return "pallas"
        return "pallas_sorted" if self.sorted else "pallas_grouped"


def fitted_tile(u, experts) -> Tiles | None:
    """The kernel's tiles for ``u (T, h)`` over the stacked ``experts``
    (arrays or their shapes: ``{"wg", "wu", "wd"}``, or ``{"wu", "wd"}``
    for experts without a gate), ``None`` where the XLA form runs: a kernel on
    a TPU backend with no mesh in scope, one 2- or 4-byte float type for
    tokens and weights, ``h`` and the inner width multiples of ``LANE`` —
    ``moe_decode_fwd`` up to ``MAX_TOKENS`` tokens, ``moe_grouped_fwd`` in
    row tiles of ``ROW_TILE`` up to ``MAX_GROUPED_TOKENS``,
    ``moe_sorted_fwd`` in row tiles of ``SORTED_ROW_TILE`` above."""
    dtype = jnp.dtype(u.dtype)
    t, h = u.shape
    inner = experts["wu"].shape[-1]
    kernel = (_on_tpu() and not _mesh_in_scope()
              and all(jnp.dtype(w.dtype) == dtype for w in experts.values())
              and jnp.issubdtype(dtype, jnp.floating)
              and dtype.itemsize in (2, 4)
              and h % LANE == 0 and inner % LANE == 0)
    if not kernel:
        return None
    if t > MAX_GROUPED_TOKENS:
        return Tiles(inner_tile(h, inner, dtype.itemsize, len(experts),
                                SORTED_STEP_BYTES), SORTED_ROW_TILE, True)
    return Tiles(inner_tile(h, inner, dtype.itemsize, len(experts)),
                 None if t <= MAX_TOKENS else ROW_TILE)

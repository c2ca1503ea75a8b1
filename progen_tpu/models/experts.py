"""One chip's share of an expert layer: the grouped product over the
experts the chip holds, its window, and the device counters the families
with such a layer carry (``models/longcat.py``, ``models/deepseek_v2.py``,
``models/trinity.py``, ``models/sdar.py``, ``models/lfm2.py``,
``models/nemotron_h.py``, ``models/qwen3_next.py``,
``models/bailing_hybrid.py``), the sigmoid router several of them share
(:func:`sigmoid_route`, with ``noaux_tc``'s group limit where a family
states groups) and the softmax router of two (:func:`softmax_route`).

**Two expert forms**, told apart by what ``experts`` holds and never by a
knob: ``{"wg", "wu", "wd"}``, a gated SwiGLU of three matrices, ``(silu(u
W_g) * (u W_u)) W_d`` (five families); ``{"wu", "wd"}``, two matrices and
no gate, ``relu(u W_u)^2 W_d`` (Nemotron-H's, which also run in a width
that is not the model's: the layer projects into the latent before
:func:`held_experts` and out of it after the sum — ``u`` here is whatever
the experts take).  All three lowerings below compute both.

The router is as wide as published and picks ``moe_topk`` whatever the chip
holds; the layer adds the terms of the ``experts_held`` real experts from
``first_expert`` on and leaves out what the absent experts would add.
Nothing stands in for absent chips.  No assignment is ever dropped, and
:func:`held_experts` computes them in one of four ways, chosen from what
the code can observe and never from a knob (``ops/moe_decode.py:
fitted_tile``; noted under ``"moe_experts"``, ``ops/lowering.py``).  On a
TPU with no mesh in scope, tokens and weights of one float type and widths
on the lane tile, a call's TOKENS decide:

* a DECODE step's handful (at most 128: ``"pallas"``) goes through the
  kernel ``moe_decode_fwd``: each touched expert's matrices streamed once,
  back to back, every token through every touched expert with its routing
  weight (zero where it is not the expert's) selecting, so there is neither
  sort nor gather nor scatter-add;
* a few hundred (129 to 1,024 — a block-diffusion step's ``slots x
  block_length``, 256 in its cell, and the admissions under 2,048 tokens:
  ``"pallas_grouped"``) go through the kernel ``moe_grouped_fwd``: the
  same stream, one pass an expert, but each expert multiplied by its OWN
  rows only, gathered into row tiles of 32 — all rows through every expert
  would cost more MXU time than the stream takes from 256 rows on — and
  the terms gathered back per token (:func:`_grouped`);
* an ADMISSION's thousands (more than 1,024 tokens: ``"pallas_sorted"``)
  go through the kernel ``moe_sorted_fwd`` (:func:`_sorted`): the
  assignments sorted by held expert, a window of them at a time
  (:func:`sorted_window`: half of what the tokens would send the held
  experts if every slot were live; a window that overflows runs the loop
  again), the window's token rows gathered in sorted order, row tiles of
  128 of them through the expert whose rows they are — a tile that
  straddles experts once an expert, nothing for the tiles past the last
  live row — and each item's float32 terms added to their tokens' rows of
  ``y`` by the kernel itself, by row DMAs (XLA's scatter-add of them took
  3 us a row at 5,120 wide: PERF.md section 6, PR 59);
* every other call (the CPU, a mesh, two float types, widths off the lane
  tile: ``"xla"``) groups the tokens by held expert likewise and multiplies
  them by ``jax.lax.ragged_dot`` in windows of ``capacity`` assignments
  (:func:`moe_capacity`): a window that overflows runs the loop again.

A chip may also hold the WHOLE layer (``experts_held == router_width``,
``first_expert`` 0: ``models/sdar.py`` and ``models/lfm2.py``, one stage
of a pipeline): every
assignment is then its own, ``moe.held_load`` is the router's whole
histogram, and nothing below changes.

A config here has ``experts_held``, ``first_expert``, ``moe_topk`` and
``router_width``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from progen_tpu.ops import moe_decode
from progen_tpu.ops.lowering import note

F32 = jnp.float32

# what every family with a share counts (docs/OBSERVABILITY.md §3); a
# family adds its own keys and its attention's (``latent.STAT_KEYS``,
# ``trinity.ATTN_STAT_KEYS``) to these
STAT_KEYS = ("moe.tokens", "moe.held_load", "moe.prefill_held",
             "moe.decode_layers", "moe.experts_touched", "moe.expert_passes",
             "moe.rows_computed", "moe.prefill_rows_computed",
             "moe.prefill_rows_combined")


def zero_stats(keys, held: int) -> dict:
    """Device-side counters, all float32 sums."""
    out = {k: jnp.zeros((), F32) for k in keys}
    out["moe.held_load"] = jnp.zeros((held,), F32)
    return out


def add_stats(a: dict, b: dict) -> dict:
    with jax.named_scope("engine.stats"):
        return {k: a[k] + b[k] if k in b else a[k] for k in a}


def sigmoid_route(u, router, topk: int, *, norm: bool, scale: float,
                  eps: float, groups: int = 1, kept_groups: int = 1):
    """The sigmoid router with a selection bias, of the families that have
    it (``models/trinity.py``, ``models/lfm2.py``,
    ``models/nemotron_h.py``): ``(ids (T, k), weights
    (T, k))``, float32 throughout.  ``s = sigmoid(u W_r)``; the ``topk``
    largest of ``s + b`` are chosen (``b = router["bias"]`` picks and does
    not weigh); the weights are the chosen ``s`` alone, over ``sum s + eps``
    where ``norm``, times ``scale``.  ``eps`` is the family's own (Trinity
    and Nemotron-H 1e-20, LFM2 1e-6).  With ``groups`` > 1 (``noaux_tc``'s
    group limit, ``models/bailing_hybrid.py``) the experts lie in ``groups``
    groups of consecutive experts, a group scores the sum of its TWO largest
    ``s + b``, and only the ``kept_groups`` best groups' experts can be
    chosen."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(u.astype(F32), router["w"].astype(F32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        biased = scores + router["bias"].astype(F32)
        if groups > 1:
            t, size = biased.shape[0], biased.shape[1] // groups
            best = jnp.sum(jax.lax.top_k(
                biased.reshape(t, groups, size), 2)[0], axis=-1)
            _, keep = jax.lax.top_k(best, kept_groups)
            kept = jnp.zeros((t, groups), bool).at[
                jnp.arange(t)[:, None], keep].set(True)
            biased = jnp.where(jnp.repeat(kept, size, axis=1), biased,
                               -jnp.inf)
        _, ids = jax.lax.top_k(biased, topk)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        if norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
        return ids, w * scale


def softmax_route(u, router, topk: int, *, norm: bool):
    """The softmax router of the Qwen3-MoE layers (``models/sdar.py``: top-8
    of 128; ``models/qwen3_next.py``: top-10 of 512): ``(ids (T, k), weights
    (T, k))``, float32 throughout: the ``topk`` largest of ``softmax(u
    W_r)`` over the whole router, renormalised to sum to 1 where ``norm``
    (``norm_topk_prob``)."""
    with jax.named_scope("moe.router"):
        logits = jnp.dot(u.astype(F32), router["w"].astype(F32),
                         precision=jax.lax.Precision.HIGHEST)
        w, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), topk)
        if norm:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return ids, w


def moe_capacity(c, tokens: int) -> int:
    """Assignments per window of the grouped product: twice what the tokens
    send the held experts on average (``moe_topk * held / router_width`` a
    token: 0.25 at LongCat's 16 of 768, so half the tokens; 1.5 at
    DeepSeek-V2's 40 of 160, so three a token), in steps of 128, at least
    128, never more than there are assignments."""
    expected_twice = 2 * c.moe_topk * c.experts_held * tokens
    steps = -(-expected_twice // (c.router_width * 128))
    return min(tokens * c.moe_topk, max(128, steps * 128))


def held_experts(u, ids, w, live, experts, c, capacity=None,
                 limit: float = 0.0):
    """The held real experts' terms for ``u (T, h)``: ``(y (T, h) float32,
    load (held,))`` where ``load`` counts the live tokens' assignments to
    each held expert.  A token's ``k`` experts ``ids (T, k)`` are distinct,
    as a router's top-k are (``moe_sorted_fwd`` fetches the rows of ``y``
    that one expert's row tile adds to together).  Assignments of tokens
    that are not ``live`` (padding, finished rows) are not computed.
    ``capacity``: assignments per window of the sorted assignments where a
    form with windows runs (default :func:`moe_capacity` under the XLA
    form, :func:`sorted_window` under ``moe_sorted_fwd``); a window that
    overflows runs again, so it changes no result.  ``limit``: the layer's
    clip on a gated expert's two products (``ops/moe_decode.py:clipped``),
    a static of whichever lowering runs; 0 for none."""
    t, k = ids.shape
    held = c.experts_held
    tiles = moe_decode.fitted_tile(u, experts)
    note("moe_experts", "xla" if tiles is None else tiles.lowering)
    with jax.named_scope("moe.experts"):
        local = ids - c.first_expert
        mine = (local >= 0) & (local < held) & live[:, None]
        group = jnp.where(mine, local, held).reshape(-1)
        if tiles is not None and tiles.sorted:
            return _sorted(u, group, w, experts, c, tiles, capacity, limit)
        if tiles is not None:
            load = jnp.bincount(group, length=held + 1)[:held]
            form = _streamed if tiles.rows is None else _grouped
            return form(u, group.reshape(t, k), w, load, experts, tiles,
                        limit), load
        order = jnp.argsort(group)                    # held first, by expert
        load = jnp.bincount(group, length=held + 1)[:held]
        ends = jnp.cumsum(load)
        starts, n_mine = ends - load, ends[-1]
        cap = min(capacity or moe_capacity(c, t), t * k)
        pad = -(-(t * k) // cap) * cap - t * k
        order = jnp.pad(order, (0, pad))
        weights = w.reshape(-1)

        def window(carry):
            it, y = carry
            base = it * cap
            idx = jax.lax.dynamic_slice(order, (base,), (cap,))
            valid = base + jnp.arange(cap) < n_mine
            tok = idx // k
            sizes = (jnp.clip(ends - base, 0, cap)
                     - jnp.clip(starts - base, 0, cap)).astype(jnp.int32)
            xs = u[tok]
            gate = jax.lax.ragged_dot(
                xs, experts["wg"].astype(u.dtype),
                sizes) if "wg" in experts else None
            up = jax.lax.ragged_dot(xs, experts["wu"].astype(u.dtype), sizes)
            out = jax.lax.ragged_dot(
                moe_decode.activation(gate, up, limit),
                experts["wd"].astype(u.dtype), sizes)
            wt = jnp.where(valid, weights[idx], 0.0)
            term = jnp.where(valid[:, None], out.astype(F32) * wt[:, None], 0.0)
            return it + 1, y.at[tok].add(term)

        _, y = jax.lax.while_loop(
            lambda carry: carry[0] * cap < n_mine, window,
            (jnp.zeros((), jnp.int32), jnp.zeros(u.shape, F32)))
        return y, load


def sorted_window(c, tokens: int, k: int, row_tile: int,
                  capacity=None) -> int:
    """Rows of one window of the sorted assignments under
    ``moe_sorted_fwd``: ``capacity``, by default HALF of what the tokens
    send the held experts if every token slot is live (a third of an
    admission's slots are: one window a call, as a rule — the kernel's
    work ends at the live count, but the rows' gather before it is the
    window's), in whole row tiles, at least one and never more than hold
    the ``tokens * k`` assignments."""
    rows = capacity or (c.moe_topk * c.experts_held * tokens
                        // (2 * c.router_width))
    tiles = min(-(-rows // row_tile), -(-(tokens * k) // row_tile))
    return max(1, tiles) * row_tile


def _in_window(starts, ends, base, cap):
    """Where each expert's sorted rows ``[starts, ends)`` lie inside the
    window of ``cap`` rows from ``base`` on: ``(lo, hi)``, equal where it
    has none there."""
    return jnp.clip(starts - base, 0, cap), jnp.clip(ends - base, 0, cap)


def _sorted(u, group, w, experts, c, tiles, capacity=None,
            limit: float = 0.0):
    """``held_experts`` through ``moe_sorted_fwd``: ``(y, load)``.  The
    assignments sorted by expert (``group (T k,)``: the held expert, or
    ``held`` for what is not this chip's or not live), a window of them at
    a time: the window's token rows gathered in sorted order and ONE kernel
    call over row tiles of them — a tile that straddles experts visited
    once an expert, the work ending with the last live row —, which adds
    each item's terms to its tokens' rows of ``y`` itself (``y`` is the
    kernel's own HBM operand, carried from window to window; noted under
    ``"moe_combine"`` as ``"pallas_rows"``).  (``load`` is a sum of
    comparisons: on the chip ``bincount``'s scatter of 262,144 ones takes
    2.3 ms where this takes 3 us; PERF.md section 6, PR 53.)"""
    t = u.shape[0]
    k = group.shape[0] // t
    order = jnp.argsort(group)                    # held first, by expert
    load = jnp.sum(group[:, None] == jnp.arange(c.experts_held), axis=0,
                   dtype=jnp.int32)
    ends = jnp.cumsum(load)
    starts, n_mine = ends - load, ends[-1]
    cap = sorted_window(c, t, k, tiles.rows, capacity)
    order = jnp.pad(order, (0, -(-(t * k) // cap) * cap - t * k))
    weights = w.reshape(-1)

    note("moe_combine", "pallas_rows")

    def window(carry):
        it, y = carry
        base = it * cap
        idx = jax.lax.dynamic_slice(order, (base,), (cap,))
        tok = idx // k
        # what lies past the last real row is no expert's: the kernel
        # moves nothing for it
        return it + 1, moe_decode.pallas_sorted_add(
            y, u[tok], tok, weights[idx],
            *_in_window(starts, ends, base, cap), experts.get("wg"),
            experts["wu"], experts["wd"], row_tile=tiles.rows,
            tile=tiles.inner, limit=limit)

    # a token's row as lane tiles: one run of HBM, which a row DMA can name
    lane = moe_decode.LANE
    _, y = jax.lax.while_loop(
        lambda carry: carry[0] * cap < n_mine, window,
        (jnp.zeros((), jnp.int32), jnp.zeros((t, u.shape[1] // lane, lane),
                                             F32)))
    return y.reshape(u.shape), load


def _streamed(u, group, w, load, experts, tiles, limit: float = 0.0):
    """The terms of ``held_experts`` through ``moe_decode_fwd``: the
    touched experts in ascending order, each with the routing weight of
    every token (zero where ``group (T, k)`` does not name it)."""
    held = load.shape[0]
    touched = load > 0
    eid = jnp.argsort(~touched)                  # stable: the touched first
    names = group[None] == jnp.arange(held)[:, None, None]
    wt = jnp.sum(jnp.where(names, w.astype(F32)[None], 0.0), axis=-1)
    return moe_decode.pallas_expert_terms(
        u, eid, jnp.sum(touched), wt[eid], experts.get("wg"), experts["wu"],
        experts["wd"], tile=tiles.inner, limit=limit)


def _grouped(u, group, w, load, experts, tiles, limit: float = 0.0):
    """The terms of ``held_experts`` through ``moe_grouped_fwd``: each
    touched expert's own rows, gathered into whole row tiles (an expert's
    tiles side by side, in ascending order of expert, so that its matrices
    are streamed once), the result in that grouped order and summed back
    per token by a gather of its ``k`` rows."""
    t, k = group.shape
    held, rt = load.shape[0], tiles.rows
    # the work list: an expert with rows takes ceil(rows / rt) items
    items = t * k // rt + min(held, t * k)
    ntile = -(-load // rt)
    tile_end = jnp.cumsum(ntile)
    tile_start = tile_end - ntile                  # an expert's first item
    item = jnp.arange(items)
    eid = jnp.minimum(jnp.searchsorted(tile_end, item, side="right"),
                      held - 1)
    # what each grouped row holds: the expert's r-th assignment in token
    # order (a stable sort's), or nothing (a tile's padding, weight 0)
    order = jnp.argsort(group.reshape(-1))
    start = jnp.cumsum(load) - load
    r = ((item - tile_start[eid]) * rt)[:, None] + jnp.arange(rt)
    real = (item < tile_end[-1])[:, None] & (r < load[eid][:, None])
    src = order[jnp.where(real, start[eid][:, None] + r, 0)].reshape(-1)
    out = moe_decode.pallas_grouped_terms(
        u[src // k], eid, tile_end[-1],
        jnp.where(real.reshape(-1), w.reshape(-1)[src], 0.0),
        experts.get("wg"), experts["wu"], experts["wd"], row_tile=rt,
        tile=tiles.inner, limit=limit)
    # where each assignment's term is: its expert's first row and its place
    # among the expert's assignments (the inverse of ``order``)
    e = jnp.minimum(group, held - 1)
    row = (tile_start * rt - start)[e] + jnp.argsort(order).reshape(t, k)
    mine = group < held
    terms = out[jnp.where(mine, row, 0)]                     # (T, k, h)
    return jnp.sum(jnp.where(mine[..., None], terms, 0.0), axis=1)


def kernel_counters(u, experts, load, c) -> dict:
    """What the lowering :func:`held_experts` takes for ``u`` does, by the
    lowering's own reckoning, as float32 scalars: ``moe.expert_passes``,
    how many times it streams an expert's matrices, and
    ``moe.rows_computed``, the rows it passes through them.  Under
    ``moe_decode_fwd`` one work item a touched expert, all of the call's
    (padded) rows in each; under ``moe_grouped_fwd`` one item a row tile of
    an expert's own rows, and a pass a touched expert where a step holds
    the whole inner width (consecutive items keep the weight blocks), a
    pass an item where it does not; under ``moe_sorted_fwd`` one item a row
    tile of the sorted rows an expert has a row in, window by window, and
    the passes likewise (an expert whose rows two windows share is streamed
    in both); 0 under the XLA form, whose reads the program cannot know.
    And ``moe.prefill_rows_combined``, the rows of ``y`` that
    ``moe_sorted_fwd`` fetched, added to and wrote back: the live
    assignments to held experts, one each (the XLA scatter-add it replaced
    moved every row of every window: 1.3 to 1.4 a live assignment at
    dots3's 16,384 bucket); 0 under every other lowering."""
    with jax.named_scope("engine.stats"):
        tiles = moe_decode.fitted_tile(u, experts)
        touched = jnp.sum(load > 0)
        whole = tiles is not None and tiles.inner == experts["wu"].shape[-1]
        combined = jnp.zeros((), F32)
        if tiles is None:
            passes = rows = jnp.zeros((), F32)
        elif tiles.rows is None:
            passes = touched
            rows = touched * (-(-u.shape[0] // moe_decode.ROW_GROUP)
                              * moe_decode.ROW_GROUP)
        elif tiles.sorted:
            t, k = u.shape[0], c.moe_topk
            cap = sorted_window(c, t, k, tiles.rows)
            base = (jnp.arange(-(-(t * k) // cap)) * cap)[:, None]
            ends = jnp.cumsum(load)
            ntile = moe_decode.tiles_an_expert(       # (windows, held)
                *_in_window(ends - load, ends, base, cap), tiles.rows)
            items = jnp.sum(ntile)
            passes = jnp.sum(ntile > 0) if whole else items
            rows, combined = items * tiles.rows, jnp.sum(load)
        else:
            items = jnp.sum(-(-load // tiles.rows))
            passes, rows = touched if whole else items, items * tiles.rows
        return {"moe.expert_passes": passes.astype(F32),
                "moe.rows_computed": rows.astype(F32),
                "moe.prefill_rows_combined": combined.astype(F32)}

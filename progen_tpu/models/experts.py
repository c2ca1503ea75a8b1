"""One chip's share of an expert layer: the grouped product over the
experts the chip holds, its window, and the device counters the families
with such a layer carry (``models/longcat.py``, ``models/deepseek_v2.py``,
``models/trinity.py``).

The router is as wide as published and picks ``moe_topk`` whatever the chip
holds; the layer adds the terms of the ``experts_held`` real experts from
``first_expert`` on and leaves out what the absent experts would add.
Nothing stands in for absent chips.  No assignment is ever dropped, and
:func:`held_experts` computes them in one of two ways, chosen from what the
code can observe and never from a knob (``ops/moe_decode.py:fitted_tile``;
noted under ``"moe_experts"``, ``ops/lowering.py``):

* a call that carries a DECODE step's handful of tokens (at most 128, on a
  TPU with no mesh in scope, tokens and weights of one float type, widths
  on the lane tile) goes through the kernel ``moe_decode_fwd``: each
  touched expert's gate, up and down matrices streamed once, back to back,
  every token through every touched expert with its routing weight (zero
  where it is not the expert's) selecting, so there is neither sort nor
  gather nor scatter-add;
* every other call (an admission's thousands of tokens, the CPU, a mesh)
  groups the tokens by held expert (a sort of the assignments) and
  multiplies them by ``jax.lax.ragged_dot`` in windows of ``capacity``
  assignments: a window that overflows runs the loop again.

A chip may also hold the WHOLE layer (``experts_held == router_width``,
``first_expert`` 0: ``models/sdar.py``, one stage of a pipeline): every
assignment is then its own, ``moe.held_load`` is the router's whole
histogram, and nothing below changes.  And a call may lie BETWEEN the two
sizes above: a block-diffusion step sends a layer ``slots x block_length``
tokens (256 in its cell: twice the kernel's 128, a sixteenth of an
admission's), and as the rule stands takes the grouped form — one window
of ``ragged_dot`` over 2,048 assignments to 128 experts, 16 rows an expert,
which streams the layer's 1.2 GB at a third of the rate the kernel does
(PERF.md section 6, PR 41; its first ``perf_opt``, section 7).

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
             "moe.decode_layers", "moe.experts_touched", "moe.expert_passes")


def zero_stats(keys, held: int) -> dict:
    """Device-side counters, all float32 sums."""
    out = {k: jnp.zeros((), F32) for k in keys}
    out["moe.held_load"] = jnp.zeros((held,), F32)
    return out


def add_stats(a: dict, b: dict) -> dict:
    return {k: a[k] + b[k] if k in b else a[k] for k in a}


def moe_capacity(c, tokens: int) -> int:
    """Assignments per window of the grouped product: twice what the tokens
    send the held experts on average (``moe_topk * held / router_width`` a
    token: 0.25 at LongCat's 16 of 768, so half the tokens; 1.5 at
    DeepSeek-V2's 40 of 160, so three a token), in steps of 128, at least
    128, never more than there are assignments."""
    expected_twice = 2 * c.moe_topk * c.experts_held * tokens
    steps = -(-expected_twice // (c.router_width * 128))
    return min(tokens * c.moe_topk, max(128, steps * 128))


def held_experts(u, ids, w, live, experts, c, capacity=None):
    """The held real experts' terms for ``u (T, h)``: ``(y (T, h) float32,
    load (held,))`` where ``load`` counts the live tokens' assignments to
    each held expert.  Assignments of tokens that are not ``live``
    (padding, finished rows) are not computed.  ``capacity``: assignments
    per window of the grouped product where that form runs (default
    :func:`moe_capacity`); a window that overflows runs again, so it
    changes no result."""
    t, k = ids.shape
    held = c.experts_held
    tile = moe_decode.fitted_tile(u, experts)
    note("moe_experts", "xla" if tile is None else "pallas")
    with jax.named_scope("moe.experts"):
        local = ids - c.first_expert
        mine = (local >= 0) & (local < held) & live[:, None]
        group = jnp.where(mine, local, held).reshape(-1)
        if tile is not None:
            load = jnp.bincount(group, length=held + 1)[:held]
            return _streamed(u, group.reshape(t, k), w, load, experts,
                             tile), load
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
            gate = jax.lax.ragged_dot(xs, experts["wg"].astype(u.dtype), sizes)
            up = jax.lax.ragged_dot(xs, experts["wu"].astype(u.dtype), sizes)
            out = jax.lax.ragged_dot(jax.nn.silu(gate) * up,
                                     experts["wd"].astype(u.dtype), sizes)
            wt = jnp.where(valid, weights[idx], 0.0)
            term = jnp.where(valid[:, None], out.astype(F32) * wt[:, None], 0.0)
            return it + 1, y.at[tok].add(term)

        _, y = jax.lax.while_loop(
            lambda carry: carry[0] * cap < n_mine, window,
            (jnp.zeros((), jnp.int32), jnp.zeros(u.shape, F32)))
        return y, load


def _streamed(u, group, w, load, experts, tile):
    """The terms of ``held_experts`` through ``moe_decode_fwd``: the
    touched experts in ascending order, each with the routing weight of
    every token (zero where ``group (T, k)`` does not name it)."""
    held = load.shape[0]
    touched = load > 0
    eid = jnp.argsort(~touched)                  # stable: the touched first
    names = group[None] == jnp.arange(held)[:, None, None]
    wt = jnp.sum(jnp.where(names, w.astype(F32)[None], 0.0), axis=-1)
    return moe_decode.pallas_expert_terms(
        u, eid, jnp.sum(touched), wt[eid], experts["wg"], experts["wu"],
        experts["wd"], tile=tile)


def expert_passes(u, experts, load):
    """How many times the lowering :func:`held_experts` takes for ``u``
    streams an expert's three matrices, by the lowering's own reckoning, as
    a float32 scalar: one work item a touched expert under the kernel (all
    of a call's tokens fit one item), 0 under the XLA form, whose reads the
    program cannot know."""
    if moe_decode.fitted_tile(u, experts) is None:
        return jnp.zeros((), F32)
    return jnp.sum(load > 0).astype(F32)

"""One chip's share of an expert layer: the grouped product over the
experts the chip holds, its window, and the device counters the families
with such a layer carry (``models/longcat.py``, ``models/deepseek_v2.py``,
``models/trinity.py``).

The router is as wide as published and picks ``moe_topk`` whatever the chip
holds; the layer adds the terms of the ``experts_held`` real experts from
``first_expert`` on and leaves out what the absent experts would add.
Nothing stands in for absent chips.  Tokens are grouped by held expert (a
sort of the assignments) and multiplied by ``jax.lax.ragged_dot`` in windows
of ``capacity`` assignments: a window that overflows runs the loop again, so
no assignment is ever dropped.

A config here has ``experts_held``, ``first_expert``, ``moe_topk`` and
``router_width``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

# what every family with a share counts (docs/OBSERVABILITY.md §3); a
# family adds its own keys and its attention's (``latent.STAT_KEYS``,
# ``trinity.ATTN_STAT_KEYS``) to these
STAT_KEYS = ("moe.tokens", "moe.held_load", "moe.prefill_held",
             "moe.decode_layers", "moe.experts_touched")


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
    per window of the grouped product (default :func:`moe_capacity`); a
    window that overflows runs again, so it changes no result."""
    t, k = ids.shape
    held = c.experts_held
    with jax.named_scope("moe.experts"):
        local = ids - c.first_expert
        mine = (local >= 0) & (local < held) & live[:, None]
        group = jnp.where(mine, local, held).reshape(-1)
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

"""The model driver of the families served as plain functions over a
parameter dict (no flax): embedding -> a family's layer stack -> logits,
the seeded weights' common pieces, and the seam ``ServingEngine`` calls
(``decode/family.py``).  What a family brings is its config, its ``stack``
and **its attention blocks, each of which states its own cache**
(``blocks_of(config)``):
``models/latent.py`` has one kind (a latent row per token, ``max_len``
long), ``models/trinity.py`` two in one model (a ring of ``sliding_window``
rows beside keys and values that grow with the request).

**A block** (``blocks_of(config)`` gives ``{name: block}``, one per
attention block of the stack, in the stack's order) is an object with

``init_cache(slots, max_len, dtype)``
    the block's cache of ``slots`` idle rows: a pytree whose every leaf
    has the slot as its leading axis.  Its other axes are the block's own
    business: the driver, the family and the engine never look inside
``prefill(x (R, P, h), weights, lengths (R,))``
    ``(out (R, P, h), rows)``: attention over R right-padded rows, and per
    TOKEN what the cache will hold of it (leaves ``(R, ..P.., ...)``)
``cache_rows(rows, lengths, max_len)``
    those per-token rows laid out as the block's cache of R slots: where a
    token's row goes and how many rows a slot has is said here and in
    ``decode`` alone
``decode(x (S, h), pos (S,), cache, weights)``
    ``(out (S, h), cache)``: one token a row at position ``pos``, written
    into the cache and attended up to it

Precision: parameters and matrix products in the policy's dtypes (bfloat16
as published); the routers, every softmax, the norms' statistics and the
logits in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy, make_policy
from progen_tpu.models.experts import zero_stats

F32 = jnp.float32


def bf16_policy() -> Policy:
    """Parameters stored in bfloat16, as the sources publish them."""
    return make_policy(True, param_dtype=jnp.bfloat16)


# ------------------------------------------------------------------ weights


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def init_norm(key, shape, dt):
    return normal(key, shape, 0.05, F32).astype(dt) + 1


def init_ffn(key, h, width, gain, dt, lead=()):
    ks = jax.random.split(key, 3)
    return {
        "wg": normal(ks[0], lead + (h, width), h ** -0.5, dt),
        "wu": normal(ks[1], lead + (h, width), h ** -0.5, dt),
        "wd": normal(ks[2], lead + (width, h), gain * width ** -0.5, dt),
    }


def init_params(config, key, policy: Policy, init_layer):
    """Seeded weights, made on the device one layer per program so that no
    more than a layer's random bits are live beside the weights.
    ``init_layer(key, index)`` makes one layer's dict.  The embedding's
    rows are ``normal(0, 1 / embed_gain)``, so that the stream starts at
    unit scale whatever factor the family puts on the embedding."""
    c, dt, h = config, policy.param_dtype, config.hidden_size
    keys = jax.random.split(key, c.num_layers + 3)
    return {
        "embed": jax.jit(lambda k: normal(
            k, (c.vocab_size, h), 1.0 / c.embed_gain, dt))(keys[0]),
        "head": jax.jit(lambda k: normal(k, (h, c.vocab_size), h ** -0.5,
                                         dt))(keys[1]),
        "final_norm": jax.jit(lambda k: init_norm(k, (h,), dt))(keys[2]),
        "layers": [init_layer(keys[3 + i], i) for i in range(c.num_layers)],
    }


# ------------------------------------------------------------------- pieces


def rms_norm(x, scale, eps):
    """Statistics in float32, the result in ``x``'s dtype."""
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(
        x.dtype)


def rope(x, positions, inv_freq):
    """Half-split rotation of ``x (..., n, [heads,] d)`` at ``positions
    (..., n)``; ``inv_freq(d)`` gives the ``d / 2`` frequencies; tables in
    float32."""
    d = x.shape[-1]
    inv = inv_freq(d)
    ang = positions.astype(F32)[..., None] * inv
    if x.ndim == ang.ndim + 1:          # a heads axis between n and d
        ang = ang[..., None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : d // 2].astype(F32), x[..., d // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def mm(x, w):
    return jnp.dot(x, w.astype(x.dtype))


def swiglu(x, p, scope="ffn.dense"):
    with jax.named_scope(scope):
        return mm(jax.nn.silu(mm(x, p["wg"])) * mm(x, p["wu"]), p["wd"])


def _embed(params, tokens, c, dt):
    x = params["embed"][tokens].astype(dt)
    if c.embed_gain != 1:
        x = x * jnp.asarray(c.embed_gain, dt)
    return x


def _logits(x, params, c):
    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    return jnp.dot(x, params["head"].astype(x.dtype),
                   preferred_element_type=F32)


# --------------------------------------------------------------- the driver


def prefill(stack, blocks, params, tokens, lengths, config, policy: Policy,
            *, logit_positions=None, with_choices: bool = False):
    """``tokens (R, P)`` right-padded rows of ``lengths (R,)`` real tokens
    -> ``(logits (R, K, V) float32 at logit_positions (R, K)`` (default the
    last real position, K = 1), ``per-token cache rows {block: rows},
    stats)``: what each block's ``prefill`` returned, not yet laid out as
    a cache (``block.cache_rows`` does that).  ``stack(x, params, config,
    attend, live)`` is the family's layers over flat tokens, ``attend(x,
    block name, weights)`` the one thing prefill and decode differ in; it
    returns ``(x, stats, chosen ids per expert layer, held experts
    touched)``.  Padding, and the whole of a row of length 0 (an admission
    row that carries no request), is computed by the dense FFNs (the shapes
    are static) but not by the experts, and by attention only where a
    blocked XLA form runs; it is not counted, and no real position's output
    depends on what it holds."""
    c = config
    dt = policy.compute_dtype
    r, n = tokens.shape
    live = (jnp.arange(n)[None, :] < lengths[:, None]).reshape(-1)
    rows = {}

    def attend(x, name, p):
        out, rows[name] = blocks[name].prefill(x.reshape(r, n, -1), p,
                                               lengths)
        return out.reshape(r * n, -1)

    x = _embed(params, tokens.reshape(-1), c, dt)
    x, stats, chosen, _ = stack(x, params, c, attend, live)
    stats["moe.prefill_held"] = jnp.sum(stats["moe.held_load"])
    # a decode step's counter, as ``moe.experts_touched`` beside it
    stats["moe.expert_passes"] = jnp.zeros((), F32)
    if logit_positions is None:       # a row of no tokens reads position 0
        logit_positions = jnp.maximum(lengths - 1, 0)[:, None]
    x = jnp.take_along_axis(x.reshape(r, n, -1),
                            logit_positions[..., None], axis=1)
    out = _logits(x, params, c), rows, stats
    if with_choices:
        return out + (jnp.stack(chosen).reshape(len(chosen), r, n, -1),)
    return out


def decode_step(stack, blocks, attention_stats, params, tok, pos, caches,
                live, config, policy: Policy, *, with_choices: bool = False):
    """One token per row: ``tok (S,)`` at ``pos (S,)`` -> ``(logits (S, V)
    float32, caches, stats)``.  Rows that are not ``live`` run (the batch
    is static) but are not counted and reach no expert.
    ``attention_stats(dtype, caches, pos, live)`` gives the family's
    counters of the step's attention (its rows, their contexts, the cache
    rows the lowering that ran reads)."""
    c = config
    dt = policy.compute_dtype
    caches = dict(caches)

    def attend(x, name, p):
        out, caches[name] = blocks[name].decode(x, pos, caches[name], p)
        return out

    x = _embed(params, tok, c, dt)
    x, stats, chosen, touched = stack(x, params, c, attend, live)
    stats["moe.decode_layers"] = jnp.asarray(
        len(chosen), F32) * jnp.any(live)
    stats["moe.experts_touched"] = touched
    stats.update(attention_stats(dt, caches, pos, live))
    out = _logits(x, params, c), caches, stats
    if with_choices:
        return out + (jnp.stack(chosen),)
    return out


# ------------------------------------------------------- the engine's seam


class Family:
    """What ``ServingEngine``'s plain dense path calls
    (``decode/family.py``), for a family of this driver.  A family names
    itself and brings ``stack`` (its layers), ``stat_keys`` (its device
    counters), ``blocks_of(config)`` (its attention blocks by name, each
    stating its cache) and ``attention_stats``."""

    name: str
    stat_keys: tuple
    position_masks = False      # the state holds an (S, V) mask, not (S, L, V)
    idle_length = 0             # a row without a request has no token
    modes = frozenset()         # the plain dense path only
    step_model = prefill_model = None

    def __init__(self, config, policy: Policy):
        self.config = config
        self.policy = policy
        self.blocks = self.blocks_of(config)
        self.bucket_base = config.prefill_bucket
        self.vocab = config.vocab_size
        self.seq_len = config.seq_len

    def embedder(self, mesh=None, strategies=()):
        return None

    def init_caches(self, slots: int, max_len: int):
        return {name: block.init_cache(slots, max_len,
                                       self.policy.compute_dtype)
                for name, block in self.blocks.items()}

    def init_stats(self) -> dict:
        return zero_stats(self.stat_keys, self.config.experts_held)

    def bucket(self, prime_len: int, max_len: int) -> int:
        b = self.bucket_base
        while b < prime_len:
            b *= 2
        return min(b, -(-max_len // self.bucket_base) * self.bucket_base)

    def buckets(self, cap: int, max_len: int) -> list[int]:
        out = []
        p = 1
        while p <= cap:
            out.append(self.bucket(p, max_len))
            p = out[-1] + 1
        return out

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, rows, stats = prefill(self.stack, self.blocks, params,
                                      tokens, lengths, self.config,
                                      self.policy)
        caches = {name: self.blocks[name].cache_rows(v, lengths, max_len)
                  for name, v in rows.items()}
        return logits[:, 0], caches, stats

    def decode_step(self, params, tok, pos, caches, live, adapters=None,
                    tenant=None):
        return decode_step(self.stack, self.blocks, self.attention_stats,
                           params, tok, pos, caches, live, self.config,
                           self.policy)

    def publish(self, stats: dict) -> dict:
        """Registry gauges from the fetched counters (cumulative since the
        engine was built): name -> value."""
        out = {k: float(v) for k, v in stats.items() if k != "moe.held_load"}
        load = stats["moe.held_load"]
        out["moe.held_assignments"] = float(load.sum())
        out["moe.held_load_max"] = float(load.max())
        out["moe.held_load_mean"] = float(load.mean())
        return out

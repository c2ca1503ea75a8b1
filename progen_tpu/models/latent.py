"""Latent attention (MLA) and the model driver around it, shared by the
families that cache a latent row per token (``models/longcat.py``,
``models/deepseek_v2.py``).

Plain functions over a parameter dict (no flax).  A family brings a config
and its layer stack; everything here reads the config's fields and asks it
two things that differ between families: ``rope_inv_freq(d)`` (the rotary
frequency table) and the gains ``q_gain`` / ``kv_gain`` applied to the
projected query and to the normed latent (1 where there is none).

**MLA.**  ``c_q = RMSNorm(x W_qa)``; ``q = (c_q W_qb) * q_gain``;
``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv) * kv_gain`` (so the CACHE
holds the scaled latent); ``[k_nope | v] = c_kv W_kvb``.  RoPE (half-split)
on the rope part of q and on ``k_r``, which all heads share.  The cache row
of a token is ``[c_kv | rope(k_r)]``.  Scores are scaled by ``(nope +
rope)^-1/2``: a family with another softmax scale folds the factor into
``q_gain``.  Absorbed decode: ``q_lat = q_nope W_kvb[k]^T`` per head, scores
``q_lat . c_kv + q_rope . k_r``, ``o_lat = softmax . c_kv``, ``o = o_lat
W_kvb[v]``.

**The driver.**  ``prefill`` runs R rows of P tokens through a family's
``stack`` and returns logits plus each attention block's latent cache rows;
``decode_step`` advances S rows by one token in the absorbed form.
``LatentFamily`` is what ``ServingEngine`` calls (``decode/family.py``).

Precision: parameters and matrix products in the policy's dtypes (bfloat16
as published); the routers, every softmax, the norms' statistics and the
logits in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy, make_policy
from progen_tpu.models.experts import zero_stats
from progen_tpu.ops.mla_decode import decode_attention, rows_visited
from progen_tpu.ops.mla_prefill import prefill_attention
from progen_tpu.ops.row_write import write_rows

F32 = jnp.float32


def bf16_policy() -> Policy:
    """Parameters stored in bfloat16, as the sources publish them."""
    return make_policy(True, param_dtype=jnp.bfloat16)


# ------------------------------------------------------------------ weights


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def init_norm(key, shape, dt):
    return normal(key, shape, 0.05, F32).astype(dt) + 1


def init_attn(key, c, dt):
    h, heads = c.hidden_size, c.num_attention_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    return {
        "wqa": normal(ks[0], (h, c.q_lora_rank), h ** -0.5, dt),
        "q_norm": init_norm(ks[1], (c.q_lora_rank,), dt),
        "wqb": normal(ks[2], (c.q_lora_rank, heads * qk),
                      c.attn_qk_gain * c.q_lora_rank ** -0.5, dt),
        "wkva": normal(ks[3], (h, c.latent_width), h ** -0.5, dt),
        "kv_norm": init_norm(ks[4], (c.kv_lora_rank,), dt),
        "wkvb": normal(
            ks[5], (c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim)),
            c.attn_qk_gain * c.kv_lora_rank ** -0.5, dt),
        "wo": normal(ks[6], (heads * c.v_head_dim, h),
                     c.residual_gain * (heads * c.v_head_dim) ** -0.5, dt),
    }


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
    ``init_layer(key, index)`` makes one layer's dict."""
    c, dt, h = config, policy.param_dtype, config.hidden_size
    keys = jax.random.split(key, c.num_layers + 3)
    return {
        "embed": jax.jit(lambda k: normal(k, (c.vocab_size, h), 1.0, dt))(
            keys[0]),
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


def mla_project(x, p, c, positions):
    """``x (..., n, h)`` at ``positions (..., n)`` -> ``q_nope (..., n, H,
    nope)``, rotated ``q_rope (..., n, H, rope)`` and the cache row
    ``[c_kv | rope(k_r)] (..., n, latent)``."""
    heads = c.num_attention_heads
    nope, rot = c.qk_nope_head_dim, c.qk_rope_head_dim
    with jax.named_scope("mla.project"):
        c_q = rms_norm(mm(x, p["wqa"]), p["q_norm"], c.rms_norm_eps)
        q = mm(c_q, p["wqb"])
        if c.q_gain != 1:
            q = q * jnp.asarray(c.q_gain, q.dtype)
        q = q.reshape(q.shape[:-1] + (heads, nope + rot))
        kva = mm(x, p["wkva"])
        c_kv = rms_norm(kva[..., : c.kv_lora_rank], p["kv_norm"],
                        c.rms_norm_eps)
        if c.kv_gain != 1:
            c_kv = c_kv * jnp.asarray(c.kv_gain, c_kv.dtype)
        k_r = rope(kva[..., c.kv_lora_rank:], positions, c.rope_inv_freq)
        q_rope = rope(q[..., nope:], positions, c.rope_inv_freq)
        return q[..., :nope], q_rope, jnp.concatenate([c_kv, k_r], axis=-1)


def _wkvb(p, c, dtype):
    w = p["wkvb"].astype(dtype).reshape(
        c.kv_lora_rank, c.num_attention_heads,
        c.qk_nope_head_dim + c.v_head_dim)
    return w[..., : c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def mla_prefill(x, p, c, lengths=None):
    """Full causal attention over ``x (R, P, h)`` in the NON-absorbed form
    (keys and values expanded from the latent once); the core is
    ``ops/mla_prefill.py``: a flash kernel on the chip at the published
    head widths, blocks of query rows in XLA elsewhere.  ``lengths (R,)``:
    the real leading positions of each row (default all); the output at a
    pad position is finite and otherwise unspecified.  Returns ``(out (R,
    P, h), latent rows (R, P, latent))``."""
    r, n, _ = x.shape
    with jax.named_scope("mla.prefill"):
        positions = jnp.broadcast_to(jnp.arange(n), (r, n))
        q_nope, q_rope, latent = mla_project(x, p, c, positions)
        wk, wv = _wkvb(p, c, x.dtype)
        c_kv, k_r = latent[..., : c.kv_lora_rank], latent[..., c.kv_lora_rank:]
        k_nope = jnp.einsum("rnl,lhd->rhnd", c_kv, wk)
        v = jnp.einsum("rnl,lhd->rhnd", c_kv, wv)
        o = prefill_attention(q_nope, q_rope, k_nope, k_r, v, lengths)
        return mm(o, p["wo"]), latent


def mla_decode(x, pos, cache, p, c):
    """One token per row in the ABSORBED form: ``x (S, h)`` at ``pos (S,)``
    against ``cache (S, T, latent)``, which gains the row's new entry at
    ``pos`` and is then attended up to it (``ops/mla_decode.py``: one kernel
    that reads each slot's rows once on the chip, three XLA ops over the
    whole cache elsewhere).  Returns ``(out (S, h), cache)``."""
    s = x.shape[0]
    rank = c.kv_lora_rank
    with jax.named_scope("mla.decode"):
        q_nope, q_rope, row = mla_project(x[:, None], p, c, pos[:, None])
        cache = write_rows(cache, row[:, 0].astype(cache.dtype), pos, axis=0)
        wk, wv = _wkvb(p, c, x.dtype)
        q_lat = jnp.einsum("shd,lhd->shl", q_nope[:, 0], wk)
        q_cat = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)
        o_lat = decode_attention(
            q_cat, cache, pos + 1, rank,
            1.0 / math.sqrt(c.qk_nope_head_dim + c.qk_rope_head_dim))
        o = jnp.einsum("shl,lhd->shd", o_lat, wv)
        return mm(o.reshape(s, -1), p["wo"]), cache


# --------------------------------------------------------------- the driver


def _logits(x, params, c):
    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    return jnp.dot(x, params["head"].astype(x.dtype),
                   preferred_element_type=F32)


def prefill(stack, params, tokens, lengths, config, policy: Policy, *,
            logit_positions=None, with_choices: bool = False):
    """``tokens (R, P)`` right-padded rows of ``lengths (R,)`` real tokens
    -> ``(logits (R, K, V) float32 at logit_positions (R, K)`` (default the
    last real position, K = 1), ``latent rows {block: (R, P, latent)},
    stats)``.  ``stack(x, params, config, attend, live)`` is the family's
    layers over flat tokens, ``attend(x, block name, weights)`` the one
    thing prefill and decode differ in; it returns ``(x, stats, chosen ids
    per expert layer, held experts touched)``.  Padding, and the whole of
    a row of length 0 (an admission row that carries no request), is
    computed by the dense FFNs (the shapes are static) but not by the
    experts, and by attention only where the blocked XLA form runs
    (``ops/mla_prefill.py``: the kernel visits no tile past a row's
    length); it is not counted, and no real position's output depends on
    what it holds."""
    c = config
    dt = policy.compute_dtype
    r, n = tokens.shape
    live = (jnp.arange(n)[None, :] < lengths[:, None]).reshape(-1)
    rows = {}

    def attend(x, name, p):
        out, latent = mla_prefill(x.reshape(r, n, -1), p, c, lengths)
        rows[name] = latent
        return out.reshape(r * n, -1)

    x = params["embed"][tokens.reshape(-1)].astype(dt)
    x, stats, chosen, _ = stack(x, params, c, attend, live)
    stats["moe.prefill_held"] = jnp.sum(stats["moe.held_load"])
    if logit_positions is None:       # a row of no tokens reads position 0
        logit_positions = jnp.maximum(lengths - 1, 0)[:, None]
    x = jnp.take_along_axis(x.reshape(r, n, -1),
                            logit_positions[..., None], axis=1)
    out = _logits(x, params, c), rows, stats
    if with_choices:
        return out + (jnp.stack(chosen).reshape(len(chosen), r, n, -1),)
    return out


def decode_step(stack, params, tok, pos, caches, live, config,
                policy: Policy, *, with_choices: bool = False):
    """One token per row: ``tok (S,)`` at ``pos (S,)`` -> ``(logits (S, V)
    float32, caches, stats)``.  Rows that are not ``live`` run (the batch
    is static) but are not counted and reach no expert."""
    c = config
    dt = policy.compute_dtype
    caches = dict(caches)

    def attend(x, name, p):
        out, caches[name] = mla_decode(x, pos, caches[name], p, c)
        return out

    x = params["embed"][tok].astype(dt)
    x, stats, chosen, touched = stack(x, params, c, attend, live)
    stats["moe.decode_layers"] = jnp.asarray(
        len(chosen), F32) * jnp.any(live)
    stats["moe.experts_touched"] = touched
    stats["mla.decode_rows"] = jnp.sum(live).astype(F32)
    stats["mla.context_tokens"] = jnp.sum(
        jnp.where(live, pos + 1, 0)).astype(F32)
    # every block's core has these shapes: the rows ONE of them reads
    stats["mla.cache_rows_read"] = rows_visited(
        dt, next(iter(caches.values())), pos + 1,
        c.kv_lora_rank) * jnp.any(live)
    out = _logits(x, params, c), caches, stats
    if with_choices:
        return out + (jnp.stack(chosen),)
    return out


# ------------------------------------------------------- the engine's seam


class LatentFamily:
    """What ``ServingEngine``'s plain dense path calls
    (``decode/family.py``).  The cache is a second kind beside ProGen's
    rings: per attention block a latent row per token, ``max_len`` long.
    A family names itself and brings ``stack`` (its layers), ``stat_keys``
    (its device counters) and ``cache_names(config)``."""

    name: str
    stat_keys: tuple
    position_masks = False      # the state holds an (S, V) mask, not (S, L, V)
    idle_length = 0             # a row without a request has no token
    modes = frozenset()         # the plain dense path only
    step_model = prefill_model = None

    def __init__(self, config, policy: Policy):
        self.config = config
        self.policy = policy
        self.bucket_base = config.prefill_bucket
        self.vocab = config.vocab_size
        self.seq_len = config.seq_len

    def embedder(self, mesh=None, strategies=()):
        return None

    def init_caches(self, slots: int, max_len: int):
        return {name: jnp.zeros((slots, max_len, self.config.latent_width),
                                self.policy.compute_dtype)
                for name in self.cache_names(self.config)}

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
        logits, rows, stats = prefill(self.stack, params, tokens, lengths,
                                      self.config, self.policy)
        n = tokens.shape[1]
        caches = {k: v[:, :max_len] if n >= max_len else jnp.pad(
            v, ((0, 0), (0, max_len - n), (0, 0))) for k, v in rows.items()}
        return logits[:, 0], caches, stats

    def decode_step(self, params, tok, pos, caches, live, adapters=None,
                    tenant=None):
        return decode_step(self.stack, params, tok, pos, caches, live,
                           self.config, self.policy)

    def publish(self, stats: dict) -> dict:
        """Registry gauges from the fetched counters (cumulative since the
        engine was built): name -> value."""
        out = {k: float(v) for k, v in stats.items() if k != "moe.held_load"}
        load = stats["moe.held_load"]
        out["moe.held_assignments"] = float(load.sum())
        out["moe.held_load_max"] = float(load.max())
        out["moe.held_load_mean"] = float(load.mean())
        return out

"""Latent attention (MLA): the attention block of the families that cache a
latent row per token (``models/longcat.py``, ``models/deepseek_v2.py``).
The model driver around it — embedding, a family's stack, logits, the
engine's seam — is ``models/driver.py``, which both families share with
``models/trinity.py``; its names are re-exported here.

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

**What a latent block states about its cache** (``LatentBlock``, the one
kind every block of these families is): one leaf ``(slots, max_len,
latent_width)``; the token at position ``p`` lies in row ``p``; a slot at
position ``pos`` has ``pos + 1`` rows.
"""

from __future__ import annotations

import math
from collections import defaultdict
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.models import driver
from progen_tpu.models.driver import (  # noqa: F401
    F32,
    bf16_policy,
    init_ffn,
    init_norm,
    init_params,
    mm,
    normal,
    rms_norm,
    rope,
    swiglu,
)
from progen_tpu.ops.mla_decode import decode_attention, rows_visited
from progen_tpu.ops.mla_prefill import prefill_attention
from progen_tpu.ops.row_write import write_rows

# the device counters of a latent family's decode steps, beside the shared
# ``experts.STAT_KEYS`` (docs/OBSERVABILITY.md section 3)
STAT_KEYS = ("mla.decode_rows", "mla.context_tokens", "mla.cache_rows_read")


# ------------------------------------------------------------------ weights


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


# ---------------------------------------------------------------------- MLA


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


# ------------------------------------------- the block, and the driver over it


class LatentBlock:
    """The one kind of attention block of a latent family
    (``models/driver.py`` says what a block is)."""

    def __init__(self, config):
        self.config = config

    def init_cache(self, slots: int, max_len: int, dtype):
        return jnp.zeros((slots, max_len, self.config.latent_width), dtype)

    def prefill(self, x, p, lengths):
        return mla_prefill(x, p, self.config, lengths)

    def cache_rows(self, rows, lengths, max_len: int):
        n = rows.shape[1]
        return rows[:, :max_len] if n >= max_len else jnp.pad(
            rows, ((0, 0), (0, max_len - n), (0, 0)))

    def decode(self, x, pos, cache, p):
        return mla_decode(x, pos, cache, p, self.config)


def attention_stats(config, dt, caches, pos, live) -> dict:
    """A decode step's ``mla.*`` counters."""
    return {
        "mla.decode_rows": jnp.sum(live).astype(F32),
        "mla.context_tokens": jnp.sum(
            jnp.where(live, pos + 1, 0)).astype(F32),
        # every block's core has these shapes: the rows ONE of them reads
        "mla.cache_rows_read": rows_visited(
            dt, next(iter(caches.values())), pos + 1,
            config.kv_lora_rank) * jnp.any(live),
    }


def _any_name(config):
    """``{any name: a latent block}``: every block is of the one kind, and
    these functions are not told the stack's names."""
    return defaultdict(partial(LatentBlock, config))


def prefill(stack, params, tokens, lengths, config, policy: Policy,
            **kwargs):
    """``driver.prefill`` over latent blocks: the per-token cache rows are
    ``{block: (R, P, latent)}``."""
    return driver.prefill(stack, _any_name(config), params, tokens, lengths,
                          config, policy, **kwargs)


def decode_step(stack, params, tok, pos, caches, live, config,
                policy: Policy, **kwargs):
    """``driver.decode_step`` over latent blocks."""
    return driver.decode_step(
        stack, _any_name(config), partial(attention_stats, config),
        params, tok, pos, caches, live, config, policy, **kwargs)


class LatentFamily(driver.Family):
    """A family whose every attention block is a :class:`LatentBlock`; it
    brings ``cache_names(config)``, the blocks' names in its stack."""

    def blocks_of(self, config):
        return dict.fromkeys(self.cache_names(config), LatentBlock(config))

    def attention_stats(self, dt, caches, pos, live):
        return attention_stats(self.config, dt, caches, pos, live)

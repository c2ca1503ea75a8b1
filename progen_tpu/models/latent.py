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

**MLA.**  ``c_q = RMSNorm(x W_qa)``; ``q = (c_q W_qb) * q_gain`` (or ``q = (x
W_q) * q_gain`` where the weights hold ``"wq"`` and no low-rank pair: a
config whose ``q_lora_rank`` is null, ``models/bailing_hybrid.py``);
``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv) * kv_gain`` (so the CACHE
holds the scaled latent); ``[k_nope | v] = c_kv W_kvb``.  RoPE (half-split)
on the rope part of q and on ``k_r``, which all heads share.  The cache row
of a token is ``[c_kv | rope(k_r)]``.  Scores are scaled by ``(nope +
rope)^-1/2``: a family with another softmax scale folds the factor into
``q_gain``.  Absorbed decode: ``q_lat = q_nope W_kvb[k]^T`` per head, scores
``q_lat . c_kv + q_rope . k_r``, ``o_lat = softmax . c_kv``, ``o = o_lat
W_kvb[v]``.

**What a latent block states about its cache** (``LatentBlock``): one leaf
``(slots, max_len, latent_width)``; the token at position ``p`` lies in row
``p``; a slot at position ``pos`` has ``pos + 1`` rows.  That is the one
kind every block of LongCat and DeepSeek-V2 is, under ONE shape (the config
itself).  ``models/dots3.py`` brings two shapes in one stack and asks three
more things of a block, ``models/glm_dsa.py`` a fourth, each an option of
its constructor that changes nothing where it is not asked for:

* ``window`` — the latent lies in a RING of ``min(window, max_len)`` rows
  (``models/kv.py``'s rule: the token at ``p`` in row ``p % rows``, a slot
  at ``pos`` has ``min(pos + 1, rows)`` of them; rows are cached rotated,
  so their order in the ring does not matter); the admission's core is
  ``ops/gqa.py``'s windowed form over the expanded heads, the step's
  ``ops/mla_decode.py`` over the ring;
* ``indexer`` — the cache is ``{"latent": .., "index": (slots, max_len,
  index_head_dim)}``: a SECOND leaf, the indexer's key a token, written by
  the same step; the attention runs over the ``index_topk`` rows the
  indexer selects (``ops/dsa.py``; :func:`index_project` has its
  equations);
* ``gate`` — one sigmoid a head, ``o_h <- o_h * sigmoid(x W_g)_h``, between
  the core and ``W_o`` (:func:`gate_heads`; ``x`` is the block's normed
  input);
* ``selection`` — the block attends under a selection that SEVERAL blocks
  read (``models/driver.py`` carries it from one to the next): ``"own"``,
  the block has the ``indexer``'s weights and cache leaf, computes the
  selection once and hands it on — an admission's keep mask ``(R, P, P)``
  (``ops/dsa.py:prefill_keep``; ``None`` where nothing is dropped), a decode
  step's ``(rows (S, K), kept (S,))`` —; ``"borrow"``, the block has NO
  indexer, neither weights nor a second leaf — its cache is the plain
  latent leaf — and its core reads what the last owner handed on: the same
  mask, ITS OWN latent rows gathered at the same numbers.

A config may also state ``rope_interleave`` (and ``indexer_rope_interleave``
for the indexer's side): the rotations then pair the columns ``(2i, 2i +
1)`` (``driver.rope_pairs``) where they pair ``(i, i + d / 2)`` without it.
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
from progen_tpu.ops import dsa, gqa
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


def _rope_of(c, key: str = "rope_interleave"):
    """The rotation ``c`` states under ``key``: interleaved pairs or (the
    default) half-split ones."""
    return driver.rope_pairs if getattr(c, key, False) else rope


def mla_project(x, p, c, positions, latent_query: bool = False):
    """``x (..., n, h)`` at ``positions (..., n)`` -> ``q_nope (..., n, H,
    nope)``, rotated ``q_rope (..., n, H, rope)`` and the cache row
    ``[c_kv | rope(k_r)] (..., n, latent)``; with ``latent_query`` the
    normed query latent ``c_q (..., n, q_lora_rank)`` too (an indexer reads
    it)."""
    heads = c.num_attention_heads
    nope, rot = c.qk_nope_head_dim, c.qk_rope_head_dim
    rope = _rope_of(c)
    with jax.named_scope("mla.project"):
        if "wqa" in p:
            c_q = rms_norm(mm(x, p["wqa"]), p["q_norm"], c.rms_norm_eps)
            q = mm(c_q, p["wqb"])
        else:       # ``q_lora_rank`` null: no low rank, no query norm
            c_q, q = None, mm(x, p["wq"])
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
        out = q[..., :nope], q_rope, jnp.concatenate([c_kv, k_r], axis=-1)
        return out + (c_q,) if latent_query else out


def index_project(x, c_q, p, c, positions):
    """The indexer's side of a token (DeepSeek-V3.2's; ``ops/dsa.py`` has
    the score): ``q^I = c_q W^I_q * q_gain (..., n, J, d)`` — it reads the
    query latent as the attention's query does —, ``k^I = LayerNorm(x
    W^I_k) (..., n, d)``, one key for all ``J`` heads and the row the cache
    keeps, the leading ``qk_rope_head_dim`` columns of both rotated
    (half-split, or as ``indexer_rope_interleave`` says) at the block's own
    base, and ``w = x W^I_w * J^-1/2 * d^-1/2 (..., n, J)`` in float32."""
    heads, d, rot = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
    rope = _rope_of(c, "indexer_rope_interleave")

    def rotated(a):
        return jnp.concatenate(
            [rope(a[..., :rot], positions, c.rope_inv_freq), a[..., rot:]],
            axis=-1)

    with jax.named_scope("dsa.project"):
        q = mm(c_q, p["wiq"])
        if c.q_gain != 1:
            q = q * jnp.asarray(c.q_gain, q.dtype)
        q = rotated(q.reshape(q.shape[:-1] + (heads, d)))
        k = mm(x, p["wik"]).astype(F32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(
            jnp.mean(k * k, axis=-1, keepdims=True) + c.index_norm_eps)
        k = (k * p["ik_scale"].astype(F32) + p["ik_bias"].astype(F32)
             ).astype(x.dtype)
        w = mm(x, p["wiw"]).astype(F32) * (heads * d) ** -0.5
        return q, w, rotated(k)


def gate_heads(o, x, p, heads: int):
    """``o (..., H * v)`` with each head times its own sigmoid of ``x W_g
    (..., H)`` (float32 inside the sigmoid)."""
    g = jax.nn.sigmoid(mm(x, p["wgate"]).astype(F32)).astype(o.dtype)
    return (o.reshape(o.shape[:-1] + (heads, -1)) * g[..., None]).reshape(
        o.shape)


def _wkvb(p, c, dtype):
    w = p["wkvb"].astype(dtype).reshape(
        c.kv_lora_rank, c.num_attention_heads,
        c.qk_nope_head_dim + c.v_head_dim)
    return w[..., : c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def mla_prefill(x, p, c, lengths=None, *, window=None, indexer=False,
                gate=False, selection=None, handed=None):
    """Full causal attention over ``x (R, P, h)`` in the NON-absorbed form
    (keys and values expanded from the latent once); the core is
    ``ops/mla_prefill.py``: a flash kernel on the chip at the published
    head widths, blocks of query rows in XLA elsewhere.  ``lengths (R,)``:
    the real leading positions of each row (default all); the output at a
    pad position is finite and otherwise unspecified.  Returns ``(out (R,
    P, h), latent rows (R, P, latent))``.  The options are
    :class:`LatentBlock`'s: under a ``window`` the core is ``ops/gqa.py``'s
    windowed form over the expanded heads; with an ``indexer`` it is
    ``ops/dsa.py``'s — the selection in XLA, and under it the SAME core
    with the selection as its keep mask where the kernel applies, masked
    blocks in XLA elsewhere — and the rows are ``{"latent": .., "index":
    (R, P, index_head_dim)}``.  Under a ``selection`` the core is
    ``ops/gqa.py``'s over the JOINED heads under the keep mask (its kernel
    where the joined width and the values' are one lane multiple, GLM-5.2's
    256; PERF.md section 6, PR 60, has why not ``ops/mla_prefill.py``'s),
    the mask computed here (``"own"``; the rows as an indexer's) or
    ``handed`` over (``"borrow"``; the plain rows; ``None``: nothing is
    dropped), and a third result is the mask for the blocks that follow."""
    r, n, _ = x.shape
    with jax.named_scope("mla.prefill"):
        positions = jnp.broadcast_to(jnp.arange(n), (r, n))
        q_nope, q_rope, latent, c_q = mla_project(x, p, c, positions, True)
        wk, wv = _wkvb(p, c, x.dtype)
        c_kv, k_r = latent[..., : c.kv_lora_rank], latent[..., c.kv_lora_rank:]
        k_nope = jnp.einsum("rnl,lhd->rhnd", c_kv, wk)
        v = jnp.einsum("rnl,lhd->rhnd", c_kv, wv)
        rows = latent
        if selection is not None:
            if selection == "own":
                q_idx, w, k_idx = index_project(x, c_q, p, c, positions)
                handed = dsa.prefill_keep(q_idx, w, k_idx, c.index_topk)
                rows = {"latent": latent, "index": k_idx}
            with jax.named_scope("attn.sparse" if selection == "own"
                                 else "dsa.borrow"):
                q, k = dsa.joined_heads(q_nope, q_rope, k_nope, k_r)
                o = gqa.prefill_attention(q, k, v, q.shape[-1] ** -0.5,
                                          lengths=lengths, keep=handed)
        elif indexer:
            q_idx, w, k_idx = index_project(x, c_q, p, c, positions)
            o = dsa.sparse_prefill_attention(
                q_nope, q_rope, k_nope, k_r, v, q_idx, w, k_idx,
                c.index_topk, lengths)
            rows = {"latent": latent, "index": k_idx}
        elif window is not None:
            with jax.named_scope("attn.window"):
                q, k = dsa.joined_heads(q_nope, q_rope, k_nope, k_r)
                o = gqa.prefill_attention(q, k, v, q.shape[-1] ** -0.5,
                                          window, lengths)
        else:
            o = prefill_attention(q_nope, q_rope, k_nope, k_r, v, lengths)
        if gate:
            o = gate_heads(o, x, p, c.num_attention_heads)
        out = mm(o, p["wo"]), rows
        return out if selection is None else out + (handed,)


def mla_decode(x, pos, cache, p, c, *, window=None, indexer=False,
               gate=False, selection=None, handed=None):
    """One token per row in the ABSORBED form: ``x (S, h)`` at ``pos (S,)``
    against ``cache (S, T, latent)``, which gains the row's new entry at
    ``pos`` and is then attended up to it (``ops/mla_decode.py``: one kernel
    that reads each slot's rows once on the chip, three XLA ops over the
    whole cache elsewhere).  Returns ``(out (S, h), cache)``.  The options
    are :class:`LatentBlock`'s: under a ``window`` the cache is the ring
    and the core reads the rows the slot has in it; with an ``indexer`` the
    cache is ``{"latent", "index"}``, both written here, and the core reads
    the rows ``ops/dsa.py`` selects.  Under a ``selection`` the core reads
    the rows selected here (``"own"``: an indexer's cache) or ``handed``
    over (``"borrow"``: the plain leaf), and a third result is ``(rows,
    kept)`` for the blocks that follow."""
    s = x.shape[0]
    rank = c.kv_lora_rank
    scale = 1.0 / math.sqrt(c.qk_nope_head_dim + c.qk_rope_head_dim)
    indexer = indexer or selection == "own"
    with jax.named_scope("mla.decode"):
        q_nope, q_rope, row, c_q = mla_project(x[:, None], p, c,
                                               pos[:, None], True)
        index = None
        if indexer:
            cache, index = cache["latent"], cache["index"]
        at = pos if window is None else pos % cache.shape[1]
        cache = write_rows(cache, row[:, 0].astype(cache.dtype), at, axis=0)
        wk, wv = _wkvb(p, c, x.dtype)
        q_lat = jnp.einsum("shd,lhd->shl", q_nope[:, 0], wk)
        q_cat = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)
        if indexer:
            q_idx, w, k_idx = index_project(x[:, None], c_q, p, c,
                                            pos[:, None])
            index = write_rows(index, k_idx[:, 0].astype(index.dtype), pos,
                               axis=0)
            handed = dsa.select_rows(q_idx[:, 0], w[:, 0], index, pos + 1,
                                     c.index_topk)
            with jax.named_scope("attn.sparse"):
                o_lat = dsa.sparse_decode_attention(q_cat, cache, *handed,
                                                    rank, scale)
            cache = {"latent": cache, "index": index}
        elif selection == "borrow":
            with jax.named_scope("dsa.borrow"):
                o_lat = dsa.sparse_decode_attention(q_cat, cache, *handed,
                                                    rank, scale)
        elif window is not None:
            with jax.named_scope("attn.window"):
                o_lat = decode_attention(
                    q_cat, cache, jnp.minimum(pos + 1, cache.shape[1]), rank,
                    scale)
        else:
            o_lat = decode_attention(q_cat, cache, pos + 1, rank, scale)
        o = jnp.einsum("shl,lhd->shd", o_lat, wv).reshape(s, -1)
        if gate:
            o = gate_heads(o, x, p, c.num_attention_heads)
        out = mm(o, p["wo"]), cache
        return out if selection is None else out + (handed,)


# ------------------------------------------- the block, and the driver over it


def _grown(rows, max_len: int):
    """Per-token rows ``(R, P, w)`` as ``max_len`` rows a slot."""
    n = rows.shape[1]
    return rows[:, :max_len] if n >= max_len else jnp.pad(
        rows, ((0, 0), (0, max_len - n), (0, 0)))


class LatentBlock:
    """An attention block over latent rows of ``config``'s shape
    (``models/driver.py`` says what a block is); the module docstring has
    the four options."""

    def __init__(self, config, *, window: int | None = None,
                 indexer: bool = False, gate: bool = False,
                 selection: str | None = None):
        if selection not in (None, "own", "borrow"):
            raise ValueError(f"a selection is owned or borrowed, not "
                             f"{selection!r}")
        # an owner is a block with an indexer: its weights, its cache leaf
        indexer = indexer or selection == "own"
        if (window is not None and (indexer or selection)) or (
                indexer and selection == "borrow"):
            raise ValueError("an indexer selects among grown rows, not a "
                             "ring's, and a borrower has none")
        self.config = config
        self.window = window
        self.indexer = indexer
        self.gate = gate
        self.selection = selection

    @property
    def options(self) -> dict:
        out = {"window": self.window, "indexer": self.indexer,
               "gate": self.gate}
        return out if self.selection is None else {
            **out, "selection": self.selection}

    def rows(self, max_len: int) -> int:
        """Latent rows a slot's cache has in an engine of ``max_len``."""
        return max_len if self.window is None else min(self.window, max_len)

    def init_cache(self, slots: int, max_len: int, dtype):
        c = self.config
        latent = jnp.zeros((slots, self.rows(max_len), c.latent_width), dtype)
        if not self.indexer:
            return latent
        return {"latent": latent,
                "index": jnp.zeros((slots, max_len, c.index_head_dim), dtype)}

    def prefill(self, x, p, lengths, handed=None):
        """``handed``: under a ``selection`` alone, what the last owner
        handed on; it comes back as a third result."""
        return mla_prefill(x, p, self.config, lengths, **self.options,
                           handed=handed)

    def cache_rows(self, rows, lengths, max_len: int):
        if self.indexer:
            return {name: _grown(a, max_len) for name, a in rows.items()}
        if self.window is None:
            return _grown(rows, max_len)
        # a ring takes, for each of its rows ``j``, the LAST real token ``p
        # < length`` with ``p % size == j`` (``models/kv.py``'s rule)
        size = self.rows(max_len)
        j = jnp.arange(size)[None, :]
        last = lengths[:, None] - 1
        at = jnp.clip(j + size * ((last - j) // size), 0, rows.shape[1] - 1)
        return jnp.take_along_axis(rows, at[..., None], axis=1)

    def decode(self, x, pos, cache, p, handed=None):
        return mla_decode(x, pos, cache, p, self.config, **self.options,
                          handed=handed)


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

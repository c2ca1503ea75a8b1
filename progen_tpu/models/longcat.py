"""LongCat-Flash: latent attention (MLA) and a shortcut-connected expert
layer with zero-compute experts, as ONE CHIP'S SHARE of an expert-parallel
deployment.

Plain functions over a parameter dict (no flax): ``init_params`` makes the
weights on the device from a key, ``prefill`` runs R rows of P tokens and
returns logits plus each attention block's latent cache rows, and
``decode_step`` advances S rows by one token in the ABSORBED form, reading
the latent cache instead of per-head keys and values.  ``LongCatFamily`` at
the bottom is what ``ServingEngine`` calls (``decode/family.py``).

One layer (a "double layer")::

    a = x + MLA0(N0(x));  u = N1(a);  m = MoE(u);  b = a + FFN0(u)
    c = b + MLA1(N2(b));  out = c + FFN1(N3(c)) + m

**MLA.**  ``c_q = RMSNorm(x W_qa)``; ``q = (c_q W_qb) * sqrt(h /
q_lora_rank)`` (``mla_scale_q_lora``, applied to the projected query);
``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv) * sqrt(h /
kv_lora_rank)`` (``mla_scale_kv_lora``, applied to the normed latent, so
the CACHE holds the scaled latent); ``[k_nope | v] = c_kv W_kvb``.  RoPE
(half-split) on the rope part of q and on ``k_r``, which all heads share.
The cache row of a token is ``[c_kv | rope(k_r)]``, 576 numbers a block.
Absorbed decode: ``q_lat = q_nope W_kvb[k]^T`` per head, scores ``q_lat .
c_kv + q_rope . k_r``, ``o_lat = softmax . c_kv``, ``o = o_lat W_kvb[v]``.

**The expert layer as a share.**  The router is ``n_routed_experts +
zero_expert_num`` wide and picks ``moe_topk`` by ``p + b`` whatever the
chip holds; the layer adds the terms of the ``experts_held`` real experts
from ``first_expert`` on and of ALL identity experts (a token's identity
terms are computed where the token lives), and leaves out what the absent
experts would add.  Nothing stands in for absent chips.  Tokens are grouped
by held expert (a sort of the assignments) and multiplied by
``jax.lax.ragged_dot`` in windows of ``capacity`` assignments: a window
that overflows runs the loop again, so no assignment is ever dropped.

Precision: parameters and matrix products in the policy's dtypes
(bfloat16 as published); the router, every softmax, the norms' statistics
and the logits in float32.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy, make_policy
from progen_tpu.ops.mla_prefill import prefill_attention
from progen_tpu.ops.row_write import write_rows

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LongCatConfig:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights."""

    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_position_embeddings: int = 131072
    # the share: real experts ``first_expert .. first_expert + held - 1``
    experts_held: int = 512
    first_expert: int = 0
    # seeded weights (``init_params``): standard deviations and gains
    router_logit_std: float = 1.0
    router_bias_std: float = 3e-4
    attn_qk_gain: float = 0.5
    residual_gain: float = 0.5
    # the engine pads primes to ``prefill_bucket * 2^k`` tokens
    prefill_bucket: int = 512

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def seq_len(self) -> int:
        return self.max_position_embeddings

    @classmethod
    def from_dict(cls, d) -> "LongCatConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def __post_init__(self):
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} real experts")


def bf16_policy() -> Policy:
    """Parameters stored in bfloat16, as the source publishes them."""
    return make_policy(True, param_dtype=jnp.bfloat16)


# ------------------------------------------------------------------ weights


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def _init_attn(key, c: LongCatConfig, dt):
    h, heads = c.hidden_size, c.num_attention_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    return {
        "wqa": _normal(ks[0], (h, c.q_lora_rank), h ** -0.5, dt),
        "q_norm": _normal(ks[1], (c.q_lora_rank,), 0.05, F32).astype(dt) + 1,
        "wqb": _normal(ks[2], (c.q_lora_rank, heads * qk),
                       c.attn_qk_gain * c.q_lora_rank ** -0.5, dt),
        "wkva": _normal(ks[3], (h, c.latent_width), h ** -0.5, dt),
        "kv_norm": _normal(ks[4], (c.kv_lora_rank,), 0.05, F32).astype(dt) + 1,
        "wkvb": _normal(
            ks[5], (c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim)),
            c.attn_qk_gain * c.kv_lora_rank ** -0.5, dt),
        "wo": _normal(ks[6], (heads * c.v_head_dim, h),
                      c.residual_gain * (heads * c.v_head_dim) ** -0.5, dt),
    }


def _init_ffn(key, h, width, gain, dt, lead=()):
    ks = jax.random.split(key, 3)
    return {
        "wg": _normal(ks[0], lead + (h, width), h ** -0.5, dt),
        "wu": _normal(ks[1], lead + (h, width), h ** -0.5, dt),
        "wd": _normal(ks[2], lead + (width, h), gain * width ** -0.5, dt),
    }


def _init_layer(key, c: LongCatConfig, dt):
    ks = jax.random.split(key, 8)
    h = c.hidden_size
    return {
        "norm": _normal(ks[0], (4, h), 0.05, F32).astype(dt) + 1,
        "attn": [_init_attn(ks[1], c, dt), _init_attn(ks[2], c, dt)],
        "ffn": [_init_ffn(ks[3], h, c.ffn_hidden_size, c.residual_gain, dt),
                _init_ffn(ks[4], h, c.ffn_hidden_size, c.residual_gain, dt)],
        "router": {
            # logits spread by ``router_logit_std`` per token (the normed
            # input has unit RMS): with a 0.02 normal all 768 would be all
            # but equal and the bias alone would choose
            "w": _normal(ks[5], (h, c.router_width),
                         c.router_logit_std * h ** -0.5, dt),
            "bias": _normal(ks[6], (c.router_width,), c.router_bias_std, F32),
        },
        "experts": _init_ffn(ks[7], h, c.expert_ffn_hidden_size, 1.0, dt,
                             lead=(c.experts_held,)),
    }


def init_params(config: LongCatConfig, key, policy: Policy | None = None):
    """Seeded weights, made on the device one layer per program so that no
    more than a layer's random bits are live beside the weights."""
    c = config
    dt = (policy or bf16_policy()).param_dtype
    keys = jax.random.split(key, c.num_layers + 3)
    layer = jax.jit(partial(_init_layer, c=c, dt=dt))
    h = c.hidden_size
    return {
        "embed": jax.jit(partial(_normal, shape=(c.vocab_size, h), std=1.0,
                                 dtype=dt))(keys[0]),
        "head": jax.jit(partial(_normal, shape=(h, c.vocab_size),
                                std=h ** -0.5, dtype=dt))(keys[1]),
        "final_norm": jax.jit(lambda k: _normal(
            k, (h,), 0.05, F32).astype(dt) + 1)(keys[2]),
        "layers": [layer(keys[3 + i]) for i in range(c.num_layers)],
    }


# ------------------------------------------------------------------- pieces


def rms_norm(x, scale, eps):
    """Statistics in float32, the result in ``x``'s dtype."""
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(
        x.dtype)


def _rope(x, positions, theta):
    """Half-split rotation of ``x (..., n, [heads,] d)`` at ``positions
    (..., n)``; tables in float32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[..., None] * inv
    if x.ndim == ang.ndim + 1:          # a heads axis between n and d
        ang = ang[..., None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : d // 2].astype(F32), x[..., d // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _mm(x, w):
    return jnp.dot(x, w.astype(x.dtype))


def _mla_project(x, p, c: LongCatConfig, positions):
    """``x (..., n, h)`` at ``positions (..., n)`` -> ``q_nope (..., n, H,
    nope)``, rotated ``q_rope (..., n, H, rope)`` and the cache row
    ``[c_kv | rope(k_r)] (..., n, latent)``."""
    h, heads = c.hidden_size, c.num_attention_heads
    nope, rot = c.qk_nope_head_dim, c.qk_rope_head_dim
    c_q = rms_norm(_mm(x, p["wqa"]), p["q_norm"], c.rms_norm_eps)
    q = _mm(c_q, p["wqb"])
    if c.mla_scale_q_lora:
        q = q * jnp.asarray(math.sqrt(h / c.q_lora_rank), q.dtype)
    q = q.reshape(q.shape[:-1] + (heads, nope + rot))
    kva = _mm(x, p["wkva"])
    c_kv = rms_norm(kva[..., : c.kv_lora_rank], p["kv_norm"], c.rms_norm_eps)
    if c.mla_scale_kv_lora:
        c_kv = c_kv * jnp.asarray(math.sqrt(h / c.kv_lora_rank), c_kv.dtype)
    k_r = _rope(kva[..., c.kv_lora_rank:], positions, c.rope_theta)
    q_rope = _rope(q[..., nope:], positions, c.rope_theta)
    return q[..., :nope], q_rope, jnp.concatenate([c_kv, k_r], axis=-1)


def _wkvb(p, c: LongCatConfig, dtype):
    w = p["wkvb"].astype(dtype).reshape(
        c.kv_lora_rank, c.num_attention_heads,
        c.qk_nope_head_dim + c.v_head_dim)
    return w[..., : c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def mla_prefill(x, p, c: LongCatConfig, lengths=None):
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
        q_nope, q_rope, latent = _mla_project(x, p, c, positions)
        wk, wv = _wkvb(p, c, x.dtype)
        c_kv, k_r = latent[..., : c.kv_lora_rank], latent[..., c.kv_lora_rank:]
        k_nope = jnp.einsum("rnl,lhd->rhnd", c_kv, wk)
        v = jnp.einsum("rnl,lhd->rhnd", c_kv, wv)
        o = prefill_attention(q_nope, q_rope, k_nope, k_r, v, lengths)
        return _mm(o, p["wo"]), latent


def mla_decode(x, pos, cache, p, c: LongCatConfig):
    """One token per row in the ABSORBED form: ``x (S, h)`` at ``pos (S,)``
    against ``cache (S, T, latent)``, which gains the row's new entry at
    ``pos``.  Returns ``(out (S, h), cache)``."""
    s = x.shape[0]
    rank = c.kv_lora_rank
    with jax.named_scope("mla.decode"):
        q_nope, q_rope, row = _mla_project(x[:, None], p, c, pos[:, None])
        cache = write_rows(cache, row[:, 0].astype(cache.dtype), pos, axis=0)
        wk, wv = _wkvb(p, c, x.dtype)
        q_lat = jnp.einsum("shd,lhd->shl", q_nope[:, 0], wk)
        q_cat = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)
        scale = 1.0 / math.sqrt(c.qk_nope_head_dim + c.qk_rope_head_dim)
        logits = jnp.einsum("shl,stl->sht", q_cat, cache.astype(x.dtype),
                            preferred_element_type=F32) * scale
        seen = jnp.arange(cache.shape[1])[None, :] <= pos[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None], logits, -jnp.inf), axis=-1)
        o_lat = jnp.einsum("sht,stl->shl", probs.astype(x.dtype),
                           cache[..., :rank].astype(x.dtype))
        o = jnp.einsum("shl,lhd->shd", o_lat, wv)
        return _mm(o.reshape(s, -1), p["wo"]), cache


def swiglu(x, p):
    with jax.named_scope("ffn.dense"):
        return _mm(jax.nn.silu(_mm(x, p["wg"])) * _mm(x, p["wu"]), p["wd"])


def route(u, router, c: LongCatConfig):
    """``(ids (T, k), weights (T, k))``, float32 throughout: softmax over
    the whole router, the ``moe_topk`` largest of ``p + b``, weighted by
    ``routed_scaling_factor * p`` (not renormalised)."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(u.astype(F32), router["w"].astype(F32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        _, ids = jax.lax.top_k(probs + router["bias"].astype(F32), c.moe_topk)
        w = jnp.take_along_axis(probs, ids, axis=-1)
        return ids, w * c.routed_scaling_factor


def moe_capacity(c: LongCatConfig, tokens: int) -> int:
    """Assignments per window of the grouped product.  A token sends a held
    expert ``moe_topk * held / router_width`` assignments on average (0.25
    at 16 of 768): half the tokens, at least 128, never more than there
    are assignments."""
    return min(tokens * c.moe_topk, max(128, -(-tokens // 256) * 128))


def held_experts(u, ids, w, live, experts, c: LongCatConfig, capacity=None):
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


def moe_share(u, layer, c: LongCatConfig, live):
    """This chip's share of the expert layer over ``u (T, h)`` and what it
    counted over the ``live`` tokens."""
    ids, w = route(u, layer["router"], c)
    w_identity = jnp.sum(jnp.where(ids >= c.n_routed_experts, w, 0.0), -1)
    y, load = held_experts(u, ids, w, live, layer["experts"], c)
    y = y + w_identity[:, None] * u.astype(F32)
    real = jnp.sum((ids < c.n_routed_experts) & live[:, None])
    stats = {"moe.tokens": jnp.sum(live).astype(F32),
             "moe.real_chosen": real.astype(F32),
             "moe.held_load": load.astype(F32)}
    return y.astype(u.dtype), ids, stats


STAT_KEYS = ("moe.tokens", "moe.real_chosen", "moe.held_load",
             "moe.prefill_held", "moe.decode_layers", "moe.experts_touched",
             "mla.decode_rows", "mla.context_tokens")


def zero_stats(c: LongCatConfig) -> dict:
    """Device-side counters, all float32 sums (docs/OBSERVABILITY.md §3)."""
    out = {k: jnp.zeros((), F32) for k in STAT_KEYS}
    out["moe.held_load"] = jnp.zeros((c.experts_held,), F32)
    return out


def _add(a: dict, b: dict) -> dict:
    return {k: a[k] + b[k] if k in b else a[k] for k in a}


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens; ``attend(x, layer index,
    block index, weights)`` is the one thing prefill and decode differ in.
    Returns ``(x, stats, chosen ids per layer, held experts touched)``."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    for i, layer in enumerate(params["layers"]):
        n, eps = layer["norm"], c.rms_norm_eps
        a = x + attend(rms_norm(x, n[0], eps), i, 0, layer["attn"][0])
        u = rms_norm(a, n[1], eps)
        m, ids, s = moe_share(u, layer, c, live)
        stats = _add(stats, s)
        touched += jnp.sum(s["moe.held_load"] > 0).astype(F32)
        chosen.append(ids)
        b = a + swiglu(u, layer["ffn"][0])
        cc = b + attend(rms_norm(b, n[2], eps), i, 1, layer["attn"][1])
        x = cc + swiglu(rms_norm(cc, n[3], eps), layer["ffn"][1]) + m
    return x, stats, chosen, touched


def _logits(x, params, c):
    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    return jnp.dot(x, params["head"].astype(x.dtype),
                   preferred_element_type=F32)


def cache_names(c: LongCatConfig) -> list[str]:
    return [f"l{i}a{j}" for i in range(c.num_layers) for j in range(2)]


def init_caches(c: LongCatConfig, rows: int, max_len: int, dtype) -> dict:
    return {name: jnp.zeros((rows, max_len, c.latent_width), dtype)
            for name in cache_names(c)}


def prefill(params, tokens, lengths, config: LongCatConfig,
            policy: Policy | None = None, *, logit_positions=None,
            with_choices: bool = False):
    """``tokens (R, P)`` right-padded rows of ``lengths (R,)`` real tokens
    -> ``(logits (R, K, V) float32 at logit_positions (R, K)`` (default the
    last real position, K = 1), ``latent rows {block: (R, P, latent)},
    stats)``.  Padding, and the whole of a row of length 0 (an admission
    row that carries no request), is computed by the dense FFNs (the shapes
    are static) but not by the experts, and by attention only where the
    blocked XLA form runs (``ops/mla_prefill.py``: the kernel visits no
    tile past a row's length); it is not counted, and no real position's
    output depends on what it holds."""
    c = config
    dt = (policy or bf16_policy()).compute_dtype
    r, n = tokens.shape
    live = (jnp.arange(n)[None, :] < lengths[:, None]).reshape(-1)
    rows = {}

    def attend(x, i, j, p):
        out, latent = mla_prefill(x.reshape(r, n, -1), p, c, lengths)
        rows[f"l{i}a{j}"] = latent
        return out.reshape(r * n, -1)

    x = params["embed"][tokens.reshape(-1)].astype(dt)
    x, stats, chosen, _ = _layers(x, params, c, attend, live)
    stats["moe.prefill_held"] = jnp.sum(stats["moe.held_load"])
    if logit_positions is None:       # a row of no tokens reads position 0
        logit_positions = jnp.maximum(lengths - 1, 0)[:, None]
    x = jnp.take_along_axis(x.reshape(r, n, -1),
                            logit_positions[..., None], axis=1)
    out = _logits(x, params, c), rows, stats
    if with_choices:
        return out + (jnp.stack(chosen).reshape(c.num_layers, r, n, -1),)
    return out


def decode_step(params, tok, pos, caches, live, config: LongCatConfig,
                policy: Policy | None = None, *, with_choices: bool = False):
    """One token per row: ``tok (S,)`` at ``pos (S,)`` -> ``(logits (S, V)
    float32, caches, stats)``.  Rows that are not ``live`` run (the batch
    is static) but are not counted and reach no expert."""
    c = config
    dt = (policy or bf16_policy()).compute_dtype
    caches = dict(caches)

    def attend(x, i, j, p):
        name = f"l{i}a{j}"
        out, caches[name] = mla_decode(x, pos, caches[name], p, c)
        return out

    x = params["embed"][tok].astype(dt)
    x, stats, chosen, touched = _layers(x, params, c, attend, live)
    stats["moe.decode_layers"] = jnp.asarray(
        c.num_layers, F32) * jnp.any(live)
    stats["moe.experts_touched"] = touched
    stats["mla.decode_rows"] = jnp.sum(live).astype(F32)
    stats["mla.context_tokens"] = jnp.sum(
        jnp.where(live, pos + 1, 0)).astype(F32)
    out = _logits(x, params, c), caches, stats
    if with_choices:
        return out + (jnp.stack(chosen),)
    return out


# ------------------------------------------------------- the engine's seam


class LongCatFamily:
    """What ``ServingEngine``'s plain dense path calls
    (``decode/family.py``).  The cache is a second kind beside ProGen's
    rings: per attention block a latent row per token, ``max_len`` long."""

    name = "longcat"
    position_masks = False      # the state holds an (S, V) mask, not (S, L, V)
    idle_length = 0             # a row without a request has no token
    modes = frozenset()         # the plain dense path only
    step_model = prefill_model = None

    def __init__(self, config: LongCatConfig, policy: Policy):
        self.config = config
        self.policy = policy
        self.bucket_base = config.prefill_bucket
        self.vocab = config.vocab_size
        self.seq_len = config.seq_len

    def embedder(self, mesh=None, strategies=()):
        return None

    def init_caches(self, slots: int, max_len: int):
        return init_caches(self.config, slots, max_len,
                           self.policy.compute_dtype)

    def init_stats(self) -> dict:
        return zero_stats(self.config)

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
        logits, rows, stats = prefill(params, tokens, lengths, self.config,
                                      self.policy)
        n = tokens.shape[1]
        caches = {k: v[:, :max_len] if n >= max_len else jnp.pad(
            v, ((0, 0), (0, max_len - n), (0, 0))) for k, v in rows.items()}
        return logits[:, 0], caches, stats

    def decode_step(self, params, tok, pos, caches, live, adapters=None,
                    tenant=None):
        return decode_step(params, tok, pos, caches, live, self.config,
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

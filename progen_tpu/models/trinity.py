"""Trinity (``model_type`` ``afmoe``): gated grouped-query attention with
RMSNorm on q and k, sliding-window blocks and full-attention blocks mixed in
one model, a leading dense layer, then expert layers with sigmoid top-k
routing beside a shared expert, as ONE CHIP'S SHARE of an expert-parallel
deployment.

What Trinity alone has: its config, the two kinds of attention block and
what each states about its cache, the sigmoid router with its selection
bias, the four-norm layer's wiring and the seeded weights' layout.  The
model driver and the engine's seam are ``models/driver.py``; the held
experts' grouped product, its window and the ``moe.*`` counters are
``models/experts.py`` — both shared with ``models/longcat.py`` and
``models/deepseek_v2.py``.  The attention cores are ``ops/gqa.py``.

Layer ``l`` (sandwich norms, ``N_*`` RMSNorms with a learned scale)::

    a = x + N_post_attn(Attn_l(N_in(x)))
    out = a + N_post_mlp(F_l(N_pre_mlp(a)))

``x = E[token] * sqrt(hidden)`` where ``mup_enabled``.  ``F_l`` is a dense
SwiGLU of width ``intermediate_size`` for ``l < num_dense_layers`` and the
expert layer after: ``F(u) = S(u) + sum_i w_i E_i(u)``, ``S`` one SwiGLU of
width ``num_shared_experts * moe_intermediate_size`` on every token.

**Attention.**  ``q = u W_q`` (H heads of d), ``k = u W_k``, ``v = u W_v``
(KV heads of d), ``g = sigmoid(u W_gate)`` (H x d); q and k RMS-normed per
head over d.  A ``sliding_attention`` block rotates q and k (half-split
RoPE on all d) and token ``i`` sees ``j`` iff ``0 <= i - j <
sliding_window``; a ``full_attention`` block has NO rotary embedding and
sees ``j <= i``.  Scores scaled by ``d^-1/2``, softmax in float32, each
key/value head serves ``H / KV`` query heads; result ``((softmax . v) * g)
W_o``.

**Two kinds of cache in one slot** (``models/kv.py:KVBlock``, shared with
``models/granite_hybrid.py``, under this family's projection;
``models/driver.py`` says what a block is).  Both hold ``{"k", "v"}:
(slots, KV, rows, d)`` — the token axis second to last, so that the chip
tiles ``(rows, d)`` and the step's write is ``ops/row_write.py``'s kernel —
and both are read by one decode core (``ops/gqa.py:decode_attention``) up
to a per-slot count.  They differ in two lines:

* a sliding block's cache is a RING of ``min(sliding_window, max_len)``
  rows: the token at position ``p`` lies in row ``p % rows`` and a slot at
  ``pos`` has ``min(pos + 1, rows)`` of them (rotated keys are cached, so
  their order in the ring does not matter);
* a full block's cache GROWS with the request: ``max_len`` rows, the token
  at ``p`` in row ``p``, ``pos + 1`` of them.

**The router**, float32: ``s = sigmoid(u W_r)`` over ``num_experts``; the
``num_experts_per_tok`` largest of ``s + b`` are chosen (``b`` the
balancing bias, a buffer: it picks and does not weigh); ``w_i = route_scale
* s_i / (sum_chosen s + 1e-20)`` (``route_norm``).  ``n_group = topk_group
= 1``: no group limit.

**The share.**  As the sibling families': the router keeps its width and
top-k whatever is held; the layer adds the terms of the held experts and
leaves out the absent ones' (BEFORE ``N_post_mlp``, which therefore norms
this chip's partial sum: a deployment norms the sum of all shares);
attention, the dense layer and the shared expert are whole on every chip.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.models import driver, experts, kv
from progen_tpu.models.driver import (  # noqa: F401
    F32,
    bf16_policy,
    mm,
    residual,
    rms_norm,
    stack_norm,
    swiglu,
)
from progen_tpu.models.experts import held_experts, kernel_counters

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    # one of SLIDING / FULL a layer; empty: every
    # ``global_attn_every_n_layers``-th layer full, the others sliding
    layer_types: tuple = ()
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 131072
    # the share: experts ``first_expert .. first_expert + held - 1``
    experts_held: int = 128
    first_expert: int = 0
    # seeded weights (``init_params``): the router logits' spread a token,
    # and the selection bias's (in units of a score: it moves some choices)
    router_logit_std: float = 1.0
    router_bias_std: float = 0.02
    # the engine pads primes to ``prefill_bucket * 2^k`` tokens
    prefill_bucket: int = 512

    # what the shared code reads under its own names
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def moe_topk(self) -> int:
        return self.num_experts_per_tok

    @property
    def router_width(self) -> int:
        return self.num_experts

    @property
    def seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def embed_gain(self) -> float:
        return math.sqrt(self.hidden_size) if self.mup_enabled else 1.0

    def rope_inv_freq(self, d: int):
        return 1.0 / (self.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d))

    @classmethod
    def from_dict(cls, d) -> "TrinityConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        if "layer_types" in d:
            d["layer_types"] = tuple(d["layer_types"])
        return cls(**d)

    def __post_init__(self):
        if not self.layer_types:
            every = self.global_attn_every_n_layers
            object.__setattr__(self, "layer_types", tuple(
                FULL if (i + 1) % every == 0 else SLIDING
                for i in range(self.num_hidden_layers)))
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {SLIDING, FULL}):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {SLIDING!r} or {FULL!r}: {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split over "
                f"{self.num_key_value_heads} key/value heads")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.num_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.num_experts} routed experts")
        if self.score_func != "sigmoid" or self.n_group != 1 \
                or self.topk_group != 1:
            raise ValueError(
                "the router is sigmoid top-k with no group limit: "
                f"score_func {self.score_func!r}, n_group {self.n_group}, "
                f"topk_group {self.topk_group} are not supported")


# ------------------------------------------------------------------ weights


def _init_attn(key, c: TrinityConfig, dt):
    h, d = c.hidden_size, c.head_dim
    q, kv = c.num_attention_heads * d, c.num_key_value_heads * d
    ks = jax.random.split(key, 7)
    return {
        "wq": driver.normal(ks[0], (h, q), h ** -0.5, dt),
        "wk": driver.normal(ks[1], (h, kv), h ** -0.5, dt),
        "wv": driver.normal(ks[2], (h, kv), h ** -0.5, dt),
        "wgate": driver.normal(ks[3], (h, q), h ** -0.5, dt),
        "wo": driver.normal(ks[4], (q, h), q ** -0.5, dt),
        "q_norm": driver.init_norm(ks[5], (d,), dt),
        "k_norm": driver.init_norm(ks[6], (d,), dt),
    }


def _init_layer(key, c: TrinityConfig, dt, dense: bool):
    ks = jax.random.split(key, 7)
    h = c.hidden_size
    layer = {"norm": driver.init_norm(ks[0], (4, h), dt),
             "attn": _init_attn(ks[1], c, dt)}
    if dense:
        layer["ffn"] = driver.init_ffn(ks[2], h, c.intermediate_size, 1.0, dt)
        return layer
    # logits spread by ``router_logit_std`` per token (the normed input has
    # unit RMS), so choices differ between tokens; the bias is a float32
    # buffer, as the release keeps it
    layer["router"] = {
        "w": driver.normal(ks[3], (h, c.num_experts),
                           c.router_logit_std * h ** -0.5, dt),
        "bias": driver.normal(ks[4], (c.num_experts,), c.router_bias_std,
                              F32)}
    layer["experts"] = driver.init_ffn(ks[5], h, c.moe_intermediate_size,
                                       1.0, dt, lead=(c.experts_held,))
    layer["shared"] = driver.init_ffn(
        ks[6], h, c.num_shared_experts * c.moe_intermediate_size, 1.0, dt)
    return layer


def init_params(config: TrinityConfig, key, policy: Policy | None = None):
    policy = policy or bf16_policy()
    layer = {dense: jax.jit(partial(_init_layer, c=config,
                                    dt=policy.param_dtype, dense=dense))
             for dense in (True, False)}
    return driver.init_params(
        config, key, policy,
        lambda k, i: layer[i < config.num_dense_layers](k))


# ---------------------------------------------------------------- attention


def project(x, p, c: TrinityConfig, positions, rotary: bool):
    """``x (..., n, h)`` at ``positions (..., n)`` -> ``q (..., n, H, d)``,
    ``k, v (..., n, KV, d)`` (q and k normed per head, rotated where
    ``rotary``) and the gate ``(..., n, H * d)``."""
    d = c.head_dim
    with jax.named_scope("attn.project"):
        q = mm(x, p["wq"])
        q = q.reshape(q.shape[:-1] + (c.num_attention_heads, d))
        k = mm(x, p["wk"])
        k = k.reshape(k.shape[:-1] + (c.num_key_value_heads, d))
        v = mm(x, p["wv"]).reshape(k.shape)
        q = rms_norm(q, p["q_norm"], c.rms_norm_eps)
        k = rms_norm(k, p["k_norm"], c.rms_norm_eps)
        if rotary:
            q = driver.rope(q, positions, c.rope_inv_freq)
            k = driver.rope(k, positions, c.rope_inv_freq)
    with jax.named_scope("attn.gate"):
        gate = jax.nn.sigmoid(mm(x, p["wgate"]))
    return q, k, v, gate


class KVBlock(kv.KVBlock):
    """One of Trinity's attention blocks (``models/kv.py`` has the cache's
    two layouts and the step): gated, q and k normed per head, rotated in a
    sliding block (``window`` rows in a ring) and not in a full one
    (``window`` None)."""

    def __init__(self, config: TrinityConfig, window: int | None):
        super().__init__(config.num_key_value_heads, config.head_dim,
                         1.0 / math.sqrt(config.head_dim), window)
        self.config = config

    def project(self, x, p, positions):
        return project(x, p, self.config, positions,
                       rotary=self.window is not None)

    def finish(self, o, gate, p):
        return mm(o * gate, p["wo"])


def blocks_of(c: TrinityConfig) -> dict:
    kinds = {SLIDING: KVBlock(c, c.sliding_window), FULL: KVBlock(c, None)}
    return {f"l{i}": kinds[kind] for i, kind in enumerate(c.layer_types)}


ATTN_STAT_KEYS = kv.DECODE_STAT_KEYS + kv.PREFILL_STAT_KEYS
attention_stats = kv.decode_stats
prefill_attention_stats = kv.prefill_stats


# ------------------------------------------------------------------ experts


def route(u, router, c: TrinityConfig):
    """``(ids (T, k), weights (T, k))``, float32 throughout: the largest
    ``s + b`` are chosen, the weights come from ``s`` alone
    (``models/experts.py:sigmoid_route``, shared with ``models/lfm2.py``,
    at Trinity's ``1e-20``)."""
    return experts.sigmoid_route(
        u, router, c.num_experts_per_tok, norm=c.route_norm,
        scale=c.route_scale, eps=1e-20)


def moe_share(u, layer, c: TrinityConfig, live):
    """This chip's share of the ROUTED experts over ``u (T, h)`` (the
    shared expert is the caller's: every chip computes it alike) and what
    it counted over the ``live`` tokens."""
    ids, w = route(u, layer["router"], c)
    y, load = held_experts(u, ids, w, live, layer["experts"], c)
    stats = {"moe.tokens": jnp.sum(live).astype(F32),
             "moe.held_load": load.astype(F32),
             **kernel_counters(u, layer["experts"], load, c)}
    with jax.named_scope("moe.experts"):    # the terms' own rounding
        y = y.astype(u.dtype)
    return y, ids, stats


STAT_KEYS = experts.STAT_KEYS + ATTN_STAT_KEYS


def zero_stats(c: TrinityConfig) -> dict:
    """Device-side counters, all float32 sums (docs/OBSERVABILITY.md §3)."""
    return experts.zero_stats(STAT_KEYS, c.experts_held)


# -------------------------------------------------------------------- model


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``driver.prefill`` says
    what the driver asks of it)."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    for i, layer in enumerate(params["layers"]):
        n, eps = layer["norm"], c.rms_norm_eps
        attn = attend(stack_norm(x, n[0], eps), f"l{i}", layer["attn"])
        a = residual(x, stack_norm(attn, n[1], eps))
        u = stack_norm(a, n[2], eps)
        if "experts" not in layer:
            x = residual(a, stack_norm(swiglu(u, layer["ffn"]), n[3], eps))
            continue
        m, ids, s = moe_share(u, layer, c, live)
        stats = experts.add_stats(stats, s)
        touched += jnp.sum(s["moe.held_load"] > 0).astype(F32)
        chosen.append(ids)
        f = residual(m, swiglu(u, layer["shared"], scope="moe.shared"))
        x = residual(a, stack_norm(f, n[3], eps))
    return x, stats, chosen, touched


def prefill(params, tokens, lengths, config: TrinityConfig,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over Trinity's stack and blocks: the per-token
    cache rows are ``{block: {"k", "v"}: (R, KV, P, d)}``; the stats gain
    the attention cores' pair counters."""
    policy = policy or bf16_policy()
    blocks = blocks_of(config)
    out = driver.prefill(_layers, blocks, params, tokens, lengths, config,
                         policy, **kwargs)
    out[2].update(prefill_attention_stats(
        blocks, tokens.shape[1], lengths, policy.compute_dtype))
    return out


def caches_from(rows, lengths, config: TrinityConfig, max_len: int):
    """The per-token rows :func:`prefill` returned, as the caches of R
    slots in an engine of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def decode_step(params, tok, pos, caches, live, config: TrinityConfig,
                policy: Policy | None = None, **kwargs):
    """``driver.decode_step`` over Trinity's stack and blocks."""
    blocks = blocks_of(config)
    return driver.decode_step(
        _layers, blocks,
        lambda dt, caches, pos, live: attention_stats(blocks, caches, pos,
                                                      live),
        params, tok, pos, caches, live, config, policy or bf16_policy(),
        **kwargs)


class TrinityFamily(driver.Family):
    name = "trinity"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    blocks_of = staticmethod(blocks_of)

    def attention_stats(self, dt, caches, pos, live):
        return attention_stats(self.blocks, caches, pos, live)

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, rows, stats = prefill(params, tokens, lengths, self.config,
                                      self.policy)
        return logits[:, 0], caches_from(rows, lengths, self.config,
                                         max_len), stats

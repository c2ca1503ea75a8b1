"""Qwen3-Next (``model_type`` ``qwen3_next``): three gated delta-rule layers
— a float32 state a slot and no keys — to one gated full-attention layer,
every layer with an expert layer (softmax top-10 of 512 beside a shared
expert that a sigmoid of the token scales), as ONE CHIP'S SHARE of an
expert-parallel deployment (``experts_held`` of ``num_experts`` from
``first_expert`` on), a token a row a step.

What Qwen3-Next alone has: its config, the layer kinds, the attention
block's projection (the gate that the query projection itself emits, the
zero-centred QK norms, the rotation over a quarter of a head), the gated
shared expert and the seeded weights' layout.  The model driver and the
engine's seam are ``models/driver.py``; the delta-rule mixer and what it
states about its cache are ``models/state.py:DeltaBlock`` (its recurrence
``ops/gdn.py``, its convolution ``ops/ssd.py``'s); the grown-key cache and
its decode step are ``models/kv.py``; the held experts' product, its
counters and the softmax router are ``models/experts.py`` (the router is
SDAR's at another width: ``experts.softmax_route``).

``x0 = E[token]``, ``eps = rms_norm_eps``, ``N_w(x) = x rsqrt(mean(x^2) +
eps) (1 + w)`` (a ZERO-CENTRED weight; statistics float32).  Layer ``i``::

    x <- x + Mixer_i(N(x)),   x <- x + MoE(N(x))

``Mixer_i`` is full attention where ``(i + 1) % full_attention_interval ==
0`` and the gated delta rule otherwise (``models/state.py:DeltaBlock`` has
its equations: 16 key heads feeding 32 value heads of 128, four taps, l2
norms on q and k, a gated norm whose gate comes AFTER the norm).  ``logits =
N_f(x) W_head`` (untied).

**Full attention** (``H`` query heads, ``KV`` key/value heads, ``d =
head_dim``, causal, grown keys)::

    [q_j | gate_j] (2 d a head) = u W_q,   k, v = u W_k, u W_v   (no bias)
    q <- N_wq(q), k <- N_wk(k) over d;  rotate the FIRST partial_rotary_factor
    * d columns of q and k (half-split pairs, rope_theta), the rest pass
    o = softmax(q k^T d^-1/2) v,   out = [o * sigmoid(gate)] W_o

**Expert layer**: ``p = softmax(u W_r)`` over all ``num_experts``
(float32), the ``num_experts_per_tok`` largest, renormalised to sum 1
(``norm_topk_prob``); expert ``e``: ``(silu(u W_g) * u W_u) W_d``; plus
``sigmoid(u . w_s) * shared(u)``, ``shared`` the same form
``shared_expert_intermediate_size`` wide and ``w_s (h,)``.

**The share.**  The router keeps its width and top-k whatever is held; the
layer adds the terms of the held experts; the gated shared expert is every
chip's alike and counted ONCE over the chips that share a layer.

The multi-token-prediction module beside the stack is not served (the
config has no key for it).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.models import driver, experts, kv, state
from progen_tpu.models.driver import (  # noqa: F401
    F32,
    bf16_policy,
    mm,
    residual,
    swiglu,
)
from progen_tpu.models.experts import held_experts, kernel_counters

DELTA, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5120       # no layer is dense: unused
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # the gated delta rule
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # full attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rope_scaling: None = None
    use_sliding_window: bool = False
    # the expert layer
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    # the share: experts ``first_expert .. first_expert + held - 1``
    experts_held: int = 512
    first_expert: int = 0
    # the chunked delta rule's chunk (a size of the block, no published key)
    chunk: int = 64
    # seeded weights (``init_params``): the router logits' spread a token,
    # and the ranges the per-head ``softplus(dt_bias)`` and ``A`` are drawn
    # from (log-uniform): where ``a`` is 0 a step's decay is ``exp(-A
    # softplus(dt_bias))``, 0.999 to 0.5 over the heads
    router_logit_std: float = 1.0
    dt_range: tuple = (0.01, 0.2)
    a_range: tuple = (0.1, 3.5)
    # the engine pads primes to ``prefill_bucket * 2^k`` tokens
    prefill_bucket: int = 512

    embed_gain = 1.0

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
    def layer_types(self) -> tuple:
        return tuple(
            FULL if (i + 1) % self.full_attention_interval == 0 else DELTA
            for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def rope_inv_freq(self, d: int):
        return 1.0 / (self.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d))

    @classmethod
    def from_dict(cls, d) -> "Qwen3NextConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        for key in ("dt_range", "a_range", "mlp_only_layers"):
            if key in d:
                d[key] = tuple(d[key])
        return cls(**d)

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split over "
                f"{self.num_key_value_heads} key/value heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"{self.linear_num_value_heads} value heads do not split "
                f"over {self.linear_num_key_heads} key heads")
        if (self.rotary_dim != self.head_dim * self.partial_rotary_factor
                or self.rotary_dim % 2
                or not 0 < self.rotary_dim <= self.head_dim):
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of a "
                f"head of {self.head_dim} is not a whole number of pairs")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.num_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.num_experts} routed experts")
        unsupported = {
            "decoder_sparse_step": 1, "mlp_only_layers": (),
            "hidden_act": "silu", "rope_scaling": None,
            "use_sliding_window": False, "tie_word_embeddings": False}
        for key, only in unsupported.items():
            if getattr(self, key) != only:
                raise ValueError(
                    f"{key} {getattr(self, key)!r} is not supported: the "
                    f"served model has {only!r}")


# ------------------------------------------------------------------ weights


def _zero_centred(key, shape, dt):
    """A norm's weight ``w`` of ``N_w``: the scale is ``1 + w``."""
    return driver.normal(key, shape, 0.05, F32).astype(dt)


def _init_attn(key, c: Qwen3NextConfig, dt):
    h, d = c.hidden_size, c.head_dim
    q, kvw = c.num_attention_heads * d, c.num_key_value_heads * d
    ks = jax.random.split(key, 6)
    return {
        # a head's 2 d columns are its query, then its gate
        "wq": driver.normal(ks[0], (h, 2 * q), h ** -0.5, dt),
        "wk": driver.normal(ks[1], (h, kvw), h ** -0.5, dt),
        "wv": driver.normal(ks[2], (h, kvw), h ** -0.5, dt),
        "wo": driver.normal(ks[3], (q, h), q ** -0.5, dt),
        "q_norm": _zero_centred(ks[4], (d,), dt),
        "k_norm": _zero_centred(ks[5], (d,), dt),
    }


def _init_layer(key, c: Qwen3NextConfig, dt, kind: str):
    ks = jax.random.split(key, 6)
    h = c.hidden_size
    mixer = (delta_block(c).init_weights(ks[1], h, dt, c.dt_range, c.a_range)
             if kind == DELTA else _init_attn(ks[1], c, dt))
    return {
        "norm": _zero_centred(ks[0], (2, h), dt),
        "mixer": mixer,
        # logits spread by ``router_logit_std`` per token (the normed input
        # has unit RMS), so choices differ between tokens
        "router": {"w": driver.normal(
            ks[2], (h, c.num_experts), c.router_logit_std * h ** -0.5, dt)},
        "experts": driver.init_ffn(ks[3], h, c.moe_intermediate_size, 1.0,
                                   dt, lead=(c.experts_held,)),
        "shared": driver.init_ffn(ks[4], h, c.shared_expert_intermediate_size,
                                  1.0, dt),
        "shared_gate": driver.normal(ks[5], (h,), h ** -0.5, dt),
    }


def init_params(config: Qwen3NextConfig, key, policy: Policy | None = None):
    """Seeded weights in the driver's layout, the head untied, the final
    norm zero-centred like every norm of the stream."""
    policy = policy or bf16_policy()
    c, dt = config, policy.param_dtype
    layer = {kind: jax.jit(partial(_init_layer, c=c, dt=dt, kind=kind))
             for kind in (DELTA, FULL)}
    params = driver.init_params(
        c, key, policy, lambda k, i: layer[c.layer_types[i]](k))
    params["final_norm"] = params["final_norm"] - jnp.asarray(1, dt)
    return params


# ------------------------------------------------------------------- blocks


def norm(x, w, eps):
    """``N_w``: the scale is ``1 + w``."""
    return driver.rms_norm(x, 1.0 + w.astype(F32), eps)


def stack_norm(x, w, eps):
    """``N_w`` between two blocks (``driver.stack_norm``'s name)."""
    with jax.named_scope("norm.rms"):
        return norm(x, w, eps)


def delta_block(c: Qwen3NextConfig) -> state.DeltaBlock:
    """Qwen3-Next's sizes of the shared delta-rule block."""
    return state.DeltaBlock(
        c.linear_num_key_heads, c.linear_num_value_heads,
        c.linear_key_head_dim, c.linear_value_head_dim,
        c.linear_conv_kernel_dim, c.rms_norm_eps, c.chunk)


class AttentionBlock(kv.KVBlock):
    """The full-attention block (``models/kv.py`` has the grown cache and
    the step): a gate an ELEMENT that the query projection emits beside the
    query, q and k normed per head with zero-centred weights, the first
    ``rotary_dim`` columns of a head rotated."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__(config.num_key_value_heads, config.head_dim,
                         1.0 / math.sqrt(config.head_dim), None)
        self.config = config

    def _rotate(self, x, positions):
        c = self.config
        r = c.rotary_dim
        return jnp.concatenate(
            [driver.rope(x[..., :r], positions, c.rope_inv_freq),
             x[..., r:]], axis=-1)

    def project(self, x, p, positions):
        c, d = self.config, self.head_dim
        with jax.named_scope("attn.project"):
            qg = mm(x, p["wq"])
            qg = qg.reshape(qg.shape[:-1] + (c.num_attention_heads, 2 * d))
            q, gate = qg[..., :d], qg[..., d:]
            k = mm(x, p["wk"])
            k = k.reshape(k.shape[:-1] + (c.num_key_value_heads, d))
            v = mm(x, p["wv"]).reshape(k.shape)
            q = self._rotate(norm(q, p["q_norm"], c.rms_norm_eps), positions)
            k = self._rotate(norm(k, p["k_norm"], c.rms_norm_eps), positions)
        return q, k, v, gate.reshape(gate.shape[:-2] + (-1,))

    def finish(self, o, gate, p):
        with jax.named_scope("attn.gated"):
            o = o * jax.nn.sigmoid(gate)
        return mm(o, p["wo"])


def blocks_of(c: Qwen3NextConfig) -> dict:
    """A delta block per ``linear_attention`` layer, a grown-key block per
    ``full_attention`` layer."""
    kinds = {DELTA: delta_block(c), FULL: AttentionBlock(c)}
    return {f"l{i}": kinds[kind] for i, kind in enumerate(c.layer_types)}


# device-side counters, all float32 sums (docs/OBSERVABILITY.md section 3):
# the experts' as every family with a share, the delta block's three, the
# attention blocks' as Trinity's full blocks
ATTN_STAT_KEYS = ("attn.decode_rows", "attn.context_tokens",
                  "attn.full_rows_read") + kv.PREFILL_STAT_KEYS
STAT_KEYS = experts.STAT_KEYS + state.DELTA_STAT_KEYS + ATTN_STAT_KEYS


def decode_stats(blocks: dict, caches, pos, live) -> dict:
    """A decode step's ``gdn.*`` and ``attn.*`` counters."""
    attn = kv.decode_stats(blocks, caches, pos, live)
    return {**state.delta_decode_stats(blocks, live),
            **{k: attn[k] for k in ATTN_STAT_KEYS if k in attn}}


def prefill_stats(blocks: dict, tokens_shape, lengths, dt) -> dict:
    """A prefill's ``gdn.*`` counters and the attention cores' pairs."""
    full = {n: b for n, b in blocks.items() if isinstance(b, kv.KVBlock)}
    return {**state.delta_prefill_stats(blocks, tokens_shape, lengths),
            **kv.prefill_stats(full, tokens_shape[1], lengths, dt)}


# ------------------------------------------------------------------ experts


def route(u, router, c: Qwen3NextConfig):
    """``(ids (T, k), weights (T, k))``, float32 throughout
    (``models/experts.py:softmax_route`` at Qwen3-Next's numbers)."""
    return experts.softmax_route(u, router, c.num_experts_per_tok,
                                 norm=c.norm_topk_prob)


def gated_shared(u, layer):
    """``sigmoid(u . w_s) * shared(u)``: the shared expert under its gate a
    token (float32 the gate's product and sigmoid)."""
    with jax.named_scope("moe.shared_gate"):
        gate = jax.nn.sigmoid(jnp.dot(u, layer["shared_gate"].astype(u.dtype),
                                      preferred_element_type=F32))
    return swiglu(u, layer["shared"], scope="moe.shared") * gate[
        ..., None].astype(u.dtype)


def moe_share(u, layer, c: Qwen3NextConfig, live):
    """This chip's share of the ROUTED experts over ``u (T, h)`` (the gated
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


def zero_stats(c: Qwen3NextConfig) -> dict:
    return experts.zero_stats(STAT_KEYS, c.experts_held)


# -------------------------------------------------------------------- model


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``driver.prefill`` says
    what the driver asks of it)."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    for i, layer in enumerate(params["layers"]):
        n, eps = layer["norm"], c.rms_norm_eps
        x = residual(x, attend(stack_norm(x, n[0], eps), f"l{i}",
                               layer["mixer"]))
        u = stack_norm(x, n[1], eps)
        y, ids, s = moe_share(u, layer, c, live)
        stats = experts.add_stats(stats, s)
        touched += jnp.sum(s["moe.held_load"] > 0).astype(F32)
        chosen.append(ids)
        x = residual(residual(x, y), gated_shared(u, layer))
    return x, stats, chosen, touched


def _one_plus(params):
    """``params`` with the final norm's zero-centred weight as the scale
    the driver's head multiplies by."""
    return {**params, "final_norm": 1.0 + params["final_norm"].astype(F32)}


def prefill(params, tokens, lengths, config: Qwen3NextConfig,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over Qwen3-Next's stack and blocks: what comes
    back for a block is a delta block's ``{"state", "conv"}`` of R rows or
    the attention block's per-token ``{"k", "v"}: (R, KV, P, d)``."""
    policy = policy or bf16_policy()
    blocks = blocks_of(config)
    out = driver.prefill(_layers, blocks, _one_plus(params), tokens, lengths,
                         config, policy, **kwargs)
    out[2].update(prefill_stats(blocks, tokens.shape, lengths,
                                policy.compute_dtype))
    return out


def caches_from(rows, lengths, config: Qwen3NextConfig, max_len: int):
    """What :func:`prefill` returned, as the caches of R slots in an engine
    of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def decode_step(params, tok, pos, caches, live, config: Qwen3NextConfig,
                policy: Policy | None = None, **kwargs):
    """``driver.decode_step`` over Qwen3-Next's stack and blocks."""
    blocks = blocks_of(config)
    return driver.decode_step(
        _layers, blocks,
        lambda dt, caches, pos, live: decode_stats(blocks, caches, pos, live),
        _one_plus(params), tok, pos, caches, live, config,
        policy or bf16_policy(), **kwargs)


class Qwen3NextFamily(driver.Family):
    name = "qwen3_next"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    blocks_of = staticmethod(blocks_of)

    def attention_stats(self, dt, caches, pos, live):
        return decode_stats(self.blocks, caches, pos, live)

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, rows, stats = prefill(params, tokens, lengths, self.config,
                                      self.policy)
        return logits[:, 0], caches_from(rows, lengths, self.config,
                                         max_len), stats

    def decode_step(self, params, tok, pos, caches, live, adapters=None,
                    tenant=None):
        return decode_step(params, tok, pos, caches, live, self.config,
                           self.policy)

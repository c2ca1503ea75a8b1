"""LongCat-Flash: latent attention (MLA) and a shortcut-connected expert
layer with zero-compute experts, as ONE CHIP'S SHARE of an expert-parallel
deployment.

What LongCat alone has: its config, the router (softmax over real and
identity experts, chosen by ``p + b``), the double layer's wiring and the
seeded weights' layout.  The latent attention is ``models/latent.py``, the
model driver (``prefill`` / ``decode_step``) and the engine's seam
``models/driver.py``; the held experts' grouped product, its window and
the counters are ``models/experts.py`` — both shared with
``models/deepseek_v2.py``.

One layer (a "double layer")::

    a = x + MLA0(N0(x));  u = N1(a);  m = MoE(u);  b = a + FFN0(u)
    c = b + MLA1(N2(b));  out = c + FFN1(N3(c)) + m

**MLA** (``latent.py``) with ``q_gain = sqrt(h / q_lora_rank)``
(``mla_scale_q_lora``, applied to the projected query) and ``kv_gain =
sqrt(h / kv_lora_rank)`` (``mla_scale_kv_lora``, applied to the normed
latent, so the CACHE holds the scaled latent); plain RoPE, one ``theta``.

**The expert layer as a share.**  The router is ``n_routed_experts +
zero_expert_num`` wide and picks ``moe_topk`` by ``p + b`` whatever the
chip holds; the layer adds the terms of the held real experts
(``experts.py``) and of ALL identity experts (a token's identity terms are
computed where the token lives).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.models import experts, latent
from progen_tpu.models.driver import residual, stack_norm
from progen_tpu.models.experts import (  # noqa: F401
    held_experts, kernel_counters, moe_capacity)
from progen_tpu.models.latent import (  # noqa: F401
    F32,
    bf16_policy,
    mla_decode,
    mla_prefill,
    rms_norm,
    swiglu,
)


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

    @property
    def q_gain(self) -> float:
        return (math.sqrt(self.hidden_size / self.q_lora_rank)
                if self.mla_scale_q_lora else 1.0)

    @property
    def kv_gain(self) -> float:
        return (math.sqrt(self.hidden_size / self.kv_lora_rank)
                if self.mla_scale_kv_lora else 1.0)

    embed_gain = 1.0    # no factor on the embedding (models/driver.py)

    def rope_inv_freq(self, d: int):
        return 1.0 / (self.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d))

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


# ------------------------------------------------------------------ weights

_normal, _init_attn, _init_ffn = latent.normal, latent.init_attn, latent.init_ffn


def _init_layer(key, c: LongCatConfig, dt):
    ks = jax.random.split(key, 8)
    h = c.hidden_size
    return {
        "norm": latent.init_norm(ks[0], (4, h), dt),
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
    policy = policy or bf16_policy()
    layer = jax.jit(partial(_init_layer, c=config, dt=policy.param_dtype))
    return latent.init_params(config, key, policy, lambda k, i: layer(k))


# ------------------------------------------------------------------- pieces


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
             "moe.held_load": load.astype(F32),
             **kernel_counters(u, layer["experts"], load, c)}
    with jax.named_scope("moe.experts"):    # the terms' own rounding
        y = y.astype(u.dtype)
    return y, ids, stats


STAT_KEYS = (("moe.tokens", "moe.real_chosen") + experts.STAT_KEYS[1:]
             + latent.STAT_KEYS)


def zero_stats(c: LongCatConfig) -> dict:
    """Device-side counters, all float32 sums (docs/OBSERVABILITY.md §3)."""
    return experts.zero_stats(STAT_KEYS, c.experts_held)


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``latent.prefill`` says
    what the driver asks of it)."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    for i, layer in enumerate(params["layers"]):
        n, eps = layer["norm"], c.rms_norm_eps
        a = residual(x, attend(stack_norm(x, n[0], eps), f"l{i}a0",
                               layer["attn"][0]))
        u = stack_norm(a, n[1], eps)
        m, ids, s = moe_share(u, layer, c, live)
        stats = experts.add_stats(stats, s)
        touched += jnp.sum(s["moe.held_load"] > 0).astype(F32)
        chosen.append(ids)
        b = residual(a, swiglu(u, layer["ffn"][0]))
        cc = residual(b, attend(stack_norm(b, n[2], eps), f"l{i}a1",
                                layer["attn"][1]))
        x = residual(residual(cc, swiglu(stack_norm(cc, n[3], eps),
                                         layer["ffn"][1])), m)
    return x, stats, chosen, touched


def cache_names(c: LongCatConfig) -> list[str]:
    return [f"l{i}a{j}" for i in range(c.num_layers) for j in range(2)]


def prefill(params, tokens, lengths, config: LongCatConfig,
            policy: Policy | None = None, **kwargs):
    """``latent.prefill`` over LongCat's stack."""
    return latent.prefill(_layers, params, tokens, lengths, config,
                          policy or bf16_policy(), **kwargs)


def decode_step(params, tok, pos, caches, live, config: LongCatConfig,
                policy: Policy | None = None, **kwargs):
    """``latent.decode_step`` over LongCat's stack."""
    return latent.decode_step(_layers, params, tok, pos, caches, live,
                              config, policy or bf16_policy(), **kwargs)


class LongCatFamily(latent.LatentFamily):
    name = "longcat"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    cache_names = staticmethod(cache_names)

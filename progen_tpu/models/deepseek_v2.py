"""DeepSeek-V2: 128-head latent attention (MLA) under a YaRN rotary table,
a leading dense layer, then expert layers with group-limited routing over
160 experts beside two shared experts, as ONE CHIP'S SHARE of an
expert-parallel deployment.

What DeepSeek-V2 alone has: its config (YaRN table, the softmax scale's
``mscale^2``), the group-limited router, the sequential layer's wiring and
the seeded weights' layout.  The latent attention is ``models/latent.py``,
the model driver and the engine's seam ``models/driver.py``; the held experts' grouped product,
its window and the counters are ``models/experts.py`` — both shared with
``models/longcat.py``.

Layer ``l``::

    a = x + MLA(N(x));  out = a + F_l(N'(a))

``F_l`` is a dense SwiGLU of width ``intermediate_size`` for ``l <
first_k_dense_replace`` and the expert layer after: ``F(u) = sum_i w_i
E_i(u) + S(u)``, ``S`` one SwiGLU of width ``n_shared_experts *
moe_intermediate_size`` on every token.

**The router.**  ``p = softmax(u W_g)`` over ``n_routed_experts`` in
float32; the experts lie in ``n_group`` groups of consecutive experts, a
group scores its largest ``p``, the ``topk_group`` best groups stay and
``p`` is 0 elsewhere; the ``num_experts_per_tok`` largest of what is left
are chosen, weighted ``routed_scaling_factor * p`` from the unmasked
softmax (``norm_topk_prob`` false: not renormalised), no bias.

**MLA** (``latent.py``) with ``q_gain = m^2``, ``m = 0.1 * mscale_all_dim
* ln(factor) + 1``: the release scales the scores by ``192^-1/2 * m^2``,
and the shared code's scale is ``192^-1/2`` (the prefill kernel's contract),
so the factor rides on the projected query.  There is no ``kv_gain``.

**The share.**  As LongCat's: the router keeps its width, groups and top-k
whatever is held; the layer adds the terms of the held experts and leaves
out the absent ones'; attention, the dense layer and the shared experts are
whole on every chip and serve the chip's own tokens.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.core.precision import Policy
from progen_tpu.models import experts, latent
from progen_tpu.models.driver import residual, stack_norm
from progen_tpu.models.experts import held_experts, kernel_counters
from progen_tpu.models.latent import F32, bf16_policy, rms_norm, swiglu


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """``rope_scaling`` of the published config (``type`` "yarn")."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class DeepSeekV2Config:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights."""

    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    first_k_dense_replace: int = 1
    num_attention_heads: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    n_group: int = 8
    topk_group: int = 3
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: YarnScaling = YarnScaling()
    max_position_embeddings: int = 163840
    # the share: experts ``first_expert .. first_expert + held - 1``
    experts_held: int = 160
    first_expert: int = 0
    # seeded weights (``init_params``): standard deviations and gains
    router_logit_std: float = 1.0
    attn_qk_gain: float = 0.5
    residual_gain: float = 0.5
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
        return self.n_routed_experts

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def q_gain(self) -> float:
        s = self.rope_scaling
        return yarn_mscale(s.factor, s.mscale_all_dim) ** 2

    kv_gain = 1.0
    embed_gain = 1.0    # no factor on the embedding (models/driver.py)

    def yarn_bounds(self, d: int) -> tuple[int, int]:
        """The first and last of the ``d / 2`` frequencies between which
        the table goes from the base's own to the interpolated ones."""
        s = self.rope_scaling

        def dim(rotations):
            return (d * math.log(s.original_max_position_embeddings
                                 / (rotations * 2 * math.pi))
                    / (2 * math.log(self.rope_theta)))

        return (max(math.floor(dim(s.beta_fast)), 0),
                min(math.ceil(dim(s.beta_slow)), d - 1))

    def rope_inv_freq(self, d: int):
        """YaRN: the base's frequencies where a period fits the original
        context ``beta_fast`` times or more, ``1 / factor`` of them where
        it fits ``beta_slow`` times or fewer, a ramp between."""
        s = self.rope_scaling
        base = self.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        low, high = self.yarn_bounds(d)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
        return jnp.asarray(base / s.factor * ramp + base * (1 - ramp), F32)

    @classmethod
    def from_dict(cls, d) -> "DeepSeekV2Config":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        scaling = d.get("rope_scaling")
        if isinstance(scaling, dict):
            if scaling.get("type", "yarn") != "yarn":
                raise ValueError(f"rope_scaling type {scaling['type']!r}")
            keys = {f.name for f in dataclasses.fields(YarnScaling)}
            d["rope_scaling"] = YarnScaling(
                **{k: v for k, v in scaling.items() if k in keys})
        return cls(**d)

    def __post_init__(self):
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} routed experts")
        if self.n_routed_experts % self.n_group:
            raise ValueError(f"{self.n_routed_experts} experts do not split "
                             f"into {self.n_group} groups")
        s = self.rope_scaling
        if yarn_mscale(s.factor, s.mscale) != yarn_mscale(s.factor,
                                                          s.mscale_all_dim):
            # the cos/sin tables' own factor is their ratio: 1 as published
            raise ValueError("rope_scaling with mscale != mscale_all_dim "
                             "scales the rotary tables: not supported")


# ------------------------------------------------------------------ weights


def _init_layer(key, c: DeepSeekV2Config, dt, dense: bool):
    ks = jax.random.split(key, 6)
    h = c.hidden_size
    layer = {"norm": latent.init_norm(ks[0], (2, h), dt),
             "attn": latent.init_attn(ks[1], c, dt)}
    if dense:
        layer["ffn"] = latent.init_ffn(ks[2], h, c.intermediate_size,
                                       c.residual_gain, dt)
        return layer
    # logits spread by ``router_logit_std`` per token (the normed input has
    # unit RMS), so choices and groups differ between tokens
    layer["router"] = {"w": latent.normal(
        ks[3], (h, c.n_routed_experts), c.router_logit_std * h ** -0.5, dt)}
    layer["experts"] = latent.init_ffn(ks[4], h, c.moe_intermediate_size, 1.0,
                                       dt, lead=(c.experts_held,))
    layer["shared"] = latent.init_ffn(
        ks[5], h, c.n_shared_experts * c.moe_intermediate_size,
        c.residual_gain, dt)
    return layer


def init_params(config: DeepSeekV2Config, key, policy: Policy | None = None):
    policy = policy or bf16_policy()
    layer = {dense: jax.jit(partial(_init_layer, c=config,
                                    dt=policy.param_dtype, dense=dense))
             for dense in (True, False)}
    return latent.init_params(
        config, key, policy,
        lambda k, i: layer[i < config.first_k_dense_replace](k))


# ------------------------------------------------------------------- pieces


def route(u, router, c: DeepSeekV2Config):
    """``(ids (T, k), weights (T, k), kept (T, n_group))``, float32
    throughout: group-limited greedy choice, weights from the unmasked
    softmax; ``kept`` marks each token's ``topk_group`` groups."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(u.astype(F32), router["w"].astype(F32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        t = probs.shape[0]
        best = probs.reshape(t, c.n_group, -1).max(axis=-1)
        _, groups = jax.lax.top_k(best, c.topk_group)
        kept = jnp.zeros((t, c.n_group), bool).at[
            jnp.arange(t)[:, None], groups].set(True)
        size = c.n_routed_experts // c.n_group
        masked = jnp.where(jnp.repeat(kept, size, axis=1), probs, 0.0)
        _, ids = jax.lax.top_k(masked, c.num_experts_per_tok)
        w = jnp.take_along_axis(probs, ids, axis=-1)
        return ids, w * c.routed_scaling_factor, kept


def moe_share(u, layer, c: DeepSeekV2Config, live):
    """This chip's share of the ROUTED experts over ``u (T, h)`` (the
    shared experts are the caller's: every chip computes them alike) and
    what it counted over the ``live`` tokens."""
    ids, w, kept = route(u, layer["router"], c)
    y, load = held_experts(u, ids, w, live, layer["experts"], c)
    size = c.n_routed_experts // c.n_group
    first = c.first_expert // size
    last = (c.first_expert + c.experts_held - 1) // size
    mine = jnp.sum(kept[:, first:last + 1] & live[:, None])
    stats = {"moe.tokens": jnp.sum(live).astype(F32),
             "moe.held_groups_chosen": mine.astype(F32),
             "moe.held_load": load.astype(F32),
             **kernel_counters(u, layer["experts"], load, c)}
    with jax.named_scope("moe.experts"):    # the terms' own rounding
        y = y.astype(u.dtype)
    return y, ids, stats


STAT_KEYS = (experts.STAT_KEYS + latent.STAT_KEYS
             + ("moe.held_groups_chosen",))


def zero_stats(c: DeepSeekV2Config) -> dict:
    """Device-side counters, all float32 sums (docs/OBSERVABILITY.md §3)."""
    return experts.zero_stats(STAT_KEYS, c.experts_held)


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``latent.prefill`` says
    what the driver asks of it)."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    for i, layer in enumerate(params["layers"]):
        n, eps = layer["norm"], c.rms_norm_eps
        a = residual(x, attend(stack_norm(x, n[0], eps), f"l{i}",
                               layer["attn"]))
        u = stack_norm(a, n[1], eps)
        if "experts" not in layer:
            x = residual(a, swiglu(u, layer["ffn"]))
            continue
        m, ids, s = moe_share(u, layer, c, live)
        stats = experts.add_stats(stats, s)
        touched += jnp.sum(s["moe.held_load"] > 0).astype(F32)
        chosen.append(ids)
        x = residual(residual(a, m),
                     swiglu(u, layer["shared"], scope="moe.shared"))
    return x, stats, chosen, touched


def cache_names(c: DeepSeekV2Config) -> list[str]:
    return [f"l{i}" for i in range(c.num_hidden_layers)]


def prefill(params, tokens, lengths, config: DeepSeekV2Config,
            policy: Policy | None = None, **kwargs):
    """``latent.prefill`` over DeepSeek-V2's stack."""
    return latent.prefill(_layers, params, tokens, lengths, config,
                          policy or bf16_policy(), **kwargs)


def decode_step(params, tok, pos, caches, live, config: DeepSeekV2Config,
                policy: Policy | None = None, **kwargs):
    """``latent.decode_step`` over DeepSeek-V2's stack."""
    return latent.decode_step(_layers, params, tok, pos, caches, live,
                              config, policy or bf16_policy(), **kwargs)


class DeepSeekV2Family(latent.LatentFamily):
    name = "deepseek_v2"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    cache_names = staticmethod(cache_names)

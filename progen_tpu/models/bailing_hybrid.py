"""Ling-3.0-flash (``model_type`` ``bailing_hybrid``): five delta-rule layers
whose decay is a key CHANNEL's — a float32 state a slot and no keys — to one
latent-attention layer, two leading dense layers and an expert layer after
(sigmoid top-8 of 512 under a group limit, beside a shared expert; the last
layers clip the SwiGLU), as ONE CHIP'S SHARE of an expert-parallel
deployment (``experts_held`` of ``num_experts`` from ``first_expert`` on;
``layer_ids``, the published layers this chip holds), a token a row a step.

What this family alone has: its config, the layer kinds, the latent block's
weights (a full-rank query, a gate a head), the limits a layer and the
seeded weights' layout.  The model driver and the engine's seam are
``models/driver.py``; the channel-decay delta mixer and what it states about
its cache ``models/state.py:ChannelDeltaBlock`` (its recurrence
``ops/gdn.py:kda_scan`` / ``kda_step``, its convolution ``ops/ssd.py``'s);
latent attention ``models/latent.py:LatentBlock`` with its ``gate`` option;
the held experts' product, its counters and the router
``models/experts.py`` (``sigmoid_route`` with ``noaux_tc``'s groups).

``x0 = E[token]``, ``eps = rms_norm_eps``, ``N_w(x) = x rsqrt(mean(x^2) +
eps) w`` (statistics float32).  Layer ``i`` of the published 42::

    x <- x + Mixer_i(N(x)),   x <- x + F_i(N(x))

``Mixer_i`` is latent attention where ``(i + 1) % layer_group_size == 0``
and the channel-decay delta rule otherwise (``ChannelDeltaBlock`` has its
equations: 32 heads of 128, four taps, l2 norms on q and k, a bounded gate
``g = kda_lower_bound * sigmoid(..)``, a norm a head and THEN a gate a
head); ``F_i`` a dense SwiGLU ``intermediate_size`` wide for ``i <
first_k_dense_replace`` and the expert layer after.  ``logits = N_f(x)
W_head`` (untied).

**Latent attention** (``models/latent.py``; ``q_lora_rank`` null)::

    q = u W_q (H heads of [nope | rope]);  [c_kv | k_r] = u W_kva;  c_kv <-
    N_w(c_kv);  [k_nope | v] = c_kv W_kvb;  INTERLEAVED rotary pairs on q's
    rope part and k_r (rope_theta, no scaling);  the cache row [c_kv |
    rope(k_r)];  o = softmax(q . [k_nope | k_r] (nope + rope)^-1/2) v;
    out = [o_head * sigmoid(u W_g)_head] W_o

**Expert layer** (``noaux_tc``): ``s = sigmoid(u W_r)`` over all
``num_experts`` (float32); ``c = s + b``; the experts lie in ``n_group``
groups of consecutive experts, a group scores the sum of its 2 largest
``c``, the ``topk_group`` best stay; the ``num_experts_per_tok`` largest
``c`` among them are chosen, weighted ``s / (sum s + 1e-20) *
routed_scaling_factor``.  Expert ``e`` of layer ``i``: ``a = u W_g, b = u
W_u``; where ``l = expert_swiglu_limit_list[i]`` is not 0, ``a <- min(a,
l)``, ``b <- clip(b, -l, l)``; ``(silu(a) * b) W_d``.  Plus the shared
expert, the same form under ``share_expert_swiglu_limit_list[i]``, ungated.

**The share.**  The router keeps its width, groups and top-k whatever is
held; the layer adds the terms of the held experts; the shared expert is
every chip's alike and counted ONCE over the chips that share a layer.

The multi-token-prediction module beside the stack is not served.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.models import driver, experts, latent, state
from progen_tpu.models.driver import (  # noqa: F401
    F32,
    bf16_policy,
    residual,
    rms_norm,
    stack_norm,
    swiglu,
)
from progen_tpu.models.experts import held_experts, kernel_counters
from progen_tpu.ops.mla_decode import rows_visited

DELTA, LATENT = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights.  It is also the latent shape
    ``models/latent.py`` reads."""

    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    # the delta rule (key heads = value heads: num_kv_heads_for_linear_attn 0)
    num_attention_heads: int = 32
    num_kv_heads_for_linear_attn: int = 0
    head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5
    kda_safe_gate: bool = True
    no_kda_lora: bool = True
    use_kda_lora: bool = False
    linear_silu: bool = True
    group_norm_size: int = 1
    gated_attention_proj_granularity_type: str = "head_wise"
    use_qk_norm: bool = True
    # latent attention
    q_lora_rank: None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6000000.0
    rope_interleave: bool = True
    rope_scaling: None = None
    # the expert layer
    num_experts: int = 512
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    score_function: str = "sigmoid"
    topk_method: str = "noaux_tc"
    moe_router_enable_expert_bias: bool = True
    scale_router_input: bool = False
    # a limit a PUBLISHED layer on the SwiGLU's two products, 0 for none
    expert_swiglu_limit_list: tuple = (0,) * 42
    share_expert_swiglu_limit_list: tuple = (0,) * 42
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    # the share: the published layers held (empty: 0 .. num_hidden_layers -
    # 1) and experts ``first_expert .. first_expert + held - 1``
    layer_ids: tuple = ()
    experts_held: int = 512
    first_expert: int = 0
    # the chunked delta rule's chunk and its blocks of rows (sizes of the
    # block, no published key)
    chunk: int = 64
    block: int = 16
    # seeded weights (``init_params``): the router logits' spread a token and
    # the selection bias's; ``exp(A_log)`` a head (log-uniform) and
    # ``dt_bias`` a channel (uniform): a step's decay ``exp(bound *
    # sigmoid(A (f + dt_bias)))`` spans 0.999 to 0.01 over the channels; the
    # experts' and the shared expert's gate and up matrices times a gain, so
    # that the limits bind on a share of their products
    router_logit_std: float = 1.0
    router_bias_std: float = 0.02
    a_range: tuple = (0.5, 2.0)
    dt_bias_range: tuple = (-6.0, 2.0)
    expert_in_gain: float = 1.6
    shared_in_gain: float = 2.0
    # the engine pads primes to ``prefill_bucket * 2^k`` tokens
    prefill_bucket: int = 512

    embed_gain = 1.0            # no multiplier on the embedding
    q_gain = kv_gain = 1.0      # no rescale of the latents

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
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def layer_types(self) -> tuple:
        """The mixer of each HELD layer, by its published number."""
        return tuple(LATENT if (i + 1) % self.layer_group_size == 0
                     else DELTA for i in self.layer_ids)

    def is_dense(self, layer: int) -> bool:
        return self.layer_ids[layer] < self.first_k_dense_replace

    def limits(self, layer: int) -> tuple:
        """``(the routed experts' limit, the shared expert's)`` of held
        layer ``layer``, floats, 0 for none."""
        i = self.layer_ids[layer]
        return (float(self.expert_swiglu_limit_list[i]),
                float(self.share_expert_swiglu_limit_list[i]))

    def rope_inv_freq(self, d: int):
        return 1.0 / (self.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d))

    @classmethod
    def from_dict(cls, d) -> "BailingHybridConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list", "layer_ids", "a_range",
                    "dt_bias_range"):
            if key in d:
                d[key] = tuple(d[key])
        return cls(**d)

    def __post_init__(self):
        n = self.num_hidden_layers
        if not self.layer_ids:
            object.__setattr__(self, "layer_ids", tuple(range(n)))
        ids = self.layer_ids
        if len(ids) != n or list(ids) != sorted(set(ids)):
            raise ValueError(f"layer_ids {ids} do not name "
                             f"{n} published layers in ascending order")
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            if len(getattr(self, key)) <= ids[-1]:
                raise ValueError(f"{key} has no entry for layer {ids[-1]}")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.num_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.num_experts} routed experts")
        if self.num_experts % self.n_group:
            raise ValueError(f"{self.num_experts} experts do not split into "
                             f"{self.n_group} groups")
        unsupported = {
            "num_kv_heads_for_linear_attn": 0, "kda_safe_gate": True,
            "no_kda_lora": True, "use_kda_lora": False, "linear_silu": True,
            "group_norm_size": 1, "use_qk_norm": True, "q_lora_rank": None,
            "gated_attention_proj_granularity_type": "head_wise",
            "rope_interleave": True, "rope_scaling": None,
            "num_shared_experts": 1, "norm_topk_prob": True,
            "score_function": "sigmoid", "topk_method": "noaux_tc",
            "moe_router_enable_expert_bias": True,
            "scale_router_input": False, "hidden_act": "silu",
            "tie_word_embeddings": False}
        for key, only in unsupported.items():
            if getattr(self, key) != only:
                raise ValueError(
                    f"{key} {getattr(self, key)!r} is not supported: the "
                    f"served model has {only!r}")


# ------------------------------------------------------------------ weights


def _init_attn(key, c: BailingHybridConfig, dt):
    """Scales chosen so that q, k and v have unit spread an entry."""
    h, heads = c.hidden_size, c.num_attention_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    normal = driver.normal
    return {
        "wq": normal(ks[0], (h, heads * qk), h ** -0.5, dt),
        "wkva": normal(ks[1], (h, c.latent_width), h ** -0.5, dt),
        "kv_norm": driver.init_norm(ks[2], (c.kv_lora_rank,), dt),
        "wkvb": normal(
            ks[3], (c.kv_lora_rank, heads * (c.qk_nope_head_dim
                                             + c.v_head_dim)),
            c.kv_lora_rank ** -0.5, dt),
        "wgate": normal(ks[4], (h, heads), h ** -0.5, dt),
        "wo": normal(ks[5], (heads * c.v_head_dim, h),
                     (heads * c.v_head_dim) ** -0.5, dt),
    }


def _init_ffn(key, h, width, gain, dt, lead=()):
    """``driver.init_ffn`` with the gate and up matrices times ``gain`` and
    the down matrix over its square: the products' spread is ``gain``, what
    leaves the layer as without it."""
    p = driver.init_ffn(key, h, width, gain ** -2, dt, lead)
    g = jnp.asarray(gain, F32)
    return {**p, "wg": (p["wg"].astype(F32) * g).astype(dt),
            "wu": (p["wu"].astype(F32) * g).astype(dt)}


def _init_layer(key, c: BailingHybridConfig, dt, kind: str, dense: bool):
    ks = jax.random.split(key, 7)
    h = c.hidden_size
    mixer = (delta_block(c).init_weights(ks[1], h, dt, c.dt_bias_range,
                                         c.a_range)
             if kind == DELTA else _init_attn(ks[1], c, dt))
    layer = {"norm": driver.init_norm(ks[0], (2, h), dt), "mixer": mixer}
    if dense:
        layer["ffn"] = driver.init_ffn(ks[2], h, c.intermediate_size, 1.0, dt)
        return layer
    # logits spread by ``router_logit_std`` per token, so choices and groups
    # differ between tokens; the bias is a float32 buffer
    layer["router"] = {
        "w": driver.normal(ks[3], (h, c.num_experts),
                           c.router_logit_std * h ** -0.5, dt),
        "bias": driver.normal(ks[4], (c.num_experts,), c.router_bias_std,
                              F32)}
    layer["experts"] = _init_ffn(ks[5], h, c.moe_intermediate_size,
                                 c.expert_in_gain, dt, (c.experts_held,))
    layer["shared"] = _init_ffn(
        ks[6], h, c.moe_shared_expert_intermediate_size, c.shared_in_gain, dt)
    return layer


def init_params(config: BailingHybridConfig, key,
                policy: Policy | None = None):
    policy = policy or bf16_policy()
    c = config
    made = {}

    def layer(k, i):
        kind = (c.layer_types[i], c.is_dense(i))
        if kind not in made:        # one program a kind of layer
            made[kind] = jax.jit(partial(
                _init_layer, c=c, dt=policy.param_dtype, kind=kind[0],
                dense=kind[1]))
        return made[kind](k)

    return driver.init_params(config, key, policy, layer)


# ------------------------------------------------------------------- blocks


def delta_block(c: BailingHybridConfig) -> state.ChannelDeltaBlock:
    """This family's sizes of the shared channel-decay block."""
    return state.ChannelDeltaBlock(
        c.num_attention_heads, c.head_dim, c.head_dim,
        c.short_conv_kernel_size, c.rms_norm_eps, c.chunk, c.block,
        c.kda_lower_bound)


def blocks_of(c: BailingHybridConfig) -> dict:
    """A channel-decay delta block per ``kda`` layer, a gated latent block
    per ``mla`` layer; ONE instance a kind."""
    kinds = {DELTA: delta_block(c), LATENT: latent.LatentBlock(c, gate=True)}
    return {f"l{i}": kinds[kind] for i, kind in enumerate(c.layer_types)}


# device-side counters, all float32 sums (docs/OBSERVABILITY.md section 3):
# the experts' as every family with a share, the delta block's three, the
# latent blocks' as LongCat's
STAT_KEYS = experts.STAT_KEYS + state.KDA_STAT_KEYS + latent.STAT_KEYS


def _latent_names(blocks: dict) -> list:
    return [n for n, b in blocks.items()
            if isinstance(b, latent.LatentBlock)]


def decode_stats(blocks: dict, dt, caches, pos, live) -> dict:
    """A decode step's ``kda.*`` and ``mla.*`` counters: the carries every
    delta layer moves, and the latent rows ONE latent block's core reads
    (every latent block's has the same shapes)."""
    out = state.kda_decode_stats(blocks, live)
    names = _latent_names(blocks)
    if names:
        c = blocks[names[0]].config
        out.update({
            "mla.decode_rows": jnp.sum(live).astype(F32),
            "mla.context_tokens": jnp.sum(
                jnp.where(live, pos + 1, 0)).astype(F32),
            "mla.cache_rows_read": rows_visited(
                dt, caches[names[0]], pos + 1,
                c.kv_lora_rank) * jnp.any(live)})
    return out


# ------------------------------------------------------------------ experts


def route(u, router, c: BailingHybridConfig):
    """``(ids (T, k), weights (T, k))``, float32 throughout
    (``models/experts.py:sigmoid_route`` under the group limit)."""
    return experts.sigmoid_route(
        u, router, c.num_experts_per_tok, norm=c.norm_topk_prob,
        scale=float(c.routed_scaling_factor), eps=1e-20, groups=c.n_group,
        kept_groups=c.topk_group)


def moe_share(u, layer, c: BailingHybridConfig, live, limit: float = 0.0):
    """This chip's share of the routed experts over ``u (T, h)`` under the
    layer's ``limit`` (the shared expert is the caller's: every chip
    computes it alike) and what it counted over the ``live`` tokens."""
    ids, w = route(u, layer["router"], c)
    y, load = held_experts(u, ids, w, live, layer["experts"], c, limit=limit)
    stats = {"moe.tokens": jnp.sum(live).astype(F32),
             "moe.held_load": load.astype(F32),
             **kernel_counters(u, layer["experts"], load, c)}
    with jax.named_scope("moe.experts"):    # the terms' own rounding
        y = y.astype(u.dtype)
    return y, ids, stats


def zero_stats(c: BailingHybridConfig) -> dict:
    return experts.zero_stats(STAT_KEYS, c.experts_held)


# -------------------------------------------------------------------- model


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``driver.prefill`` says
    what the driver asks of it)."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    eps = c.rms_norm_eps
    for i, layer in enumerate(params["layers"]):
        n = layer["norm"]
        x = residual(x, attend(stack_norm(x, n[0], eps), f"l{i}",
                               layer["mixer"]))
        u = stack_norm(x, n[1], eps)
        if "experts" not in layer:
            x = residual(x, swiglu(u, layer["ffn"]))
            continue
        limit, shared_limit = c.limits(i)
        m, ids, s = moe_share(u, layer, c, live, limit)
        stats = experts.add_stats(stats, s)
        touched += jnp.sum(s["moe.held_load"] > 0).astype(F32)
        chosen.append(ids)
        x = residual(residual(x, m), swiglu(
            u, layer["shared"], scope="moe.shared", limit=shared_limit))
    return x, stats, chosen, touched


def prefill(params, tokens, lengths, config: BailingHybridConfig,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over this family's stack and blocks: what comes
    back for a block is a delta block's ``{"state", "conv"}`` of R rows or
    the latent block's per-token rows ``(R, P, latent)``."""
    policy = policy or bf16_policy()
    blocks = blocks_of(config)
    out = driver.prefill(_layers, blocks, params, tokens, lengths, config,
                         policy, **kwargs)
    out[2].update(state.kda_prefill_stats(blocks, tokens.shape, lengths))
    return out


def caches_from(rows, lengths, config: BailingHybridConfig, max_len: int):
    """What :func:`prefill` returned, as the caches of R slots in an engine
    of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def decode_step(params, tok, pos, caches, live, config: BailingHybridConfig,
                policy: Policy | None = None, **kwargs):
    """``driver.decode_step`` over this family's stack and blocks."""
    blocks = blocks_of(config)
    return driver.decode_step(
        _layers, blocks, partial(decode_stats, blocks), params, tok, pos,
        caches, live, config, policy or bf16_policy(), **kwargs)


class BailingHybridFamily(driver.Family):
    name = "bailing_hybrid"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    blocks_of = staticmethod(blocks_of)

    def attention_stats(self, dt, caches, pos, live):
        return decode_stats(self.blocks, dt, caches, pos, live)

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

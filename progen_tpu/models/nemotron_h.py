"""Nemotron-H (``model_type`` ``nemotron_h``, as Nemotron-3-Super publishes
it): a stack in which EVERY LAYER IS ONE SUBLAYER — a Mamba-2 mixer with
eight B/C groups, a LatentMoE whose experts are two matrices with a
``relu^2`` between them and run in a latent narrower than the stream, or a
grouped-query attention block with no positional embedding — chosen layer by
layer by ``hybrid_override_pattern`` (``M`` / ``E`` / ``*``).  Here the chip
holds a SHARE of every expert layer (``experts_held`` of ``n_routed_experts``
from ``first_expert`` on), a token a row a step.

What Nemotron-H alone has: its config, the pattern, the latent expert
layer's wiring, the attention block's projection and the seeded weights'
layout.  The model driver and the engine's seam are ``models/driver.py``;
the Mamba-2 mixer and what it states about its cache are
``models/state.py`` (shared with ``models/granite_hybrid.py``, there with
one group); the grown-key cache and its decode step are ``models/kv.py``;
the held experts' product in its two-matrix form, its counters and the
sigmoid router are ``models/experts.py`` (the router is Trinity's equation
at other numbers: ``experts.sigmoid_route``).

``x0 = E[token]``.  Layer ``l`` of kind ``hybrid_override_pattern[l]``::

    x <- x + Mixer_l(RMSNorm_l(x))                  once: no second half

``logits = RMSNorm_f(x) W_head`` (untied).  RMSNorm: float32 statistics,
eps ``layer_norm_epsilon`` 1e-5, a learned scale that multiplies as it is.

**``M`` — Mamba-2 mixer** (``models/state.py`` has the equations): ``I =
mamba_num_heads * mamba_head_dim``, ``G = n_groups``, ``N =
ssm_state_size``, ``K = conv_kernel``; ``[z (I) | xBC (I + 2 G N) | dt
(heads)] = u W_in``, the depthwise causal convolution with a bias and a
``silu`` over ``xBC``, ``[x | B (G, N) | C (G, N)]``, head ``h`` reading
group ``h // (heads / G)``, ``dt = softplus(dt + dt_bias)`` with no clamp,
the recurrence in float32 plus ``D_h x_t``, then ``RMSNorm_w(y * silu(z))``
PER GROUP of ``I / G`` channels (the gate before the norm), then ``W_out``.
Its cache a slot: the float32 carry ``(heads, head_dim, N)`` — 4.19 MB at
the published widths — and a tail of ``K - 1`` rows of ``xBC``, whatever
``max_len``.

**``*`` — attention**: ``q = u W_q`` (``num_attention_heads`` of
``head_dim``), ``k, v = u W_k, u W_v`` (``num_key_value_heads``), no bias,
NO ROTATION (the family puts no positional embedding in its attention
layers; the config's ``rope_theta`` is unused), causal, scores times
``head_dim^-1/2``, softmax in float32, ``H / KV`` query heads a key head,
``W_o``.  Its cache is ``models/kv.py``'s grown one.

**``E`` — LatentMoE**, float32 router on the full width: ``s = sigmoid(u
W_r)`` over ``n_routed_experts``; the ``num_experts_per_tok`` largest of
``s + b`` are chosen (``b`` picks and does not weigh; ``n_group`` =
``topk_group`` = 1: no group limit); ``w = s_chosen / (sum s_chosen +
1e-20) * routed_scaling_factor``.  ``v = u W_down`` (hidden ->
``moe_latent_size``); expert ``e``: ``f_e(v) = relu(v W_up,e)^2 W_dn,e``
(latent -> ``moe_intermediate_size`` -> latent, no gate, no bias); ``routed
= (sum_e w_e f_e(v)) W_up`` (latent -> hidden); ``shared = relu(u W_su)^2
W_sd`` on the FULL width, every token; out ``= routed + shared``.  **The
layer states no cache**: ``blocks_of`` has no entry for it.

**The share.**  The router keeps its width and top-k whatever is held; the
sum over the HELD experts is taken in the latent and ``W_up`` applied to
that partial sum (linear, so the shares add up); the shared expert is every
chip's alike and counted once.
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
    rms_norm,
    stack_norm,
)
from progen_tpu.models.experts import held_experts, kernel_counters
from progen_tpu.ops.moe_decode import activation

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
_PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")
ROUTE_EPS = 1e-20


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = _PUBLISHED_PATTERN
    # M: the Mamba-2 mixer
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    # *: attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    # E: the latent expert layer
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    n_shared_experts: int = 1
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    # the share: experts ``first_expert .. first_expert + held - 1``
    experts_held: int = 512
    first_expert: int = 0
    # seeded weights (``init_params``): the router logits' spread a token,
    # the selection bias's (in units of a score: it moves some choices),
    # and the range the per-head step ``softplus(dt_bias)`` and ``A`` are
    # drawn from (log-uniform)
    router_logit_std: float = 1.0
    router_bias_std: float = 0.02
    dt_range: tuple = (0.001, 0.1)
    a_range: tuple = (1.0, 16.0)
    # the engine pads primes to ``prefill_bucket * 2^k`` tokens
    prefill_bucket: int = 128

    embed_gain = 1.0

    # what the shared code reads under its own names
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def rms_norm_eps(self) -> float:
        return self.layer_norm_epsilon

    @property
    def moe_topk(self) -> int:
        return self.num_experts_per_tok

    @property
    def router_width(self) -> int:
        return self.n_routed_experts

    @property
    def seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    def layers_of(self, kind: str) -> int:
        return self.hybrid_override_pattern.count(kind)

    @classmethod
    def from_dict(cls, d) -> "NemotronHConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        for key in ("dt_range", "a_range"):
            if key in d:
                d[key] = tuple(d[key])
        return cls(**d)

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if (len(pattern) != self.num_hidden_layers
                or set(pattern) - {MAMBA, EXPERTS, ATTENTION}):
            raise ValueError(
                f"hybrid_override_pattern must name {self.num_hidden_layers}"
                f" layers, each {MAMBA!r}, {EXPERTS!r} or {ATTENTION!r}: "
                f"{pattern!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split over "
                f"{self.num_key_value_heads} key/value heads")
        if self.mamba_inner != self.expand * self.hidden_size:
            raise ValueError(
                f"{self.mamba_num_heads} heads of {self.mamba_head_dim} are "
                f"not {self.expand} x hidden {self.hidden_size}")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} routed experts")
        unsupported = {
            "use_conv_bias": True, "mamba_proj_bias": False,
            "mamba_hidden_act": "silu", "attention_bias": False,
            "mlp_bias": False, "mlp_hidden_act": "relu2", "n_group": 1,
            "topk_group": 1, "n_shared_experts": 1,
            "tie_word_embeddings": False,
            # the draft module beside the stack is not served (ROADMAP)
            "num_nextn_predict_layers": 0}
        for key, only in unsupported.items():
            if getattr(self, key) != only:
                raise ValueError(
                    f"{key} {getattr(self, key)!r} is not supported: the "
                    f"served model has {only!r}")


# ------------------------------------------------------------------ weights


# relu(a)^2 of a unit normal ``a`` has second moment 3/2: a down matrix of
# N(0, (3/2 * width)^-1) hands back a row of unit RMS
RELU2_GAIN = 1.5 ** -0.5


def _init_relu2(key, h, width, dt, lead=()):
    """Two matrices with ``relu^2`` between them, ``h -> width -> h``."""
    ks = jax.random.split(key, 2)
    return {"wu": driver.normal(ks[0], lead + (h, width), h ** -0.5, dt),
            "wd": driver.normal(ks[1], lead + (width, h),
                                RELU2_GAIN * width ** -0.5, dt)}


def _init_attn(key, c: NemotronHConfig, dt):
    h, d = c.hidden_size, c.head_dim
    q, kvw = c.num_attention_heads * d, c.num_key_value_heads * d
    ks = jax.random.split(key, 4)
    return {
        "wq": driver.normal(ks[0], (h, q), h ** -0.5, dt),
        "wk": driver.normal(ks[1], (h, kvw), h ** -0.5, dt),
        "wv": driver.normal(ks[2], (h, kvw), h ** -0.5, dt),
        "wo": driver.normal(ks[3], (q, h), q ** -0.5, dt),
    }


def _init_experts(key, c: NemotronHConfig, dt):
    h, latent = c.hidden_size, c.moe_latent_size
    ks = jax.random.split(key, 6)
    return {
        # logits spread by ``router_logit_std`` per token (the normed input
        # has unit RMS), so choices differ between tokens; the bias is a
        # float32 buffer, as the release keeps it
        "router": {
            "w": driver.normal(ks[0], (h, c.n_routed_experts),
                               c.router_logit_std * h ** -0.5, dt),
            "bias": driver.normal(ks[1], (c.n_routed_experts,),
                                  c.router_bias_std, F32)},
        "latent_in": driver.normal(ks[2], (h, latent), h ** -0.5, dt),
        "latent_out": driver.normal(ks[3], (latent, h), latent ** -0.5, dt),
        "experts": _init_relu2(ks[4], latent, c.moe_intermediate_size, dt,
                               lead=(c.experts_held,)),
        "shared": _init_relu2(ks[5], h,
                              c.moe_shared_expert_intermediate_size, dt),
    }


def _init_layer(key, c: NemotronHConfig, dt, kind: str):
    ks = jax.random.split(key, 2)
    layer = {"norm": driver.init_norm(ks[0], (c.hidden_size,), dt)}
    if kind == EXPERTS:
        return {**layer, **_init_experts(ks[1], c, dt)}
    mixer = (state_block(c).init_weights(ks[1], c.hidden_size, dt,
                                         c.dt_range, c.a_range)
             if kind == MAMBA else _init_attn(ks[1], c, dt))
    return {**layer, "mixer": mixer}


def init_params(config: NemotronHConfig, key, policy: Policy | None = None):
    """Seeded weights in the driver's layout, the head untied.  Every
    branch hands the stream a row of its own order: a normed input has unit
    RMS, every matrix is N(0, 1 / fan_in), and the down matrix after a
    ``relu^2`` (the experts', the shared expert's) is scaled by
    :data:`RELU2_GAIN`, so one expert's output has unit RMS and the 22 of a
    token, weighted ``5 / 22`` each, about 1.07."""
    policy = policy or bf16_policy()
    c = config
    layer = {kind: jax.jit(partial(_init_layer, c=c, dt=policy.param_dtype,
                                   kind=kind))
             for kind in (MAMBA, EXPERTS, ATTENTION)}
    return driver.init_params(
        c, key, policy, lambda k, i: layer[c.hybrid_override_pattern[i]](k))


# ------------------------------------------------------------------- blocks


def state_block(c: NemotronHConfig) -> state.StateBlock:
    """Nemotron-H's sizes of the shared Mamba-2 block."""
    return state.StateBlock(c.mamba_num_heads, c.mamba_head_dim,
                            c.ssm_state_size, c.n_groups, c.conv_kernel,
                            c.layer_norm_epsilon, c.chunk_size)


class AttentionBlock(kv.KVBlock):
    """A full-attention block (``models/kv.py`` has the grown cache and the
    step): plain projections, no gate, no norm, no rotation."""

    def __init__(self, config: NemotronHConfig):
        super().__init__(config.num_key_value_heads, config.head_dim,
                         1.0 / math.sqrt(config.head_dim), None)
        self.config = config

    def project(self, x, p, positions):
        c, d = self.config, self.head_dim
        with jax.named_scope("attn.project"):
            q = mm(x, p["wq"])
            q = q.reshape(q.shape[:-1] + (c.num_attention_heads, d))
            k = mm(x, p["wk"])
            k = k.reshape(k.shape[:-1] + (c.num_key_value_heads, d))
            v = mm(x, p["wv"]).reshape(k.shape)
        return q, k, v, None

    def finish(self, o, rest, p):
        return mm(o, p["wo"])


def blocks_of(c: NemotronHConfig) -> dict:
    """A state block per ``M`` layer, a grown-key block per ``*`` layer and
    NOTHING per ``E`` layer: an expert layer states no cache."""
    kinds = {MAMBA: state_block(c), ATTENTION: AttentionBlock(c)}
    return {f"l{i}": kinds[kind]
            for i, kind in enumerate(c.hybrid_override_pattern)
            if kind in kinds}


# device-side counters, all float32 sums (docs/OBSERVABILITY.md section 3):
# the experts' as every family with a share, the state block's four, the
# attention blocks' as Trinity's full blocks
STAT_KEYS = experts.STAT_KEYS + state.STAT_KEYS + (
    "attn.decode_rows", "attn.context_tokens", "attn.full_rows_read")


def decode_stats(blocks: dict, caches, pos, live) -> dict:
    """A decode step's ``ssm.*`` and ``attn.*`` counters."""
    attn = kv.decode_stats(blocks, caches, pos, live)
    return {**state.decode_stats(blocks, live),
            **{k: attn[k] for k in STAT_KEYS if k in attn}}


# ------------------------------------------------------------------ experts


def relu2(x, p, scope):
    """``relu(x W_u)^2 W_d``: the experts' ungated form
    (``ops/moe_decode.py:activation``) over one pair of matrices."""
    with jax.named_scope(scope):
        return mm(activation(None, mm(x, p["wu"])), p["wd"])


def route(u, router, c: NemotronHConfig):
    """``(ids (T, k), weights (T, k))``, float32 throughout
    (``models/experts.py:sigmoid_route`` at Nemotron-H's numbers)."""
    return experts.sigmoid_route(
        u, router, c.num_experts_per_tok, norm=c.norm_topk_prob,
        scale=c.routed_scaling_factor, eps=ROUTE_EPS)


def moe_share(u, layer, c: NemotronHConfig, live):
    """This chip's share of the ROUTED experts over ``u (T, h)``: routed on
    the full width, computed in the latent, the held experts' sum projected
    back (the shared expert is the caller's: every chip computes it alike)
    — and what it counted over the ``live`` tokens."""
    ids, w = route(u, layer["router"], c)
    with jax.named_scope("moe.latent_in"):
        v = mm(u, layer["latent_in"])
    y, load = held_experts(v, ids, w, live, layer["experts"], c)
    with jax.named_scope("moe.latent_out"):
        y = mm(y.astype(u.dtype), layer["latent_out"])
    stats = {"moe.tokens": jnp.sum(live).astype(F32),
             "moe.held_load": load.astype(F32),
             **kernel_counters(v, layer["experts"], load, c)}
    return y, ids, stats


def zero_stats(c: NemotronHConfig) -> dict:
    return experts.zero_stats(STAT_KEYS, c.experts_held)


# -------------------------------------------------------------------- model


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``driver.prefill`` says
    what the driver asks of it): one sublayer a layer."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    for i, (kind, layer) in enumerate(zip(c.hybrid_override_pattern,
                                          params["layers"])):
        u = stack_norm(x, layer["norm"], c.layer_norm_epsilon)
        if kind != EXPERTS:
            x = residual(x, attend(u, f"l{i}", layer["mixer"]))
            continue
        y, ids, s = moe_share(u, layer, c, live)
        stats = experts.add_stats(stats, s)
        touched += jnp.sum(s["moe.held_load"] > 0).astype(F32)
        chosen.append(ids)
        x = residual(residual(x, y),
                     relu2(u, layer["shared"], "ffn.shared"))
    return x, stats, chosen, touched


def prefill(params, tokens, lengths, config: NemotronHConfig,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over Nemotron-H's stack and blocks: what comes
    back for a block is a state block's ``{"ssm", "conv"}`` of R rows or an
    attention block's per-token ``{"k", "v"}: (R, KV, P, d)``."""
    blocks = blocks_of(config)
    out = driver.prefill(_layers, blocks, params, tokens, lengths, config,
                         policy or bf16_policy(), **kwargs)
    out[2].update(state.prefill_stats(blocks, tokens.shape, lengths))
    return out


def caches_from(rows, lengths, config: NemotronHConfig, max_len: int):
    """What :func:`prefill` returned, as the caches of R slots in an engine
    of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def decode_step(params, tok, pos, caches, live, config: NemotronHConfig,
                policy: Policy | None = None, **kwargs):
    """``driver.decode_step`` over Nemotron-H's stack and blocks."""
    blocks = blocks_of(config)
    return driver.decode_step(
        _layers, blocks,
        lambda dt, caches, pos, live: decode_stats(blocks, caches, pos, live),
        params, tok, pos, caches, live, config, policy or bf16_policy(),
        **kwargs)


class NemotronHFamily(driver.Family):
    name = "nemotron_h"
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

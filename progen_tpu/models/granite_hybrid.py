"""Granite 4.0-H (``model_type`` ``granitemoehybrid`` with no experts):
Mamba-2 layers whose per-slot state is a fixed carry, beside a few
grouped-query attention layers with NO positional embedding whose keys grow
with the request, a shared SwiGLU MLP in every layer, four muP multipliers
and a head tied to the embedding.  Whole on one chip: nothing is a share.

What Granite alone has: its config, the attention block's projection,
the layer's wiring with its multipliers and the seeded weights' layout.
The model driver and the engine's seam are ``models/driver.py``; the
grown-key cache and its decode step are ``models/kv.py`` (shared with
``models/trinity.py``); the Mamba-2 mixer and what it states about its
cache are ``models/state.py`` (shared with ``models/nemotron_h.py``: the
block was here until a second family needed it; Granite gives it its sizes,
``mamba_n_groups`` 1); the recurrence and the convolution are
``ops/ssd.py``, the attention cores ``ops/gqa.py``.

``x0 = E[token] * embedding_multiplier``.  Layer ``l``, both kinds, pre-norm
with scaled branches (``N_*`` RMSNorms with a learned scale)::

    a   = x + residual_multiplier * Mixer_l(N_in(x))
    out = a + residual_multiplier * W_d(silu(u W_g) * (u W_u)),  u = N_post(a)

``logits = N_f(x) E^T / logits_scaling``.  The MLP's published
``input_linear`` is ``[W_g | W_u]`` side by side; they are two matrices
here.

**Attention mixer** (``layer_types[l] == "attention"``): ``q = u W_q`` (H
heads of d), ``k = u W_k``, ``v = u W_v`` (KV heads of d), no bias, no
rotation (``position_embedding_type`` ``nope``), causal, scores times
``attention_multiplier`` (published 1/64 at d = 64: NOT ``d^-1/2``),
softmax in float32, each key/value head serving ``H / KV`` query heads,
result ``W_o``.  Its cache is ``models/kv.py``'s grown one.

**Mamba-2 mixer** (``"mamba"``): ``[z (I) | xBC (I + 2 N) | dt (heads)] =
u W_in`` in that order, ``I = mamba_expand * hidden = heads * d_head``;
``xBC_t <- silu(sum_j w_conv[:, j] * xBC_{t-3+j} + b_conv)`` (depthwise,
causal, zeros before the row's first token); ``[x (heads, d_head) | B (N) |
C (N)] = xBC_t`` (``mamba_n_groups`` 1: B and C shared by all heads); per
head in float32 ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` (no
clamp on ``dt``: ``time_step_limit`` is the default), then the recurrence of
``ops/ssd.py`` plus ``D_h x_t``; ``y <- RMSNorm_w(y * silu(z))`` over all
``I`` channels (the gate BEFORE the norm, one group), then ``W_out``.

**The state block's cache** (``models/state.py:StateBlock``): ``{"ssm":
(slots, heads, d_head, N) float32, "conv": (slots, d_conv - 1, I + 2 N)}``,
2.1 MB a slot and layer at the published widths whatever ``max_len``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.models import driver, kv, state
from progen_tpu.models.driver import (  # noqa: F401
    F32,
    bf16_policy,
    mm,
    residual,
    rms_norm,
    stack_norm,
    swiglu,
)

MAMBA, ATTENTION = "mamba", "attention"
_PUBLISHED_LAYERS = tuple(
    ATTENTION if i % 10 == 5 else MAMBA for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published keys (catalog names) plus the scales of the seeded
    weights."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: tuple = _PUBLISHED_LAYERS      # MAMBA / ATTENTION a layer
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12
    residual_multiplier: float = 0.22
    logits_scaling: float = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    num_local_experts: int = 0
    position_embedding_type: str = "nope"
    tie_word_embeddings: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    # seeded weights (``init_params``): the stream's RMS after the embedding
    # multiplier (small, so that the tied head does not hand every token its
    # own logit), the spread of the logits the last norm's scale is set
    # for, the spread of an attention score, and the range the per-head
    # step ``softplus(dt_bias)`` and ``A`` are drawn from (log-uniform)
    embed_rms: float = 0.0625
    logit_std: float = 1.0
    attn_score_std: float = 1.0
    dt_range: tuple = (0.001, 0.1)
    a_range: tuple = (1.0, 16.0)
    # the engine pads primes to ``prefill_bucket * 2^k`` tokens
    prefill_bucket: int = 128

    # what the shared code reads under its own names
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def embed_gain(self) -> float:
        return self.embedding_multiplier

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @classmethod
    def from_dict(cls, d) -> "GraniteHybridConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        for key in ("layer_types", "dt_range", "a_range"):
            if key in d:
                d[key] = tuple(d[key])
        return cls(**d)

    def __post_init__(self):
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {MAMBA, ATTENTION}):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {MAMBA!r} or {ATTENTION!r}: {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split "
                f"{self.hidden_size} columns over "
                f"{self.num_key_value_heads} key/value heads")
        if self.mamba_inner != self.mamba_expand * self.hidden_size:
            raise ValueError(
                f"{self.mamba_n_heads} heads of {self.mamba_d_head} are not "
                f"{self.mamba_expand} x hidden {self.hidden_size}")
        unsupported = {
            "num_local_experts": 0, "mamba_n_groups": 1,
            "position_embedding_type": "nope", "mamba_conv_bias": True,
            "mamba_proj_bias": False, "attention_bias": False,
            "tie_word_embeddings": True}
        for key, only in unsupported.items():
            if getattr(self, key) != only:
                raise ValueError(
                    f"{key} {getattr(self, key)!r} is not supported: the "
                    f"served model has {only!r}")


# ------------------------------------------------------------------ weights


def _init_attn(key, c: GraniteHybridConfig, dt):
    h, d = c.hidden_size, c.head_dim
    q, kvw = c.num_attention_heads * d, c.num_key_value_heads * d
    ks = jax.random.split(key, 4)
    # q . k of unit-variance entries spreads by sqrt(d); the published
    # multiplier presumes what training grew, so both are scaled up until a
    # score spreads by ``attn_score_std``
    grow = math.sqrt(c.attn_score_std / (c.attention_multiplier
                                         * math.sqrt(d)))
    return {
        "wq": driver.normal(ks[0], (h, q), grow * h ** -0.5, dt),
        "wk": driver.normal(ks[1], (h, kvw), grow * h ** -0.5, dt),
        "wv": driver.normal(ks[2], (h, kvw), h ** -0.5, dt),
        "wo": driver.normal(ks[3], (q, h), q ** -0.5, dt),
    }


def _init_layer(key, c: GraniteHybridConfig, dt, kind: str):
    ks = jax.random.split(key, 3)
    mixer = (state_block(c).init_weights(
        ks[1], c.hidden_size, dt, c.dt_range, c.a_range)
        if kind == MAMBA else _init_attn(ks[1], c, dt))
    return {"norm": driver.init_norm(ks[0], (2, c.hidden_size), dt),
            "mixer": mixer,
            "ffn": driver.init_ffn(ks[2], c.hidden_size,
                                   c.shared_intermediate_size, 1.0, dt)}


def init_params(config: GraniteHybridConfig, key,
                policy: Policy | None = None):
    policy = policy or bf16_policy()
    c = config
    layer = {kind: jax.jit(partial(_init_layer, c=c, dt=policy.param_dtype,
                                   kind=kind))
             for kind in (MAMBA, ATTENTION)}
    embed_std = c.embed_rms / c.embedding_multiplier
    return driver.init_params(
        c, key, policy, lambda k, i: layer[c.layer_types[i]](k),
        embed_std=embed_std, tied_head=True,
        final_norm_gain=c.logit_std * c.logits_scaling
        / (embed_std * math.sqrt(c.hidden_size)))


# ------------------------------------------------------------------- blocks


class AttentionBlock(kv.KVBlock):
    """A full-attention block (``models/kv.py`` has the grown cache and the
    step): plain projections, no gate, no norm, no rotation, scores scaled
    by ``attention_multiplier``."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__(config.num_key_value_heads, config.head_dim,
                         config.attention_multiplier, None)
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


def state_block(c: GraniteHybridConfig) -> state.StateBlock:
    """Granite's sizes of the shared Mamba-2 block."""
    return state.StateBlock(c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                            c.mamba_n_groups, c.mamba_d_conv, c.rms_norm_eps,
                            c.mamba_chunk_size)


def blocks_of(c: GraniteHybridConfig) -> dict:
    kinds = {MAMBA: state_block(c), ATTENTION: AttentionBlock(c)}
    return {f"l{i}": kinds[kind] for i, kind in enumerate(c.layer_types)}


def mamba_layers(c: GraniteHybridConfig) -> int:
    return sum(kind == MAMBA for kind in c.layer_types)


# device-side counters, all float32 sums (docs/OBSERVABILITY.md section 3):
# the state block's four; the attention blocks' as Trinity's full blocks
STAT_KEYS = state.STAT_KEYS + ("attn.decode_rows", "attn.context_tokens",
                               "attn.full_rows_read")


def decode_stats(blocks: dict, c: GraniteHybridConfig, caches, pos,
                 live) -> dict:
    attn = kv.decode_stats(blocks, caches, pos, live)
    return {**state.decode_stats(blocks, live),
            **{k: attn[k] for k in STAT_KEYS if k in attn}}


# -------------------------------------------------------------------- model


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``driver.prefill`` says
    what the driver asks of it): no experts, so no ``moe.*`` counter, no
    choices and nothing touched."""
    m, eps = jnp.asarray(c.residual_multiplier, x.dtype), c.rms_norm_eps
    for i, layer in enumerate(params["layers"]):
        n = layer["norm"]
        a = residual(x, m * attend(stack_norm(x, n[0], eps), f"l{i}",
                                   layer["mixer"]))
        x = residual(a, m * swiglu(stack_norm(a, n[1], eps), layer["ffn"]))
    return x, driver.zero_scalars(STAT_KEYS), [], 0.0


def prefill(params, tokens, lengths, config: GraniteHybridConfig,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over Granite's stack and blocks: what comes back
    for a block is a state block's ``{"ssm", "conv"}`` of R rows or an
    attention block's per-token ``{"k", "v"}: (R, KV, P, d)``."""
    out = driver.prefill(_layers, blocks_of(config), params, tokens, lengths,
                         config, policy or bf16_policy(), **kwargs)
    out[2].update(state.prefill_stats(blocks_of(config), tokens.shape,
                                      lengths))
    return out


def caches_from(rows, lengths, config: GraniteHybridConfig, max_len: int):
    """What :func:`prefill` returned, as the caches of R slots in an engine
    of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def decode_step(params, tok, pos, caches, live, config: GraniteHybridConfig,
                policy: Policy | None = None, **kwargs):
    """``driver.decode_step`` over Granite's stack and blocks."""
    blocks = blocks_of(config)
    return driver.decode_step(
        _layers, blocks,
        lambda dt, caches, pos, live: decode_stats(blocks, config, caches,
                                                   pos, live),
        params, tok, pos, caches, live, config, policy or bf16_policy(),
        **kwargs)


class GraniteHybridFamily(driver.Family):
    name = "granite_hybrid"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    blocks_of = staticmethod(blocks_of)

    def attention_stats(self, dt, caches, pos, live):
        return decode_stats(self.blocks, self.config, caches, pos, live)

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, rows, stats = prefill(params, tokens, lengths, self.config,
                                      self.policy)
        return logits[:, 0], caches_from(rows, lengths, self.config,
                                         max_len), stats

"""LFM2 (``model_type`` ``lfm2_moe``): a stack in which three layers of four
are DOUBLE-GATED SHORT CONVOLUTIONS that keep no keys — a mixer whose whole
cache is the last ``conv_L_cache - 1`` gated inputs, as large at token 1 as
at token 100,000 — beside grouped-query attention blocks whose keys grow
with the request; two leading dense SwiGLU layers, then expert layers with
sigmoid top-k routing under a selection bias and no shared expert; a final
``embedding_norm`` and a head tied to the embedding.  Here the chip holds
EVERY expert of its layers (``experts_held == num_experts``: one stage of a
pipeline), a token a row a step.

What LFM2 alone has: its config, the short-convolution block and what it
states about its cache, the attention block's projection, the two-norm
layer's wiring and the seeded weights' layout.  The model driver and the
engine's seam are ``models/driver.py``; the grown-key cache and its decode
step are ``models/kv.py`` (shared with ``models/trinity.py``,
``models/granite_hybrid.py`` and ``models/sdar.py``); the held experts'
product, its counters and the sigmoid router are ``models/experts.py``
(the router is Trinity's equation at other numbers:
``experts.sigmoid_route``); the depthwise convolution's three forms are
``ops/ssd.py``'s (Granite's, here with no bias and no activation).

The short-convolution block lives HERE and not beside ``KVBlock``:
Granite's tail is one leaf of its Mamba-2 block's state, gathered and
shifted inside that block between its own projection, ``silu`` and
recurrence, so there is no block of Granite's that this one could be; what
the two share is the convolution itself, and that is ``ops/ssd.py``.

``x0 = E[token]``.  Layer ``l`` (pre-norm, ``N_*`` RMSNorms with a learned
scale that multiplies as it is, statistics in float32, eps ``norm_eps``)::

    r   = x + Mixer_l(N_op(x))            operator_norm
    out = r + FFN_l(N_ffn(r))             ffn_norm

``FFN_l`` is a dense SwiGLU of ``intermediate_size`` for ``l <
num_dense_layers`` and the expert layer after.  ``logits = N_emb(x_L) E^T``.

**Short convolution** (``layer_types[l] == "conv"``), ``u (T, h)``: ``[B |
C | X] = u W_in`` (``h -> 3 h``, no bias, in that order), ``z = B * X``,
``c_t = sum_j w[:, j] * z_{t-(K-1)+j}`` (depthwise, causal, ``K =
conv_L_cache`` taps, no bias, no activation, zeros before the row's first
token), ``out = (C * c) W_out``.  **Its cache is the tail** ``z_{t-1}, ..,
z_{t-K+1}``: ``{"conv": (slots, K - 1, h)}`` in the compute dtype, whatever
``max_len``.  A prefill of right-padded rows hands over the tail at each
row's TRUE length (zeros where the row is shorter than the tail), and an
admission overwrites ALL of a slot's tail: a prompt of one token leaves one
zero row in it, never the last request's.

**Attention** (``"full_attention"``): ``q = u W_q`` (H heads of d = hidden
/ H), ``k = u W_k``, ``v = u W_v`` (KV heads of d), no bias; q and k
RMS-normed per head over d, then rotated (half-split RoPE on all d,
``rope_theta``, positions from 0); causal, scores times ``d^-1/2``, softmax
in float32, ``H / KV`` query heads a key head; result ``W_o``.  No window,
no gate.  Its cache is ``models/kv.py``'s grown one.

**Expert layer**, float32 router: ``s = sigmoid(u W_r)`` over
``num_experts``; the ``num_experts_per_tok`` largest of ``s + b`` are
chosen (``use_expert_bias``: ``b`` a float32 buffer that picks and does not
weigh); ``w_i = routed_scaling_factor * s_i / (sum_chosen s + 1e-6)``
(``norm_topk_prob``); ``y = sum_i w_i W2_i(silu(W1_i u) * W3_i u)``.

**The share.**  The router keeps its width and top-k whatever is held; the
layer adds the terms of the held experts (``first_expert .. first_expert +
experts_held - 1``) and leaves out the absent ones'.  The served cell holds
all of them.
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
from progen_tpu.ops import ssd

CONV, FULL = "conv", "full_attention"
_PUBLISHED_LAYERS = tuple(
    FULL if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    num_dense_layers: int = 2
    layer_types: tuple = _PUBLISHED_LAYERS          # CONV / FULL a layer
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    # the share: experts ``first_expert .. first_expert + held - 1``
    experts_held: int = 32
    first_expert: int = 0
    # seeded weights (``init_params``): the stream's RMS after the
    # embedding (small, so that the tied head does not hand every token its
    # own logit), the spread of the logits the last norm's scale is set
    # for, the router logits' spread a token, and the selection bias's (in
    # units of a score: it moves some choices)
    embed_rms: float = 0.0625
    logit_std: float = 1.0
    router_logit_std: float = 1.0
    router_bias_std: float = 0.02
    # the engine pads primes to ``prefill_bucket * 2^k`` tokens
    prefill_bucket: int = 128

    embed_gain = 1.0

    # what the shared code reads under its own names
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def rms_norm_eps(self) -> float:
        return self.norm_eps

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
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def rope_inv_freq(self, d: int):
        return 1.0 / (self.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d))

    @classmethod
    def from_dict(cls, d) -> "LFM2Config":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        if "layer_types" in d:
            d["layer_types"] = tuple(d["layer_types"])
        return cls(**d)

    def __post_init__(self):
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {CONV, FULL}):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {CONV!r} or {FULL!r}: {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split "
                f"{self.hidden_size} columns over "
                f"{self.num_key_value_heads} key/value heads")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.num_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.num_experts} routed experts")
        if self.conv_L_cache < 2:
            raise ValueError(
                f"conv_L_cache {self.conv_L_cache}: a short convolution has "
                "at least two taps (its cache is the tail before the token)")
        unsupported = {"conv_bias": False, "use_expert_bias": True,
                       "tie_word_embeddings": True}
        for key, only in unsupported.items():
            if getattr(self, key) != only:
                raise ValueError(
                    f"{key} {getattr(self, key)!r} is not supported: the "
                    f"served model has {only!r}")


# ------------------------------------------------------------------ weights


def _init_conv(key, c: LFM2Config, dt):
    h, taps = c.hidden_size, c.conv_L_cache
    ks = jax.random.split(key, 3)
    return {
        "in_proj": driver.normal(ks[0], (h, 3 * h), h ** -0.5, dt),
        "conv_w": driver.normal(ks[1], (h, taps), taps ** -0.5, dt),
        "out_proj": driver.normal(ks[2], (h, h), h ** -0.5, dt),
    }


def _init_attn(key, c: LFM2Config, dt):
    h, d = c.hidden_size, c.head_dim
    q, kvw = c.num_attention_heads * d, c.num_key_value_heads * d
    ks = jax.random.split(key, 6)
    return {
        "wq": driver.normal(ks[0], (h, q), h ** -0.5, dt),
        "wk": driver.normal(ks[1], (h, kvw), h ** -0.5, dt),
        "wv": driver.normal(ks[2], (h, kvw), h ** -0.5, dt),
        "wo": driver.normal(ks[3], (q, h), q ** -0.5, dt),
        "q_norm": driver.init_norm(ks[4], (d,), dt),
        "k_norm": driver.init_norm(ks[5], (d,), dt),
    }


def _init_layer(key, c: LFM2Config, dt, kind: str, dense: bool):
    ks = jax.random.split(key, 5)
    h = c.hidden_size
    mixer = _init_conv if kind == CONV else _init_attn
    layer = {"norm": driver.init_norm(ks[0], (2, h), dt),
             "mixer": mixer(ks[1], c, dt)}
    if dense:
        layer["ffn"] = driver.init_ffn(ks[2], h, c.intermediate_size, 1.0, dt)
        return layer
    # logits spread by ``router_logit_std`` per token (the normed input has
    # unit RMS), so choices differ between tokens; the bias is a float32
    # buffer, as the release keeps it
    layer["router"] = {
        "w": driver.normal(ks[2], (h, c.num_experts),
                           c.router_logit_std * h ** -0.5, dt),
        "bias": driver.normal(ks[3], (c.num_experts,), c.router_bias_std,
                              F32)}
    layer["experts"] = driver.init_ffn(ks[4], h, c.moe_intermediate_size,
                                       1.0, dt, lead=(c.experts_held,))
    return layer


def init_params(config: LFM2Config, key, policy: Policy | None = None):
    """Seeded weights in the driver's layout; the head is the embedding
    (no ``"head"``), and ``"final_norm"`` is the ``embedding_norm``."""
    policy = policy or bf16_policy()
    c = config
    layer = {(kind, dense): jax.jit(partial(
        _init_layer, c=c, dt=policy.param_dtype, kind=kind, dense=dense))
        for kind in (CONV, FULL) for dense in (True, False)}
    return driver.init_params(
        c, key, policy,
        lambda k, i: layer[c.layer_types[i], i < c.num_dense_layers](k),
        embed_std=c.embed_rms, tied_head=True,
        final_norm_gain=c.logit_std / (c.embed_rms
                                       * math.sqrt(c.hidden_size)))


# ------------------------------------------------------------------- blocks


LANE = 128      # the chip's lane tile: a cache row narrower than it is padded


def heads_packed(kv_heads: int, head_dim: int) -> int:
    """Key/value heads that share one cache row: as many adjacent heads as
    fill ``LANE`` columns (a divisor of ``kv_heads``), 1 where a head fills
    it alone."""
    pack = max(1, LANE // head_dim)
    while kv_heads % pack:
        pack -= 1
    return pack


class AttentionBlock(kv.KVBlock):
    """A full-attention block (``models/kv.py`` has the grown cache and the
    step): q and k normed per head, then rotated; no window, no gate.

    **The cache row is two heads wide.**  A key of 64 columns is half the
    chip's lane tile: ``(slots, 8, rows, 64)`` is held padded to 128
    columns wherever a row is the tile's minor axis (the chunk program's
    scan carry: twice the bytes, 4.8 GB of temporaries at 128 slots), so
    this block states ``KV / 2`` heads of ``2 d`` to ``models/kv.py``:
    adjacent key/value heads ``2 j, 2 j + 1`` side by side in one row, and
    each query head zero-filled to ``2 d`` outside the half of ITS
    key/value head.  ``q . k`` over ``2 d`` is then the head's own ``d``
    products and ``d`` zeros, the softmax is the head's own, and of ``P v``
    over the packed values :meth:`finish` keeps the head's half: the
    equations' result, bit for bit what ``d``-wide rows would give, from
    rows that fill the tile (the same bytes a token; the cores' products
    are twice as wide and half of them zeros)."""

    def __init__(self, config: LFM2Config):
        c, d = config, config.head_dim
        self.pack = heads_packed(c.num_key_value_heads, d)
        super().__init__(c.num_key_value_heads // self.pack, self.pack * d,
                         1.0 / math.sqrt(d), None)
        self.config = config
        # which half of its packed row a query head's key/value head is
        group = c.num_attention_heads // c.num_key_value_heads
        self.half = (jnp.arange(c.num_attention_heads) // group) % self.pack

    def _halves(self, dtype):
        """``(H, pack)``: 1 at a query head's own half."""
        return jax.nn.one_hot(self.half, self.pack, dtype=dtype)

    def project(self, x, p, positions):
        c, d = self.config, self.config.head_dim
        with jax.named_scope("attn.project"):
            q = mm(x, p["wq"])
            q = q.reshape(q.shape[:-1] + (c.num_attention_heads, d))
            k = mm(x, p["wk"])
            k = k.reshape(k.shape[:-1] + (c.num_key_value_heads, d))
            v = mm(x, p["wv"]).reshape(k.shape)
            q = driver.rope(rms_norm(q, p["q_norm"], c.norm_eps), positions,
                            c.rope_inv_freq)
            k = driver.rope(rms_norm(k, p["k_norm"], c.norm_eps), positions,
                            c.rope_inv_freq)
            packed = k.shape[:-2] + (self.kv_heads, self.head_dim)
            k, v = k.reshape(packed), v.reshape(packed)
            q = (q[..., None, :] * self._halves(q.dtype)[..., None]).reshape(
                q.shape[:-1] + (self.head_dim,))
        return q, k, v, None

    def finish(self, o, rest, p):
        c = self.config
        o = o.reshape(o.shape[:-1] + (c.num_attention_heads, self.pack,
                                      c.head_dim))
        o = jnp.einsum("...hpd,hp->...hd", o, self._halves(o.dtype))
        return mm(o.reshape(o.shape[:-2] + (-1,)), p["wo"])


class ShortConvBlock:
    """A double-gated short convolution and what it states about its cache
    (``models/driver.py`` says what a block is): the tail alone, ``{"conv":
    (slots, conv_L_cache - 1, hidden)}``; the module docstring has the
    equations.  One token a row a step: no ``decode_block``."""

    def __init__(self, config: LFM2Config):
        self.config = config
        self.tail_rows = config.conv_L_cache - 1

    def init_cache(self, slots: int, max_len: int, dtype):
        return {"conv": jnp.zeros(
            (slots, self.tail_rows, self.config.hidden_size), dtype)}

    def _gates(self, u, p):
        """``(z = B * X, C)`` of ``u (..., h)``."""
        with jax.named_scope("shortconv.in"):
            b, c, x = jnp.split(mm(u, p["in_proj"]), 3, axis=-1)
            return b * x, c

    def _out(self, conv, c, p):
        """``conv`` float32 from the taps: rounded once, gated, projected."""
        with jax.named_scope("shortconv.out"):
            return mm(c * conv.astype(c.dtype), p["out_proj"])

    def prefill(self, u, p, lengths):
        """The mixer over ``u (R, P, h)``; what the slot will hold is each
        row's last ``conv_L_cache - 1`` REAL gated inputs (zeros where the
        row is shorter)."""
        z, c = self._gates(u, p)
        with jax.named_scope("shortconv.conv"):
            tail = ssd.conv_tail(z, lengths, self.config.conv_L_cache)
            conv = ssd.causal_conv(z, p["conv_w"], None)
        return self._out(conv, c, p), {"conv": tail}

    def cache_rows(self, rows, lengths, max_len: int):
        return rows

    def decode(self, u, pos, cache, p):
        """One token a row: the taps over ``(tail, z_t)``, the tail shifted
        by one row."""
        z, c = self._gates(u, p)
        with jax.named_scope("shortconv.conv"):
            conv, tail = ssd.conv_step(cache["conv"], z, p["conv_w"], None)
        return self._out(conv, c, p), {"conv": tail}


def blocks_of(c: LFM2Config) -> dict:
    kinds = {CONV: ShortConvBlock(c), FULL: AttentionBlock(c)}
    return {f"l{i}": kinds[kind] for i, kind in enumerate(c.layer_types)}


# device-side counters, all float32 sums (docs/OBSERVABILITY.md section 3):
# the experts' as every family with a share; the attention blocks' as
# Trinity's full blocks; tokens through a short-convolution block in decode
# steps (live rows x such blocks a step: each reads the block's whole tail
# and writes one row of it, so the tails' bytes follow from this count)
STAT_KEYS = experts.STAT_KEYS + (
    "attn.decode_rows", "attn.context_tokens", "attn.full_rows_read",
    "conv.tokens")


def decode_stats(blocks: dict, caches, pos, live) -> dict:
    """A decode step's ``attn.*`` and ``conv.*`` counters."""
    attn = kv.decode_stats(blocks, caches, pos, live)
    convs = sum(isinstance(b, ShortConvBlock) for b in blocks.values())
    return {**{k: attn[k] for k in STAT_KEYS if k in attn},
            "conv.tokens": convs * jnp.sum(live).astype(F32)}


# ------------------------------------------------------------------ experts


def route(u, router, c: LFM2Config):
    """``(ids (T, k), weights (T, k))``, float32 throughout
    (``models/experts.py:sigmoid_route`` at LFM2's ``1e-6``)."""
    return experts.sigmoid_route(
        u, router, c.num_experts_per_tok, norm=c.norm_topk_prob,
        scale=c.routed_scaling_factor, eps=1e-6)


def moe_share(u, layer, c: LFM2Config, live):
    """This chip's share of the experts over ``u (T, h)`` and what it
    counted over the ``live`` tokens."""
    ids, w = route(u, layer["router"], c)
    y, load = held_experts(u, ids, w, live, layer["experts"], c)
    stats = {"moe.tokens": jnp.sum(live).astype(F32),
             "moe.held_load": load.astype(F32),
             **kernel_counters(u, layer["experts"], load, c)}
    with jax.named_scope("moe.experts"):    # the terms' own rounding
        y = y.astype(u.dtype)
    return y, ids, stats


def zero_stats(c: LFM2Config) -> dict:
    return experts.zero_stats(STAT_KEYS, c.experts_held)


# -------------------------------------------------------------------- model


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``driver.prefill`` says
    what the driver asks of it)."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    eps = c.norm_eps
    for i, layer in enumerate(params["layers"]):
        n = layer["norm"]
        r = residual(x, attend(stack_norm(x, n[0], eps), f"l{i}",
                               layer["mixer"]))
        u = stack_norm(r, n[1], eps)
        if "experts" not in layer:
            x = residual(r, swiglu(u, layer["ffn"]))
            continue
        y, ids, s = moe_share(u, layer, c, live)
        stats = experts.add_stats(stats, s)
        touched += jnp.sum(s["moe.held_load"] > 0).astype(F32)
        chosen.append(ids)
        x = residual(r, y)
    return x, stats, chosen, touched


def prefill(params, tokens, lengths, config: LFM2Config,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over LFM2's stack and blocks: what comes back for
    a block is a short convolution's ``{"conv"}`` tail of R rows or an
    attention block's per-token ``{"k", "v"}: (R, KV, P, d)``."""
    return driver.prefill(_layers, blocks_of(config), params, tokens, lengths,
                          config, policy or bf16_policy(), **kwargs)


def caches_from(rows, lengths, config: LFM2Config, max_len: int):
    """What :func:`prefill` returned, as the caches of R slots in an engine
    of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def decode_step(params, tok, pos, caches, live, config: LFM2Config,
                policy: Policy | None = None, **kwargs):
    """``driver.decode_step`` over LFM2's stack and blocks."""
    blocks = blocks_of(config)
    return driver.decode_step(
        _layers, blocks,
        lambda dt, caches, pos, live: decode_stats(blocks, caches, pos, live),
        params, tok, pos, caches, live, config, policy or bf16_policy(),
        **kwargs)


class LFM2Family(driver.Family):
    name = "lfm2"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    blocks_of = staticmethod(blocks_of)

    def attention_stats(self, dt, caches, pos, live):
        return decode_stats(self.blocks, caches, pos, live)

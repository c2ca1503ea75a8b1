"""SDAR (``model_type`` ``sdar_moe``): a Qwen3-MoE layer stack — grouped-
query attention with RMSNorm on q and k, every layer an expert layer with
softmax top-k routing and no shared expert — that GENERATES BY DIFFUSION
OVER BLOCKS: the attention mask is causal across blocks of ``block_length``
tokens and open inside one, the logits at a position predict that
position's own token (no shift), and a block of mask tokens is denoised a
few positions a forward, then committed to the cache — by the forward that
opens the next block, which carries the finished block in front of the one
in progress (``models/kv.py:KVBlock.decode_block``).

What SDAR alone has: its config, the router, the two-norm layer's wiring,
the attention block's projection and the seeded weights' layout.  The model
driver, its block step and the engine's seam are ``models/driver.py``; the
grown-key cache, the block of B queries and the withheld write are
``models/kv.py`` (shared with ``models/trinity.py`` and
``models/granite_hybrid.py``); the experts' grouped product and the
``moe.*`` counters are ``models/experts.py`` — here the chip holds EVERY
expert of a layer (``experts_held == num_experts``).  How a block is
denoised (the draw, its confidence, which positions are kept, the commit)
is the engine's block step (``decode/engine.py``), from what
:class:`SDARFamily` states.

Layer ``l`` (pre-norm, ``N_*`` RMSNorms with a learned scale)::

    a   = x + W_o Attn(q, k, v)     q = rope(N_q(u W_q)), k = rope(N_k(u W_k)),
                                    v = u W_v, u = N_in(x)
    out = a + sum_{e in top8(p)} (p_e / sum_top8 p) E_e(t)
                                    t = N_post(a), p = softmax(t W_r)

``x0 = E[token]``; 32 query heads over 4 key/value heads of 128, no bias;
``N_q`` / ``N_k`` over the 128 of each head, before the rotation; half-split
RoPE on all 128, ``rope_theta`` 1e6; scores scaled by ``128^-1/2``, softmax
in float32; ``E_e`` a SwiGLU of width ``moe_intermediate_size``; ``logits =
N_f(x) W_head``, untied.

**The mask**, in prefill and in generation alike: query ``i`` sees key ``j``
iff ``j // B <= i // B``.  A prime of ``P`` tokens is prefilled over its
``P // B`` whole blocks; the last ``P % B`` tokens open the block in
progress beside mask tokens.
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
)
from progen_tpu.models.experts import held_experts, kernel_counters
from progen_tpu.ops import gqa

REMASKING = ("low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    """The published keys (catalog names), how a block is generated (the
    family's convention: the config carries no key for it) and the scales
    of the seeded weights."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144       # no layer is dense: unused
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    # generation by diffusion over blocks
    block_length: int = 4
    mask_token_id: int = 151669
    denoising_steps: int = 4
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    # seeded weights (``init_params``): the router logits' spread a token
    router_logit_std: float = 1.0
    # the engine pads primes to ``prefill_bucket * 2^k`` tokens
    prefill_bucket: int = 128

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
    def experts_held(self) -> int:
        return self.num_experts         # the chip holds the whole layer

    first_expert = 0
    embed_gain = 1.0

    @property
    def seq_len(self) -> int:
        return self.max_position_embeddings

    def rope_inv_freq(self, d: int):
        return 1.0 / (self.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d))

    @classmethod
    def from_dict(cls, d) -> "SDARConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        if "mlp_only_layers" in d:
            d["mlp_only_layers"] = tuple(d["mlp_only_layers"])
        return cls(**d)

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split over "
                f"{self.num_key_value_heads} key/value heads")
        if (self.decoder_sparse_step != 1 or self.mlp_only_layers
                or self.attention_bias or self.tie_word_embeddings
                or self.use_sliding_window):
            raise ValueError(
                "every layer is an expert layer over full attention with "
                "no bias and an untied head: decoder_sparse_step "
                f"{self.decoder_sparse_step}, mlp_only_layers "
                f"{self.mlp_only_layers}, attention_bias "
                f"{self.attention_bias}, tie_word_embeddings "
                f"{self.tie_word_embeddings}, use_sliding_window "
                f"{self.use_sliding_window} are not supported")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} is not among the "
                f"{self.vocab_size} tokens")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} is not in 1.."
                f"block_length {self.block_length}: every denoise forward "
                "fills a position")
        if self.remasking not in REMASKING:
            raise ValueError(
                f"remasking {self.remasking!r} is not one of {REMASKING}")


# ------------------------------------------------------------------ weights


def _init_layer(key, c: SDARConfig, dt):
    ks = jax.random.split(key, 9)
    h, d = c.hidden_size, c.head_dim
    q, kvw = c.num_attention_heads * d, c.num_key_value_heads * d
    return {
        "norm": driver.init_norm(ks[0], (2, h), dt),
        "attn": {
            "wq": driver.normal(ks[1], (h, q), h ** -0.5, dt),
            "wk": driver.normal(ks[2], (h, kvw), h ** -0.5, dt),
            "wv": driver.normal(ks[3], (h, kvw), h ** -0.5, dt),
            "wo": driver.normal(ks[4], (q, h), q ** -0.5, dt),
            "q_norm": driver.init_norm(ks[5], (d,), dt),
            "k_norm": driver.init_norm(ks[6], (d,), dt),
        },
        # logits spread by ``router_logit_std`` per token (the normed input
        # has unit RMS), so choices differ between tokens
        "router": {"w": driver.normal(
            ks[7], (h, c.num_experts), c.router_logit_std * h ** -0.5, dt)},
        "experts": driver.init_ffn(ks[8], h, c.moe_intermediate_size, 1.0,
                                   dt, lead=(c.num_experts,)),
    }


def init_params(config: SDARConfig, key, policy: Policy | None = None):
    """Seeded weights; the embedding row of the mask token is seeded like
    any other."""
    policy = policy or bf16_policy()
    layer = jax.jit(partial(_init_layer, c=config, dt=policy.param_dtype))
    return driver.init_params(config, key, policy, lambda k, i: layer(k))


# ---------------------------------------------------------------- attention


class KVBlock(kv.KVBlock):
    """SDAR's attention block over ``models/kv.py``'s grown keys under the
    block mask: q and k normed per head, then rotated; no gate."""

    def __init__(self, config: SDARConfig):
        super().__init__(config.num_key_value_heads, config.head_dim,
                         1.0 / math.sqrt(config.head_dim), None,
                         config.block_length)
        self.config = config

    def project(self, x, p, positions):
        c, d = self.config, self.config.head_dim
        with jax.named_scope("attn.project"):
            q = mm(x, p["wq"])
            q = q.reshape(q.shape[:-1] + (c.num_attention_heads, d))
            k = mm(x, p["wk"])
            k = k.reshape(k.shape[:-1] + (c.num_key_value_heads, d))
            v = mm(x, p["wv"]).reshape(k.shape)
            q = driver.rope(rms_norm(q, p["q_norm"], c.rms_norm_eps),
                            positions, c.rope_inv_freq)
            k = driver.rope(rms_norm(k, p["k_norm"], c.rms_norm_eps),
                            positions, c.rope_inv_freq)
        return q, k, v, None

    def finish(self, o, rest, p):
        return mm(o, p["wo"])


def blocks_of(c: SDARConfig) -> dict:
    block = KVBlock(c)
    return {f"l{i}": block for i in range(c.num_hidden_layers)}


ATTN_STAT_KEYS = kv.DECODE_STAT_KEYS + ("attn.prefill_pairs_allowed",
                                        "attn.prefill_pairs_visited")


def prefill_attention_stats(c: SDARConfig, n: int, lengths, dt) -> dict:
    """A prefill's ``attn.prefill_pairs_*`` counters over rows of ``lengths
    (R,)`` whole blocks padded to ``n``: the pairs the block mask allows
    and the pairs the lowering that runs computes, over the layers, per
    head."""
    lowering = gqa.prefill_lowering(n, c.head_dim, dt, None, c.block_length)
    layers = c.num_hidden_layers
    return {"attn.prefill_pairs_allowed": layers * gqa.pairs_allowed(
                lengths, None, c.block_length),
            "attn.prefill_pairs_visited": layers * gqa.pairs_visited(
                lengths, n, None, lowering)}


# ------------------------------------------------------------------ experts


def route(u, router, c: SDARConfig):
    """``(ids (T, k), weights (T, k))``, float32 throughout: the largest of
    ``softmax(u W_r)``, renormalised to sum to 1 (``norm_topk_prob``)."""
    return experts.softmax_route(u, router, c.num_experts_per_tok,
                                 norm=c.norm_topk_prob)


STAT_KEYS = experts.STAT_KEYS + ATTN_STAT_KEYS


def zero_stats(c: SDARConfig) -> dict:
    """Device-side counters, all float32 sums (docs/OBSERVABILITY.md §3)."""
    return experts.zero_stats(STAT_KEYS, c.experts_held)


# -------------------------------------------------------------------- model


def _layers(x, params, c, attend, live, tail=None):
    """The stack over ``x (T, h)`` flat tokens (``driver.prefill`` says
    what the driver asks of it).  ``tail``: the LAST layer's attention
    returns fewer rows than it was given (``driver.block_step``), and
    ``tail(array of all rows)`` gives those rows of it: the stack goes on
    with them alone."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    for i, layer in enumerate(params["layers"]):
        n, eps = layer["norm"], c.rms_norm_eps
        out = attend(stack_norm(x, n[0], eps), f"l{i}", layer["attn"])
        if out.shape[0] != x.shape[0]:
            x, live = tail(x), tail(live)
        a = residual(x, out)
        t = stack_norm(a, n[1], eps)
        ids, w = route(t, layer["router"], c)
        y, load = held_experts(t, ids, w, live, layer["experts"], c)
        stats = experts.add_stats(stats, {
            "moe.tokens": jnp.sum(live).astype(F32),
            "moe.held_load": load.astype(F32),
            **kernel_counters(t, layer["experts"], load, c)})
        touched += jnp.sum(load > 0).astype(F32)
        chosen.append(ids)
        with jax.named_scope("moe.experts"):    # the terms' own rounding
            y = y.astype(x.dtype)
        x = residual(a, y)
    return x, stats, chosen, touched


def whole_blocks(lengths, c: SDARConfig):
    """The tokens of primes of ``lengths`` that lie in whole blocks: what a
    prefill computes and caches (the rest opens the block in progress)."""
    return lengths // c.block_length * c.block_length


def prefill(params, tokens, lengths, config: SDARConfig,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over SDAR's stack under the block mask, of the
    WHOLE blocks of each row (``lengths`` may be any: the tokens past the
    last whole block are padding here); the per-token cache rows are
    ``{block: {"k", "v"}: (R, KV, P, d)}``."""
    policy = policy or bf16_policy()
    whole = whole_blocks(lengths, config)
    out = driver.prefill(_layers, blocks_of(config), params, tokens, whole,
                         config, policy, **kwargs)
    out[2].update(prefill_attention_stats(
        config, tokens.shape[1], whole, policy.compute_dtype))
    return out


def caches_from(rows, lengths, config: SDARConfig, max_len: int):
    """The per-token rows :func:`prefill` returned, as the caches of R
    slots in an engine of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def block_step(params, tok, pos0, caches, live, commit, config: SDARConfig,
               policy: Policy | None = None, **kwargs):
    """``driver.block_step`` over SDAR's stack and blocks: one forward of
    ``tok (S, B)`` at ``pos0 .. pos0 + B - 1``; where ``commit``, keys are
    written — ``tok``'s own, or with ``pending=(S, B)`` the finished block's
    at ``pos0 - B``, which then rides in front of ``tok``."""
    blocks = blocks_of(config)
    return driver.block_step(
        _layers, blocks,
        lambda dt, caches, pos0, live, riding: kv.block_decode_stats(
            blocks, caches, pos0, live, tok.shape[1], riding),
        params, tok, pos0, caches, live, commit, config,
        policy or bf16_policy(), **kwargs)


class SDARFamily(driver.Family):
    """SDAR behind the seam: it generates ``block_length`` tokens a row a
    step (``decode/family.py`` says what such a family states)."""

    name = "sdar"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    blocks_of = staticmethod(blocks_of)

    def __init__(self, config: SDARConfig, policy: Policy):
        super().__init__(config, policy)
        self.block_length = config.block_length
        self.mask_token_id = config.mask_token_id
        self.denoising_steps = config.denoising_steps
        self.remasking = config.remasking
        self.confidence_threshold = config.confidence_threshold

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, rows, stats = prefill(params, tokens, lengths, self.config,
                                      self.policy)
        return logits[:, 0], caches_from(rows, lengths, self.config,
                                         max_len), stats

    def decode_step(self, *args, **kwargs):
        raise NotImplementedError(
            "the sdar family generates a block a step: block_step")

    def block_step(self, params, tok, pos0, caches, live, commit,
                   pending=None):
        return block_step(params, tok, pos0, caches, live, commit,
                          self.config, self.policy, pending=pending)

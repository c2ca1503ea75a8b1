"""The model-step seam: what ``ServingEngine``'s plain dense path asks of a
model family, and ProGen's answer.

The engine owns slots, sampling, admission in runs of ``admit_rows``,
harvest, clocks, fault containment and spans; a family owns what is in a
cache and how a token moves through the model:

``name``, ``vocab`` (the logits' width), ``seq_len`` (the longest sequence
the model takes), ``position_masks`` (whether the slot state holds a logit
mask per WRITE POSITION, ``(S, L, V)``, or one row per slot, ``(S, V)``),
``idle_length`` (the ``lengths`` entry of an admission row that carries no
request: 0 where the prefill takes an empty row, so that its counters count
real prime tokens only; 1, a dummy one-token prime, where it does not)

What a family can do beyond the plain dense path it states itself, and the
engine tests nothing else (no family's name, no config's type):

``modes``
    the serving modes it has, of ``SERVING_MODES``; the engine refuses any
    other at construction with :class:`UnsupportedFamilyMode`
``step_model`` / ``prefill_model``
    the flax modules behind ``decode_step`` / ``prefill`` (None for a family
    that is plain functions)
``embedder(mesh, strategies)``
    the embedding program, or None for a family without one

**How it generates**, which the engine reads to choose the chunk program it
builds and nothing else of it:

``block_length``
    ``None``: one token a row a step — ``decode_step`` below, the engine's
    scan of ``chunk_size`` single-token steps, a first token drawn at
    admission.  A number ``B``: GENERATION BY DIFFUSION OVER BLOCKS — a
    step is one forward a row (``block_step``), a block of mask tokens is
    denoised a few positions a forward, its keys enter the cache in the
    forward that opens the NEXT block (the finished block rides in front of
    the block in progress: no forward is a commit's alone), and admission
    hands over a prime whose last ``P mod B`` tokens open the first block.
    Such a family also states
    ``mask_token_id`` (never drawn, never a committed token),
    ``denoising_steps`` (T: a block is filled in at most T denoise
    forwards), ``remasking`` (``low_confidence_static``: the ``B / T``
    masked positions of highest confidence take their draw each forward;
    ``low_confidence_dynamic``: every masked position whose confidence is
    over ``confidence_threshold`` does, and at least the static count) and
``block_step(params, tok (S, B), pos0 (S,), caches, live (S,), commit (S,), pending (S, B) = None)``
    ``(logits (S, B, V), caches, stats)``: the B tokens of each row (mask
    tokens among them) at ``pos0 .. pos0 + B - 1`` over the row's committed
    cache and each other.  The logits at a position predict that position's
    own token.  ``commit`` says in which rows keys enter the cache in this
    forward: with ``pending`` — each row's finished block at ``pos0 - B ..
    pos0 - 1``, not in the cache yet — they are the pending block's, which
    the forward carries in front of ``tok`` (``tok`` sees its keys beside
    its own; a row whose ``commit`` is false has no pending block, and what
    ``pending`` holds there reaches nothing); without, they are ``tok``'s
    own.  ``tok``'s keys are never stored by a forward that carries a
    pending block

``init_caches(slots, max_len)``
    the decode caches of ``slots`` rows, a pytree whose every leaf has the
    slot as its leading axis (the engine merges row-wise and knows no key)
``init_stats()``
    device-side counters carried in the slot state (``{}`` for none)
``prefill(params, tokens (R, P), lengths (R,), max_len, adapters, tenant)``
    ``(last-position logits (R, V) float32, cache rows of R, stats)``
``decode_step(params, tok (S,), pos (S,), caches, live (S,), adapters, tenant)``
    ``(logits (S, V), caches, stats)``
``bucket(prime_len, max_len)`` / ``buckets(cap, max_len)``
    the padded prefill length of a prime, and every such length up to
    ``cap``: the admission programs ``aot_warmup`` compiles
``publish(stats)``
    registry gauge values from the fetched counters

Paged, LoRA, disaggregated, quantized and mesh serving are
ProGen's alone today (its ``modes``); there is no fallback.  Their programs
are the plain path's own — one chunk body, one admission — reading the
engine's cache layout (``decode/paging.py``): ``SlotCaches`` steps through
this seam, ``PagedGates`` is ProGen's paged step.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.decode.incremental import ProGenDecodeStep, init_caches
from progen_tpu.decode.prefill import (
    harvest_caches,
    make_embedder,
    pad_prime_length,
    prime_buckets,
)
from progen_tpu.models.progen import ProGen, ProGenConfig


# every mode beyond the plain dense path, by ``ServingEngine``'s argument
SERVING_MODES = frozenset(
    {"paged", "disagg", "lora_bank", "quantize", "mesh"})


class UnsupportedFamilyMode(ValueError):
    """A serving mode was asked of a model family that does not have it."""


class ProGenFamily:
    """ProGen behind the seam: fixed k/v rings, token-shift carries and SGU
    gate rows, prefill buckets ``window_size * 2^k``, a logit mask per
    write position."""

    name = "progen"
    position_masks = True
    idle_length = 1
    modes = SERVING_MODES
    block_length = None

    def __init__(self, config: ProGenConfig, policy: Policy,
                 weights: str = "bf16"):
        self.config = config
        self.policy = policy
        self.weights = weights
        self.vocab = config.num_tokens
        self.seq_len = config.seq_len
        self.step_model = ProGenDecodeStep(config=config, policy=policy,
                                           weights=weights)
        self.prefill_model = ProGen(config=config, policy=policy,
                                    weights=weights)

    def embedder(self, mesh=None, strategies=()):
        return make_embedder(self.config, self.policy, mesh=mesh,
                             strategies=strategies, weights=self.weights)

    def init_caches(self, slots: int, max_len: int):
        return init_caches(self.config, slots, self.policy,
                           decode_len=max_len)

    def init_stats(self) -> dict:
        return {}

    def bucket(self, prime_len: int, max_len: int) -> int:
        return pad_prime_length(prime_len, self.config.window_size,
                                self.config.seq_len, bucket=True)

    def buckets(self, cap: int, max_len: int) -> list[int]:
        return prime_buckets(self.config.window_size, self.config.seq_len,
                             cap)

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, varz = self.prefill_model.apply(
            params, tokens, adapters, tenant, mutable=["cache"])
        with jax.named_scope("engine.rows"):
            caches = harvest_caches(self.config, varz["cache"], lengths,
                                    self.policy, max_len)
        with jax.named_scope("head.logits"):
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1
            )[:, 0].astype(jnp.float32)
        return last, caches, {}

    def decode_step(self, params, tok, pos, caches, live, adapters=None,
                    tenant=None):
        logits, caches = self.step_model.apply(params, tok, pos, caches,
                                               adapters, tenant)
        return logits, caches, {}

    def publish(self, stats: dict) -> dict:
        return {}


# the families that are plain functions over ``models/driver.py``, imported
# only when a config is not ProGen's: (module, its config, its family).
# Three hold one chip's share of an expert layer (LongCat-Flash and
# DeepSeek-V2 over latent attention, Trinity over rings and grown keys); the
# fourth, Granite 4.0-H, has no experts and a recurrent state beside its
# grown keys; the fifth, SDAR, holds whole expert layers and generates by
# diffusion over blocks (it states a ``block_length``); the sixth, LFM2,
# holds whole expert layers too, a token a step, under mixers whose whole
# cache is a convolution's tail beside a few blocks of grown keys; the
# seventh, Nemotron-H, a share again, in layers that are ONE sublayer each:
# a state block, a grown-key block, or an expert layer that states no cache;
# the eighth, MiMo-V2, a share under two kinds of attention with two head
# shapes: a short ring under a learned sink beside grown keys, the keys
# wider than the values; the ninth, dots3, a share over LATENT attention of
# two shapes: rows an indexer thins to ``index_topk`` beside a ring of
# latents, a gate a head on both; the tenth, GLM-5.2, a share over latent
# attention of ONE shape under a selection in every layer, which one layer in
# four computes (an indexer's second cache leaf) and the next three borrow
# (the plain leaf); the eleventh, Qwen3-Next, a share under three delta-rule
# layers (a float32 state that erases before it writes, no keys) to one
# gated full-attention layer; the twelfth, Ling-3.0-flash, a share under five
# delta-rule layers whose decay is a CHANNEL's to one latent-attention layer
_DRIVER_FAMILIES = (
    ("progen_tpu.models.longcat", "LongCatConfig", "LongCatFamily"),
    ("progen_tpu.models.deepseek_v2", "DeepSeekV2Config", "DeepSeekV2Family"),
    ("progen_tpu.models.trinity", "TrinityConfig", "TrinityFamily"),
    ("progen_tpu.models.granite_hybrid", "GraniteHybridConfig",
     "GraniteHybridFamily"),
    ("progen_tpu.models.sdar", "SDARConfig", "SDARFamily"),
    ("progen_tpu.models.lfm2", "LFM2Config", "LFM2Family"),
    ("progen_tpu.models.nemotron_h", "NemotronHConfig", "NemotronHFamily"),
    ("progen_tpu.models.mimo_v2", "MiMoV2Config", "MiMoV2Family"),
    ("progen_tpu.models.dots3", "Dots3Config", "Dots3Family"),
    ("progen_tpu.models.glm_dsa", "GLMDSAConfig", "GLMDSAFamily"),
    ("progen_tpu.models.qwen3_next", "Qwen3NextConfig", "Qwen3NextFamily"),
    ("progen_tpu.models.bailing_hybrid", "BailingHybridConfig",
     "BailingHybridFamily"),
)


def family_for(config, policy: Policy, weights: str = "bf16"):
    """The family that serves ``config``."""
    if isinstance(config, ProGenConfig):
        return ProGenFamily(config, policy, weights)
    for module, config_name, family_name in _DRIVER_FAMILIES:
        models = importlib.import_module(module)
        if isinstance(config, getattr(models, config_name)):
            return getattr(models, family_name)(config, policy)
    raise TypeError(f"no model family serves a {type(config).__name__}")

"""Qwen3-Next at tiny widths for the CPU tests: every mechanism of the
published configuration (two whole periods of three gated delta-rule layers
to one gated full-attention layer; two key heads feeding four value heads,
four taps with no bias, l2-normed q and k, a gated norm whose gate comes
after it, a chunk of 4 so that a 9-token prime crosses chunks; a gate an
element that the query projection emits, zero-centred QK norms, a rotation
over the first quarter of a head; a softmax router's top-3 of 16 renormalised
beside a shared expert under a sigmoid gate a token, a share of 4 of 16
experts or all of them, an untied head).  What the families' tests share
(``as_dict``, ``make``) is ``tests/longcat_tiny.py``'s."""

import dataclasses
import functools

from progen_tpu.models import qwen3_next
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

TINY = qwen3_next.Qwen3NextConfig(
    vocab_size=64, hidden_size=32, num_hidden_layers=8,
    full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, num_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, max_position_embeddings=64,
    experts_held=16, first_expert=0, chunk=4, prefill_bucket=8)


def share(first: int, held: int = 4, config=TINY):
    """The configuration of the chip that holds experts ``first .. first +
    held - 1``."""
    return dataclasses.replace(config, experts_held=held, first_expert=first)


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=qwen3_next)

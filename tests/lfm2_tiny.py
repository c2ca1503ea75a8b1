"""LFM2 at tiny widths for the CPU tests: every mechanism of the published
configuration (double-gated short convolutions of three taps whose whole
cache is a two-row tail, beside grouped-query attention blocks with q/k
norms and a rotation whose keys grow; two leading dense layers — one under
each kind of mixer is not needed: a convolution and an attention layer are
dense here —, then expert layers of both kinds, a sigmoid router whose bias
changes some choices, no shared expert, all experts held, a tied head).
What the families' tests share (``as_dict``, ``make``) is
``tests/longcat_tiny.py``'s."""

import functools

from progen_tpu.models import lfm2
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

TINY = lfm2.LFM2Config(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=6, num_dense_layers=2,
    layer_types=("conv", "full_attention", "conv", "conv", "full_attention",
                 "conv"),
    num_attention_heads=4, num_key_value_heads=2, num_experts=8,
    num_experts_per_tok=3, max_position_embeddings=64, experts_held=8,
    first_expert=0, router_bias_std=0.05, prefill_bucket=8)


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=lfm2)

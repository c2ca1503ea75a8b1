"""SDAR at tiny widths for the CPU tests: every mechanism of the published
configuration (grouped-query attention with q/k norms before the rotation,
every layer an expert layer, softmax top-k routing renormalised over the
chosen, every expert held, an untied head) and of its generation (blocks of
4 under a mask that is causal across blocks and open inside one, a mask
token inside the vocabulary).  What the families' tests share (``as_dict``,
``make``) is ``tests/longcat_tiny.py``'s."""

import functools

from progen_tpu.models import sdar
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

BLOCK = 4
MASK_ID = 95

TINY = sdar.SDARConfig(
    vocab_size=96, hidden_size=64, moe_intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=8, num_experts_per_tok=2,
    max_position_embeddings=64, block_length=BLOCK, mask_token_id=MASK_ID,
    denoising_steps=2, remasking="low_confidence_static",
    prefill_bucket=8)


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=sdar)

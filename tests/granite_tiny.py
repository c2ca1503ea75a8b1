"""Granite 4.0-H at tiny widths for the CPU tests: every mechanism of the
published configuration (Mamba-2 layers with a chunk SHORTER than the
sequences so that a scan crosses chunk boundaries, one attention layer with
no rotation and a published score multiplier that is not ``d^-1/2``, the
shared MLP, the four multipliers, the tied head).  What the families' tests
share (``as_dict``, ``make``) is ``tests/longcat_tiny.py``'s."""

import functools

from progen_tpu.models import granite_hybrid as gh
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

CHUNK = 8

TINY = gh.GraniteHybridConfig(
    vocab_size=96, hidden_size=64, shared_intermediate_size=96,
    num_hidden_layers=6,
    layer_types=("mamba", "mamba", "attention", "mamba", "mamba", "mamba"),
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.1,
    mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16,
    mamba_chunk_size=CHUNK, max_position_embeddings=128, prefill_bucket=8)


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=gh)

"""Ling-3.0-flash at tiny widths for the CPU tests: every mechanism of the
published configuration (published layers 0 and 7-11 of 12: a dense layer
under a delta mixer, then one whole period of five channel-decay delta-rule
layers to one gated latent-attention layer, an expert layer in each; two
heads of 8, four taps with no bias, l2-normed q and k, a gate bounded at -5,
a norm a head and then a gate a head, a chunk of 8 in blocks of 4 so that a
19-token prime crosses blocks and chunks; a full-rank query, interleaved
rotary pairs, a gate a head; a sigmoid router's top-3 of 16 under a group
limit of 2 of 4 groups beside a shared expert, a non-zero SwiGLU limit in
the last layers, a share of 4 of 16 experts or all of them, an untied
head).  What the families' tests share (``as_dict``, ``make``) is
``tests/longcat_tiny.py``'s."""

import dataclasses
import functools

from progen_tpu.models import bailing_hybrid
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

TINY = bailing_hybrid.BailingHybridConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_hidden_layers=6,
    first_k_dense_replace=2, layer_group_size=6, num_attention_heads=2,
    head_dim=8, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, num_experts=16, num_experts_per_tok=3, n_group=4,
    topk_group=2, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=16,
    expert_swiglu_limit_list=(0,) * 9 + (1.0, 1.0, 1.0),
    share_expert_swiglu_limit_list=(0,) * 8 + (1.5, 1.5, 1.5, 2.0),
    layer_ids=(0, 7, 8, 9, 10, 11), max_position_embeddings=64,
    experts_held=16, first_expert=0, chunk=8, block=4,
    router_bias_std=0.05, prefill_bucket=8)


def share(first: int, held: int = 4, config=TINY):
    """The configuration of the chip that holds experts ``first .. first +
    held - 1``."""
    return dataclasses.replace(config, experts_held=held, first_expert=first)


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=bailing_hybrid)

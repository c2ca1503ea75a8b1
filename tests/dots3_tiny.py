"""dots3-note at tiny widths for the CPU tests: every mechanism of the
published configuration (two latent SHAPES in one stack — different head
counts, ranks, head widths and rotary bases —, an indexer whose ``index_topk``
is SMALLER than the sequences so that keys are dropped, a sliding window
smaller still so that a ring wraps, the per-head gate on both kinds, the
rescale, a leading dense full layer and a second full layer before the
period of one full to three sliding as published, a sigmoid router whose
bias changes some choices beside a shared expert, a share of the experts).
Sixteen indexer heads, so that no score is an exact 0 (every head's product
negative: one pair in 65,536): the admission keeps EVERY key tied with the
``index_topk``-th, ``lax.top_k`` the lower-numbered ones.
What the families' tests share (``as_dict``, ``make``) is
``tests/longcat_tiny.py``'s."""

import functools

from progen_tpu.models import dots3 as dm
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

WINDOW, TOP_K = 5, 8

TINY = dm.Dots3Config(
    vocab_size=64, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=16, num_hidden_layers=4,
    layer_types=(dm.FULL, dm.FULL, dm.SLIDING, dm.SLIDING),
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=1e5,
    index_n_heads=16, index_head_dim=8, index_topk=TOP_K,
    swa_num_attention_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=24,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
    swa_rope_theta=100.0, sliding_window_size=WINDOW, n_routed_experts=8,
    num_experts_per_tok=2, max_position_embeddings=64, experts_held=8,
    first_expert=0, router_bias_std=0.05, prefill_bucket=8)


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=dm)

"""Trinity at tiny widths for the CPU tests: every mechanism of the
published configuration (gated grouped-query attention with q/k norms, a
sliding window SMALLER than the sequences so that a ring wraps, sliding and
full blocks in one model — a leading dense sliding layer, then expert layers
of both kinds —, a sigmoid router whose bias changes some choices, a shared
expert, a share of the routed experts).  What the families' tests share
(``as_dict``, ``make``) is ``tests/longcat_tiny.py``'s."""

import functools

from progen_tpu.models import trinity as tr
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

WINDOW = 8

TINY = tr.TrinityConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=5, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    sliding_window=WINDOW, num_experts=8, num_experts_per_tok=3,
    max_position_embeddings=64, experts_held=8, first_expert=0,
    router_bias_std=0.05, prefill_bucket=8)


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=tr)

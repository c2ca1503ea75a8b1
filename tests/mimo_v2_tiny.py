"""MiMo-V2 at tiny widths for the CPU tests: every mechanism of the
published configuration (keys wider than values, two kinds of attention
with two key/value head counts and two rotary bases, a rotation over a
third of a head, a sliding window SMALLER than the sequences so that a ring
wraps, sinks drawn from N(4, 1), a value scale, a leading dense full layer,
then expert layers of both kinds in the published order, a sigmoid router
whose bias changes some choices, no shared expert, a share of the experts).
What the families' tests share (``as_dict``, ``make``) is
``tests/longcat_tiny.py``'s."""

import functools

from progen_tpu.models import mimo_v2 as mm
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

WINDOW = 4

TINY = mm.MiMoV2Config(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=7,
    num_attention_heads=4, num_key_value_heads=1, head_dim=12, v_head_dim=8,
    swa_num_attention_heads=4, swa_num_key_value_heads=2, swa_head_dim=12,
    swa_v_head_dim=8, sliding_window=WINDOW, rope_theta=1e5,
    swa_rope_theta=100.0, n_routed_experts=8, num_experts_per_tok=2,
    max_position_embeddings=64, experts_held=8, first_expert=0,
    router_bias_std=0.05, prefill_bucket=8)


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=mm)

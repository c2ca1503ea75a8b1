"""DeepSeek-V2 at tiny widths for the CPU tests: every mechanism of the
published configuration (latent attention with unequal q/k and v widths
under a YaRN table whose ramp lies inside the table, a leading dense layer
and two expert layers, group-limited routing, two shared experts, a share
of the routed experts).  What the two latent families' tests share
(``as_dict``, ``make``) is ``tests/longcat_tiny.py``'s."""

import functools

from progen_tpu.models import deepseek_v2 as ds
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

TINY = ds.DeepSeekV2Config(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=3, num_attention_heads=4,
    kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8, qk_nope_head_dim=8,
    v_head_dim=12, n_routed_experts=16, n_shared_experts=2, n_group=4,
    topk_group=2, num_experts_per_tok=3, max_position_embeddings=64,
    rope_scaling=ds.YarnScaling(original_max_position_embeddings=16),
    experts_held=16, first_expert=0, prefill_bucket=8)



@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=ds)

"""LongCat-Flash at tiny widths for the CPU tests: every mechanism of the
published configuration (latent attention with unequal q/k and v widths, a
router wider than the real experts, identity experts, a share of the real
experts, a router bias large enough to change the choice)."""

import dataclasses

import jax

from progen_tpu.core.precision import make_policy
from progen_tpu.models import longcat as lc

TINY = lc.LongCatConfig(
    vocab_size=64, hidden_size=32, ffn_hidden_size=64,
    expert_ffn_hidden_size=16, num_layers=2, num_attention_heads=4,
    kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8, qk_nope_head_dim=8,
    v_head_dim=12, n_routed_experts=8, zero_expert_num=4, moe_topk=3,
    max_position_embeddings=64, experts_held=8, first_expert=0,
    router_bias_std=0.05, prefill_bucket=8)


def as_dict(config) -> dict:
    """The configuration as the reference reads it."""
    return dataclasses.asdict(config)


def make(config=TINY, mixed=False, seed=0, family=lc):
    """``(params, policy)``: float32 end to end, or bfloat16 parameters and
    compute with the float32 islands.  ``family``: the model's module."""
    policy = family.bf16_policy() if mixed else make_policy(False)
    return family.init_params(config, jax.random.key(seed), policy), policy

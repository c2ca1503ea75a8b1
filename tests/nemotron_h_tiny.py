"""Nemotron-H at tiny widths for the CPU tests: every mechanism of the
published configuration (layers that are ONE sublayer each — Mamba-2 mixers
with four B/C groups and a per-group gated norm, latent expert layers whose
experts are two matrices with ``relu^2`` between them in a latent half the
stream's width beside a shared expert on the full width, a grouped-query
attention block with no rotation — in the published order of kinds, a
sigmoid router whose bias changes some choices, a share of 4 of 16 experts
or all of them, an untied head).  What the families' tests share
(``as_dict``, ``make``) is ``tests/longcat_tiny.py``'s."""

import dataclasses
import functools

from progen_tpu.models import nemotron_h
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

TINY = nemotron_h.NemotronHConfig(
    vocab_size=64, hidden_size=32, num_hidden_layers=7,
    hybrid_override_pattern="MEM*EME", mamba_num_heads=8, mamba_head_dim=8,
    n_groups=4, ssm_state_size=8, conv_kernel=4, chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    n_routed_experts=16, num_experts_per_tok=5, moe_intermediate_size=24,
    moe_latent_size=16, moe_shared_expert_intermediate_size=48,
    max_position_embeddings=64, experts_held=16, first_expert=0,
    router_bias_std=0.05, prefill_bucket=8)


def share(first: int, held: int = 4, config=TINY):
    """The configuration of the chip that holds experts ``first .. first +
    held - 1``."""
    return dataclasses.replace(config, experts_held=held, first_expert=first)


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=nemotron_h)

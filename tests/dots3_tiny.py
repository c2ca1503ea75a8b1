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

import dataclasses
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


# the FULL layers' heads at the published widths (128 + 64 beside 128), two
# of them, and a selection of 512: what ``ops/mla_prefill.py``'s kernel takes
# (the tests run it under the interpreter); everything else as tiny as above
WIDE_TOP_K = 512
WIDE = dataclasses.replace(
    TINY, num_attention_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, index_head_dim=64, index_topk=WIDE_TOP_K,
    max_position_embeddings=2048,
    prefill_bucket=512)


def force_prefill_kernel(monkeypatch, tile=256):
    """``ops/mla_prefill.py``'s kernel lowering on the CPU: the backend test
    patched, tiles of ``tile``, the interpreter."""
    from progen_tpu.ops import mla_prefill

    monkeypatch.setattr(mla_prefill, "_on_tpu", lambda: True)
    monkeypatch.setattr(mla_prefill, "TILE", tile)
    monkeypatch.setattr(mla_prefill, "MIN_TILE", tile)
    monkeypatch.setattr(
        mla_prefill, "pallas_prefill_attention",
        lambda *a, _f=mla_prefill.pallas_prefill_attention: _f(
            *a, interpret=True))


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=dm)

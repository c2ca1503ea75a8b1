"""GLM-5.2 at tiny widths for the CPU tests: every mechanism of the
published configuration (latent attention of ONE shape under a selection in
every layer — an ``index_topk`` SMALLER than the sequences so that keys are
dropped —, two FULL layers that compute a selection and three SHARED ones
that borrow it, one of them after the second full layer so that WHOSE
selection a layer reads matters; interleaved rotary pairs on the attention's
and the indexer's side; keys wider than the rope part and narrower than the
values; a leading dense layer, a sigmoid router whose bias changes some
choices and whose weights are scaled by 2.5, a shared expert, a share of the
experts).  Sixteen indexer heads, so that no score is an exact 0 (every
head's product negative: one pair in 65,536): the admission keeps EVERY key
tied with the ``index_topk``-th, ``lax.top_k`` the lower-numbered ones.
What the families' tests share (``as_dict``, ``make``) is
``tests/longcat_tiny.py``'s."""

import dataclasses
import functools

from progen_tpu.models import glm_dsa as gm
from tests.longcat_tiny import as_dict, make as _make  # noqa: F401

TOP_K = 8

TINY = gm.GLMDSAConfig(
    vocab_size=64, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=16, num_hidden_layers=5,
    indexer_types=(gm.FULL, gm.SHARED, gm.SHARED, gm.FULL, gm.SHARED),
    mlp_layer_types=(gm.DENSE,) + (gm.SPARSE,) * 4,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, rope_theta=1e4,
    index_n_heads=16, index_head_dim=8, index_topk=TOP_K,
    n_routed_experts=8, num_experts_per_tok=2, max_position_embeddings=64,
    experts_held=8, first_expert=0, router_bias_std=0.05, prefill_bucket=8)


# the heads at the published widths (192 + 64 beside 256), two of them, and
# a selection of 512: what ``ops/gqa.py``'s kernel takes over the joined
# 256-wide heads (the tests run it under the interpreter); everything else as
# tiny as above, in three layers (an owner, a borrower, an owner)
WIDE_TOP_K, WIDE_LAYERS = 512, 3
WIDE = dataclasses.replace(
    TINY, num_hidden_layers=WIDE_LAYERS,
    indexer_types=(gm.FULL, gm.SHARED, gm.FULL),
    mlp_layer_types=(gm.DENSE, gm.SPARSE, gm.SPARSE), num_attention_heads=2, qk_nope_head_dim=192, qk_rope_head_dim=64,
    v_head_dim=256, index_head_dim=64, index_topk=WIDE_TOP_K,
    max_position_embeddings=2048, prefill_bucket=512)


def force_prefill_kernel(monkeypatch, tile=256):
    """``ops/gqa.py``'s prefill kernel lowering on the CPU: the backend test
    patched, tiles of ``tile``, the interpreter."""
    from progen_tpu.ops import gqa

    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    monkeypatch.setattr(gqa, "TILE", tile)
    monkeypatch.setattr(gqa, "MIN_TILE", tile)
    monkeypatch.setattr(
        gqa, "pallas_prefill_attention",
        lambda *a, _f=gqa.pallas_prefill_attention, **k: _f(
            *a, **k, interpret=True))


@functools.cache
def make(config=TINY, mixed=False, seed=0):
    """One set of weights per (config, precision, seed) for the whole run:
    the tests read them and never write."""
    return _make(config, mixed, seed, family=gm)

"""DeepSeek-V2 through ``ServingEngine``'s normal path (the seam of
``decode/family.py``, unchanged): the tests every driver family runs
(``tests/families.py``); what is DeepSeek-V2's own here: a latent cache a
layer, and the counters of a group-limited router."""

import pytest

from progen_tpu.observe.metrics import get_registry
from tests import families
from tests.deepseek_v2_tiny import TINY

pytestmark = pytest.mark.serving

CASE = families.CASES["deepseek_v2"]


def slot_holds(engine):
    assert sorted(engine.state["caches"]) == ["l0", "l1", "l2"]


def states(family):
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings


def counters(engine, reqs, stats, total):
    expert_layers = TINY.num_hidden_layers - TINY.first_k_dense_replace
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    assert stats["moe.tokens"] == expert_layers * (prime_tokens + steps)
    assert stats["mla.decode_rows"] == steps
    assert stats["moe.held_load"].shape == (TINY.experts_held,)
    assert stats["moe.held_load"].sum() == 3 * stats["moe.tokens"]
    assert stats["moe.held_groups_chosen"] == (TINY.topk_group
                                               * stats["moe.tokens"])
    assert stats["mla.context_tokens"] > stats["mla.decode_rows"]
    assert 0 < stats["moe.experts_touched"] <= (stats["moe.decode_layers"]
                                                * TINY.experts_held)
    snap = get_registry().snapshot()
    for name in ("moe.tokens", "moe.held_groups_chosen", "moe.decode_layers",
                 "moe.experts_touched", "mla.decode_rows",
                 "mla.context_tokens"):
        assert snap[name]["value"] == total[name], name
    assert snap["moe.held_assignments"]["value"] == total[
        "moe.held_load"].sum()
    assert snap["moe.held_load_max"]["value"] == total["moe.held_load"].max()


TestEngine = families.engine_tests(
    CASE, slot_holds=slot_holds, states=states, counters=counters)

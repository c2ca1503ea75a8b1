"""Trinity through ``ServingEngine``'s normal path (the seam of
``decode/family.py``, unchanged): the tests every driver family runs
(``tests/families.py``) over slots of mixed lengths, one past the window at
admission and all past it before they finish; what is Trinity's own here: a
slot's state holds a ring for each sliding block and ``max_len`` rows for
the full one, and the counters count windows beside contexts."""

import pytest

from progen_tpu.observe.metrics import get_registry
from tests import families
from tests.families import SLOTS
from tests.trinity_tiny import TINY, WINDOW

pytestmark = pytest.mark.serving

CASE = families.CASES["trinity"]
MAX_LEN = CASE.max_len


def greedy(case, reqs, done):
    assert all(len(r.tokens) + r.max_new_tokens > WINDOW for r in reqs)
    families.serves_the_plain_samplers_tokens(case, reqs, done)


def slot_holds(engine):
    caches = engine.state["caches"]
    assert sorted(caches) == ["l0", "l1", "l2", "l3", "l4"]
    assert {n: c["k"].shape[2] for n, c in caches.items()} == {
        "l0": WINDOW, "l1": WINDOW, "l2": WINDOW, "l3": MAX_LEN,
        "l4": WINDOW}
    assert engine.status()["row_write"] == "scatter"     # the CPU's lowering


def states(family):
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings


def counters(engine, reqs, stats, total):
    expert_layers = TINY.num_hidden_layers - TINY.num_dense_layers
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    assert stats["moe.tokens"] == expert_layers * (prime_tokens + steps)
    assert stats["attn.decode_rows"] == steps
    assert stats["moe.held_load"].shape == (TINY.experts_held,)
    assert stats["moe.held_load"].sum() == 3 * stats["moe.tokens"]
    # the i-th step of a request stands on position prime + i - 1: it has
    # prime + i tokens of context, min(prime + i, 8) of them in a window
    context = sum(len(r.tokens) + i for r in reqs
                  for i in range(1, r.max_new_tokens))
    window = sum(min(len(r.tokens) + i, WINDOW) for r in reqs
                 for i in range(1, r.max_new_tokens))
    assert stats["attn.context_tokens"] == context
    assert stats["attn.window_tokens"] == window < context
    # the XLA core reads every slot's every row, each decode step that ran
    chunk_steps = stats["attn.window_rows_read"] / (SLOTS * WINDOW)
    assert chunk_steps == int(chunk_steps) and chunk_steps >= max(
        r.max_new_tokens - 1 for r in reqs)
    assert stats["attn.full_rows_read"] == chunk_steps * SLOTS * MAX_LEN
    assert 0 < stats["moe.experts_touched"] <= (stats["moe.decode_layers"]
                                                * TINY.experts_held)
    snap = get_registry().snapshot()
    for name in ("moe.tokens", "moe.decode_layers", "moe.experts_touched",
                 "attn.decode_rows", "attn.context_tokens",
                 "attn.window_tokens", "attn.window_rows_read",
                 "attn.full_rows_read"):
        assert snap[name]["value"] == total[name], name
    assert snap["moe.held_assignments"]["value"] == total[
        "moe.held_load"].sum()
    assert snap["moe.held_load_max"]["value"] == total["moe.held_load"].max()


TestEngine = families.engine_tests(
    CASE, slot_holds=slot_holds, states=states, counters=counters,
    greedy=greedy)

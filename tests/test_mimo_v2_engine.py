"""MiMo-V2 through ``ServingEngine``'s normal path (the seam of
``decode/family.py``, unchanged): the tests every driver family runs
(``tests/families.py``) over slots of mixed lengths — one token, one under,
at and past the window at admission, all past it before they finish; what is
MiMo's own here: a slot's state holds a ring of one head shape for each
sliding block and ``max_len`` rows of another for each full one, keys wider
than values; a slot readmitted after a longer request serves what a fresh
one serves; the two byte gauges reach the registry and
``status()["model_stats"]``."""

import pytest

from progen_tpu.observe.metrics import get_registry
from tests import families
from tests.families import SLOTS
from tests.mimo_v2_tiny import TINY, WINDOW

pytestmark = pytest.mark.serving

CASE = families.CASES["mimo_v2"]
MAX_LEN = CASE.max_len
assert CASE.primes == (1, WINDOW - 1, WINDOW, WINDOW + 1, 13, 21)


@pytest.fixture(scope="module")
def engine():
    return families.engine_of(CASE)


def greedy(case, reqs, done):
    assert all(len(r.tokens) + r.max_new_tokens > WINDOW for r in reqs)
    families.serves_the_plain_samplers_tokens(case, reqs, done)


def test_a_slot_readmitted_after_a_longer_request_serves_what_a_fresh_one_does(
        engine):
    """Every slot holds a 21-token request's rings and grown rows, then
    takes a prime of 1-5 tokens: stale rows past the short request's count,
    in both kinds of cache, must reach nothing."""
    long = families.requests(CASE, SLOTS, seed=7, first_uid=200, primes=(21,))
    assert len(families.serve(engine, long)) == SLOTS
    short = families.requests(
        CASE, SLOTS, seed=8, first_uid=300,
        primes=(1, 2, WINDOW - 1, WINDOW, WINDOW + 1))
    families.serves_the_plain_samplers_tokens(
        CASE, short, families.serve(engine, short))


def slot_holds(engine):
    caches = engine.state["caches"]
    assert sorted(caches) == [f"l{i}" for i in range(7)]
    assert {n: (c["k"].shape[1:], c["v"].shape[1:])
            for n, c in caches.items()} == {
        **{f"l{i}": ((2, WINDOW, 12), (2, WINDOW, 8))
           for i in (1, 2, 3, 4, 6)},
        **{f"l{i}": ((1, MAX_LEN, 12), (1, MAX_LEN, 8)) for i in (0, 5)}}
    status = engine.status()
    assert status["row_write"] == "scatter"     # the CPU's lowering
    assert status["gqa_prefill"] == status["gqa_decode"] == "xla"


def states(family):
    assert family.block_length is None
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings


def counters(engine, reqs, stats, total):
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    assert stats["moe.tokens"] == 6 * (prime_tokens + steps)
    assert stats["attn.decode_rows"] == steps
    assert stats["moe.held_load"].sum() == 2 * stats["moe.tokens"]
    # the i-th step of a request stands on position prime + i - 1: it has
    # prime + i tokens of context, min(prime + i, 4) of them in a window
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
    # the pairs the blocked form computed beside those the masks allow
    assert stats["attn.prefill_pairs_visited"] > stats[
        "attn.prefill_pairs_allowed"] > 0
    # no byte counter rides in the state: the gauges are the rows at each
    # kind's own row bytes (float32 here) times the kind's blocks
    assert not [k for k in stats if k.endswith("_bytes_read")]
    gauges = engine.status()["model_stats"]
    ring_row, grown_row = 2 * (12 + 8) * 4, 1 * (12 + 8) * 4
    assert gauges["attn.window_bytes_read"] == (
        total["attn.window_rows_read"] * 5 * ring_row)
    assert gauges["attn.full_bytes_read"] == (
        total["attn.full_rows_read"] * 2 * grown_row)
    assert gauges["attn.full_bytes_read"] > gauges["attn.window_bytes_read"]
    snap = get_registry().snapshot()
    for name in ("moe.tokens", "moe.decode_layers", "moe.experts_touched",
                 "attn.decode_rows", "attn.context_tokens",
                 "attn.window_tokens", "attn.window_rows_read",
                 "attn.full_rows_read", "attn.prefill_pairs_visited"):
        assert snap[name]["value"] == total[name], name
    for name in ("attn.window_bytes_read", "attn.full_bytes_read"):
        assert snap[name]["value"] == gauges[name], name
    assert snap["moe.held_assignments"]["value"] == total[
        "moe.held_load"].sum()


TestEngine = families.engine_tests(
    CASE, slot_holds=slot_holds, states=states, counters=counters,
    greedy=greedy)

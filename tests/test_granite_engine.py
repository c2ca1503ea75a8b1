"""Granite 4.0-H through ``ServingEngine``'s normal path (the seam of
``decode/family.py``; ``decode/engine.py`` unchanged): the tests every driver
family runs (``tests/families.py``) over rows of mixed lengths in one
admission run — below, at and across a chunk of the scan; what is Granite's
own here: the greedy tokens are those of the family's own prefill and steps;
a slot reused after idling serves as a fresh one does; a slot's state holds a
carry and a tail for each state layer beside the attention layer's grown
keys; the counters are scalars and none is ``moe.*``."""

import jax.numpy as jnp
import pytest

from progen_tpu.models import granite_hybrid as gh
from progen_tpu.observe.metrics import get_registry
from tests import families
from tests.families import ADMIT_ROWS, SLOTS
from tests.granite_tiny import CHUNK, TINY

pytestmark = pytest.mark.serving

CASE = families.CASES["granite_hybrid"]
MAX_LEN = CASE.max_len
STATE_LAYERS = gh.mamba_layers(TINY)


@pytest.fixture(scope="module")
def engine():
    return families.engine_of(CASE)


def greedy(case, reqs, done):
    assert {len(r.tokens) % CHUNK == 0 for r in reqs} == (
        {False} if len(reqs) == 1 else {False, True})
    families.serves_the_plain_samplers_tokens(case, reqs, done)


def test_greedy_tokens_are_the_familys_own_step_by_step(engine):
    reqs = families.requests(CASE, 3, seed=8, first_uid=300)
    got = families.tokens_of(families.serve(engine, reqs))
    assert got == {r.uid: families.family_greedy(CASE, r) for r in reqs}


def test_a_slot_reused_after_idling_serves_as_a_fresh_one(engine):
    """One request alone (every other slot steps on garbage beside it),
    then requests that fill every slot, then the first again: an admission
    overwrites all of a slot's state, so the tokens are the same."""
    alone = families.requests(CASE, 1, seed=6, first_uid=400)
    first = families.tokens_of(families.serve(engine, alone))[400]
    families.serve(engine, families.requests(CASE, SLOTS + 3, seed=7,
                                             sampled=True, first_uid=410))
    again = families.requests(CASE, 1, seed=6, first_uid=500)
    # every slot has held a request and stepped on after it finished
    assert all(bool(jnp.abs(c["ssm"]).max(axis=(1, 2, 3)).min() > 0)
               for c in engine.state["caches"].values() if "ssm" in c)
    assert families.tokens_of(families.serve(engine, again))[500] == first
    assert first == families.sequential_greedy(CASE, alone[0])


def test_status_publishes_every_lowering_the_programs_noted(engine):
    """ROADMAP D20: ``status()["lowerings"]`` is ``engine.lowerings`` whole,
    so the state block's ``ssd_*`` — which have no key of their own among
    the ten the engine spells — are on ``/statusz`` without an engine edit,
    and the ten stay as they are."""
    families.serve(engine, families.requests(CASE, 1, seed=5, first_uid=600))
    status = engine.status()
    assert status["lowerings"] == engine.lowerings
    assert status["lowerings"]["ssd_prefill"] == "xla"
    assert status["lowerings"]["ssd_step"] == "xla"
    assert "ssd_step" not in status
    assert status["row_write"] == status["lowerings"]["row_write"]
    status["lowerings"]["ssd_step"] = "x"       # a copy, not the engine's
    assert engine.lowerings["ssd_step"] == "xla"


def slot_holds(engine):
    caches = engine.state["caches"]
    assert sorted(caches) == [f"l{i}" for i in range(6)]
    assert {n: sorted(c) for n, c in caches.items()} == {
        **{f"l{i}": ["conv", "ssm"] for i in (0, 1, 3, 4, 5)},
        "l2": ["k", "v"]}
    assert caches["l0"]["ssm"].shape == (SLOTS, 4, 32, 16)
    assert caches["l0"]["ssm"].dtype == jnp.float32
    assert caches["l2"]["k"].shape == (SLOTS, 2, MAX_LEN, 16)
    # which lowering each op took, as the engine's programs were traced
    assert engine.lowerings["ssd_prefill"] == "xla"
    assert engine.lowerings["ssd_step"] == "xla"
    assert engine.program_lowerings["chunk"]["ssd_step"] == "xla"
    assert "ssd_step" not in engine.program_lowerings["admit"]
    assert engine.status()["row_write"] == "scatter"     # the CPU's lowering
    assert engine.status()["moe_experts"] is None


def states(family):
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings
    # no experts: the counters are scalars and none is ``moe.*``
    assert set(family.init_stats()) == set(gh.STAT_KEYS)
    assert all(v.shape == () for v in family.init_stats().values())
    assert not hasattr(TINY, "experts_held")


def counters(engine, reqs, stats, total):
    assert set(stats) == set(gh.STAT_KEYS)
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    assert stats["ssm.prefill_tokens"] == STATE_LAYERS * prime_tokens
    assert stats["ssm.step_rows"] == STATE_LAYERS * steps
    assert stats["attn.decode_rows"] == steps
    # primes of 3, 12 and 8 in runs of ADMIT_ROWS rows: one run at the
    # bucket of 16 (two chunks a row) and one at the bucket of 8
    assert stats["ssm.prefill_slots"] == STATE_LAYERS * ADMIT_ROWS * (16 + 8)
    # the i-th step of a request stands on position prime + i - 1
    context = sum(len(r.tokens) + i for r in reqs
                  for i in range(1, r.max_new_tokens))
    assert stats["attn.context_tokens"] == context
    # the XLA core reads every slot's every row, each decode step that ran
    chunk_steps = stats["attn.full_rows_read"] / (SLOTS * MAX_LEN)
    assert chunk_steps == int(chunk_steps) and chunk_steps >= max(
        r.max_new_tokens - 1 for r in reqs)
    snap = get_registry().snapshot()
    for name in gh.STAT_KEYS:
        assert snap[name]["value"] == total[name], name
    assert "moe.held_assignments" not in engine.family.publish(total)


TestEngine = families.engine_tests(
    CASE, slot_holds=slot_holds, states=states, counters=counters,
    greedy=greedy)

"""LongCat-Flash through ``ServingEngine``'s normal path: the tests every
driver family runs (``tests/families.py``), the greedy ones whatever the
group; what is LongCat's own here: the admission runs a group takes, slots
reused deterministically, an ``(S, V)`` logit mask, what a family states of
itself beside ProGen, embedding requests refused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.family import (
    SERVING_MODES,
    UnsupportedFamilyMode,
    family_for,
)
from progen_tpu.models.progen import ProGenConfig
from progen_tpu.observe.metrics import get_registry
from tests import families
from tests.families import ADMIT_ROWS, SLOTS
from tests.longcat_tiny import TINY

pytestmark = pytest.mark.serving

CASE = families.CASES["longcat"]


@pytest.fixture(scope="module")
def engine():
    """The family's one warmed engine: requests reuse its slots."""
    return families.engine_of(CASE)


def greedy(case, reqs, done):
    engine = families.engine_of(case)
    assert len({engine.family.bucket(len(r.tokens), 32)
                for r in families.requests(case, ADMIT_ROWS + 1)}) == 2
    families.serves_the_plain_samplers_tokens(case, reqs, done)


@pytest.mark.parametrize("n", [1, ADMIT_ROWS, ADMIT_ROWS + 1])
def test_a_group_of_n_takes_its_admission_runs(engine, n):
    reqs = families.requests(CASE, n, first_uid=700)
    runs0 = engine._admit_rows_hist.count
    assert len(families.serve(engine, reqs)) == n
    assert engine._admit_rows_hist.count - runs0 == -(-n // ADMIT_ROWS)


def test_nothing_compiles_after_warmup_and_slots_reuse_deterministically(
        engine):
    listener, events = families.compile_events()
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        # more requests than slots: late ones land in slots others left
        first = families.tokens_of(families.serve(
            engine, families.requests(CASE, SLOTS + 5, seed=3, sampled=True)))
        again = families.tokens_of(families.serve(
            engine, list(reversed(families.requests(CASE, SLOTS + 5, seed=3,
                                                    sampled=True)))))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert events == []
    assert first == again and len(first) == SLOTS + 5
    assert all(t != 0 for toks in first.values() for t in toks)


def test_the_mask_is_one_row_a_slot_and_bans_token_zero(engine):
    assert engine.state["lmask"].shape == (SLOTS, TINY.vocab_size)
    assert not engine.family.position_masks
    # a head that prefers token 0 everywhere: only the mask keeps it out
    params, policy = CASE.served()
    biased = {**params, "head": params["head"].at[:, 0].set(
        10.0 * jnp.abs(params["head"]).max())}
    eng = ServingEngine(TINY, biased, policy=policy, num_slots=2,
                        chunk_size=4, max_len=32)
    free = Request(uid="free", tokens=[3, 4, 5], max_new_tokens=4,
                   temperature=0.0)
    held = Request(uid="held", tokens=[3, 4, 5], max_new_tokens=4,
                   temperature=0.0, logit_mask=families.never_zero(CASE))
    out = families.serve(eng, [free, held])
    assert out["free"].finish_reason == "eos"
    assert out["held"].finish_reason == "length"
    assert 0 not in out["held"].tokens.tolist()
    with pytest.raises(UnsupportedFamilyMode, match="same at every position"):
        eng.submit(Request(uid="grid", tokens=[1, 2], max_new_tokens=3,
                           logit_mask=np.ones((3, TINY.vocab_size), bool)))


def test_embedding_requests_are_refused(engine):
    with pytest.raises(UnsupportedFamilyMode, match="embedding"):
        engine.submit_embed(Request(uid="e", tokens=[1, 2, 3]))


def slot_holds(engine):
    assert sorted(engine.state["caches"]) == ["l0a0", "l0a1", "l1a0", "l1a1"]


def states(family):
    """No test of a family's name or a config's type in the engine: a
    family that states a mode is let past the gate for it."""
    policy = CASE.served()[1]
    assert family.step_model is None and family.embedder() is None
    progen = family_for(ProGenConfig(), policy)
    assert progen.modes == SERVING_MODES and progen.idle_length == 1
    assert progen.step_model is not None


def counters(engine, reqs, stats, total):
    layers = TINY.num_layers
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    # 3 requests are two runs of 2 rows: the unused row of the second has
    # length 0 (the family's ``idle_length``) and is not counted
    assert stats["moe.tokens"] == layers * (prime_tokens + steps)
    assert stats["mla.decode_rows"] == steps
    assert 0 < stats["moe.real_chosen"] <= TINY.moe_topk * stats["moe.tokens"]
    assert stats["moe.held_load"].shape == (TINY.experts_held,)
    assert stats["mla.context_tokens"] > stats["mla.decode_rows"]
    snap = get_registry().snapshot()
    assert snap["moe.tokens"]["value"] == total["moe.tokens"]
    assert snap["moe.held_load_max"]["value"] == total["moe.held_load"].max()


TestEngine = families.engine_tests(
    CASE, slot_holds=slot_holds, states=states, counters=counters,
    greedy=greedy)

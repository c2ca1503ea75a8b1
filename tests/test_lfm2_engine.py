"""LFM2 through ``ServingEngine``'s normal path (the seam of
``decode/family.py``; no line of ``decode/engine.py`` names the family): the
tests every driver family runs (``tests/families.py``) over rows of mixed
lengths in one admission run — primes of 1 and 2 tokens, shorter than the
convolution's taps, among them; what is LFM2's own here: greedy requests
serve the reference's argmax over the same tokens wherever its top-two gap
exceeds a float32 rounding; a slot readmitted after a longer request serves
what a fresh engine serves (a stale tail or stale keys fail it); a slot's
state holds a two-row tail for each short-convolution layer beside the
attention layers' grown keys; the counters reach ``engine.status()`` too."""

import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.decode import ServingEngine
from progen_tpu.models import lfm2
from progen_tpu.observe.metrics import get_registry
from tests import families
from tests.families import SLOTS
from tests.lfm2_tiny import TINY

pytestmark = pytest.mark.serving

CASE = families.CASES["lfm2"]
MAX_LEN = CASE.max_len
CONV_LAYERS = TINY.layer_types.count(lfm2.CONV)
EXPERT_LAYERS = TINY.num_hidden_layers - TINY.num_dense_layers
# the reference's best logit must lead its second best by this much for a
# greedy token to be held to it: float32 on both sides, where the order of
# the experts' sums moves a logit by a few 1e-6 (tests/test_lfm2_model.py)
TOP_TWO_GAP = 1e-4


@pytest.fixture(scope="module")
def engine():
    return families.engine_of(CASE)


def _held_to_the_reference(r, tokens):
    """The served greedy tokens against the reference's argmax over the
    same sequence so far, wherever its top-two gap exceeds the tolerance;
    returns how many positions were held."""
    held = 0
    for i, (at, tok) in enumerate(zip(
            families.probe_logits(CASE, r, tokens), tokens)):
        top = np.sort(at)[-2:]
        if top[1] - top[0] > TOP_TWO_GAP:
            assert tok == 1 + int(np.argmax(at)), (r.uid, len(r.tokens) + i)
            held += 1
    return held


def greedy(case, reqs, done):
    got = families.tokens_of(done)
    assert {len(r.tokens) < TINY.conv_L_cache for r in reqs} == (
        {False} if len(reqs) == 1 else {False, True})
    held = sum(_held_to_the_reference(r, got[r.uid]) for r in reqs)
    total = sum(r.max_new_tokens for r in reqs)
    assert held >= 0.9 * total              # the gap rarely excuses a token


def test_a_slot_readmitted_after_a_longer_request_serves_as_a_fresh_engine(
        engine):
    """Long requests fill every slot and finish (every slot then steps on
    after them), then short ones — one token, two tokens — are admitted
    into the same slots: an admission overwrites ALL of a slot's tail and
    keys, so they serve what an engine that never held anything serves (the
    second engine is what this tests against)."""
    params, policy = CASE.served()
    long = families.requests(CASE, SLOTS + 3, seed=7, sampled=True,
                             first_uid=400, primes=(21, 17, 19))
    families.serve(engine, long)
    caches = engine.state["caches"]
    assert all(bool(jnp.abs(c["conv"]).max(axis=(1, 2)).min() > 0)
               for c in caches.values() if "conv" in c)
    short = families.requests(CASE, SLOTS, seed=6, first_uid=500,
                              primes=(1, 2, 5, 3))
    got = families.tokens_of(families.serve(engine, short))
    fresh_engine = ServingEngine(TINY, params, policy=policy,
                                 **CASE.engine)
    fresh = families.tokens_of(families.serve(
        fresh_engine, families.requests(CASE, SLOTS, seed=6, first_uid=500,
                                        primes=(1, 2, 5, 3))))
    assert got == fresh
    assert _held_to_the_reference(short[0], got[500]) > 0


def slot_holds(engine):
    caches = engine.state["caches"]
    assert {n: sorted(c) for n, c in caches.items()} == {
        **{f"l{i}": ["conv"] for i in (0, 2, 3, 5)},
        **{f"l{i}": ["k", "v"] for i in (1, 4)}}
    assert caches["l0"]["conv"].shape == (SLOTS, 2, TINY.hidden_size)
    assert caches["l1"]["k"].shape == (SLOTS, 1, MAX_LEN, 16)
    status = engine.status()
    assert status["row_write"] == "scatter"          # the CPU's lowering
    assert status["moe_experts"] == {"chunk": "xla", "admit": "xla"}


def states(family):
    assert family.block_length is None          # a token a row a step
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings
    assert set(family.init_stats()) == set(lfm2.STAT_KEYS)
    assert family.init_stats()["moe.held_load"].shape == (TINY.experts_held,)


def counters(engine, reqs, stats, total):
    assert set(stats) == set(lfm2.STAT_KEYS)
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    # live rows x convolution layers a step
    assert stats["conv.tokens"] == CONV_LAYERS * steps
    # 3 of 8 a token an expert layer, every expert held
    assert stats["moe.tokens"] == EXPERT_LAYERS * (prime_tokens + steps)
    assert stats["moe.held_load"].sum() == (
        TINY.num_experts_per_tok * stats["moe.tokens"])
    assert stats["moe.prefill_held"] == (
        TINY.num_experts_per_tok * EXPERT_LAYERS * prime_tokens)
    assert 0 < stats["moe.experts_touched"] <= (stats["moe.decode_layers"]
                                                * TINY.experts_held)
    assert stats["attn.decode_rows"] == steps
    # the i-th step of a request stands on position prime + i - 1
    context = sum(len(r.tokens) + i for r in reqs
                  for i in range(1, r.max_new_tokens))
    assert stats["attn.context_tokens"] == context
    # the XLA core reads every slot's every row, each decode step that ran
    chunk_steps = stats["attn.full_rows_read"] / (SLOTS * MAX_LEN)
    assert chunk_steps == int(chunk_steps) and chunk_steps >= max(
        r.max_new_tokens - 1 for r in reqs)
    snap = get_registry().snapshot()
    for name in lfm2.STAT_KEYS:
        if name != "moe.held_load":
            assert snap[name]["value"] == total[name], name
    assert snap["moe.held_assignments"]["value"] == total[
        "moe.held_load"].sum()
    assert engine.status()["model_stats"]["conv.tokens"] == total[
        "conv.tokens"]


TestEngine = families.engine_tests(
    CASE, slot_holds=slot_holds, states=states, counters=counters,
    greedy=greedy)

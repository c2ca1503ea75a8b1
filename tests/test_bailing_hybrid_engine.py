"""Ling-3.0-flash through ``ServingEngine``'s normal path (the seam of
``decode/family.py``; no line of ``decode/engine.py`` names the family): the
tests every driver family runs (``tests/families.py``) over rows of mixed
lengths in one admission run — primes of 1, 2 and 3 tokens, shorter than the
convolution's taps, and primes that cross blocks and chunks of the delta
rule among them; what is this family's own here: greedy requests serve the
reference's argmax over the same tokens wherever its top-two gap exceeds a
float32 rounding; a slot readmitted after a longer request serves what a
fresh engine serves (a stale carry, tail or latent row fails it) and an idle
slot's carry stays finite; a slot's state holds a float32 carry and a tail
for each delta-rule layer and ONE latent leaf; the counters' arithmetic on a
chip that holds a share of the experts."""

import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.decode import ServingEngine
from progen_tpu.models import bailing_hybrid as bh
from progen_tpu.observe.metrics import get_registry
from tests import families
from tests.bailing_hybrid_tiny import TINY, make, share
from tests.families import SLOTS

pytestmark = pytest.mark.serving

CASE = families.CASES["bailing_hybrid"]
MAX_LEN = CASE.max_len
DELTA_LAYERS = TINY.layer_types.count(bh.DELTA)
LAYERS = TINY.num_hidden_layers
EXPERT_LAYERS = LAYERS - 1          # layer 0 is dense
CARRY_BYTES = 2 * 8 * 8 * 4         # a slot's float32 carry in one layer
# the reference's best logit must lead its second best by this much for a
# greedy token to be held to it (tests/test_qwen3_next_engine.py)
TOP_TWO_GAP = 1e-4


@pytest.fixture(scope="module")
def engine():
    return families.engine_of(CASE)


def _held_to_the_reference(r, tokens):
    """The served greedy tokens against the reference's argmax over the
    same sequence so far, wherever its top-two gap exceeds the tolerance;
    returns how many positions were held."""
    held = 0
    for at, tok in zip(families.probe_logits(CASE, r, tokens), tokens):
        top = np.sort(at)[-2:]
        if top[1] - top[0] > TOP_TWO_GAP:
            assert tok == 1 + int(np.argmax(at)), (r.uid, held)
            held += 1
    return held


def greedy(case, reqs, done):
    got = families.tokens_of(done)
    assert sum(len(r.tokens) < TINY.short_conv_kernel_size
               for r in reqs) == 3
    held = sum(_held_to_the_reference(r, got[r.uid]) for r in reqs)
    total = sum(r.max_new_tokens for r in reqs)
    assert held >= 0.9 * total              # the gap rarely excuses a token


def test_a_readmitted_slot_serves_as_a_fresh_one_and_an_idle_carry_is_finite(
        engine):
    """Long requests fill every slot and finish (every slot then steps on
    after them: rows that are not live run, and their carry — decayed a
    channel, erased and written under garbage — stays finite), then short
    ones — one, two, three tokens — are admitted into the same slots: an
    admission overwrites ALL of a slot's carry, tail and latent rows, so
    they serve what an engine that never held anything serves (the second
    engine is what this tests against)."""
    params, policy = CASE.served()
    long = families.requests(CASE, SLOTS + 3, seed=7, sampled=True,
                             first_uid=400, primes=(21, 17, 19))
    families.serve(engine, long)
    caches = engine.state["caches"]
    carries = [c["state"] for c in caches.values() if isinstance(c, dict)]
    assert len(carries) == DELTA_LAYERS
    assert all(bool(jnp.isfinite(c).all()) for c in carries)
    assert all(bool(jnp.abs(c).max(axis=(1, 2, 3)).min() > 0)
               for c in carries)
    short = families.requests(CASE, SLOTS, seed=6, first_uid=500,
                              primes=(1, 2, 3, 5))
    got = families.tokens_of(families.serve(engine, short))
    fresh_engine = ServingEngine(TINY, params, policy=policy,
                                 **CASE.engine)
    fresh = families.tokens_of(families.serve(
        fresh_engine, families.requests(CASE, SLOTS, seed=6, first_uid=500,
                                        primes=(1, 2, 3, 5))))
    assert got == fresh
    assert _held_to_the_reference(short[0], got[500]) > 0


def slot_holds(engine):
    caches = engine.state["caches"]
    # five carries + tails and ONE latent leaf: D D D D D M
    assert sorted(caches) == [f"l{i}" for i in range(LAYERS)]
    for i in range(5):
        assert sorted(caches[f"l{i}"]) == ["conv", "state"]
    assert caches["l0"]["state"].shape == (SLOTS, 2, 8, 8)
    assert caches["l0"]["state"].dtype == jnp.float32
    assert caches["l0"]["conv"].shape == (SLOTS, 3, 3 * 16)
    assert caches["l5"].shape == (SLOTS, MAX_LEN, 16 + 4)
    status = engine.status()
    assert status["row_write"] == "scatter"          # the CPU's lowering
    assert status["moe_experts"] == {"chunk": "xla", "admit": "xla"}
    assert status["mla_decode"] == "xla"
    # the delta rule's two forms say which program holds which
    assert engine.lowerings["kda_step"] == "xla"
    assert engine.lowerings["kda_prefill"] == "xla"
    assert "kda_step" not in engine.program_lowerings["admit"]
    assert "kda_prefill" not in engine.program_lowerings["chunk"]
    assert "gdn_prefill" not in engine.lowerings


def states(family):
    assert family.block_length is None          # a token a row a step
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings
    assert set(family.init_stats()) == set(bh.STAT_KEYS)
    assert family.init_stats()["moe.held_load"].shape == (TINY.experts_held,)
    assert list(family.blocks) == [f"l{i}" for i in range(LAYERS)]


def counters(engine, reqs, stats, total, config=TINY):
    """The counters' arithmetic over one small run of a chip that holds
    ``config.experts_held`` of the 16 experts."""
    assert set(stats) == set(bh.STAT_KEYS)
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    assert stats["kda.real_tokens"] == DELTA_LAYERS * prime_tokens
    assert stats["kda.scan_slots"] >= stats["kda.real_tokens"]
    assert stats["kda.scan_slots"] % (DELTA_LAYERS * TINY.chunk) == 0
    # each live row's carry read and written once a delta layer a step
    assert stats["kda.state_bytes"] == (2 * CARRY_BYTES * DELTA_LAYERS
                                        * steps)
    assert stats["moe.tokens"] == EXPERT_LAYERS * (prime_tokens + steps)
    # 3 of 16 a token: 0.75 held assignments a token where 4 are held (ONE
    # of the router's four groups, of which a token keeps two), 3 where all
    held = stats["moe.held_load"].sum()
    assert stats["moe.held_load"].shape == (config.experts_held,)
    if config.experts_held == TINY.num_experts:
        assert held == TINY.num_experts_per_tok * stats["moe.tokens"]
    else:
        assert 0.2 < held / stats["moe.tokens"] < 1.6
    assert 0 < stats["moe.prefill_held"] < held
    assert 0 < stats["moe.experts_touched"] <= (stats["moe.decode_layers"]
                                                * config.experts_held)
    assert stats["moe.decode_layers"] % EXPERT_LAYERS == 0
    assert stats["mla.decode_rows"] == steps
    # the i-th step of a request stands on position prime + i - 1
    context = sum(len(r.tokens) + i for r in reqs
                  for i in range(1, r.max_new_tokens))
    assert stats["mla.context_tokens"] == context
    snap = get_registry().snapshot()
    for name in bh.STAT_KEYS:
        if name != "moe.held_load":
            assert snap[name]["value"] == total[name], name
    assert snap["moe.held_assignments"]["value"] == total[
        "moe.held_load"].sum()
    model_stats = engine.status()["model_stats"]
    assert model_stats["kda.state_bytes"] == total["kda.state_bytes"]
    assert model_stats["moe.held_assignments"] == total[
        "moe.held_load"].sum()


def test_counters_ride_the_flags_fetch_into_the_registry_and_status():
    """A chip that holds 4 of the 16 experts (8-11, the router's third
    group): the counters' arithmetic over one small run (an engine of
    another configuration, by what this tests)."""
    config = share(8)
    params, policy = make(config)
    eng = ServingEngine(config, params, policy=policy, **CASE.engine)
    reqs = families.requests(CASE, 4, seed=5)
    families.serve(eng, reqs)
    counters(eng, reqs, families.moved(eng, {}), eng.model_stats, config)


TestEngine = families.engine_tests(
    CASE, slot_holds=slot_holds, states=states, counters=counters,
    greedy=greedy)

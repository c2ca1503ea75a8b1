"""LongCat-Flash through ``ServingEngine``'s normal path: the tokens of a
plain sequential greedy sampler over the reference's full forward, whatever
the group; nothing compiled after ``aot_warmup``; slots reused
deterministically; an ``(S, V)`` logit mask; the modes that are ProGen's
alone refused by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_longcat as ref
from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
from progen_tpu.decode.family import (
    SERVING_MODES,
    UnsupportedFamilyMode,
    family_for,
)
from progen_tpu.models.progen import ProGenConfig
from progen_tpu.observe.metrics import get_registry
from tests.longcat_tiny import TINY, as_dict, make

pytestmark = pytest.mark.serving

ADMIT_ROWS = 2
SLOTS = ADMIT_ROWS * SLOTS_PER_ADMIT_ROW
ENGINE = dict(num_slots=SLOTS, chunk_size=4, max_len=32)
NEW = 5


@pytest.fixture(scope="module")
def served():
    return make()


@pytest.fixture(scope="module")
def engine(served):
    """One warmed engine for the whole file: requests reuse its slots."""
    params, policy = served
    eng = ServingEngine(TINY, params, policy=policy, **ENGINE)
    eng.warm = eng.aot_warmup()
    return eng


def _never_zero():
    mask = np.ones((TINY.vocab_size,), bool)
    mask[0] = False
    return mask


def _requests(n, seed=0, sampled=False, first_uid=0):
    """Primes of 3-14 tokens: the buckets of 8 and 16."""
    rng = np.random.default_rng(seed)
    return [Request(
        uid=first_uid + i, max_new_tokens=NEW + i % 3, seed=50 + i,
        temperature=0.8 if sampled else 0.0, top_k=6 if sampled else None,
        logit_mask=_never_zero(),
        tokens=rng.integers(1, TINY.vocab_size, 3 + (5 * i) % 12).tolist())
        for i in range(n)]


@jax.jit
def _reference_logits(params, row, at):
    """The reference over one row padded to the engine's ``max_len``
    (causality keeps the padding out of what is read): ONE program for
    every length, where a call a length compiled the reference anew each
    time (92-130 s of this file's fixture)."""
    with jax.default_matmul_precision("highest"):
        return ref.forward_row(params, row, as_dict(TINY),
                               logit_positions=at)[0]


def _sequential_greedy(params, r):
    """The plain sampler: the reference's full forward over everything so
    far, the best allowed token appended, again."""
    seq = list(r.tokens)
    for _ in range(r.max_new_tokens):
        row = jnp.zeros((ENGINE["max_len"],), jnp.int32).at[:len(seq)].set(
            jnp.asarray(seq))
        logits = _reference_logits(params, row, jnp.array([len(seq) - 1]))
        seq.append(1 + int(jnp.argmax(logits[0, 1:])))
    return seq[len(r.tokens):]


@pytest.fixture(scope="module")
def plain(served):
    return {r.uid: _sequential_greedy(served[0], r)
            for r in _requests(ADMIT_ROWS + 1)}


@pytest.mark.parametrize("n", [1, ADMIT_ROWS, ADMIT_ROWS + 1])
def test_groups_serve_the_plain_samplers_tokens(engine, plain, n):
    reqs = _requests(n)
    assert len({engine.family.bucket(len(r.tokens), 32)
                for r in _requests(ADMIT_ROWS + 1)}) == 2
    for r in reqs:
        engine.submit(r)
    runs0 = engine._admit_rows_hist.count
    got = {c.uid: c.tokens.tolist() for c in engine.run_until_idle(100)}
    assert engine._admit_rows_hist.count - runs0 == -(-n // ADMIT_ROWS)
    assert got == {r.uid: plain[r.uid] for r in reqs}


def test_nothing_compiles_after_warmup_and_slots_reuse_deterministically(
        engine):
    assert sorted(k for k in engine._aot if k[0] == "admit") == [
        ("admit", 8), ("admit", 16), ("admit", 32)]
    assert engine.warm["programs"] == 4
    events = []

    def listener(name, secs, **kw):
        if name.startswith("/jax/core/compile"):
            events.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        # more requests than slots: late ones land in slots others left
        first = {c.uid: c.tokens.tolist() for c in _serve(
            engine, _requests(SLOTS + 5, seed=3, sampled=True))}
        again = {c.uid: c.tokens.tolist() for c in _serve(
            engine, list(reversed(_requests(SLOTS + 5, seed=3,
                                            sampled=True))))}
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert events == []
    assert first == again and len(first) == SLOTS + 5
    assert all(t != 0 for toks in first.values() for t in toks)


def _serve(engine, reqs):
    for r in reqs:
        engine.submit(r)
    return engine.run_until_idle(200)


def test_the_mask_is_one_row_a_slot_and_bans_token_zero(served, engine):
    assert engine.state["lmask"].shape == (SLOTS, TINY.vocab_size)
    assert not engine.family.position_masks
    # a head that prefers token 0 everywhere: only the mask keeps it out
    params, policy = served
    biased = {**params, "head": params["head"].at[:, 0].set(
        10.0 * jnp.abs(params["head"]).max())}
    eng = ServingEngine(TINY, biased, policy=policy, num_slots=2,
                        chunk_size=4, max_len=32)
    free = Request(uid="free", tokens=[3, 4, 5], max_new_tokens=4,
                   temperature=0.0)
    held = Request(uid="held", tokens=[3, 4, 5], max_new_tokens=4,
                   temperature=0.0, logit_mask=_never_zero())
    out = {c.uid: c for c in _serve(eng, [free, held])}
    assert out["free"].finish_reason == "eos"
    assert out["held"].finish_reason == "length"
    assert 0 not in out["held"].tokens.tolist()
    with pytest.raises(UnsupportedFamilyMode, match="same at every position"):
        eng.submit(Request(uid="grid", tokens=[1, 2], max_new_tokens=3,
                           logit_mask=np.ones((3, TINY.vocab_size), bool)))


@pytest.mark.parametrize("mode", [
    dict(paged=True), dict(disagg=True),
    dict(lora_bank={}), dict(quantize="weights"), dict(mesh=object())],
    ids=lambda m: next(iter(m)))
def test_modes_that_are_progens_alone_are_refused_by_name(served, mode):
    params, policy = served
    with pytest.raises(UnsupportedFamilyMode, match=next(iter(mode))):
        ServingEngine(TINY, params, policy=policy, **ENGINE, **mode)
    with pytest.raises(TypeError, match="no model family"):
        family_for(object(), policy)


def test_the_engine_reads_what_a_family_states_of_itself(served):
    """No test of a family's name or a config's type in the engine: a
    family that states a mode is let past the gate for it."""
    params, policy = served
    family = family_for(TINY, policy)
    assert family.modes == frozenset() and family.idle_length == 0
    assert family.step_model is None and family.embedder() is None
    progen = family_for(ProGenConfig(), policy)
    assert progen.modes == SERVING_MODES and progen.idle_length == 1
    assert progen.step_model is not None


def test_embedding_requests_are_refused(engine):
    with pytest.raises(UnsupportedFamilyMode, match="embedding"):
        engine.submit_embed(Request(uid="e", tokens=[1, 2, 3]))


def test_counters_ride_the_flags_fetch_into_the_registry(served):
    """The family's device counters are read by the harvest's own fetch and
    published as gauges; the stage histograms are observed as for ProGen."""
    params, policy = served
    eng = ServingEngine(TINY, params, policy=policy, **ENGINE)
    reqs = _requests(3, seed=5)
    registry = get_registry()
    before = {k: registry.histogram(k).count for k in (
        "engine.decode_chunk_s", "engine.prefill_s", "engine.admit_rows")}
    _serve(eng, reqs)
    stats = eng.model_stats
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
    snap = registry.snapshot()
    assert snap["moe.tokens"]["value"] == stats["moe.tokens"]
    assert snap["moe.held_load_max"]["value"] == stats["moe.held_load"].max()
    for name, count in before.items():
        assert registry.histogram(name).count > count
